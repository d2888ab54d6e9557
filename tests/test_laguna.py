"""laguna on the served path (ISSUE 52): the family's config keys and what is
refused by key, layers of two SHAPES walked in a bounded number of bodies, a
rope of its own for each kind with a partial rotary factor, the per-head gate
on attention's output, the softmax router's routed scale, its checkpoint
names through the loader, the served path against the plain reference
(`benchmark/reference/laguna.py`) through chunked prefill, the prefix cache,
decode, the mixed step and the shared short step, every control of the
reference, both forms of the expert layer and the rule that picks one, the
layouts that refuse the family, and the benchmark's roofline count.  Tiny
sizes (hidden 64, 6 / 8 heads over 2 KV heads of 16, 8 experts top 2, window
8; 7 layers = the cell's cut, 9 = two whole periods and a full layer),
float32 and bfloat16, seeded weights, CPU.
"""

import asyncio
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import KVCache, ModelConfig, init_params
from dynamo_tpu.models import laguna, llama
from dynamo_tpu.models.loader import load_params
from dynamo_tpu.ops import apply_rope, rope_by_kind
from test_nemotron_h import BENCH, PEAKS, ROOT, TOL, bench_module, logp, prompt
from test_phi4flash import loop_conditional_operands

PAGE = 8
CELL = "laguna-xs2-33b-h7"
PERIOD = ["full_attention"] + ["sliding_attention"] * 3


def tiny(n_layers, **over):
    model = {
        "model_type": "laguna", "vocab_size": 300, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": n_layers,
        "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
        "max_position_embeddings": 512, "attention_bias": False,
        "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 8,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
                "original_max_position_embeddings": 16, "beta_slow": 1,
                "beta_fast": 4, "attention_factor": 1.2,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 16},
        "layer_types": (PERIOD * 10)[:n_layers],
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5,
        "mlp_layer_types": (["dense"] + ["sparse"] * 39)[:n_layers],
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": ([6, 8, 8, 8] * 10)[:n_layers],
    }
    model.update(over)
    return model


TINY = tiny(7)


@pytest.fixture(scope="module")
def ref():
    return bench_module("reference", "laguna")


@pytest.fixture(scope="module", params=[7, 9], ids=["7-layers", "9-layers"])
def sized(request):
    """(model, cfg, params) at the cell's cut and at two periods and one."""
    model = tiny(request.param)
    cfg = ModelConfig.from_hf_config(model, name="tiny-laguna")
    return model, cfg, init_params(cfg, jax.random.PRNGKey(52), jnp.float32)


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig.from_hf_config(TINY, name="tiny-laguna")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(52), dtype=jnp.float32)


def reader_of(params, cfg):
    """`read(name)` over a param tree, under the family's tensor names (the
    loader's mapping, backwards)."""
    flat = {"model.embed_tokens.weight": params["embed"],
            "model.norm.weight": params["final_norm"],
            "lm_head.weight": params["lm_head"].T}
    ffn = (("w_gate", "gate"), ("w_up", "up"), ("w_down", "down"))
    for stack, ((_, mlp, _), ids) in laguna.stacks_of(cfg).items():
        lay = params[stack]
        for j, i in enumerate(ids):
            p, a = f"model.layers.{i}.", f"model.layers.{i}.self_attn."
            flat.update({
                p + "input_layernorm.weight": lay["attn_norm"][j],
                p + "post_attention_layernorm.weight": lay["mlp_norm"][j],
                a + "q_proj.weight": lay["wq"][j].T,
                a + "k_proj.weight": lay["wk"][j].T,
                a + "v_proj.weight": lay["wv"][j].T,
                a + "o_proj.weight": lay["wo"][j].T,
                a + "g_proj.weight": lay["w_head_gate"][j].T})
            if mlp == "dense":
                flat.update({p + f"mlp.{n}_proj.weight": lay[k][j].T
                             for k, n in ffn})
                continue
            flat[p + "mlp.gate.weight"] = lay["router"][j].T
            for k, n in ffn:
                flat[p + f"mlp.shared_expert.{n}_proj.weight"] = (
                    lay["ws" + k[1:]][j].T)
                for e in range(cfg.num_experts):
                    flat[p + f"mlp.experts.{e}.{n}_proj.weight"] = (
                        lay[k][j][e].T)
    return lambda name: np.asarray(flat[name], np.float32)


def table_for(n_tokens, batch=1):
    pages = -(-n_tokens // PAGE)
    return jnp.asarray(np.arange(1, 1 + batch * pages, dtype=np.int32)
                       .reshape(batch, pages))


def fresh_cache(cfg, tokens=128, dtype=jnp.float32):
    return KVCache.create(cfg, 2 + -(-tokens // PAGE), PAGE, dtype)


# one compile a (config, shape), not a trace a call
forward_prefill = jax.jit(llama.forward_prefill, static_argnums=(1,))


def prefill_all(cfg, params, tokens, chunk=None, dtype=jnp.float32):
    """Chunked prefill of one prompt: [(position, next-token logprobs)] a
    chunk, the cache."""
    T = len(tokens)
    chunk = chunk or T
    kv = fresh_cache(cfg, T + 8 * PAGE, dtype)
    out = []
    for s in range(0, T, chunk):
        part = tokens[s:s + chunk]
        logits, kv = forward_prefill(
            params, cfg, kv, jnp.asarray([part], jnp.int32),
            table_for(T + 8 * PAGE), jnp.asarray([s], jnp.int32),
            jnp.asarray([len(part)], jnp.int32))
        out.append((s + len(part) - 1, logp(logits)[0]))
    return out, kv


def ref_logp(ref, cfg, params, tokens, model=TINY, **controls):
    """Reference next-token logprobs after every position: [T, vocab]."""
    return ref.forward(reader_of(params, cfg), model, [np.asarray([tokens])],
                       len(tokens), **controls)[0][0]


def published():
    with open(os.path.join(BENCH, "configs", CELL + ".json")) as f:
        return json.load(f)


def catalog_row():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2"]
    return row


# -- configuration ------------------------------------------------------------- #

def test_from_hf_config_counts_the_published_model_and_the_cut():
    """The catalog row's `config` verbatim is the 33.4 B model; the cut is
    5,563,578,368 parameters, what the checkpoint file writes, and no layer
    is padded to the widest: a 48-head layer's `wq` is 6,144 wide."""
    full = ModelConfig.from_hf_config(catalog_row()["config"])
    assert full.num_params() == 33_442_596_864
    assert (full.num_hidden_layers, full.num_moe_layers) == (40, 39)
    run = published()
    c = ModelConfig.from_hf_config(run["model"])
    assert c.num_params() == 5_563_578_368 == sum(
        int(np.prod(shape)) for _, shape, _ in bench_module(
            "checkpoints", "laguna").tensors(run["model"]))
    assert run["memory"]["weights_bytes"] == 2 * c.num_params()
    assert c.layer_heads == (48, 64, 64, 64, 48, 64, 64)
    assert c.layer_windows() == [0, 512, 512, 512, 0, 512, 512]
    assert (c.num_moe_layers, c.num_experts, c.num_experts_per_tok,
            c.shared_expert_width, c.moe_routed_scale) == (6, 256, 8, 512, 2.5)
    assert c.attention_gate and c.moe_scoring == "softmax"
    shapes = jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0)))
    assert {k: v["wq"].shape for k, v in shapes.items()
            if isinstance(v, dict)} == {
        "full_dense_layers": (1, 2048, 48 * 128),
        "sliding_layers": (5, 2048, 64 * 128),
        "full_layers": (1, 2048, 48 * 128)}
    assert shapes["full_layers"]["w_head_gate"].shape == (1, 2048, 48)
    assert shapes["sliding_layers"]["w_gate"].shape == (5, 256, 2048, 512)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == (
        c.num_params())
    # ONE geometry of pages whatever the layer's heads
    assert c.cache_spec.plane_dims == ((8, 128), (8, 128))
    assert 7 * c.cache_spec.bytes_per_token_layer(2) == (
        run["memory"]["kv_bytes_per_token"]) == 28_672
    flags = run["worker_flags"]
    assert run["memory"]["kv_pool_tokens"] == flags["--num-pages"] * 16
    assert run["memory"]["kv_pool_bytes"] == (
        run["memory"]["kv_pool_tokens"] * 28_672)


def test_the_file_states_each_published_key_once_for_each_reader():
    """Top-level keys (what the driver's check reads) equal `model` (what
    the program gets) and, but for the four keys of `reduced`, the catalog
    row's values; the cell is in the benchmark under the issue's name."""
    run = published()
    model = dict(run["model"])
    assert model.pop("architectures") == ["LagunaForCausalLM"]
    assert model.pop("torch_dtype") == "bfloat16"
    assert {k: run[k] for k in model} == model
    row = catalog_row()
    cut = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_attention_heads_per_layer"]
    assert sorted(run["reduced"]) == sorted(cut)
    assert {k: v for k, v in model.items() if k not in cut} == {
        k: v for k, v in row["config"].items() if k not in cut}
    assert model["num_hidden_layers"] == 7
    for key in cut[1:]:
        assert model[key] == row["config"][key][:7]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [c for c in spec["configs"] if c["name"] == CELL]
    assert entry["reduced"] == cut and entry["source"] == row["source_url"]
    assert entry["source"] == run["source"]
    cell, = [w for w in spec["workloads"] if w["config"] == CELL]
    assert sorted(cell) == ["chips", "config", "name", "traffic", "why"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "laguna-xs2-33b.longdoc-1tok", "longdoc-1tok", 1)
    # the context is the configuration's own (its one cell's mix passes the
    # default 4096), as phi4-mini-flash's: no `cells/` file
    assert not os.path.exists(os.path.join(BENCH, "cells",
                                           cell["name"] + ".json"))
    assert run["worker_flags"] == {"--num-pages": 9984,
                                   "--max-model-len": 8192}
    # (the readers this cell brought: it is the first they list; since
    # PR 55 four of them also read the lfm2 cell, which has their keys)
    listed = [m["name"] for m in spec["per_layer"]
              if (m.get("workloads") or [""])[0] == cell["name"]]
    assert listed == ["step.routed_experts_device_pct",
                      "step.moe_dispatch_device_pct",
                      "kernel.routed_experts_roofline",
                      "engine.moe_touched_pct",
                      "engine.moe_dispatched_token_pct"]
    for key in ("gate", "router", "shared_expert", "qk_norm", "tensor_names",
                "weights", "routing"):
        assert run["assumed"][key]


@pytest.mark.parametrize("name,layer,source,better", [
    ("step.routed_experts_device_pct", "model step", "device_trace", "lower"),
    ("step.moe_dispatch_device_pct", "model step", "device_trace", "lower"),
    ("kernel.routed_experts_roofline", "kernels", "device_trace", "higher"),
    ("engine.moe_touched_pct", "engine", "program_counter", "higher"),
    ("engine.moe_dispatched_token_pct", "engine", "program_counter",
     "higher"),
])
def test_the_spec_keeps_each_reader_the_cell_brought(name, layer, source,
                                                     better):
    """What still holds of `benchmark/tests/test_laguna_readers.py::
    test_the_spec_lists_the_new_readers_for_the_new_cell_alone`, which
    tier-1 leaves out since PR 55 (`tests/test_benchmark_suite.py`): each of
    the five readers' fields, its file, and that it lists this cell.  Gone
    with that case is only that the cell is the LAST, or the only one, a
    list names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    m = by_name[name]
    assert (m["layer"], m["source"], m["better"], m["moves"], m["unit"]) == (
        layer, source, better, "ttft_p95_ms", "%")
    assert "laguna-xs2-33b.longdoc-1tok" in m["workloads"]
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name", [
    "step.attn_device_pct", "kernel.prefill_attn_roofline",
    "step.copy_device_pct", "kernel.prefill_rows_step_roofline"])
def test_the_accepted_readers_that_read_the_cell_still_list_it(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert "laguna-xs2-33b.longdoc-1tok" in by_name[name]["workloads"]


@pytest.mark.parametrize("bad,key", [
    ({"gating": False}, "gating"),
    ({"gating": "per-value"}, "gating"),
    ({"gating_types": ["per_head", "per_value"]}, "gating_types"),
    ({"moe_apply_router_weight_on_input": True},
     "moe_apply_router_weight_on_input"),
    ({"moe_router_logit_softcapping": 30.0}, "moe_router_logit_softcapping"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"layer_types": PERIOD}, "layer_types"),
    ({"layer_types": ["full_attention"] * 6 + ["chunked_attention"]},
     "layer_types"),
    ({"mlp_layer_types": ["dense"] * 8}, "mlp_layer_types"),
    ({"num_attention_heads_per_layer": [6, 8, 8, 8]},
     "num_attention_heads_per_layer"),
    ({"num_attention_heads_per_layer": [6, 8, 8, 8, 6, 8, 7]},
     "num_attention_heads_per_layer"),
    ({"num_attention_heads_per_layer": [6, 8, 8, 8, 8, 8, 8]},
     "num_attention_heads_per_layer"),
    ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"sliding_window": None}, "sliding_window"),
    ({"num_experts": 0}, "num_experts"),
    ({"rope_parameters": {"full_attention": {"rope_theta": 1e4}}},
     "rope_parameters"),
    ({"rope_parameters": dict(TINY["rope_parameters"], sliding_attention={
        "rope_type": "longrope", "rope_theta": 1e4})}, "rope_parameters"),
    ({"rope_parameters": dict(TINY["rope_parameters"], sliding_attention={
        "rope_theta": 1e4, "partial_rotary_factor": 0.3})},
     "rope_parameters"),
    ({"model_type": "lagoon"}, "num_attention_heads_per_layer"),
], ids=["no-gate", "gate-over-values", "gate-kinds", "weight-on-input",
        "softcap", "unnormalised", "short-layer-types", "another-attention",
        "long-mlp-types", "short-heads", "heads-kv-multiple",
        "heads-differ-in-a-kind", "bias", "act", "no-window", "no-experts",
        "rope-for-one-kind", "rope-type", "odd-rotary-share",
        "another-family"])
def test_from_hf_config_refuses_what_it_cannot_compute(bad, key):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config(dict(TINY, **bad))


# -- layers of two shapes in a bounded number of bodies --------------------------- #

def layer_bodies(jaxpr, scope="attn.out"):
    """How many times the layer body was TRACED: the products under `scope`
    in the jaxpr, loops' and calls' bodies among it, each counted once."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and scope in str(
                eqn.source_info.name_stack):
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += layer_bodies(sub, scope)
    return n


@pytest.mark.parametrize("n_layers,segments,bodies", [
    (7, [(1, "D"), (1, "SSS"), (1, "F"), (1, "SS")], 4),
    (9, [(1, "D"), (2, "SSSF")], 3),
    (40, [(1, "D"), (9, "SSSF"), (1, "SSS")], 4),
], ids=["the-cut", "two-periods", "published-depth"])
def test_the_loop_walks_the_published_order_in_a_bounded_number_of_bodies(
        n_layers, segments, bodies):
    """40 layers are 4 traced bodies, not 40 (and 400 would be 4): the plan
    cuts the order into runs of one kind and folds the stretch that repeats
    into one scan; every layer keeps its place and its index in its stack."""
    c = ModelConfig.from_hf_config(tiny(n_layers))
    plan = laguna.plan(c.layer_kinds)
    letter = {"full_dense_layers": "D", "full_layers": "F",
              "sliding_layers": "S"}
    assert [(s.periods, "".join(letter[laguna.stack_of(r.kind)] * r.count
                                for r in s.runs)) for s in plan] == segments
    order, index = [], []
    for s in plan:
        for t in range(s.periods):
            for r in s.runs:
                for j in range(r.count):
                    order.append(s.first_layer + t * s.period_len
                                 + r.offset + j)
                    index.append((laguna.stack_of(r.kind),
                                  r.first + t * r.stride + j))
    assert order == list(range(n_layers))
    want, seen = [], {}
    for kind in c.layer_kinds:
        stack = laguna.stack_of(kind)
        want.append((stack, seen.get(stack, 0)))
        seen[stack] = seen.get(stack, 0) + 1
    assert index == want
    p = jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0),
                                           jnp.float32))
    kv = jax.eval_shape(lambda: fresh_cache(c, 64))
    jaxpr = jax.make_jaxpr(
        lambda p, kv: llama.forward_prefill(
            p, c, kv, jnp.zeros((1, 16), jnp.int32), table_for(64),
            jnp.zeros((1,), jnp.int32), jnp.full((1,), 16, jnp.int32)))(p, kv)
    assert layer_bodies(jaxpr.jaxpr) == bodies


def test_no_layer_matrix_is_an_operand_of_a_conditional_in_the_layer_loop(
        cfg, params):
    """PR 49's guard over this family: the loop has no conditional at all
    (the head's, outside the loop, is not walked), so no layer's slice of a
    stack is a buffer of its own."""
    text = forward_prefill.lower(
        params, cfg, fresh_cache(cfg, 64), jnp.zeros((1, 32), jnp.int32),
        table_for(64), jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 32, jnp.int32), samples=jnp.asarray([True]),
    ).compiler_ir("hlo").as_hlo_text()
    assert " while(" in text and "conditional(" in text
    operands = loop_conditional_operands(text)
    one_layer = {(stack, n): a.shape[1:] for stack, lay in params.items()
                 if isinstance(lay, dict) for n, a in lay.items()
                 if a.ndim >= 3}
    assert not {n: s for n, s in one_layer.items() if s in operands}


# -- a rope of its own for each kind ---------------------------------------------- #

def test_each_kind_rotates_its_own_share_with_its_own_table(cfg, ref):
    """`ops/rotary.py` against the reference's own yarn: the full kind's
    table has 4 frequencies (half a head of 16), yarn's ramp over them and
    the amplitude 1.2; the windowed kind's 8, plain.  `apply_rope` rotates
    the first 2 x len(table) values among themselves and passes the rest."""
    tables = rope_by_kind(cfg.head_dim_, cfg.rope_parameters)
    assert set(tables) == {"full_attention", "sliding_attention"}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 3, 16), jnp.float32)
    pos = np.arange(40)
    for kind, (inv, amp) in tables.items():
        want_inv, want_amp = ref.rope_table(
            np, TINY["rope_parameters"][kind], 16)
        assert np.allclose(np.asarray(inv), want_inv, rtol=1e-6)
        assert amp == want_amp
        got = apply_rope(x, jnp.asarray(pos)[None], inv, scale=amp)
        want = ref._rope(np, np.asarray(x), pos, want_inv, want_amp)
        assert np.abs(np.asarray(got) - want).max() < 1e-5
    inv, amp = tables["full_attention"]
    assert inv.shape == (4,) and amp == 1.2
    assert tables["sliding_attention"][0].shape == (8,)
    half = apply_rope(x, jnp.asarray(pos)[None], inv, scale=amp)
    assert np.array_equal(np.asarray(half[..., 8:]), np.asarray(x[..., 8:]))
    assert not np.allclose(np.asarray(half[..., :8]), np.asarray(x[..., :8]))


@pytest.mark.parametrize("heads,window", [(6, 0), (8, 8)],
                         ids=["fold-3-full", "fold-4-windowed"])
def test_the_prefill_kernel_is_exact_at_both_head_counts(heads, window):
    """The paged prefill kernel folds a KV head's query heads into query
    rows (PR 50): the two folds of one model (6 and 8 at the published
    sizes, 3 and 4 here over 2 KV heads) against XLA's attention, in
    interpret mode."""
    from dynamo_tpu.ops import prefill_attention
    from dynamo_tpu.ops.pallas_attention import prefill_attention_pallas
    from test_pallas_attention import _make_pool, _page_table

    B, n_kv, hd, page, maxp, S = 2, 2, 64, 16, 6, 32
    prefix_lens = jnp.array([48, 16], jnp.int32)
    chunk_lens = jnp.array([S, S - 5], jnp.int32)
    k_pages, v_pages = _make_pool(jax.random.PRNGKey(1), 1 + B * maxp, page,
                                  n_kv, hd, jnp.float32)
    table = _page_table(B, maxp, jnp.full((B,), maxp * page), page)
    ks = jax.random.split(jax.random.PRNGKey(heads), 3)
    q = jax.random.normal(ks[0], (B, S, heads, hd), jnp.float32) * 0.5
    k_new = jax.random.normal(ks[1], (B, S, n_kv, hd), jnp.float32) * 0.3
    v_new = jax.random.normal(ks[2], (B, S, n_kv, hd), jnp.float32) * 0.3
    args = (q, k_new, v_new, k_pages, v_pages, table, prefix_lens, chunk_lens)
    want = prefill_attention(*args, window=jnp.int32(window))
    got = prefill_attention_pallas(*args, window=jnp.int32(window),
                                   interpret=True)
    for b in range(B):
        n = int(chunk_lens[b])
        np.testing.assert_allclose(np.asarray(got[b, :n]),
                                   np.asarray(want[b, :n]), atol=2e-5,
                                   rtol=2e-5)


# -- checkpoint names through the loader ------------------------------------------- #

def test_written_checkpoint_loads_and_agrees_with_the_reference(tmp_path, ref):
    """`benchmark/lib/checkpoint.py` + `checkpoints/laguna.py` write the
    family's tensors; `models/loader.py` reads them into one stack a kind; a
    chunked prefill over the loaded tree agrees with the reference reading
    the same file."""
    from safetensors import safe_open

    ckpt = bench_module("lib", "checkpoint")
    names = {n: shape for n, shape, _ in bench_module(
        "checkpoints", "laguna").tensors(TINY)}
    assert names["model.layers.0.self_attn.q_proj.weight"] == (6 * 16, 64)
    assert names["model.layers.1.self_attn.q_proj.weight"] == (8 * 16, 64)
    assert names["model.layers.4.self_attn.g_proj.weight"] == (6, 64)
    assert "model.layers.0.mlp.gate_proj.weight" in names
    assert "model.layers.0.mlp.gate.weight" not in names
    assert names["model.layers.1.mlp.gate.weight"] == (8, 64)
    assert "model.layers.6.mlp.experts.7.down_proj.weight" in names
    assert "model.layers.6.mlp.shared_expert.up_proj.weight" in names
    model = dict(TINY, architectures=["LagunaForCausalLM"],
                 torch_dtype="bfloat16")
    ckpt.write({"model": model, "weights_seed": 5, "checkpoint": "laguna"},
               str(tmp_path))
    c = ModelConfig.from_pretrained(str(tmp_path))
    p = load_params(str(tmp_path), c, dtype=jnp.float32)
    want_shapes = jax.eval_shape(
        lambda: init_params(c, jax.random.PRNGKey(0), jnp.float32))
    assert jax.tree.map(lambda a: a.shape, p) == jax.tree.map(
        lambda a: a.shape, want_shapes)
    reader = safe_open(str(tmp_path / "model.safetensors"), framework="np")
    toks = prompt(40, 1)
    want = ref.forward(
        lambda n: reader.get_tensor(n).astype(np.float32), TINY,
        [np.asarray([toks])], len(toks))[0][0]
    for pos, got in prefill_all(c, p, toks, chunk=16)[0]:
        assert np.abs(got - want[pos]).max() < TOL


# -- the served path against the reference ----------------------------------------- #

@pytest.mark.parametrize("chunk", [None, 16, 13],
                         ids=["one-chunk", "chunks-of-16", "chunks-of-13"])
def test_chunked_prefill_agrees_with_the_reference(sized, ref, chunk):
    """44 tokens in one chunk and in chunks that cross the window of 8 (and
    that no page divides), past yarn's original 16 positions: the last
    position of every chunk against the reference's full forward pass, at 7
    layers (a run of each kind once) and at 9 (the scan over two periods)."""
    model, c, p = sized
    toks = prompt(44, 3)
    want = ref_logp(ref, c, p, toks, model)
    for pos, got in prefill_all(c, p, toks, chunk)[0]:
        assert np.abs(got - want[pos]).max() < TOL, pos


def test_bfloat16_reads_far_from_float32_and_near_the_reference(cfg, ref):
    """The served dtype against the float32 reference over the same rounded
    weights: inside a tolerance of its own, and two orders past the float32
    one, so TOL would catch a program that rounds where float32 is stated."""
    half = init_params(cfg, jax.random.PRNGKey(52), dtype=jnp.bfloat16)
    toks = prompt(44, 3)
    want = ref_logp(ref, cfg, half, toks)
    worst = max(np.abs(got - want[pos]).max() for pos, got in prefill_all(
        cfg, half, toks, 16, dtype=jnp.bfloat16)[0])
    assert 100 * TOL < worst < 1.0, worst


def test_every_control_of_the_reference_fails_the_limit(cfg, params, ref,
                                                        subtests=None):
    """Each keyword of the reference's `forward` takes one mechanism out (or
    takes the other reading): every one moves the logprobs far past TOL, so a
    served path that lacked the mechanism would fail here."""
    toks = prompt(44, 3)
    want = ref_logp(ref, cfg, params, toks)
    served = np.stack([got for _, got in prefill_all(cfg, params, toks, 4)[0]])
    at = [pos for pos in range(3, 44, 4)]
    assert np.abs(served - want[at]).max() < TOL
    for control in ref.CONTROLS:
        moved = ref_logp(ref, cfg, params, toks, **{control: True})
        assert np.abs(moved[at] - served).max() > 300 * TOL, control


def test_the_verify_step_scores_every_position(cfg, params, ref):
    """`forward_verify` rides the same loop: all positions' logits of a chunk
    after a cached prefix."""
    toks = prompt(28, 4)
    _, kv = prefill_all(cfg, params, toks[:20])
    logits, _ = llama.forward_verify(
        params, cfg, kv, jnp.asarray([toks[20:]], jnp.int32),
        table_for(28 + 8 * PAGE), jnp.asarray([20], jnp.int32),
        jnp.asarray([8], jnp.int32))
    want = ref_logp(ref, cfg, params, toks)[20:]
    assert np.abs(logp(logits)[0] - want).max() < TOL


# -- the expert layer's two forms and the rule that picks one ---------------------- #

@pytest.mark.parametrize("tokens", [1, 5, 48])
def test_both_forms_of_the_expert_layer_agree(cfg, params, tokens):
    """All-experts and sort + `ragged_dot` over one sparse layer's params,
    the routed scale and the shared expert included."""
    lp = jax.tree.map(lambda a: a[1], params["sliding_layers"])
    x = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens, 64),
                          jnp.float32)
    out = {impl: llama._moe(lp, x, dataclasses.replace(cfg, moe_impl=impl),
                            stats=True) for impl in ("dense", "ragged")}
    assert np.abs(np.asarray(out["dense"][0] - out["ragged"][0])).max() < 1e-5
    assert [int(v) for v in out["dense"][1]] == [
        int(v) for v in out["ragged"][1]]
    assert int(out["dense"][1][0]) == tokens * 2
    weights, _ = llama._route(lp, x, cfg)
    assert np.allclose(np.asarray(weights.sum(-1)), 2.5, atol=1e-5)


PAIRS = {"smallthinker": (64, 6), "gigachat-share": (16, 8), "xing": (64, 4),
         "nemotron-share": (16, 6), "mixtral": (8, 2), "gpt-oss": (32, 4)}
# the most tokens a step of the pair runs the all-experts form at: where
# whole `prefill_step` programs crossed on the chip under a BALANCED router
# (`models/llama.py` `_TIMED`, PERF.md findings 33 and 34; nemotron's share
# since the kernel takes its expert width, no whole number of lanes, as its
# stack is stored: PR 54); the last two were not timed with the kernel (and
# gpt-oss-20b's hidden size 2,880 keeps it from one): the bounds of the
# forms before it
MOST = {"smallthinker": 256, "gigachat-share": 256, "xing": 128,
        "nemotron-share": 256, "mixtral": 1024, "gpt-oss": 1024}


@pytest.mark.parametrize("tokens", [16, 64, 256, 512, 1024, 2048])
@pytest.mark.parametrize("family", PAIRS)
def test_the_rule_keeps_the_form_of_the_families_that_are_there(family,
                                                                tokens):
    """The six (held, top-k) pairs served before this family, each at the
    form the chip timing chose once the dispatched form became the grouped
    kernel (PR 53, and PR 54 for nemotron's share): SmallThinker's and both
    shares dispatch from 512 tokens, Xing from 256; the two nobody timed
    with the kernel keep all-experts up to 1,024."""
    held, k = PAIRS[family]
    assert llama.all_experts_form(held, k, tokens) is (tokens <= MOST[family])


@pytest.mark.parametrize("rows,chunk,form", [
    (1, 64, "all_experts"), (1, 128, "all_experts"), (4, 64, "dispatched"),
    (1, 256, "dispatched"), (1, 512, "dispatched"), (16, 1, "dispatched"),
    (2, 512, "dispatched"), (1, 2048, "dispatched"), (1, 16, "dispatched"),
    (2, 16, "all_experts"), (2, 64, "all_experts"), (1, 1, "dispatched")],
    ids=["64", "128", "4x64", "256", "512", "16-decode-rows", "2x512",
         "2048", "16", "2x16", "2x64", "one-row"])
def test_the_rule_at_256_experts_top_8(rows, chunk, form):
    """Laguna-XS.2's pair, from the table beside the rule (`models/llama.py`
    `all_experts_form`): set on the chip from whole `prefill_step` programs
    under a balanced router.  The all-experts form keeps the 64- and 128-token
    steps, where both forms read every expert and the sort is extra; 16
    tokens of 8 choices cannot reach half of 256 experts, and the dispatched
    form reads only those it reaches."""
    c = ModelConfig.from_hf_config(published()["model"])
    assert llama.moe_form(c, rows * chunk) == form
    assert llama.moe_form(dataclasses.replace(c, moe_impl="dense"),
                          rows * chunk) == "all_experts"
    assert llama.moe_form(dataclasses.replace(c, moe_impl="ragged"),
                          rows * chunk) == "dispatched"


# -- the engine -------------------------------------------------------------------- #

def engine_of(cfg, params, **over):
    ecfg = dict(page_size=PAGE, num_pages=96, max_num_seqs=4,
                max_prefill_tokens=16, max_model_len=128)
    parallel = over.pop("parallel", None)
    ecfg.update(over)
    return JaxEngine(cfg, params, EngineConfig(**ecfg), eos_token_ids=[],
                     kv_dtype=jnp.float32, parallel=parallel)


async def generate(engine, toks, n):
    out, lps = [], []
    async for d in engine.generate({
            "token_ids": toks,
            "sampling_options": {"temperature": 0.0, "logprobs": True},
            "stop_conditions": {"max_tokens": n, "ignore_eos": True}}):
        assert d.get("finish_reason") != "error", d
        out += d.get("token_ids", [])
        lps += d.get("log_probs", [])
    return out, lps


def events_of(engine, kind):
    return [e for e in engine.events.dump()["events"] if e["kind"] == kind]


@pytest.mark.parametrize("how", [
    {}, {"decode_steps": 4}, {"speculative_ngram_k": 3},
    {"mixed_prefill_tokens": 16},
], ids=["default", "block-of-4", "speculative", "mixed"])
async def test_engine_decodes_what_the_reference_decodes(cfg, params, ref,
                                                         how):
    """Chunked prefill across the window, the prefix cache (the later
    requests share 36 tokens: the hit ends at a page boundary INSIDE the
    next token's window of 8, so a windowed layer reads cached keys and a
    full one all of them) and each decode path a server can reach (per-step
    through the one loop, the verify step, the mixed step): the logprob of
    every greedy token against the reference's full forward pass."""
    engine = engine_of(cfg, params, **how)
    try:
        shared = prompt(36, 6)
        for tail in (prompt(5, 7), prompt(9, 8), prompt(5, 7)):
            toks = shared + tail
            got, lps = await generate(engine, toks, 5)
            text = list(toks)
            for t, lp_t in zip(got, lps):
                want = ref_logp(ref, cfg, params, text)[-1]
                assert t == int(want.argmax()), (how, len(text))
                assert abs(lp_t - want.max()) < 5 * TOL
                text.append(t)
        m = vars(engine.metrics())
        assert m["prefix_cache_hits_total" if "prefix_cache_hits_total" in m
                 else "moe_steps_total"] > 0
    finally:
        await engine.shutdown()


async def test_short_prompts_share_a_step_and_decode_beside_a_prefill(
        cfg, params, ref):
    """Four short prompts at once: their chunks share prefill steps (the
    `[4, 64]`-style step of the benchmark's cached questions) and later
    arrivals prefill beside the decode of earlier ones (the mixed step);
    each gets the reference's tokens."""
    engine = engine_of(cfg, params, decode_steps=2, max_prefill_tokens=64,
                       mixed_prefill_tokens=64)
    try:
        assert engine.cfg.short_chunk_bucket == 8
        texts = [prompt(n, 20 + n) for n in (7, 6, 5, 8)]
        outs = await asyncio.gather(*(generate(engine, t, 4) for t in texts))
        for toks, (got, _) in zip(texts, outs):
            text = list(toks)
            for t in got:
                assert t == int(ref_logp(ref, cfg, params, text)[-1].argmax())
                text.append(t)
        assert any(e["batch"] > 1 for e in events_of(engine, "prefill_chunk"))
    finally:
        await engine.shutdown()


async def test_steps_carry_the_form_of_their_expert_layers(cfg, params):
    """Every prefill-path step slice of an expert family carries `moe_form`
    beside its stats (assignments, experts touched, largest load), decode
    slices carry the form alone, and `/metrics.json` counts steps and tokens
    by form."""
    engine = engine_of(cfg, params)
    try:
        await generate(engine, prompt(40, 9), 3)
        for _ in range(200):  # a slice is recorded AFTER its token's delivery
            chunks = events_of(engine, "prefill_chunk")
            if len(chunks) == 3 and events_of(engine, "decode_block"):
                break
            await asyncio.sleep(0.01)
        assert len(chunks) == 3
        Lm, E, k = cfg.num_moe_layers, cfg.num_experts, cfg.num_experts_per_tok
        assert (Lm, E, k) == (6, 8, 2)
        for e in chunks:
            assert e["moe_form"] == "all_experts"  # 16 tokens x 8 experts
            assert e["moe_assignments"] == e["tokens"] * k * Lm
            assert 0 < e["experts_hit"] <= Lm * E
            assert e["moe_max_load"] <= e["tokens"]
        assert all(e["moe_form"] == "all_experts"
                   for e in events_of(engine, "decode_block"))
        m = vars(engine.metrics())
        assert m["moe_steps_total"] == m["moe_all_experts_steps_total"] == 3
        assert m["moe_all_experts_tokens_total"] == 40
        assert (m["moe_dispatched_steps_total"],
                m["moe_dispatched_tokens_total"]) == (0, 0)
    finally:
        await engine.shutdown()
    ragged = engine_of(dataclasses.replace(cfg, moe_impl="ragged"), params)
    try:
        await generate(ragged, prompt(20, 9), 1)
        m = vars(ragged.metrics())
        assert m["moe_dispatched_tokens_total"] == 20
        assert m["moe_all_experts_steps_total"] == 0
    finally:
        await ragged.shutdown()


@pytest.mark.parametrize("how,match", [
    ({"parallel": {"tp": 2}}, "serving mesh"),
    ({"parallel": {"pp": 2}, "max_prefill_tokens": 128}, "serving mesh"),
    ({"parallel": {"sp": 2}, "max_prefill_tokens": 128}, "serving mesh"),
    ({"parallel": {"dp": 2}, "kv_partition": True}, "serving mesh"),
    ({"fuse_projections": True}, "fuse_projections"),
    ({"quantization": "int8"}, "int8"),
    ({"decode_continuous": True, "decode_steps": 2}, "decode-continuous"),
], ids=["tp", "pp", "sp", "partitioned-pool", "fused-projections", "int8",
        "continuous"])
def test_paths_with_a_layer_body_of_their_own_refuse_the_family(cfg, params,
                                                                how, match):
    """One line at start-up, naming the key that asks."""
    from dynamo_tpu.parallel import ParallelConfig

    how = dict(how)
    if "parallel" in how:
        how["parallel"] = ParallelConfig(**how["parallel"])
    with pytest.raises(ValueError,
                       match=match + ".*num_attention_heads_per_layer"):
        engine_of(cfg, params, **how)


def test_step_kinds_with_a_layer_scan_of_their_own_refuse_the_family(cfg,
                                                                     params):
    kv = fresh_cache(cfg)
    one = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="embedding forward.*laguna"):
        llama.forward_embed(params, cfg, jnp.zeros((1, 4), jnp.int32), one + 4)
    with pytest.raises(ValueError, match="decode block.*laguna"):
        llama.decode_block_scan(params, cfg, kv, one, one, table_for(8), 2,
                                64, None, ())


# -- the benchmark's count ------------------------------------------------------------ #

def test_the_roofline_counts_each_layer_at_its_own_heads_and_reach():
    """`roofline/laguna.py` at the published widths against hand counts: the
    step's floor charges 8 experts a sparse layer and each layer's own
    projections; attention's floor 48 heads over the whole context in the
    two full layers and 64 over at most 512 keys in the five windowed."""
    sys.path.insert(0, BENCH)
    try:
        from lib import roofline
    finally:
        sys.path.remove(BENCH)
    run = published()
    fam, model = roofline.family(run), run["model"]
    attn = {48: 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48,
            64: 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64}
    expert = 3 * 2048 * 512
    sparse = 2048 * 256 + expert + 8 * expert
    assert fam.every_step_params(model) == (
        attn[48] + 3 * 2048 * 8192 + attn[48] + sparse
        + 5 * (attn[64] + sparse)) == 471_662_592
    secs, which = fam.prefill_step_floor_s(model, PEAKS, 512)
    assert which == "compute"
    assert abs(secs - 2 * 512 * 471_662_592 / 197e12) < 1e-12
    assert fam.prefill_step_floor_s(model, PEAKS, 64)[1] == "memory"
    # a 512-token chunk whose last token sees 6,144 keys
    tokens, ctx = 512, 6144
    prefix = ctx - tokens
    full = tokens * prefix + tokens * (tokens + 1) // 2
    windowed = tokens * 512  # every token is past 512 keys of context
    flop = 4 * 128 * (2 * 48 * full + 5 * 64 * windowed) / 197e12
    got, which = fam.prefill_attn_floor_s(model, PEAKS, tokens, ctx)
    assert which == "compute" and abs(got - flop) < 1e-12
    assert full / windowed > 11  # the kinds part: 12 times the keys
    # the routed experts alone: bytes of what was touched, or the products
    t, which = fam.routed_experts_floor_s(model, PEAKS, 6 * 512 * 8, 600)
    assert which == "memory" and abs(t - 2 * 600 * expert / 819e9) < 1e-12
    t, which = fam.routed_experts_floor_s(model, PEAKS, 6 * 512 * 8, 60)
    assert which == "compute"
    assert abs(t - 2 * 6 * 512 * 8 * expert / 197e12) < 1e-12


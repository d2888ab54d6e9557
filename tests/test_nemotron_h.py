"""nemotron_h on the served path (ISSUE 44): the family's config keys, its
checkpoint names through the loader, the one layer loop over three kinds of
mixer against the plain reference (`benchmark/reference/nemotron_h.py`: the
recurrence, token by token), the chunked scan and its padding, the state
slots beside the pages with their snapshots, the chip's share of a layer's
ungated experts, the layouts that refuse the family, and the benchmark's
count and trace readers.  Tiny sizes, float32, seeded weights (`A_log`,
`dt_bias` drawn as the family initialises them: a state that REMEMBERS), CPU.
"""

import asyncio
import contextlib
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.analysis import xla_ledger
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.page_pool import PagePool, StatePool
from dynamo_tpu.models import KVCache, ModelConfig, init_params
from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models.loader import load_params
from dynamo_tpu.ops import pallas_moe, ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
PAGE = 8
CELL = "nemotron3-nano-30b-ep8"

# all three kinds, two "*": units (M,-,E) (M,*,E) (M,*,E) (M,-,E)
TINY = {
    "model_type": "nemotron_h", "vocab_size": 300, "hidden_size": 64,
    "num_hidden_layers": 10, "hybrid_override_pattern": "MEM*EM*EME",
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "mamba_hidden_act": "silu", "mamba_proj_bias": False,
    "use_conv_bias": True, "use_bias": False,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": False, "rope_theta": 10000, "partial_rotary_factor": 1,
    "intermediate_size": 32, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "mlp_hidden_act": "relu2",
    "mlp_bias": False, "n_routed_experts": 4, "ep_size": 2,
    "n_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "residual_in_fp32": False, "sliding_window": None,
}
# units that lack a kind: (-,*,-) (M,-,E) (M,-,-): every kind under a cond
ODD = dict(TINY, num_hidden_layers=4, hybrid_override_pattern="*MEM")


def bench_module(kind_dir, name):
    sys.path.insert(0, BENCH)
    try:
        from lib import checkpoint
    finally:
        sys.path.remove(BENCH)
    return checkpoint.load_module(kind_dir, name)


@pytest.fixture(scope="module")
def ref():
    return bench_module("reference", "nemotron_h")


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(
        ModelConfig.from_hf_config(TINY, name="tiny-nemotron-h"),
        moe_impl="ragged")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(44), dtype=jnp.float32)


def reader_of(params, cfg, experts=None):
    """`read(name)` over a param tree, under the family's tensor names (the
    loader's mapping, backwards).  `experts` {global index: (stack, local
    index)} overrides where an expert's matrices come from."""
    flat = {"backbone.embeddings.weight": params["embed"],
            "backbone.norm_f.weight": params["final_norm"],
            "lm_head.weight": params["lm_head"].T}
    at = {"M": 0, "*": 0, "E": 0}
    for i, kind in enumerate(cfg.layer_pattern):
        p, j = f"backbone.layers.{i}.", at[kind]
        m = p + "mixer."
        at[kind] += 1
        if kind == "M":
            lay = params["ssm_layers"]
            flat.update({
                m + "in_proj.weight": lay["in_proj"][j].T,
                m + "conv1d.weight": np.asarray(lay["conv_w"][j]).T[:, None],
                m + "conv1d.bias": lay["conv_b"][j],
                m + "dt_bias": lay["dt_bias"][j], m + "A_log": lay["A_log"][j],
                m + "D": lay["D"][j], m + "norm.weight": lay["gate_norm"][j],
                m + "out_proj.weight": lay["out_proj"][j].T})
        elif kind == "*":
            lay = params["attn_layers"]
            for n in "qkvo":
                flat[m + f"{n}_proj.weight"] = lay["w" + n][j].T
        else:
            lay = params["moe_layers"]
            flat[m + "gate.weight"] = lay["router"][j].T
            flat[m + "gate.e_score_correction_bias"] = lay["router_bias"][j]
            held = experts or {cfg.first_expert + e: (lay, e)
                               for e in range(cfg.num_experts)}
            for e, (stack, le) in held.items():
                for n in ("up", "down"):
                    flat[m + f"experts.{e}.{n}_proj.weight"] = (
                        stack[f"w_{n}"][j, le].T)
            for n in ("up", "down"):
                flat[m + f"shared_experts.{n}_proj.weight"] = (
                    lay[f"ws_{n}"][j].T)
        flat[p + "norm.weight"] = lay["norm"][j]
    return lambda name: np.asarray(flat[name], np.float32)


def with_slots(pages, slots):
    """A table of pages [B, W] with each row's state columns behind it:
    [read, write] and, 0 where not given, the slots inside the chunk."""
    cols = np.zeros((len(pages), hybrid.STATE_COLS), np.int32)
    for row, given in zip(cols, slots):
        row[:len(given)] = given
    return jnp.asarray(np.concatenate(
        [np.asarray(pages, np.int32), cols], axis=1))


def table_for(n_tokens, slots, batch=1):
    """Pages 1.. a row, then the row's state slots."""
    pages = -(-n_tokens // PAGE)
    t = np.arange(1, 1 + batch * pages, dtype=np.int32).reshape(batch, pages)
    return with_slots(t, np.asarray(slots).reshape(batch, -1))


def logp(logits):
    return np.asarray(jax.nn.log_softmax(
        jnp.asarray(logits, jnp.float32), axis=-1))


def fresh_cache(cfg, tokens=128, slots=6):
    return KVCache.create(cfg, 2 + -(-tokens // PAGE), PAGE, jnp.float32,
                          state_slots=slots)


def prefill_all(cfg, params, tokens, chunk=None, kv=None, slot=1):
    """Chunked prefill of one prompt through both pools (its state in slot
    `slot`): [(position, next-token logprobs)] a chunk, the cache."""
    T = len(tokens)
    chunk = chunk or T
    kv = kv if kv is not None else fresh_cache(cfg, T + 8 * PAGE)
    out = []
    for s in range(0, T, chunk):
        part = tokens[s:s + chunk]
        logits, kv = llama.forward_prefill(
            params, cfg, kv, jnp.asarray([part], jnp.int32),
            table_for(T + 8 * PAGE, [slot if s else 0, slot]),
            jnp.asarray([s], jnp.int32), jnp.asarray([len(part)], jnp.int32))
        out.append((s + len(part) - 1, logp(logits)[0]))
    return out, kv


def ref_logp(ref, cfg, params, tokens, model=TINY, **controls):
    """Reference next-token logprobs after every position: [T, vocab]."""
    return ref.forward(reader_of(params, cfg), model,
                       [np.asarray([tokens])], len(tokens), **controls)[0][0]


def prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(4, 290, n)]


TOL = 3e-4  # float32 on both sides; sums in another order


def published():
    with open(os.path.join(BENCH, "configs", CELL + ".json")) as f:
        return json.load(f)


# -- configuration ------------------------------------------------------------- #

def test_from_hf_config_reads_the_published_keys():
    """The catalog row's keys as published (52 layers, 128 experts, ep_size
    1) and as run: the model's name is its parameter count."""
    run = published()
    model = dict(run["model"])
    model.update({k: v["published"] for k, v in run["reduced"].items()})
    c = ModelConfig.from_hf_config(model)
    pat = c.layer_pattern
    assert (len(pat), pat.count("M"), pat.count("E"), pat.count("*")) == (
        52, 23, 23, 6)
    assert (c.ssm_inner, c.ssm_conv_dim, c.ssm_groups, c.ssm_state) == (
        4096, 6144, 8, 128)
    assert (c.num_experts, c.router_width, c.num_experts_per_tok) == (
        128, 128, 6)
    assert (c.moe_act, c.moe_scoring, c.moe_routed_scale) == (
        "relu2", "sigmoid", 2.5)
    assert c.shared_expert_width == 3712 and not c.attention_rope
    assert (c.num_kv_layers, c.num_moe_layers) == (6, 23)
    assert abs(c.num_params() - 31.58e9) < 0.01e9
    cut = ModelConfig.from_hf_config(run["model"])
    assert (cut.num_experts, cut.router_width, cut.first_expert) == (
        16, 128, 0)
    assert cut.num_params() == 5_258_420_544
    assert cut.num_params() == sum(
        int(np.prod(shape)) for _, shape, _ in bench_module(
            "checkpoints", "nemotron_h").tensors(run["model"]))
    # bf16, but the 23 x (3 x 64 + 128) values held in float32
    assert run["memory"]["weights_bytes"] - cut.num_params() * 2 == (
        23 * (3 * 64 + 128) * 2)
    spec = cut.state_spec
    assert spec.bytes_per_slot(2) == run["memory"]["state_bytes_per_slot"] == (
        49_082_368)
    assert spec.window_dims == (144, 128) and spec.state_dims == (64, 64, 128)
    assert 6 * cut.cache_spec.bytes_per_token_layer(2) == (
        run["memory"]["kv_bytes_per_token"]) == 6144
    shapes = jax.eval_shape(lambda: KVCache.create(cut, 64, 16,
                                                   state_slots=8))
    assert shapes.k.shape == (6, 64, 16, 2, 128)
    assert shapes.conv.shape == (23, 8, 144, 128)
    assert shapes.ssm.shape == (23, 8, 64, 64, 128)
    assert shapes.ssm.dtype == jnp.float32
    assert [u.sum() for u in hybrid.units_of(pat).has.T] == [23, 6, 23]
    assert len(hybrid.units_of(pat).has) == 23


@pytest.mark.parametrize("rows,chunk,form", [
    (1, 512, "dispatched"), (2, 512, "dispatched"), (1, 256, "all_experts"),
    (4, 64, "all_experts"), (1, 64, "dispatched"), (1, 16, "dispatched"),
    (1, 128, "dispatched")],
    ids=["512", "2x512", "256", "4x64", "64", "16", "128"])
def test_the_cells_long_steps_dispatch_and_its_short_ones_do_not(rows, chunk,
                                                                 form):
    """The cell's share (16 held of 128, top 6) at the form its whole
    `prefill_step` programs chose on the chip under a balanced router
    (`models/llama.py` `_TIMED`; PERF.md findings 34 and 36): a 512-token
    step is the dispatched form's, since the grouped kernel takes the
    1,856-wide stacks as they are stored (`ops/pallas_moe.py` `f_major`),
    and so, since the kernel moves its own rows and leaves those of experts
    held elsewhere alone (PR 56), is a step of up to 128 tokens; 256 tokens,
    one row or four of 64, stay all-experts.  This is what the step events
    and `/metrics.json` carry as `moe_form`."""
    c = ModelConfig.from_hf_config(published()["model"])
    assert (c.num_experts, c.num_experts_per_tok) == (16, 6)
    assert llama.moe_form(c, rows * chunk) == form
    h, f = c.hidden_size, c.moe_intermediate_size
    assert pallas_moe.f_major(h, f)
    assert pallas_moe.width_block(h, f, 2, 2) == f == 1856


def test_the_file_states_each_published_key_once_for_each_reader():
    """Top-level keys (what the driver's check reads) equal `model` (what
    the program gets); only the keys of `reduced` differ from the source,
    each by its stated `run` value; no width among them."""
    run = published()
    model = dict(run["model"])
    assert model.pop("architectures") == ["NemotronHForCausalLM"]
    assert model.pop("torch_dtype") == "bfloat16"
    assert {k: run[k] for k in model} == model
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [c for c in json.load(f)["configs"] if c["name"] == CELL]
    assert sorted(entry["reduced"]) == sorted(run["reduced"]) == [
        "ep_size", "n_routed_experts", "vocab_size"]
    for key, cut in run["reduced"].items():
        assert run[key] == cut["run"] != cut["published"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row, = [r for r in map(json.loads, f)
                if r["source_url"] == run["source"]]
    for key, value in row["config"].items():
        if key not in run["reduced"]:
            assert run[key] == value, key
        else:
            assert run["reduced"][key]["published"] == value


@pytest.mark.parametrize("bad,key", [
    ({"hybrid_override_pattern": "MEM*EM*EM-"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "ME"}, "hybrid_override_pattern"),
    ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"use_bias": True}, "use_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"residual_in_fp32": True}, "residual_in_fp32"),
    ({"sliding_window": 128}, "sliding_window"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"n_shared_experts": 2}, "n_shared_experts"),
    ({"time_step_limit": [0.0, 0.5]}, "time_step_limit"),
    ({"n_groups": 3}, "n_groups"),
    ({"norm_eps": 1e-6}, "norm_eps"),
    ({"n_routed_experts": 0}, "n_routed_experts"),
    ({"model_type": "nemotron_x"}, "hybrid_override_pattern"),
    ({"ep_rank": 2}, "moe_ep_rank"),
], ids=["dense-layer", "short-pattern", "mamba-act", "gated-experts",
        "bias", "mamba-bias", "mlp-bias", "attention-bias", "no-conv-bias",
        "fp32-residual", "window", "unnormalised", "two-shared",
        "clamped-step", "uneven-groups", "two-epsilons", "no-experts",
        "another-family", "rank-out-of-range"])
def test_from_hf_config_refuses_what_it_cannot_compute(bad, key):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config(dict(TINY, **bad))


# -- checkpoint names through the loader ----------------------------------------- #

def test_written_checkpoint_loads_and_agrees_with_the_reference(tmp_path, ref):
    """`benchmark/lib/checkpoint.py` + `checkpoints/nemotron_h.py` write the
    family's tensors (held experts only, under their global indices);
    `models/loader.py` reads them; a chunked prefill over the loaded tree
    agrees with the reference reading the same file."""
    from safetensors import safe_open

    ckpt = bench_module("lib", "checkpoint")
    layout = bench_module("checkpoints", "nemotron_h")
    rank1 = dict(TINY, ep_rank=1)
    names = [n for n, _, _ in layout.tensors(rank1)]
    assert "backbone.layers.1.mixer.experts.4.up_proj.weight" in names
    assert "backbone.layers.1.mixer.experts.0.up_proj.weight" not in names
    assert not any("gate_proj" in n for n in names)
    assert "backbone.layers.3.mixer.q_proj.weight" in names
    assert "backbone.layers.0.mixer.conv1d.weight" in names
    model = dict(rank1, architectures=["NemotronHForCausalLM"],
                 torch_dtype="bfloat16")
    ckpt.write({"model": model, "weights_seed": 5,
                "checkpoint": "nemotron_h"}, str(tmp_path))
    c = dataclasses.replace(ModelConfig.from_pretrained(str(tmp_path)),
                            moe_impl="ragged")
    assert c.first_expert == 4
    p = load_params(str(tmp_path), c, dtype=jnp.float32)
    assert p["ssm_layers"]["conv_w"].shape == (4, 4, 128)
    assert p["ssm_layers"]["A_log"].dtype == jnp.float32
    assert "w_gate" not in p["moe_layers"] and "ws_gate" not in p["moe_layers"]
    reader = safe_open(str(tmp_path / "model.safetensors"), framework="np")
    toks = prompt(40, 1)
    want = ref.forward(
        lambda n: reader.get_tensor(n).astype(np.float32), rank1,
        [np.asarray([toks])], len(toks))[0][0]
    for pos, got in prefill_all(c, p, toks, chunk=16)[0]:
        assert np.abs(got - want[pos]).max() < TOL


# -- the forward paths against the recurrence ---------------------------------------- #

@pytest.mark.parametrize("chunk", [None, 32, 13],
                         ids=["one-chunk", "two-chunks", "five-chunks"])
def test_chunked_prefill_agrees_with_the_reference(cfg, params, ref, chunk):
    """Prefill in 1, 2 and 5 chunks (13 does not divide 64, and is no
    multiple of the scan's block of 8... so such a chunk runs padded to 16),
    then 8 decode steps through both pools, against the reference's full
    forward over the text: logits, not tokens."""
    toks = prompt(72, 2)
    P = 64
    want = ref_logp(ref, cfg, params, toks)
    if chunk == 13:  # a bucket's padding: 13 real tokens of 16
        kv, out = fresh_cache(cfg, 200), []
        for s in range(0, P, 13):
            part = toks[s:min(s + 13, P)]
            logits, kv = llama.forward_prefill(
                params, cfg, kv,
                jnp.asarray([part + [0] * (16 - len(part))], jnp.int32),
                table_for(136, [1 if s else 0, 1]),
                jnp.asarray([s], jnp.int32), jnp.asarray([len(part)],
                                                         jnp.int32))
            out.append((s + len(part) - 1, logp(logits)[0]))
    else:
        out, kv = prefill_all(cfg, params, toks[:P], chunk)
    for pos, got in out:
        assert np.abs(got - want[pos]).max() < TOL, pos
    table = table_for(136, [1, 1])
    for i in range(8):
        logits, kv = llama.forward_decode(
            params, cfg, kv, jnp.asarray([toks[P + i]], jnp.int32),
            jnp.asarray([P + i], jnp.int32), table)
        assert np.abs(logp(logits)[0] - want[P + i]).max() < TOL, i


def test_a_pattern_whose_units_lack_a_kind_agrees_too(ref):
    """`*MEM`: units (-,*,-) (M,-,E) (M,-,-): every kind runs under its
    `lax.cond` and indexes its own stack."""
    c = dataclasses.replace(ModelConfig.from_hf_config(ODD), moe_impl="dense")
    units = hybrid.units_of(c.layer_pattern)
    assert units.has.tolist() == [[False, True, False], [True, False, True],
                                  [True, False, False]]
    assert not units.has.all(0).any()
    p = init_params(c, jax.random.PRNGKey(4), dtype=jnp.float32)
    toks = prompt(40, 3)
    want = ref_logp(ref, c, p, toks, model=ODD)
    for pos, got in prefill_all(c, p, toks, chunk=16)[0]:
        assert np.abs(got - want[pos]).max() < TOL


# the scan's two forms: the blocks as plain `jnp` at tiny widths, and the
# kernel (interpreted) at this family's geometry: 8 heads of 64 a group, 128
# state values; (form, rows, block, tokens)
SCAN_CASES = [("jnp", 2, 8, 64), ("jnp", 2, 16, 48), ("jnp", 2, 64, 40),
              ("jnp", 2, 8, 1), ("kernel", 1, 128, 128),
              ("kernel", 4, 128, 200), ("kernel", 1, 128, 512)]


def scan_operands(rng, form, geometry, B, block, tokens, dtype=np.float32):
    """(x, dt, A, Bm, Cm, D, h0) of a scan over `tokens` real tokens padded
    to whole blocks (a zero step size past them; the rows after the first
    end eight tokens earlier each), and S.  `geometry` (heads, head_dim,
    groups, state) is the kernel's; the `jnp` form runs at tiny widths."""
    nh, hp, G, N = geometry if form == "kernel" else (4, 8, 2, 16)
    S = -(-tokens // min(block, tokens)) * min(block, tokens)
    x = rng.standard_normal((B, S, nh, hp)).astype(dtype)
    dt = rng.uniform(0.001, 0.1, (B, S, nh)).astype(np.float32)
    for b in range(B):
        dt[b, max(tokens - 8 * b, 1):] = 0.0
    A = -rng.uniform(1, 16, nh).astype(np.float32)
    Bm = (rng.standard_normal((B, S, G, N)) * N ** -0.5 * 4).astype(dtype)
    Cm = (rng.standard_normal((B, S, G, N)) * N ** -0.5 * 4).astype(dtype)
    D = rng.standard_normal(nh).astype(np.float32)
    h0 = rng.standard_normal((B, nh, hp, N)).astype(np.float32)
    return (x, dt, A, Bm, Cm, D, h0), S


def scan_in_form(form, ops, block, at):
    """`ops.ssm.scan` in one of its two forms: where it chooses by itself
    (the CPU: `jnp`), or under a check that interprets the kernel."""
    ops = tuple(map(jnp.asarray, ops))
    B, S = ops[0].shape[:2]
    if form == "jnp":
        out = ssm.scan(*ops, block, at)
        want = "xla"
    else:
        with pallas_moe.checked(interpret=True):
            out = ssm.scan(*ops, block, at)
        want = "pallas"
    assert xla_ledger.path_choice("ssm_scan", rows=B, chunk=S) == want
    return out


def recurrence(ops, tokens, at, y, h, hs, tol=2e-4):
    """y, h and the states handed out after `at` against the token-by-token
    loop over the first `tokens` positions."""
    x, dt, A, Bm, Cm, D, h0 = (np.asarray(a, np.float32) for a in ops)
    nh, G = x.shape[2], Bm.shape[2]
    H, group = h0.copy(), np.arange(nh) // (nh // G)
    for t in range(tokens):
        H = (np.exp(dt[:, t] * A)[..., None, None] * H
             + (dt[:, t, :, None] * x[:, t])[..., None]
             * Bm[:, t, group][:, :, None, :])
        want = np.einsum("bhpn,bhn->bhp", H, Cm[:, t, group]) + (
            D[:, None] * x[:, t])
        assert np.abs(np.asarray(y[:, t]) - want).max() < tol, t
        if t + 1 in at:  # handed out at a block's end, or inside a block
            assert np.abs(np.asarray(hs[at.index(t + 1)]) - H).max() < tol
    assert np.abs(np.asarray(h) - H).max() < tol


@pytest.mark.parametrize("form,rows,block,tokens", SCAN_CASES,
                         ids=lambda v: str(v))
def test_the_chunked_scan_is_the_recurrence(form, rows, block, tokens):
    """`ops.ssm.scan` in both forms, at block sizes that do and do not
    divide the length (the caller pads to whole blocks with a zero step
    size), against the token-by-token loop, from a carried state; the
    kernel also against the `jnp` form, every handed-out state compared."""
    rng = np.random.default_rng(block + tokens)
    ops, S = scan_operands(rng, form, (16, 64, 2, 128), rows, block, tokens)
    if form == "jnp":  # after every whole block, and in the middle of each
        at = tuple(sorted({*range(block, S, block),
                           *range(block // 2, S, block)} - {0}))
    else:  # where the served path hands out: the blocks' ends
        at = tuple(range(block, S, block))
    y, h, hs = scan_in_form(form, ops, block, at)
    assert len(hs) == len(at)
    recurrence(ops, tokens, at, y, h, hs)
    if form == "kernel":
        for got, want in zip((y, h, *hs), jax.tree.leaves(
                ssm.scan_blocks(*map(jnp.asarray, ops), block, at))):
            assert np.abs(np.asarray(got - want)).max() < 2e-5


def test_the_scan_kernel_rounds_as_the_blocks_do_in_the_served_dtype():
    """bf16 x, B and C: the kernel's y and states lie within the served
    dtype's rounding of the `jnp` form's (on a TPU both hand the matrix unit
    bf16 operands; here the `jnp` form's state products stay float32)."""
    rng = np.random.default_rng(7)
    ops, S = scan_operands(rng, "kernel", (16, 64, 2, 128), 2, 128, 256,
                           jnp.bfloat16)
    y, h, hs = scan_in_form("kernel", ops, 128, (128,))
    want_y, want_h, want_hs = ssm.scan_blocks(*map(jnp.asarray, ops), 128,
                                              (128,))
    assert y.dtype == jnp.bfloat16 and h.dtype == jnp.float32
    scale = float(jnp.abs(want_y.astype(jnp.float32)).max())
    assert np.abs(np.asarray(y.astype(jnp.float32)
                             - want_y.astype(jnp.float32))).max() < scale / 64
    for got, want in ((h, want_h), (hs[0], want_hs[0])):
        assert np.abs(np.asarray(got - want)).max() < float(
            jnp.abs(want).max()) / 128


@pytest.mark.parametrize("shape,at,check,choice", [
    ((1, 512, 64, 64, 8, 128), (128, 256, 384), False, "pallas"),
    ((1, 128, 64, 64, 8, 128), (), False, "pallas"),
    ((1, 256, 32, 128, 2, 256), (128,), True, "pallas"),
    ((4, 64, 64, 64, 8, 128), (16, 32, 48), False, "xla"),  # a short row
    ((1, 512, 64, 64, 8, 128), (64, 128), False, "xla"),  # inside a block
    ((1, 512, 8, 8, 2, 16), (128,), False, "xla"),  # no whole lane tiles
    ((1, 512, 64, 64, 8, 128), (128, 256, 384), None, "xla"),  # the CPU
], ids=["512", "128", "falcon-256", "short-row", "inside-a-block",
        "narrow", "cpu"])
def test_the_scan_takes_the_kernel_where_its_blocks_end_at_the_hand_outs(
        shape, at, check, choice):
    """`pallas_ssm.scan_lowering` over static shapes (rows, tokens, heads,
    head_dim, groups, state) and the hand-outs: the 128-, 256- and
    512-token chunks of both geometries are the kernel's under a check (and
    on a single TPU device); a short row, a hand-out inside a block, widths
    its tiles do not hold, the CPU by itself and a mesh keep `jnp`."""
    from dynamo_tpu.ops import pallas_ssm

    B, S, nh, hp, G, N = shape
    x = jax.ShapeDtypeStruct((B, S, nh, hp), jnp.bfloat16)

    def lowering(x):
        return pallas_ssm.scan_lowering(x, G, N, 128, at)

    if check is None:
        interpret, why = lowering(x)
    else:
        with pallas_moe.checked(interpret=check):
            interpret, why = lowering(x)
    assert (None if interpret is None else "pallas", interpret) == (
        None if choice == "xla" else "pallas",
        check if choice == "pallas" else None), why
    assert pallas_ssm.scan_block(S, 128, at) == (
        16 if at == (16, 32, 48) else 64 if at == (64, 128) else 128)


def test_a_mesh_keeps_the_scan_as_jnp(monkeypatch):
    """On a TPU backend an operand that lies on a mesh keeps the `jnp` form
    (a Pallas call does not partition) and one that does not takes the
    kernel; the backend is this test's say-so, nothing is compiled."""
    from jax.sharding import NamedSharding, PartitionSpec

    from dynamo_tpu.ops import pallas_ssm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = jax.make_mesh((2,), ("x",))
    x = jnp.zeros((2, 256, 16, 64), jnp.bfloat16)
    seen = []

    def trace(x):
        seen.append(pallas_ssm.scan_lowering(x, 2, 128, 128, (128,))[0])
        return x

    jax.eval_shape(trace, x)
    jax.jit(trace).lower(jax.device_put(
        x, NamedSharding(mesh, PartitionSpec("x"))))
    assert seen == [False, None]


def test_a_step_slice_says_scan_only_for_a_model_with_a_mamba2_layer(cfg):
    """`JaxEngine._scan_of`: the noted choice for the step's shape under
    `scan`; no key before a trace noted one, and none for a model without a
    Mamba-2 layer even where another model in the process noted that shape
    (the ledger's choices are the process's)."""
    import types

    from dynamo_tpu.models import tiny_config

    xla_ledger.note_path_choice("ssm_scan", "xla", "a test's", rows=3,
                                chunk=48)
    mine = types.SimpleNamespace(model_cfg=cfg)
    assert JaxEngine._scan_of(mine, 3, 48) == {"scan": "xla"}  # noqa: SLF001
    assert JaxEngine._scan_of(mine, 3, 40) == {}  # noqa: SLF001
    other = types.SimpleNamespace(model_cfg=tiny_config())
    assert JaxEngine._scan_of(other, 3, 48) == {}  # noqa: SLF001


def test_pad_positions_leave_the_state_where_the_last_real_token_left_it(
        cfg, params):
    """The shared short step (4 rows of unequal lengths, one of them an
    empty pad row) and a bucket's padding: each row gets its lone answer and
    writes back its lone run's state (to rounding: another batch is another
    program), and what the pad positions HOLD moves nothing: with other
    tokens there the same program writes the same bits."""
    lens = [16, 5, 11]
    rows = [prompt(n, 20 + n) for n in lens]
    kv0 = fresh_cache(cfg, 4 * 16, slots=8)

    def pad(r, fill=0):
        return r + [fill] * (16 - len(r))

    table = with_slots([[1, 2], [3, 4], [5, 6], [0, 0]],
                       [[0, 1], [0, 2], [0, 3], [0, 0]])

    def shared(fill):
        return llama.forward_prefill(
            params, cfg, kv0,
            jnp.asarray([pad(r, fill) for r in rows] + [[fill] * 16],
                        jnp.int32),
            table, jnp.zeros((4,), jnp.int32),
            jnp.asarray(lens + [1], jnp.int32))

    logits, kv = shared(0)
    logits_b, kv_b = shared(123)
    assert np.array_equal(np.asarray(logits[:3]), np.asarray(logits_b[:3]))
    for a, b in ((kv.ssm, kv_b.ssm), (kv.conv, kv_b.conv)):
        assert np.array_equal(np.asarray(a[:, 1:]), np.asarray(b[:, 1:]))
    for i, r in enumerate(rows):
        lone, kv1 = llama.forward_prefill(
            params, cfg, kv0, jnp.asarray([pad(r)], jnp.int32),
            table[i:i + 1], jnp.zeros((1,), jnp.int32),
            jnp.asarray([len(r)], jnp.int32))
        assert np.abs(np.asarray(logits[i] - lone[0])).max() < 1e-5
        for both, own in ((kv.ssm, kv1.ssm), (kv.conv, kv1.conv)):
            assert np.abs(np.asarray(both[:, i + 1] - own[:, i + 1])
                          ).max() < 1e-5
        if len(r) % cfg.ssm_chunk:  # whole blocks only: pad to the block
            continue
        bare, kv2 = llama.forward_prefill(
            params, cfg, kv0, jnp.asarray([r], jnp.int32), table[i:i + 1],
            jnp.zeros((1,), jnp.int32), jnp.asarray([len(r)], jnp.int32))
        assert np.abs(np.asarray(lone - bare)).max() < 1e-5
    # an 11-token row padded to 16 holds what 11 decode steps leave
    kvd = kv0
    for t, tok in enumerate(rows[2]):
        _, kvd = llama.forward_decode(
            params, cfg, kvd, jnp.asarray([tok], jnp.int32),
            jnp.asarray([t], jnp.int32),
            with_slots([[5, 6]], [[3 if t else 0, 3]]))
    assert np.abs(np.asarray(kv.ssm[:, 3] - kvd.ssm[:, 3])).max() < 1e-5
    assert np.abs(np.asarray(kv.conv[:, 3] - kvd.conv[:, 3])).max() < 1e-5
    # a pad row reads no state and writes the trash slot alone
    assert not np.asarray(kv.ssm[:, 4:]).any()


# the tiny model at widths the scan's kernel holds: 2 heads of 64, one group,
# 128 state values, blocks of 128 tokens
KERNEL_TINY = dict(TINY, num_hidden_layers=3, hybrid_override_pattern="M*M",
                   mamba_num_heads=2, mamba_head_dim=64, n_groups=1,
                   ssm_state_size=128, chunk_size=128)


@pytest.mark.parametrize("form,tokens,at", [
    ("jnp", 64, (16, 32, 48)), ("jnp", 32, (8, 16, 24)),
    ("kernel", 512, (128, 256, 384))],
    ids=["every-interval", "short-row-every-page", "kernel-block-ends"])
def test_the_scan_hands_out_the_state_inside_a_chunk(cfg, params, every16,
                                                     monkeypatch, form,
                                                     tokens, at):
    """A 64-token chunk with snapshot positions every 16: the slots named in
    the table's last columns take the state after 16, 32 and 48 tokens, each
    what a prefill of that many tokens alone leaves (to rounding: another
    program), and the chunk's own slot its state after all 64.  A short row
    (four pages of 8 at most) hands out after every page.  And through the
    scan's kernel (interpreted; a model at widths its tiles hold): a
    512-token chunk hands out at its blocks' ends, 128, 256 and 384."""
    if form == "kernel":
        monkeypatch.setattr(hybrid, "SNAPSHOT_BLOCKS", 1)
        cfg = ModelConfig.from_hf_config(KERNEL_TINY, name="tiny-kernel")
        params = init_params(cfg, jax.random.PRNGKey(45), dtype=jnp.float32)
    else:
        assert hybrid._inside(cfg, 16, PAGE) == (8,)  # noqa: SLF001
        assert hybrid._inside(cfg, 1, PAGE) == ()  # noqa: SLF001
        assert hybrid.handout_every(cfg, 128, 16) == 16
    assert hybrid._inside(cfg, tokens, PAGE) == at  # noqa: SLF001
    toks = prompt(tokens, 9)
    kv0 = fresh_cache(cfg, tokens, slots=8)

    def prefill(toks, slots):
        with pallas_moe.checked(interpret=True) if form == "kernel" else (
                contextlib.nullcontext()):
            return llama.forward_prefill(
                params, cfg, kv0, jnp.asarray([toks], jnp.int32),
                table_for(tokens, slots), jnp.zeros((1,), jnp.int32),
                jnp.asarray([len(toks)], jnp.int32))[1]

    kv = prefill(toks, [0, 1, 2, 3, 4])
    assert xla_ledger.path_choice("ssm_scan", rows=1, chunk=tokens) == (
        "pallas" if form == "kernel" else "xla")
    for slot, n in (*zip((2, 3, 4), at), (1, tokens)):
        alone = prefill(toks[:n], [0, 5])
        for pool, want in ((kv.ssm, alone.ssm), (kv.conv, alone.conv)):
            assert np.abs(np.asarray(pool[:, slot] - want[:, 5])).max() < 1e-5
    assert not np.asarray(kv.ssm[:, 5:]).any()


@pytest.mark.parametrize("control", [
    {"lower_precision": True},
    *({"faults": (f,)} for f in (
        "state_not_carried", "pad_advances_state", "window_dropped",
        "norm_before_gate", "norm_ungrouped", "no_d_skip", "relu_not_squared",
        "gated_experts", "wrong_group", "decay_without_dt")),
], ids=lambda c: c.get("faults", ("lower-precision",))[0])
def test_the_comparison_catches(cfg, params, ref, control):
    """What the benchmark's `correct` rests on, at the tiny size: against
    the reference computed with one thing wrong (the chunk-boundary faults
    16 tokens before the compared position), the model is out of the
    tolerance that it meets against the reference as written."""
    assert set(control.get("faults", ())) <= set(ref.FAULTS)
    toks = prompt(48, 3)
    (_, got), = prefill_all(cfg, params, toks)[0]
    assert np.abs(got - ref_logp(ref, cfg, params, toks)[-1]).max() < TOL
    wrong = ref_logp(ref, cfg, params, toks, fault_chunk=32, **control)
    assert np.abs(got - wrong[-1]).max() > 10 * TOL, control
    assert len(ref.FAULTS) == 10


# -- the chip's share of the ungated experts ---------------------------------------- #

@pytest.mark.parametrize("impl", ["dense", "ragged"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(cfg, params, ref,
                                                         impl):
    """With `ep_size` 2 (as the cell's 8): the two ranks' expert-layer
    outputs, the shared expert counted once, add up to the reference's uncut
    layer (every expert held, `ep_size` 1)."""
    lay = params["moe_layers"]
    E = cfg.num_experts
    key = jax.random.PRNGKey(9)
    other = {k: jax.random.normal(key, lay[k].shape, jnp.float32) * 0.1
             for k in ("w_up", "w_down")}
    stacks = [lay, dict(lay, **other)]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, cfg.hidden_size),
                          jnp.float32)
    total = 0.0
    for rank, stack in enumerate(stacks):
        c = dataclasses.replace(cfg, moe_ep_rank=rank, moe_impl=impl)
        lp = jax.tree.map(lambda a: a[0], stack)
        routed = llama._moe(  # noqa: SLF001
            {k: v for k, v in lp.items() if not k.startswith("ws_")}, x, c)
        total = total + np.asarray(routed)
    total = total + np.asarray(llama._moe_shared(  # noqa: SLF001
        jax.tree.map(lambda a: a[0], lay), x))
    whole = dict(TINY, n_routed_experts=2 * E, ep_size=1)
    everyone = {r * E + e: (stacks[r], e) for r in range(2) for e in range(E)}
    read = reader_of(params, cfg, experts=everyone)
    want, = ref.experts(np, read, "backbone.layers.1.mixer.", whole,
                        [np.asarray(x)])
    assert np.abs(total - want).max() < 2e-4
    assert "w_gate" not in lay  # an unused gate is not computed: none is held


@pytest.mark.parametrize("tokens", [1, 5, 48])
def test_ragged_and_all_experts_forms_agree_without_a_gate(cfg, params,
                                                           tokens):
    lp = jax.tree.map(lambda a: a[1], params["moe_layers"])
    x = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens,
                                                       cfg.hidden_size))
    a = llama._moe(lp, x, dataclasses.replace(cfg, moe_impl="dense"))  # noqa: SLF001
    b = llama._moe(lp, x, dataclasses.replace(cfg, moe_impl="ragged"))  # noqa: SLF001
    assert np.abs(np.asarray(a - b)).max() < 1e-5
    with pytest.raises(ValueError, match="gate"):
        llama._moe(lp, x, dataclasses.replace(cfg, moe_impl="capacity"))  # noqa: SLF001


# -- the state pool -------------------------------------------------------------------- #

def test_state_pool_commits_refcounts_and_evicts_like_pages():
    pool = StatePool(5, snapshot_every=16, inside=3)  # slots 1-4
    a, b = pool.allocate(), pool.allocate()
    assert (a, b) == (1, 2) and pool.running == 2 and pool.available == 2
    pool.commit(a, 111, 16)  # a becomes a snapshot, its committer reads it
    assert pool.has(111) and pool.snapshots == 1 and pool.running == 1
    assert pool.available == 2  # held by its reader: not evictable
    assert pool.lookup(111) == a and pool.lookup(999) == 0
    pool.unref(a)
    assert pool.available == 2  # the second reader still holds it
    pool.unref(a)
    assert pool.available == 3
    c, d = pool.allocate(), pool.allocate()
    assert (c, d) == (3, 4) and pool.evictions_total == 0
    assert pool.allocate() == a and pool.evictions_total == 1  # LRU went
    assert not pool.has(111) and pool.allocate() == 0
    pool.release(b)
    assert pool.allocate() == b
    assert (pool.stored_total, pool.hits_total) == (1, 1)


def engine_of(cfg, params, **over):
    ecfg = dict(page_size=PAGE, num_pages=96, max_num_seqs=4,
                max_prefill_tokens=16, max_model_len=160, num_state_slots=8)
    parallel = over.pop("parallel", None)
    tiered = over.pop("tiered", None)
    ecfg.update(over)
    return JaxEngine(cfg, params, EngineConfig(**ecfg), eos_token_ids=[],
                     kv_dtype=jnp.float32, parallel=parallel, tiered=tiered)


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


@pytest.fixture
def every16(monkeypatch):
    """Snapshots every 16 tokens, two of the tiny model's scan blocks of 8:
    as many as the engines' chunk here."""
    monkeypatch.setattr(hybrid, "SNAPSHOT_BLOCKS", 2)


async def agrees(engine, ref, cfg, params, toks, n=3):
    got, lps = await generate(engine, toks, n)
    text = list(toks)
    for t, lp_t in zip(got, lps):
        want = ref_logp(ref, cfg, params, text)[-1]
        assert t == int(want.argmax()), len(text)
        assert abs(lp_t - want.max()) < 5 * TOL, len(text)
        text.append(t)


def admits(engine):
    return [e for e in engine.events.dump()["events"] if e["kind"] == "admit"]


@pytest.mark.parametrize("how", [
    {}, {"decode_steps": 4}, {"mixed_prefill_tokens": 16},
    {"decode_steps": 2, "decode_chain": 2},
], ids=["default", "block-of-4", "mixed", "chained"])
async def test_engine_decodes_what_the_reference_decodes(cfg, params, ref,
                                                         every16, how):
    """Chunked prefill, a prefix hit at a snapshot and each decode path the
    family serves, through both pools: the logprob of every greedy token
    against the reference's full forward pass over the text so far."""
    engine = engine_of(cfg, params, **how)
    try:
        shared = prompt(40, 6)
        for tail in (prompt(5, 7), prompt(9, 8), prompt(5, 7)):
            await agrees(engine, ref, cfg, params, shared + tail, 5)
        first, second, third = admits(engine)
        assert (first["cached"], first["kv_cached"]) == (0, 0)
        # 40 shared tokens: 5 pages cached, and the first prompt's tail row
        # (from 16 on, in chunks of 16) left a snapshot at every page of it
        assert (second["cached"], second["kv_cached"]) == (40, 40)
        assert (third["cached"], third["kv_cached"]) == (40, 40)
        m = vars(engine.metrics())
        assert m["state_snapshot_hits_total"] == 2
        assert m["state_hit_tokens_shortened_total"] == 0
        assert m["state_slots_total"] == 7 and m["state_slots_running"] == 0
        assert m["state_snapshots"] == m["state_snapshot_stored_total"] >= 2
    finally:
        await engine.shutdown()


async def test_a_hit_is_as_deep_as_the_deepest_snapshot_under_the_pages(
        cfg, params, ref, every16):
    """Pages reach past every snapshot (another document's snapshots evicted
    them all under the cached pages): a cold start, not the pages' 40; it
    equals the uncached run.  (A snapshot left UNDER the pages, shallower
    than they: `test_two_readers_of_one_snapshot_and_a_preempted_reader`.)"""
    engine = engine_of(cfg, params, num_state_slots=4)  # 3 slots
    try:
        doc = prompt(40, 31)
        await agrees(engine, ref, cfg, params, doc + prompt(4, 1), 1)
        st = engine.scheduler.state
        # the tail row's last two, at 32 and 40 (those at 16 and 24 made
        # room for them); its own slot went back
        assert st.snapshots == 2 and st.running == 0
        # another document's snapshots evict both (LRU)
        await agrees(engine, ref, cfg, params, prompt(48, 32), 1)
        assert st.evictions_total >= 2
        await agrees(engine, ref, cfg, params, doc + prompt(6, 2), 2)
        last = admits(engine)[-1]
        assert (last["kv_cached"], last["cached"]) == (40, 0)
        assert vars(engine.metrics())[
            "state_hit_tokens_shortened_total"] == 40
        evicts = [e for e in engine.events.dump()["events"]
                  if e["kind"] == "state_evict"]
        stores = [e for e in engine.events.dump()["events"]
                  if e["kind"] == "state_store"]
        assert len(evicts) == st.evictions_total
        assert len(stores) == st.stored_total
        assert {e["tokens"] for e in stores} <= set(range(8, 48, PAGE))
    finally:
        await engine.shutdown()


async def test_snapshots_inside_a_chunk_are_hit_like_those_at_its_end(
        cfg, params, ref, every16):
    """64-token chunks, a state handed out every 16 tokens and, in a prompt's
    tail row, every page of 8.  The first request's 118 tokens: [0, 64)
    commits the states after 16, 32 and 48; [64, 88) stops where the tail
    row starts; the tail row [88, 118) commits 96, 104 and 112; and each
    chunk's end is committed when the next one starts there.  A request that
    shares 40 of them resumes at 32, one that shares 116 at 112: its last
    shared page."""
    engine = engine_of(cfg, params, max_prefill_tokens=64, num_state_slots=16)
    try:
        def stores():
            return [e["tokens"] for e in engine.events.dump()["events"]
                    if e["kind"] == "state_store"]

        doc = prompt(118, 61)
        await agrees(engine, ref, cfg, params, doc, 1)
        st = engine.scheduler.state
        assert stores() == [16, 32, 48, 64, 88, 96, 104, 112]
        assert (st.snapshots, st.running) == (8, 0)
        await agrees(engine, ref, cfg, params, doc[:40] + prompt(9, 1), 2)
        await agrees(engine, ref, cfg, params, doc[:116] + prompt(20, 2), 2)
        assert [(a["cached"], a["kv_cached"]) for a in admits(engine)] == [
            (0, 0), (32, 40), (112, 112)]
        # the second request's chunk [32, 49) and the third's [112, 136) are
        # tail rows of their own prompts (longer than an interval past their
        # snapshot): ITS 40 and 48 tokens' states (another text than the
        # first's from 40 on), and the third's after 120 and 128
        assert stores()[8:] == [40, 48, 120, 128]
    finally:
        await engine.shutdown()


async def test_two_readers_of_one_snapshot_and_a_preempted_reader(
        cfg, params, ref, every16):
    """Two requests admitted on one snapshot side by side; then a sequence
    preempted mid-prompt frees its slot and resumes through the same lookup:
    each gets the reference's tokens."""
    engine = engine_of(cfg, params)
    try:
        doc = prompt(36, 41)
        await agrees(engine, ref, cfg, params, doc + prompt(3, 1), 1)
        await asyncio.gather(
            agrees(engine, ref, cfg, params, doc + prompt(7, 2), 4),
            agrees(engine, ref, cfg, params, doc + prompt(5, 3), 4))
        assert [a["cached"] for a in admits(engine)] == [0, 32, 32]
        assert engine.scheduler.state.running == 0
    finally:
        await engine.shutdown()
    # preemption: the scheduler alone, a pool with room for one sequence
    from dynamo_tpu.engine.scheduler import (SamplingOptions, Scheduler,
                                             Sequence)
    ecfg = EngineConfig(page_size=PAGE, num_pages=12, max_num_seqs=4,
                        max_prefill_tokens=16, max_model_len=160)
    state = StatePool(6, snapshot_every=16, inside=3)
    sched = Scheduler(ecfg, PagePool(12, PAGE), state)
    a = Sequence("a", prompt(48, 51), SamplingOptions(max_tokens=4))
    sched.add(a)
    for _ in range(2):  # two chunks of a: a snapshot at 16
        plan = sched.schedule()
        sched.chunk_dispatched(plan.prefill[0].seq, plan.prefill[0].chunk_len)
        sched.commit_full_pages(a)
    assert a.num_computed == 32 and state.snapshots == 1
    held = (a.state_slot, a.state_src)
    assert held[0] and not held[1]
    sched._preempt(a)  # noqa: SLF001
    assert (a.state_slot, a.state_src, a.num_computed) == (0, 0, 0)
    assert state.running == 0
    plan = sched.schedule()  # resumes through the same lookup
    assert a.num_cached == 16 and a.kv_cached == 32
    assert plan.prefill[0].chunk_start == 16 and a.state_src and a.state_slot
    sched.finish(a, "stop")
    assert state.running == 0 and state.available == 5


@pytest.mark.parametrize("doc,first,second", [(1420, 31, 41), (2005, 46, 30),
                                              (1040, 47, 32), (1072, 16, 48)])
def test_a_question_after_a_document_resumes_at_its_last_shared_page(
        doc, first, second):
    """The served geometry (pages of 16, chunks of 512, a state every 128
    tokens, short rows of 64) on the scheduler alone: a document and a
    question prefill in whole chunks and ONE tail row, the last three whole
    pages and what is left, which commits a snapshot at each of its pages;
    another question after the same document finds the one at its last
    shared page, computes less than a page of the document again, and is a
    short row (one that shares a step) that leaves no snapshot."""
    from dynamo_tpu.engine.scheduler import (SamplingOptions, Scheduler,
                                             Sequence)
    ps = 16
    ecfg = EngineConfig(page_size=ps, num_pages=512, max_num_seqs=4,
                        max_prefill_tokens=512, max_model_len=4096)
    assert ecfg.short_chunk_bucket == 64
    state = StatePool(64, snapshot_every=128, inside=3,
                      every_of=lambda tokens: ps if tokens <= 64 else 128)
    sched = Scheduler(ecfg, PagePool(512, ps), state)

    def serve(seq):
        sched.add(seq)
        chunks = []
        while not seq.prefill_done:
            (item,) = sched.schedule().prefill
            chunks.append((item.chunk_start, item.chunk_len, item.short))
            sched.chunk_dispatched(seq, item.chunk_len)
            sched.commit_full_pages(seq)
        sched.finish(seq, "stop")
        return chunks

    text = prompt(doc, 71)
    a = Sequence("a", text + prompt(first, 72), SamplingOptions(max_tokens=1))
    chunks = serve(a)
    tail = (a.prompt_len - 1) // ps * ps - 3 * ps
    assert chunks[-1] == (tail, a.prompt_len - tail, True)
    assert 3 * ps < chunks[-1][1] <= 4 * ps
    assert [c[0] for c in chunks[:-1]] == list(range(0, tail, 512))
    assert not any(short for _, _, short in chunks[:-1])
    stored = state.stored_total
    assert all(state.has(sched._prompt_hash(a, at))  # noqa: SLF001
               for at in (tail, tail + ps, tail + 2 * ps, tail + 3 * ps))
    b = Sequence("b", text + prompt(second, 73),
                 SamplingOptions(max_tokens=1))
    (chunk,) = serve(b)
    shared = doc // ps * ps
    assert b.kv_cached == b.num_cached == shared == chunk[0]
    assert chunk == (shared, b.prompt_len - shared, True) and chunk[1] < 64
    assert state.stored_total == stored and state.running == 0
    assert state.hit_tokens_shortened_total == 0


async def test_the_engine_reports_both_pools_from_their_descriptions(cfg,
                                                                     params):
    engine = engine_of(cfg, params)
    try:
        cache, state = engine.cache_report(), engine.state_report()
        assert (cache["kind"], cache["layers"]) == ("kv", 2)
        assert cache["bytes_per_token"] == 2 * 2 * 2 * 16 * 4
        spec = cfg.state_spec
        assert (state["layers"], state["slots"]) == (4, 8)
        # a [3, 128] window as 3 tiles of 128 and an [8, 8, 16] state
        assert state["bytes_per_slot"] == spec.bytes_per_slot(4) == 4 * (
            3 * 128 * 4 + 8 * 8 * 16 * 4)
        assert state["snapshot_every"] == hybrid.snapshot_tokens(cfg) == 8
        assert engine.kv.ssm.shape == (4, 8, 8, 8, 16)
        assert engine.kv.k.shape[0] == 2
    finally:
        await engine.shutdown()
    dense = engine_of_dense()
    try:
        assert dense.state_report() is None and len(dense.kv) == 2
        assert dense.scheduler.state is None
    finally:
        await dense.shutdown()


def engine_of_dense():
    from dynamo_tpu.models import tiny_config

    c = tiny_config()
    return JaxEngine(c, init_params(c, jax.random.PRNGKey(0), jnp.float32),
                     EngineConfig(page_size=PAGE, num_pages=16),
                     eos_token_ids=[], kv_dtype=jnp.float32)


@pytest.mark.parametrize("how,match", [
    ({"parallel": {"tp": 2}}, "serving mesh"),
    ({"parallel": {"pp": 2}, "max_prefill_tokens": 160}, "serving mesh"),
    ({"parallel": {"sp": 2}, "max_prefill_tokens": 160}, "serving mesh"),
    ({"parallel": {"dp": 2}, "kv_partition": True}, "serving mesh"),
    ({"fuse_projections": True}, "fuse_projections"),
    ({"quantization": "int8"}, "int8"),
    ({"park_max_pages": 8}, "parking"),
    ({"tiered": object()}, "KVBM"),
    ({"speculative_ngram_k": 3}, "speculative-ngram-k"),
    ({"decode_continuous": True, "decode_steps": 2}, "decode-continuous"),
    ({"page_size": 6}, "snapshot interval"),
    ({"num_state_slots": 2}, "num_state_slots"),
], ids=["tp", "pp", "sp", "partitioned-pool", "fused-projections", "int8",
        "parking", "kvbm-tier", "speculative", "continuous", "page-size",
        "too-few-slots"])
def test_paths_that_cannot_carry_a_state_refuse_the_family(cfg, params, how,
                                                           match):
    from dynamo_tpu.parallel import ParallelConfig

    how = dict(how)
    if "parallel" in how:
        how["parallel"] = ParallelConfig(**how["parallel"])
    with pytest.raises(ValueError, match=match):
        engine_of(cfg, params, **how)


def test_step_kinds_without_a_state_refuse_the_family_by_name(cfg, params):
    kv = fresh_cache(cfg)
    toks = jnp.zeros((1, 4), jnp.int32)
    one = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="draft-verify.*nemotron_h"):
        llama.forward_verify(params, cfg, kv, toks, table_for(8, [0, 1]),
                             one, one + 4)
    with pytest.raises(ValueError, match="embedding forward.*nemotron_h"):
        llama.forward_embed(params, cfg, toks, one + 4)
    with pytest.raises(ValueError, match="decode block.*nemotron_h"):
        llama.decode_block_scan(params, cfg, kv, one, one,
                                table_for(8, [0, 1]), 2, 64, None, ())
    from dynamo_tpu.disagg.transfer import KvLayout

    stub = type("Stub", (), {"model_cfg": cfg, "_kv_dtype": jnp.bfloat16,
                             "cfg": EngineConfig(page_size=PAGE)})
    with pytest.raises(ValueError, match="disagg KV transfer"):
        KvLayout.of_engine(stub)


# -- the benchmark's count and its trace readers ------------------------------------ #

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

# device ops of the 512-token prefill program as the TPU compiler names
# them (AOT compile for a described v5e, PR 44), and where each belongs
PLACED = [
    ("%fusion.306 = bf16[1,512,10304]{2,1,0} fusion(bf16[23,2688,10304]"
     "{2,1,0} %w, s32[] %i, bf16[1,512,2688]{2,1,0} %x, f32[2688]{0} %n, "
     "f32[512]{0} %r)", "ssm.proj"),
    ("%bitcast_add_fusion.6 = bf16[1,512,2688]{2,1,0} fusion(bf16[1,512,2688]"
     "{2,1,0} %x, bf16[23,4096,2688]{2,1,0} %w, s32[] %i, f32[1,512,4096]"
     "{2,1,0} %y)", "ssm.proj"),
    ("%fusion.38 = bf16[1,512,2688]{2,1,0} fusion(bf16[1,512,2688]{2,1,0} %x,"
     " bf16[6,4096,2688]{2,1,0} %wo, s32[] %i, bf16[1,512,4096]{2,1,0} %a)",
     None),
    ("%fusion.29 = bf16[1,512,4096]{2,1,0} fusion(bf16[6,2688,4096]{2,1,0} "
     "%wq, s32[] %i, bf16[1,512,2688]{2,1,0} %x)", None),
    ("%attn.core.3 = bf16[1,512,4096]{2,1,0} custom-call(s32[1,256]{1,0} %t, "
     "bf16[1,512,4096]{2,1,0} %q, bf16[1,512,256]{2,1,0} %k)", None),
    ("%fusion.42 = bf16[512,4096]{1,0} fusion(bf16[1,512,4096]{2,1,0} %a)",
     None),
    ("%multiply_convert_fusion.13 = bf16[1,512,6144]{2,1,0} fusion(bf16[1,512"
     ",6144]{2,1,0} %x, bf16[1,3,6144]{2,1,0} %w, f32[6144]{0} %b)",
     "ssm.scan"),
    ("%fusion.335 = f32[8,128,128]{2,1,0} fusion(bf16[4,1,128,8,128]"
     "{4,2,3,1,0} %c, s32[] %i, bf16[4,1,128,8,128]{4,2,3,1,0} %b)",
     "ssm.scan"),
    ("%fusion.337 = f32[1,128,64,64]{3,2,1,0} fusion(f32[64,128,128]{2,1,0} "
     "%m, f32[64,128]{1,0} %d, pred[128,128]{1,0} %tri)", "ssm.scan"),
    ("%bitcast_add_fusion.8 = f32[1,64,64,128]{3,2,1,0} fusion(f32[1,64,64,"
     "128]{3,2,1,0} %h, f32[64]{0} %e)", "ssm.scan"),
    ("%fusion.334 = f32[8,8,64,128]{3,2,1,0} fusion(f32[128,8,8,64]{3,2,1,0} "
     "%xw, f32[128,8,8]{2,1,0} %w)", "ssm.scan"),
    ("%fusion.295 = bf16[512,4096]{1,0} fusion(f32[1,512,4096]{2,1,0} %y, "
     "f32[4096]{0} %w, bf16[1,512,10304]{2,1,0} %z)", "ssm.scan"),
    ("%select_bitcast_fusion.2 = f32[1,64,64,128]{3,2,1,0} fusion(f32[23,81,"
     "64,64,128]{4,3,2,1,0} %pool, s32[] %i, s32[] %slot)", "state"),
    ("%fusion.233 = bf16[23,81,144,128]{3,2,1,0} fusion(bf16[23,81,144,128]"
     "{3,2,1,0} %pool, s32[] %slot, bf16[23,1,144,128]{3,2,1,0} %new)",
     "state"),
    ("%fusion.314 = bf16[16,512,1856]{2,1,0} fusion(bf16[23,16,2688,1856]"
     "{3,2,1,0} %w, s32[] %i, bf16[1,512,2688]{2,1,0} %x)", "moe"),
    ("%fusion.321 = bf16[512,2688]{1,0} fusion(bf16[16,512,1856]{2,1,0} %a, "
     "bf16[23,16,1856,2688]{3,2,1,0} %w, s32[] %i, bf16[512,16]{1,0} %c)",
     "moe"),
    ("%fusion.322 = bf16[512,3712]{1,0} fusion(bf16[23,2688,3712]{2,1,0} %w, "
     "s32[] %i, bf16[1,512,2688]{2,1,0} %x)", "moe"),
    ("%convolution.71 = f32[512,128]{1,0} convolution(bf16[512,2688]{1,0} %x,"
     " bf16[2688,128]{1,0} %r)", "moe"),
    ("%fusion.320 = bf16[512,16]{1,0} fusion(s32[512,6]{1,0} %i, bf16[512,6]"
     "{1,0} %w)", "moe"),
    ("%fusion.5 = bf16[6,98304,2,128]{3,2,1,0} fusion(bf16[6,98304,2,128]"
     "{3,2,1,0} %pool, s32[512]{0} %s, bf16[512,6,2,128]{3,2,1,0} %k)",
     None),
    ("%fusion.1 = bf16[512,2688]{1,0} fusion(bf16[16384,2688]{1,0} %e, "
     "s32[1024]{0} %t)", None),
    ("%while.5 = (s32[], bf16[1,512,2688]{2,1,0}) while(%t)", None),
]


@pytest.fixture(scope="module")
def bench_lib():
    sys.path.insert(0, BENCH)
    try:
        from lib import roofline, ssm_trace
    finally:
        sys.path.remove(BENCH)
    return ssm_trace, roofline


@pytest.mark.parametrize("name,kind", PLACED,
                         ids=[n.split(" = ")[0] for n, _ in PLACED])
def test_trace_ops_are_placed_by_the_arrays_they_touch(bench_lib, name, kind):
    ssm_trace, _ = bench_lib
    assert ssm_trace.place(name, published()["model"]) == kind


def test_the_roofline_counts_what_every_step_must(bench_lib):
    """1.50 B parameters every step reads and every token multiplies by
    (mixers, attention, router, shared expert; NOT the routed experts): 3.7
    ms of reading, 7.8 ms of operations a 512-token step; the scan's floor
    0.53 ms of that step's traffic; an expert is two matrices."""
    _, roofline = bench_lib
    config = published()
    fam = roofline.family(config)
    model = config["model"]
    assert fam.every_step_params(model) == (
        23 * (2688 * 10304 + 4096 * 2688) + 6 * (2 * 2688 * 4096
                                                 + 2 * 2688 * 256)
        + 23 * (2688 * 128 + 2 * 2688 * 3712)) == 1_497_538_560
    secs, which = fam.prefill_step_floor_s(model, PEAKS, 512)
    assert which == "compute" and abs(secs * 1e3 - 7.784) < 0.001
    secs, which = fam.prefill_step_floor_s(model, PEAKS, 16)
    assert which == "memory" and abs(secs * 1e3 - 3.657) < 0.001
    secs, which = fam.ssm_scan_floor_s(model, PEAKS, 512, 1)
    per_token = 2 * (6144 + 64 + 4096 + 4096)  # 14,400 values in bf16
    state = 2 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert which == "memory" and abs(
        secs - 23 * (512 * per_token + state) / 819e9) < 1e-12
    assert abs(secs * 1e3 - 0.534) < 0.001
    four = fam.ssm_scan_floor_s(model, PEAKS, 256, 4)[0]
    assert abs(four - 23 * (256 * per_token + 4 * state) / 819e9) < 1e-12
    secs, which = fam.experts_floor_s(model, PEAKS, 512, 23 * 16)
    assert which == "memory" and abs(
        secs - 23 * 16 * 2 * 2688 * 1856 * 2 / 819e9) < 1e-12


def test_the_new_readers_reduce_a_traced_window(bench_lib, tmp_path,
                                                monkeypatch):
    """The four new readers over a hand-made window: one 512-token prefill
    step whose program runs 50 ms: 4 in in_proj, 10 in the scan, 1 in the
    slots, 25 in the expert layer, 5 in attention's own products; a program
    or a run without the spans and counters, or another family's
    configuration, returns None."""
    ssm_trace, _ = bench_lib
    config = published()
    ms = 1_000_000
    by_kind = {k: n for n, k in PLACED}
    names = ["%while.9 = (s32[]) while(%t)", by_kind["ssm.proj"],
             by_kind["ssm.scan"], by_kind["state"], by_kind["moe"],
             PLACED[3][0]]
    t0 = 100 * ms
    ops = [[0, t0, 50 * ms], [1, t0 + 1 * ms, 4 * ms],
           [2, t0 + 6 * ms, 10 * ms], [3, t0 + 17 * ms, 1 * ms],
           [4, t0 + 19 * ms, 25 * ms], [5, t0 + 45 * ms, 5 * ms]]
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({"names": names, "planes": [{
        "name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]}]}))
    sys.path.insert(0, BENCH)
    try:
        from lib import checkpoint, moe_trace

        monkeypatch.setattr(moe_trace, "trace_path", lambda: str(path))
        step = {"kind": "prefill_chunk", "t_ns": t0 - 3 * ms,
                "dur_ns": 60 * ms, "batch": 1, "tokens": 512}
        admits = [{"kind": "admit", "t_ns": t0, "dur_ns": 0,
                   "prompt_len": 1500, "cached": 1024, "kv_cached": 1456},
                  {"kind": "admit", "t_ns": t0, "dur_ns": 0,
                   "prompt_len": 1200, "cached": 0, "kv_cached": 0}]
        run = {"t0": 0.0, "t1": 1.0, "events": [step, *admits],
               "config": config, "peaks": PEAKS, "metrics0": {},
               "metrics1": {},
               "trace": {"modules": [[(t0, t0 + 50 * ms,
                                       "jit_prefill_step(1)")]]}}
        readers = {n: checkpoint.load_module("layer_metrics", n).read
                   for n in ("step.ssm_device_pct",
                             "kernel.ssm_scan_roofline",
                             "step.expert_layer_device_pct",
                             "engine.state_hit_depth_pct")}
        assert abs(readers["step.ssm_device_pct"](run) - 30.0) < 1e-6
        assert abs(readers["step.expert_layer_device_pct"](run) - 50.0) < 1e-6
        assert abs(readers["kernel.ssm_scan_roofline"](run)
                   - 100 * 0.5339603614 / 10) < 1e-4
        assert abs(readers["engine.state_hit_depth_pct"](run)
                   - 100 * 1024 / 1456) < 1e-9
        # the parent's program: no `kv_cached` on its admit events, no trace
        bare = dict(run, trace=None, events=[
            step, *({k: v for k, v in a.items() if k != "kv_cached"}
                    for a in admits)])
        assert all(read(bare) is None for read in readers.values())
        with open(os.path.join(BENCH, "configs",
                               "qwen2.5-7b-h14.json")) as f:
            other = dict(run, config=json.load(f))
        ssm_trace._MEMO.clear()  # noqa: SLF001
        for name, read in readers.items():
            if name != "engine.state_hit_depth_pct":
                assert read(other) is None, name
    finally:
        sys.path.remove(BENCH)

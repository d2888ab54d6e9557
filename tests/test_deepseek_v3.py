"""deepseek_v3 on the served path (ISSUE 35): the family's config keys, its
checkpoint names through the loader, every forward path against the plain
reference (`benchmark/reference/deepseek_v3.py`), the latent page cache, the
grouped sigmoid router with its shared expert, the chip's share of a layer's
experts, the moe stats a step carries, the layouts that refuse the family,
and the benchmark's count and trace placement.  Tiny sizes, float32, seeded
weights, CPU."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import KVCache, ModelConfig, init_params, tiny_config
from dynamo_tpu.models import llama
from dynamo_tpu.models.loader import load_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
PAGE = 8
CELL = "gigachat3.1-702b-ep16"

TINY = {
    "model_type": "deepseek_v3", "vocab_size": 300, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_nextn_predict_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "n_shared_experts": 1, "n_routed_experts": 4, "ep_size": 4,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 32, "q_lora_rank": 48,
    "qk_rope_head_dim": 8, "v_head_dim": 24, "qk_nope_head_dim": 16,
    "topk_method": "noaux_tc", "n_group": 4, "topk_group": 2,
    "num_experts_per_tok": 4, "moe_layer_freq": 1,
    "first_k_dense_replace": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "rope_theta": 100000, "max_position_embeddings": 512,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "rope_type": "yarn"},
    "attention_bias": False, "tie_word_embeddings": False,
}


def bench_module(kind_dir, name):
    sys.path.insert(0, BENCH)
    try:
        from lib import checkpoint
    finally:
        sys.path.remove(BENCH)
    return checkpoint.load_module(kind_dir, name)


@pytest.fixture(scope="module")
def ref():
    return bench_module("reference", "deepseek_v3")


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig.from_hf_config(TINY, name="tiny-deepseek-v3")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(35), dtype=jnp.float32)


def reader_of(params, cfg, experts=None):
    """`read(name)` over a param tree, under the family's tensor names (the
    loader's mapping, backwards: halves back to interleaved pairs, `w_uk` /
    `w_uv` back into `kv_b_proj`), for the plain reference.  `experts`
    {global index: (layer stack, local index)} overrides where an expert's
    matrices come from (the whole layer, for the shares' sum)."""
    nh, r, pe = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope = cfg.qk_nope_head_dim
    pairs = np.argsort(np.concatenate([np.arange(0, pe, 2),
                                       np.arange(1, pe, 2)]))
    flat = {"model.embed_tokens.weight": params["embed"],
            "model.norm.weight": params["final_norm"],
            "lm_head.weight": params["lm_head"].T}
    k = cfg.first_k_dense
    for i in range(cfg.num_hidden_layers):
        lay, j = ((params["dense_layers"], i) if i < k
                  else (params["layers"], i - k))
        p = f"model.layers.{i}."
        a = p + "self_attn."
        q_b = np.asarray(lay["wq_b"][j]).T.reshape(nh, nope + pe, -1)
        q_b = np.concatenate([q_b[:, :nope], q_b[:, nope:][:, pairs]], 1)
        kv_a = np.asarray(lay["wkv_a"][j]).T
        kv_a = np.concatenate([kv_a[:r], kv_a[r:][pairs]], 0)
        kv_b = np.concatenate([np.asarray(lay["w_uk"][j]),
                               np.asarray(lay["w_uv"][j]).transpose(0, 2, 1)],
                              axis=1).reshape(-1, r)
        flat.update({
            p + "input_layernorm.weight": lay["attn_norm"][j],
            p + "post_attention_layernorm.weight": lay["mlp_norm"][j],
            a + "q_a_proj.weight": lay["wq_a"][j].T,
            a + "q_a_layernorm.weight": lay["q_norm"][j],
            a + "q_b_proj.weight": q_b.reshape(nh * (nope + pe), -1),
            a + "kv_a_proj_with_mqa.weight": kv_a,
            a + "kv_a_layernorm.weight": lay["kv_norm"][j],
            a + "kv_b_proj.weight": kv_b,
            a + "o_proj.weight": lay["wo"][j].T})
        if i < k:
            for n in ("gate", "up", "down"):
                flat[p + f"mlp.{n}_proj.weight"] = lay[f"w_{n}"][j].T
            continue
        flat[p + "mlp.gate.weight"] = lay["router"][j].T
        flat[p + "mlp.gate.e_score_correction_bias"] = lay["router_bias"][j]
        held = experts or {cfg.first_expert + e: (params["layers"], e)
                           for e in range(cfg.num_experts)}
        for e, (stack, le) in held.items():
            for n in ("gate", "up", "down"):
                flat[p + f"mlp.experts.{e}.{n}_proj.weight"] = (
                    stack[f"w_{n}"][j, le].T)
        for n in ("gate", "up", "down"):
            flat[p + f"mlp.shared_experts.{n}_proj.weight"] = (
                lay[f"ws_{n}"][j].T)
    return lambda name: np.asarray(flat[name], np.float32)


def table_for(n_tokens, batch=1):
    pages = -(-n_tokens // PAGE)
    return jnp.arange(1, 1 + batch * pages, dtype=jnp.int32).reshape(
        batch, pages)


def logp(logits):
    return np.asarray(jax.nn.log_softmax(
        jnp.asarray(logits, jnp.float32), axis=-1))


def prefill_all(cfg, params, tokens, chunk=None):
    """Chunked prefill of one prompt through the paged cache; returns the
    next-token logprobs after each chunk's last token [(position, lp)], the
    cache and the table."""
    T = len(tokens)
    chunk = chunk or T
    kv = KVCache.create(cfg, 2 + -(-T // PAGE) + 8, PAGE, jnp.float32)
    table = table_for(T + 8 * PAGE)
    out = []
    for s in range(0, T, chunk):
        part = tokens[s:s + chunk]
        logits, kv = llama.forward_prefill(
            params, cfg, kv, jnp.asarray([part], jnp.int32), table,
            jnp.asarray([s], jnp.int32), jnp.asarray([len(part)], jnp.int32))
        out.append((s + len(part) - 1, logp(logits)[0]))
    return out, kv, table


def ref_logp(ref, cfg, params, tokens, model=TINY, **controls):
    """Reference next-token logprobs after every position: [T, vocab]."""
    return ref.forward(reader_of(params, cfg), model,
                       [np.asarray([tokens])], len(tokens), **controls)[0][0]


def prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(4, 290, n)]


TOL = 2e-4  # float32 on both sides; sums in another order


def published():
    with open(os.path.join(BENCH, "configs", CELL + ".json")) as f:
        return json.load(f)


# -- configuration ------------------------------------------------------------- #

def test_from_hf_config_reads_the_published_keys():
    """The catalog row's keys as published (64 layers, 256 experts, ep_size
    1) through `from_hf_config`: the model's name is its parameter count."""
    run = published()
    model = dict(run["model"])
    model.update({k: v["published"] for k, v in run["reduced"].items()})
    c = ModelConfig.from_hf_config(model)
    assert c.is_latent and c.cache_spec.kind == "latent"
    assert (c.kv_lora_rank, c.q_lora_rank) == (512, 1536)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (
        128, 64, 192)
    assert (c.num_experts, c.router_width, c.num_experts_per_tok) == (
        256, 256, 8)
    assert (c.moe_n_group, c.moe_topk_group, c.moe_routed_scale) == (8, 4, 2.5)
    assert c.first_k_dense == 3 and c.num_moe_layers == 61
    assert c.n_shared_experts == 1 and c.moe_scoring == "sigmoid"
    assert abs(c.num_params() - 702.04e9) < 0.01e9
    assert abs(c.latent_softmax_scale * 192 ** 0.5 - 2.00474) < 1e-5
    cut = ModelConfig.from_hf_config(run["model"])
    assert (cut.num_experts, cut.router_width, cut.first_expert) == (16, 256, 0)
    assert abs(cut.num_params() - 6.057e9) < 1e6
    # the choosing bias is held in float32: 6 x 256 x 2 B more
    assert run["memory"]["weights_bytes"] - cut.num_params() * 2 == 3072


def test_the_file_states_each_published_key_once_for_each_reader():
    """Top-level keys (what the driver's check reads) equal `model` (what
    the program gets); only the keys of `reduced` differ from the source,
    each by its stated `run` value."""
    run = published()
    model = dict(run["model"])
    assert model.pop("architectures") == ["DeepseekV3ForCausalLM"]
    assert model.pop("torch_dtype") == "bfloat16"
    assert {k: run[k] for k in model} == model
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [c for c in json.load(f)["configs"] if c["name"] == CELL]
    assert sorted(entry["reduced"]) == sorted(run["reduced"])
    for key, cut in run["reduced"].items():
        assert run[key] == cut["run"] != cut["published"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
                "qk_nope_head_dim", "v_head_dim", "num_experts_per_tok",
                "num_attention_heads"):
        assert key not in run["reduced"]


@pytest.mark.parametrize("bad,key", [
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"first_k_dense_replace": 3}, "first_k_dense_replace"),
    ({"rope_scaling": {"rope_type": "llama3", "factor": 8}}, "rope_scaling"),
    ({"model_type": "deepseek_v2"}, "kv_lora_rank"),
    ({"n_group": 3}, "moe_n_group"),
    ({"ep_rank": 4}, "moe_ep_rank"),
], ids=["full-rank-q", "topk-method", "softmax-scores", "unnormalised",
        "layer-freq", "no-expert-layer", "llama3-rope", "another-family",
        "uneven-groups", "rank-out-of-range"])
def test_from_hf_config_refuses_what_it_cannot_compute(bad, key):
    """By the key it cannot compute; a config with `kv_lora_rank` of a
    family that is not implemented no longer falls to the llama branch."""
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config(dict(TINY, **bad))


# -- checkpoint names through the loader ----------------------------------------- #

def test_written_checkpoint_loads_and_agrees_with_the_reference(tmp_path, ref):
    """`benchmark/lib/checkpoint.py` + `checkpoints/deepseek_v3.py` write
    the family's tensors (held experts only, under their global indices);
    `models/loader.py` reads them; a chunked prefill over the loaded tree
    agrees with the reference reading the same file."""
    from safetensors import safe_open

    ckpt = bench_module("lib", "checkpoint")
    layout = bench_module("checkpoints", "deepseek_v3")
    rank2 = dict(TINY, ep_rank=2)
    names = [n for n, _, _ in layout.tensors(rank2)]
    assert "model.layers.1.mlp.experts.8.gate_proj.weight" in names
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in names
    assert "model.layers.0.mlp.gate_proj.weight" in names
    assert not any(n.startswith("model.layers.3.") for n in names)
    out = str(tmp_path / "ckpt")
    ckpt.write({"model": rank2, "weights_seed": 5,
                "checkpoint": "deepseek_v3"}, out)
    c = ModelConfig.from_pretrained(out)
    loaded = load_params(out, c, dtype=jnp.float32)
    assert c.first_expert == 8
    lay = loaded["layers"]
    assert lay["router"].shape == (2, 64, 16)
    assert lay["router_bias"].dtype == jnp.float32
    assert lay["w_gate"].shape == lay["w_up"].shape == (2, 4, 64, 32)
    assert lay["w_uk"].shape == (2, 4, 16, 32)
    assert lay["w_uv"].shape == (2, 4, 32, 24)
    assert loaded["dense_layers"]["w_gate"].shape == (1, 64, 96)
    reader = safe_open(os.path.join(out, "model.safetensors"), framework="np")
    toks = prompt(24, 1)
    want = ref.tail_logprobs(
        lambda n: reader.get_tensor(n).astype(np.float32), rank2,
        [np.asarray([toks])], 1)[0][0, 0]
    steps, _, _ = prefill_all(c, loaded, toks, chunk=16)
    assert np.abs(steps[-1][1] - want).max() < TOL


# -- the forward paths against the reference ------------------------------------- #

def test_chunked_prefill_agrees_with_the_reference(cfg, params, ref):
    toks = prompt(40)
    want = ref_logp(ref, cfg, params, toks)
    steps, _, _ = prefill_all(cfg, params, toks, chunk=16)
    assert [p for p, _ in steps] == [15, 31, 39]
    for pos, got in steps:
        assert np.abs(got - want[pos]).max() < TOL, pos


@pytest.mark.parametrize("path", ["decode", "block", "verify"])
def test_decode_through_the_latent_cache_agrees_with_the_reference(
        cfg, params, ref, path):
    """Prefill 24 tokens, then 6 more through the latent cache by the
    per-step decode, the block scan and the speculative verify: each step's
    logits against the reference's full forward over the whole text."""
    toks, P, N = prompt(30, 2), 24, 6
    want = ref_logp(ref, cfg, params, toks)
    _, kv, table = prefill_all(cfg, params, toks[:P], chunk=16)
    if path == "decode":
        got = []
        for i in range(N):
            logits, kv = llama.forward_decode(
                params, cfg, kv, jnp.asarray([toks[P + i]], jnp.int32),
                jnp.asarray([P + i], jnp.int32), table)
            got.append(logp(logits)[0])
    elif path == "block":
        forced = jnp.asarray(toks[P + 1:] + [0], jnp.int32)

        def sample_step(carry, logits, tok_prev, step):
            return carry, forced[step][None], logits

        _, ys, _, _, kv = llama.decode_block_scan(
            params, cfg, kv, jnp.asarray([toks[P]], jnp.int32),
            jnp.asarray([P], jnp.int32), table, N, 512, sample_step, ())
        got = list(logp(ys)[:, 0])
    else:
        logits, kv = llama.forward_verify(
            params, cfg, kv, jnp.asarray([toks[P:]], jnp.int32), table,
            jnp.asarray([P], jnp.int32), jnp.asarray([N], jnp.int32))
        got = list(logp(logits)[0])
    for i, g in enumerate(got):
        assert np.abs(g - want[P + i]).max() < TOL, (path, i)


@pytest.mark.parametrize("control", [
    {"lower_precision": True}, {"faults": ("bf16_routing",)},
    {"faults": ("no_shared",)}, {"faults": ("no_routed_scale",)},
    {"faults": ("no_mscale",)}, {"faults": ("bias_in_weights",)},
    {"faults": ("ungrouped",)},
], ids=["lower-precision", "bf16-routing", "dropped-shared-expert",
        "missing-2.5", "missing-m2", "bias-in-weights", "outside-the-groups"])
def test_the_comparison_catches(cfg, params, ref, control):
    """What the benchmark's `correct` rests on, at the tiny size: against
    the reference computed with one thing wrong, the model is out of the
    tolerance that it meets against the reference as written."""
    toks = prompt(48, 3)
    (_, got), = prefill_all(cfg, params, toks)[0]
    assert np.abs(got - ref_logp(ref, cfg, params, toks)[-1]).max() < TOL
    wrong = ref_logp(ref, cfg, params, toks, **control)
    # a bf16 router moves a float32 model least: its scores keep 8 bits
    out_by = 1.2 if control.get("faults") == ("bf16_routing",) else 10
    assert np.abs(got - wrong[-1]).max() > out_by * TOL, control


# -- the latent cache ---------------------------------------------------------------- #

def test_the_latent_cache_holds_the_latent_and_the_shared_key(cfg, params):
    """576 values a token a layer at the published widths and no per-head
    key or value: the 64-wide rotated key all heads share and the 512-wide
    latent, each stored as whole lane tiles (a power of two and at least
    two of them: the geometry the TPU compiler leaves in place), 1,536 B in
    bf16 where per-head keys and values would be 49,152 B."""
    full = ModelConfig.from_hf_config(published()["model"])
    spec = full.cache_spec
    assert (spec.kind, spec.values) == ("latent", 576)
    assert spec.plane_dims == ((2, 128), (4, 128))
    assert spec.bytes_per_token_layer(2) == 1536
    assert 64 * (192 + 192) * 2 // spec.bytes_per_token_layer(2) == 32
    shapes = jax.eval_shape(lambda: KVCache.create(full, 64, 16))
    assert shapes.k.shape == (7, 64, 16, 2, 128)
    assert shapes.v.shape == (7, 64, 16, 4, 128)
    dense = tiny_config()
    assert dense.cache_spec.plane_dims == ((2, 16), (2, 16))
    assert dense.cache_spec.values == 2 * 2 * 16
    assert KVCache.create(dense, 4, PAGE).v.shape == (2, 4, PAGE, 2, 16)
    _, kv, _ = prefill_all(cfg, params, prompt(20), chunk=8)
    assert kv.k.shape == kv.v.shape == (3, 13, PAGE, 2, 128)
    # the written rows: a unit-RMS latent of 32 values, a rotated key of 8,
    # zeros up to the lane tile
    lat = np.asarray(kv.v[:, 1, 0]).reshape(3, -1)
    key = np.asarray(kv.k[:, 1, 0]).reshape(3, -1)
    assert np.allclose((lat[:, :32] ** 2).mean(-1), 1.0, atol=1e-3)
    assert not lat[:, 32:].any() and not key[:, 8:].any() and key[:, :8].any()


async def test_the_engine_reports_the_cache_from_its_one_description(cfg,
                                                                     params):
    """The worker's `CACHE` start-up line: kind, values and stored bytes a
    token, the pool's size."""
    engine = engine_of(cfg, params)
    try:
        assert engine.cache_report() == {
            "kind": "latent", "values_per_token_layer": 32 + 8,
            "planes": [[2, 128], [2, 128]], "dtype": "float32", "layers": 3,
            "bytes_per_token": 3 * 2 * 256 * 4, "pool_tokens": 96 * PAGE,
            "pool_bytes": 96 * PAGE * 3 * 2 * 256 * 4}
    finally:
        await engine.shutdown()


def test_export_and_import_carry_both_planes(cfg, params):
    from dynamo_tpu.engine import steps

    _, kv, _ = prefill_all(cfg, params, prompt(20), chunk=8)
    pages = jnp.asarray([1, 2, 0, 0], jnp.int32)
    k, v = jax.jit(steps.gather_pages())(kv, pages)
    assert k.shape == v.shape == (3, 4, PAGE, 2, 128)
    blank = KVCache.create(cfg, 13, PAGE, jnp.float32)
    back = jax.jit(steps.set_pages())(blank, k, v, pages)
    for got, want in zip(back, kv):
        assert np.array_equal(np.asarray(got[:, 1:3]), np.asarray(want[:, 1:3]))


# -- the router and the chip's share ---------------------------------------------------- #

def test_route_chooses_within_the_best_groups_and_weighs_without_the_bias(
        cfg, params, ref):
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 64), jnp.float32)
    lp = dict(lp, router_bias=lp["router_bias"] * 20)  # a bias that matters
    w, sel = llama._route(lp, x, cfg)
    logits = np.asarray(x) @ np.asarray(lp["router"])
    idx, wts = ref.route(np, TINY, logits.astype(np.float32),
                         np.asarray(lp["router_bias"]))
    assert np.array_equal(np.sort(np.asarray(sel), -1), np.sort(idx, -1))
    order = np.argsort(np.asarray(sel), -1)
    assert np.allclose(np.take_along_axis(np.asarray(w), order, -1),
                       np.take_along_axis(wts, np.argsort(idx, -1), -1),
                       atol=1e-6)
    assert np.allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)
    groups = np.asarray(sel) // 4  # 4 groups of 4
    assert all(len(set(g)) <= 2 for g in groups.reshape(-1, 4))
    other, _ = ref.route(np, TINY, logits.astype(np.float32),
                         np.asarray(lp["router_bias"]), faults=("ungrouped",))
    assert not np.array_equal(np.sort(other, -1), np.sort(idx, -1))


@pytest.mark.parametrize("impl", ["dense", "ragged"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(cfg, params, ref,
                                                         impl):
    """Every rank of the layer's share routes over all 16 experts and
    computes its own 4; the four parts, with the shared expert that every
    rank computes alike counted once, are the uncut layer (the same layer
    with ep_size 1 and 16 experts held, and the reference's `whole`)."""
    key = jax.random.PRNGKey(7)
    whole_cfg = dataclasses.replace(cfg, num_experts=16, moe_ep_size=1,
                                    moe_impl=impl)
    whole = init_params(whole_cfg, key, dtype=jnp.float32)["layers"]
    lp_all = jax.tree.map(lambda a: a[0], whole)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64), jnp.float32)
    want = llama._moe(lp_all, x, whole_cfg)
    shared = llama._moe_shared(lp_all, x)
    total = 0.0
    for rank in range(4):
        c = dataclasses.replace(cfg, moe_ep_rank=rank, moe_impl=impl)
        lp = dict(lp_all, **{k: lp_all[k][4 * rank:4 * rank + 4]
                             for k in ("w_gate", "w_up", "w_down")})
        part, st = llama._moe(lp, x, c, stats=True)
        assert int(st[0]) == 24 * 4 and 0 <= int(st[3]) <= int(st[0])
        total = total + (part - shared)
    assert np.abs(np.asarray(total + shared - want)).max() < 1e-5
    # and the reference's uncut layer, through a one-expert-layer model
    one = dict(TINY, num_hidden_layers=2)
    c1 = ModelConfig.from_hf_config(one)
    p1 = init_params(c1, jax.random.PRNGKey(9), dtype=jnp.float32)
    p1["layers"] = jax.tree.map(lambda a: a[:1], whole)
    held = {e: (p1["layers"], e) for e in range(16)}
    toks = prompt(24, 5)
    uncut = ref.forward(reader_of(p1, c1, held), one, [np.asarray([toks])],
                        1, whole=True)[0][0, 0]
    c_all = dataclasses.replace(c1, num_experts=16, moe_ep_size=1,
                                moe_impl=impl)
    steps, _, _ = prefill_all(c_all, p1, toks)
    assert np.abs(steps[-1][1] - uncut).max() < TOL


@pytest.mark.parametrize("tokens", [1, 5, 48])
def test_ragged_and_all_experts_forms_agree_under_a_share(cfg, params, tokens):
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens, 64),
                          jnp.float32)
    for rank in (0, 3):
        c = dataclasses.replace(cfg, moe_ep_rank=rank)
        dense = llama._moe_dense(lp, x, c)
        ragged = llama._moe_ragged(lp, x, c)
        assert np.abs(np.asarray(dense - ragged)).max() < 1e-5, rank


def test_moe_step_stats_count_held_and_all_assignments(cfg, params):
    """A share's stats: every choice of a valid row, the held experts
    touched, the fullest held expert, and the choices that were local."""
    sel = jnp.asarray([[[0, 1, 5, 9], [0, 2, 6, 10], [0, 1, 2, 3]]])
    valid = jnp.asarray([[True, True, False]])
    st = llama.moe_step_stats(sel, 4, valid, 4)
    assert [int(v) for v in st] == [8, 3, 2, 4]
    merged = llama.merge_moe_stats(jnp.stack([st, jnp.asarray([8, 1, 5, 5]),
                                              jnp.zeros(4, jnp.int32)]))
    assert [int(v) for v in merged] == [16, 4, 5, 9]
    assert llama.moe_stats_width(cfg) == 4
    assert llama.moe_stats_width(tiny_config(num_experts=4)) == 3


# -- the engine -------------------------------------------------------------------- #

def engine_of(cfg, params, **over):
    ecfg = dict(page_size=PAGE, num_pages=96, max_num_seqs=4,
                max_prefill_tokens=16, max_model_len=128)
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


@pytest.mark.parametrize("how", [
    {}, {"decode_steps": 4}, {"speculative_ngram_k": 3},
    {"decode_continuous": True, "decode_steps": 2},
    {"mixed_prefill_tokens": 16},
], ids=["default", "block-of-4", "speculative", "continuous-chain", "mixed"])
async def test_engine_decodes_what_the_reference_decodes(cfg, params, ref,
                                                         how):
    """Chunked prefill, the prefix cache over latent pages (the second
    request shares 32 tokens: a hit gives the cold run's logits) and each
    decode path a server can reach: the logprob of every greedy token
    against the reference's full forward pass over the text so far."""
    engine = engine_of(cfg, params, **how)
    try:
        shared = prompt(32, 6)
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


async def test_two_requests_in_flight_share_the_latent_pool(cfg, params, ref):
    """Two prompts decode side by side (a batch of 2 through the block
    scan); each gets the reference's tokens."""
    import asyncio

    engine = engine_of(cfg, params, decode_steps=2)
    try:
        a, b = prompt(21, 11), prompt(13, 12)
        (ga, _), (gb, _) = await asyncio.gather(
            generate(engine, a, 4), generate(engine, b, 4))
        for toks, got in ((a, ga), (b, gb)):
            text = list(toks)
            for t in got:
                assert t == int(ref_logp(ref, cfg, params, text)[-1].argmax())
                text.append(t)
    finally:
        await engine.shutdown()


async def test_share_steps_carry_their_stats_and_counters(cfg, params):
    """Every prefill-path step slice of a share carries `experts_hit` (of
    the HELD experts), `moe_max_load` and `moe_local`, and `/metrics.json`
    counts the local assignments beside all of them."""
    engine = engine_of(cfg, params)
    try:
        import asyncio

        await generate(engine, prompt(40, 9), 1)
        for _ in range(200):  # a slice is recorded AFTER its token's delivery
            chunks = [e for e in engine.events.dump()["events"]
                      if e["kind"] == "prefill_chunk"]
            if len(chunks) == 3:
                break
            await asyncio.sleep(0.01)
        assert len(chunks) == 3
        Lm, E, k = cfg.num_moe_layers, cfg.num_experts, cfg.num_experts_per_tok
        assert (Lm, E, k) == (2, 4, 4)
        for e in chunks:
            assert 0 <= e["experts_hit"] <= Lm * E
            assert 0 <= e["moe_local"] <= e["tokens"] * k * Lm
            assert e["moe_max_load"] <= e["tokens"]
            assert e["moe_max_load"] * max(e["experts_hit"], 1) >= e["moe_local"]
        m = vars(engine.metrics())
        assert m["moe_steps_total"] == 3
        assert m["moe_assignments_total"] == 40 * k * Lm
        assert m["moe_local_assignments_total"] == sum(
            e["moe_local"] for e in chunks)
        assert 0 < m["moe_local_assignments_total"] < m["moe_assignments_total"]
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("how,match", [
    ({"parallel": {"tp": 2}}, "serving mesh"),
    ({"parallel": {"pp": 2}, "max_prefill_tokens": 128}, "serving mesh"),
    ({"parallel": {"sp": 2}, "max_prefill_tokens": 128}, "serving mesh"),
    ({"parallel": {"dp": 2}, "kv_partition": True}, "serving mesh"),
    ({"fuse_projections": True}, "fuse_projections"),
    ({"quantization": "int8"}, "int8"),
    ({"park_max_pages": 8}, "parking"),
    ({"tiered": object()}, "KVBM"),
], ids=["tp", "pp", "sp", "partitioned-pool", "fused-projections", "int8",
        "parking", "kvbm-tier"])
def test_paths_that_cannot_carry_latent_pages_refuse_the_family(cfg, params,
                                                                how, match):
    from dynamo_tpu.parallel import ParallelConfig

    how = dict(how)
    if "parallel" in how:
        how["parallel"] = ParallelConfig(**how["parallel"])
    with pytest.raises(ValueError, match=match):
        engine_of(cfg, params, **how)


def test_disagg_transfer_takes_its_geometry_from_the_cache_description(
        cfg, params):
    from dynamo_tpu.disagg.transfer import KvLayout

    class Stub:
        _kv_dtype = jnp.bfloat16

        def __init__(self, model_cfg):
            self.model_cfg = model_cfg
            self.cfg = EngineConfig(page_size=PAGE)

    dense = tiny_config()
    lay = KvLayout.of_engine(Stub(dense))
    assert (lay.n_kv_heads, lay.head_dim) == (2, 16)
    assert lay.bytes_per_page == PAGE * dense.num_hidden_layers * (
        dense.cache_spec.bytes_per_token_layer(2))
    with pytest.raises(ValueError, match="disagg KV transfer"):
        KvLayout.of_engine(Stub(cfg))


# -- models without leading dense layers trace what they traced ------------------------- #

GOLDEN = os.path.join(ROOT, "tests", "data", "prefill_step_lowering.json")


def lowering_digest(model_cfg, by_rows=False):
    """sha256 of a tiny prefill step's StableHLO, locations and labels
    stripped: of the step that heads every row, or `by_rows` of the one a
    flat engine serves, which takes which rows sample as an operand."""
    import hashlib
    import re

    from dynamo_tpu.engine.layout import Layout

    p = jax.eval_shape(lambda: init_params(model_cfg, jax.random.PRNGKey(0),
                                           jnp.float32))
    layout = Layout.resolve(model_cfg, EngineConfig(
        page_size=PAGE, num_pages=16, attention_impl="xla"))[0]
    step = layout.prefill_step(False, greedy=True)
    kv = jax.eval_shape(lambda: KVCache.create(model_cfg, 16, PAGE,
                                               jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    from dynamo_tpu.ops.sampling import SamplingParams

    samp = SamplingParams(f32(1), i32(1), f32(1), f32(1), f32(1))
    rows = (jax.ShapeDtypeStruct((1,), jnp.bool_),) if by_rows else ()
    text = step.lower(p, kv, i32(1, 16), i32(1, 4 + layout.state_cols),
                      i32(1), i32(1), samp,
                      jax.ShapeDtypeStruct((1,), jnp.uint32), i32(1), *rows
                      ).as_text()
    text = re.sub(r"loc\([^)]*\)", "", text)
    text = "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith("#loc"))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("by_rows", [False, True],
                         ids=["every-row", "by-rows"])
@pytest.mark.parametrize("family", ["qwen2", "smallthinker", "deepseek_v3",
                                    "xing4_0", "nemotron_h"])
def test_a_model_without_leading_dense_layers_traces_what_it_traced(family,
                                                                    by_rows):
    """The prefill step of a tiny Qwen2 (dense, biased) and of a tiny
    SmallThinker (experts, pre-attention router, rotary switch, windows)
    lowers to the text it lowered to before the layer loop learned of
    stacks, latent pages and shares, and that of the tiny deepseek_v3 (latent
    pages, a dense layer, a share, its stats) to the text it lowered to
    before the residual add left the layer's halves (PR 37).  Digests taken
    on the parent commit by this same function; regenerate with the
    container's jax if it moves.

    Since PR 41 the step takes which of its rows sample.  Without that
    operand (`every-row`: what pp, sp and lockstep layouts run) it lowers to
    the three digests above, UNMOVED: the parent's program to the letter.
    With it (`by-rows`: what a flat engine serves) the text gains the
    operand and one conditional around the head; those three digests, under
    `<family>.by_rows`, were taken on PR 41's tree by `lowering_digest(cfg,
    by_rows=True)` and hold the served step still from here on.

    Since PR 43 a latent model's prefill attention is chosen a trace
    (`ops.latent_attention.prefill_attention`): off the chip, and wherever
    "xla" is asked for, it is the form it was, to the letter.  The tiny
    xing4_0's two digests (latent pages around a residual of streams) were
    taken on PR 58's tree, which carries the streams [.., n x h] (a token's
    streams side by side: `ops/pallas_hyper_connections.py`) where PR 42's
    carried [.., n, h], and left every other digest as it was.

    The tiny nemotron_h's two (one scan over units of three kinds of mixer,
    pages for its attention layers alone, state slots beside them, their
    numbers in the table's last two columns) were taken on PR 44's tree,
    which left every digest above as it was."""
    from test_nemotron_h import TINY as NEMOTRON
    from test_smallthinker import TINY as ST
    from test_xing4_0 import TINY as XING

    model_cfg = {"qwen2": lambda: tiny_config(attention_bias=True,
                                              model_type="qwen2"),
                 "smallthinker": lambda: ModelConfig.from_hf_config(ST),
                 "deepseek_v3": lambda: ModelConfig.from_hf_config(
                     TINY, name="tiny-deepseek-v3"),
                 "xing4_0": lambda: ModelConfig.from_hf_config(
                     XING, name="tiny-xing4-0"),
                 "nemotron_h": lambda: ModelConfig.from_hf_config(
                     NEMOTRON, name="tiny-nemotron-h")}[family]()
    with open(GOLDEN) as f:
        golden = json.load(f)
    assert lowering_digest(model_cfg, by_rows) == golden[
        family + ".by_rows" * by_rows]


# -- the benchmark's count and its trace readers ------------------------------------ #

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def bench_lib():
    sys.path.insert(0, BENCH)
    try:
        from lib import latent_trace, roofline
    finally:
        sys.path.remove(BENCH)
    return latent_trace, roofline


@pytest.mark.parametrize("name,group", [
    ("%fusion.10 = bf16[1,512,1536]{2,1,0} fusion(bf16[1,512,7168]{2,1,0} %x,"
     " bf16[6,7168,1536]{2,1,0} %gte.3, s32[] %i), kind=kOutput",
     "latent_attn"),
    ("%fusion.11 = bf16[1,512,12288]{2,1,0} fusion(bf16[1,512,1536]{2,1,0} "
     "%c, bf16[6,1536,12288]{2,1,0} %gte.4, s32[] %i)", "latent_attn"),
    ("%fusion.12 = bf16[1,512,576]{2,1,0} fusion(bf16[1,512,7168]{2,1,0} %x, "
     "bf16[6,7168,576]{2,1,0} %gte.5, s32[] %i)", "latent_attn"),
    ("%gather.2 = bf16[1,256,16,4,128]{4,3,2,1,0} gather(bf16[7,4096,16,4,128]"
     "{4,3,2,1,0} %pool, s32[1,256,2]{2,1,0} %ids)", "latent_attn"),
    ("%fusion.13 = bf16[1,512,64,512]{3,2,1,0} fusion(bf16[1,512,64,128]"
     "{3,2,1,0} %q, bf16[6,64,128,512]{3,2,1,0} %gte.6, s32[] %i)",
     "latent_attn"),
    ("%fusion.14 = f32[1,8,512,4608]{3,2,1,0} fusion(bf16[1,512,8,512]"
     "{3,2,1,0} %q, bf16[1,4096,512]{2,1,0} %lat)", "latent_attn"),
    ("%fusion.15 = bf16[1,512,7168]{2,1,0} fusion(bf16[1,512,12288]{2,1,0} "
     "%o, bf16[6,12288,7168]{2,1,0} %gte.7, s32[] %i)", "latent_attn"),
    ("%fusion.20 = bf16[16,1,512,2048]{3,2,1,0} fusion(bf16[1,512,7168]"
     "{2,1,0} %u, bf16[6,16,7168,2048]{3,2,1,0} %gte.8, s32[] %i)",
     "experts"),
    ("%fusion.21 = bf16[16,1,512,7168]{3,2,1,0} fusion(bf16[16,1,512,2048]"
     "{3,2,1,0} %fusion.20, bf16[6,16,2048,7168]{3,2,1,0} %gte.9, s32[] %i)",
     "experts"),
    ("%fusion.22 = f32[1,512,256]{2,1,0} fusion(bf16[1,512,7168]{2,1,0} %u, "
     "bf16[6,7168,256]{2,1,0} %gte.10, s32[] %i)", "expert_share_rest"),
    ("%fusion.23 = f32[1,512,8,32]{3,2,1,0} fusion(f32[1,512,256]{2,1,0} "
     "%fusion.22, f32[6,256]{1,0} %bias)", "expert_share_rest"),
    ("%fusion.24 = bf16[1,512,2048]{2,1,0} fusion(bf16[1,512,7168]{2,1,0} %u,"
     " bf16[6,7168,2048]{2,1,0} %gte.11, s32[] %i)", "expert_share_rest"),
    ("%fusion.25 = bf16[1,512,7168]{2,1,0} fusion(bf16[16,1,512,7168]"
     "{3,2,1,0} %fusion.21, bf16[1,512,16]{2,1,0} %combine)",
     "expert_share_rest"),
    ("%fusion.30 = bf16[1,512,18432]{2,1,0} fusion(bf16[1,512,7168]{2,1,0} "
     "%u, bf16[1,7168,18432]{2,1,0} %gte.12, s32[] %i)", None),
    ("%while.5 = (s32[], bf16[1,512,7168]{2,1,0}, bf16[6,16,7168,2048]"
     "{3,2,1,0}) while(%tuple.1)", None),
    ("%multiply_reduce_fusion = f32[16032]{0} fusion(bf16[7168,16032]{1,0} "
     "%params__lm_head__.1, f32[7168]{0} %fusion.97)", None),
], ids=["q_a", "q_b", "kv_a", "latent-gather", "absorb-uk", "scores",
        "o_proj", "held-gate-up", "held-down", "router", "group-scores",
        "shared-expert", "combine", "dense-ffn", "layer-loop", "head"])
def test_trace_ops_are_placed_by_the_arrays_they_touch(bench_lib, name,
                                                       group):
    latent_trace, _ = bench_lib
    assert latent_trace.group_of(name, published()["model"]) == group


@pytest.mark.parametrize("tokens,bound,ms", [
    (16, "memory", 3.906), (128, "memory", 3.906), (512, "compute", 8.315),
    (4096, "compute", 66.520)])
def test_prefill_step_floor_counts_what_every_step_must(
        bench_lib, tokens, bound, ms):
    """7 layers of 132.58 M attention weights, one dense feed-forward of
    396.36 M, six routers (1.84 M) and shared experts (44.04 M): 1.600 B
    weights every step reads (3.91 ms at 819 GB/s) and every token
    multiplies by, which binds from 241 tokens on; no routed expert is
    charged (none is certain under a share)."""
    _, roofline = bench_lib
    config = published()
    fam = roofline.family(config)
    assert abs(fam.every_step_params(config["model"]) - 1.5997e9) < 1e5
    secs, which = fam.prefill_step_floor_s(config["model"], PEAKS, tokens)
    assert which == bound and abs(secs * 1e3 - ms) < 0.001
    touched, e_which = fam.experts_floor_s(config["model"], PEAKS, tokens, 96)
    assert e_which == "memory"
    assert abs(touched * 1e3 - 96 * 44.04e6 * 2 / 819e9 * 1e3) < 0.01
    assert fam.experts_floor_s(config["model"], PEAKS, tokens, 0)[0] == 0


def test_share_readers_reduce_a_traced_window(bench_lib, tmp_path,
                                              monkeypatch):
    """The four new readers over a hand-made window: one 512-token prefill
    step whose program runs 40 ms: 20 in the held experts' matmuls, 2 in
    the router, 8 in latent attention; a program or run without the spans
    and counters returns None."""
    latent_trace, _ = bench_lib
    config = published()
    ms = 1_000_000
    names = ["%while.5 = (s32[]) while(%t)",
             "%fusion.20 = bf16[16,1,512,2048]{3,2,1,0} fusion(bf16[6,16,7168"
             ",2048]{3,2,1,0} %g)",
             "%fusion.22 = f32[1,512,256]{2,1,0} fusion(bf16[6,7168,256]"
             "{2,1,0} %r)",
             "%fusion.12 = bf16[1,512,576]{2,1,0} fusion(bf16[6,7168,576]"
             "{2,1,0} %q)",
             "%fusion.30 = bf16[1,512,18432]{2,1,0} fusion(bf16[1,7168,18432]"
             "{2,1,0} %d)"]
    t0 = 100 * ms
    ops = [[0, t0, 40 * ms], [3, t0 + 1 * ms, 8 * ms],
           [2, t0 + 10 * ms, 2 * ms], [1, t0 + 12 * ms, 20 * ms],
           [4, t0 + 33 * ms, 5 * ms]]
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({"names": names, "planes": [{
        "name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]}]}))
    sys.path.insert(0, BENCH)
    try:
        from lib import checkpoint, moe_trace

        monkeypatch.setattr(moe_trace, "trace_path", lambda: str(path))
        step = {"kind": "prefill_chunk", "t_ns": t0 - 3 * ms,
                "dur_ns": 50 * ms, "batch": 1, "tokens": 512,
                "experts_hit": 48, "moe_max_load": 70, "moe_local": 256}
        run = {"t0": 0.0, "t1": 1.0, "events": [step], "config": config,
               "peaks": PEAKS, "metrics0": {}, "metrics1": {},
               "trace": {"modules": [[(t0, t0 + 40 * ms,
                                       "jit_prefill_step(1)")]]}}
        readers = {n: checkpoint.load_module("layer_metrics", n).read
                   for n in ("step.latent_attn_device_pct",
                             "step.expert_share_device_pct",
                             "kernel.expert_share_roofline",
                             "engine.moe_held_hit_pct")}
        assert abs(readers["step.latent_attn_device_pct"](run) - 20.0) < 1e-6
        assert abs(readers["step.expert_share_device_pct"](run) - 55.0) < 1e-6
        floor_ms = 48 * 3 * 7168 * 2048 * 2 / 819e9 * 1e3  # 5.16
        assert abs(readers["kernel.expert_share_roofline"](run)
                   - 100 * floor_ms / 20) < 1e-6
        assert abs(readers["engine.moe_held_hit_pct"](run) - 50.0) < 1e-9
        # a program without the spans and counters (the parent's), a run
        # without a trace, another family's configuration: nothing
        bare = dict(run, trace=None, events=[
            {k: v for k, v in step.items()
             if k not in ("experts_hit", "moe_local", "moe_max_load")}])
        assert all(read(bare) is None for read in readers.values())
        with open(os.path.join(BENCH, "configs",
                               "qwen2.5-7b-h14.json")) as f:
            other = dict(run, config=json.load(f))
        latent_trace._MEMO.clear()
        assert all(read(other) is None for read in readers.values())
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("tokens,ctx,form", [
    (256, 256, "absorbed"), (512, 512, "up_projected"),
    (512, 1024, "up_projected"),
    (512, 1536, "up_projected"), (16, 2048, "absorbed"),
    (48, 1100, "absorbed")])
def test_latent_attn_roofline_counts_the_cheaper_form(bench_lib, tokens, ctx,
                                                      form):
    """The attention core's operations over the pairs a causal chunk can
    see, as the lesser of the absorbed and the up-projected form: at 64
    heads the absorbed form is cheaper until a key is seen by some 233
    queries on average."""
    sys.path.insert(0, BENCH)
    try:
        from lib import checkpoint

        mod = checkpoint.load_module("layer_metrics",
                                     "kernel.latent_attn_roofline")
    finally:
        sys.path.remove(BENCH)
    pairs = tokens * (ctx - tokens) + tokens * (tokens + 1) // 2
    forms = {"absorbed": 2 * pairs * 64 * (2 * 512 + 64),
             "up_projected": (2 * pairs * 64 * (128 + 64 + 192)
                              + 2 * ctx * 64 * (128 + 192) * 512)}
    assert mod.core_ops(published()["model"], tokens, ctx) == forms[form]
    assert forms[form] == min(forms.values())


def test_latent_attn_roofline_reduces_a_traced_window(bench_lib, tmp_path,
                                                      monkeypatch):
    """Two one-sequence steps (512 tokens with nothing cached, 16 tokens
    behind 1,008) whose latent-attention ops (a projection fusion and the
    kernel's custom call, placed by its `attn.core` name) take 12 ms: the
    reading is the steps' operations at the bf16 peak over those 12 ms;
    a shared step, a run without a trace and another family read nothing."""
    latent_trace, _ = bench_lib
    config = published()
    ms = 1_000_000
    names = ["%while.5 = (s32[]) while(%t)",
             "%fusion.12 = bf16[1,512,576]{2,1,0} fusion(bf16[6,7168,576]"
             "{2,1,0} %q)",
             "%attn.core.7 = bf16[1,32768,512]{2,1,0} custom-call(s32[1,32]"
             "{1,0} %table, bf16[1,32768,512]{2,1,0} %q)",
             "%fusion.30 = bf16[1,512,18432]{2,1,0} fusion(bf16[1,7168,18432]"
             "{2,1,0} %d)"]
    assert latent_trace.group_of(names[2], config["model"]) == "latent_attn"
    t0, t1 = 100 * ms, 200 * ms
    ops = [[0, t0, 40 * ms], [1, t0 + 1 * ms, 4 * ms],
           [2, t0 + 6 * ms, 5 * ms], [3, t0 + 20 * ms, 9 * ms],
           [0, t1, 20 * ms], [1, t1 + 1 * ms, 1 * ms],
           [2, t1 + 3 * ms, 2 * ms]]
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({"names": names, "planes": [{
        "name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]}]}))
    sys.path.insert(0, BENCH)
    try:
        from lib import checkpoint, moe_trace

        monkeypatch.setattr(moe_trace, "trace_path", lambda: str(path))
        steps = [{"kind": "prefill_chunk", "t_ns": t0 - 3 * ms,
                  "dur_ns": 50 * ms, "batch": 1, "tokens": 512, "ctx": 512},
                 {"kind": "prefill_chunk", "t_ns": t1 - 3 * ms,
                  "dur_ns": 30 * ms, "batch": 1, "tokens": 16, "ctx": 1024}]
        run = {"t0": 0.0, "t1": 1.0, "events": steps, "config": config,
               "peaks": PEAKS, "metrics0": {}, "metrics1": {},
               "trace": {"modules": [[
                   (t0, t0 + 40 * ms, "jit_prefill_step(1)"),
                   (t1, t1 + 20 * ms, "jit_prefill_step(1)")]]}}
        mod = checkpoint.load_module("layer_metrics",
                                     "kernel.latent_attn_roofline")
        latent_trace._MEMO.clear()
        model = config["model"]
        projections = 132_579_328  # a layer's five projections' weights
        want_ops = 7 * (2 * 528 * projections
                        + mod.core_ops(model, 512, 512)
                        + mod.core_ops(model, 16, 1024))
        got = mod.read(run)
        assert abs(got - 100 * want_ops / 197e12 / 12e-3) < 1e-6
        assert 0 < got < 100
        shared = dict(run, events=[dict(e, batch=2) for e in steps])
        latent_trace._MEMO.clear()
        assert mod.read(shared) is None
        assert mod.read(dict(run, trace=None)) is None
        with open(os.path.join(BENCH, "configs",
                               "qwen2.5-7b-h14.json")) as f:
            other = dict(run, config=json.load(f))
        latent_trace._MEMO.clear()
        assert mod.read(other) is None
    finally:
        sys.path.remove(BENCH)

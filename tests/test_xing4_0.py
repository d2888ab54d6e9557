"""xing4_0 on the served path (ISSUE 37): a residual of `hc_mult` streams
mixed by Sinkhorn-normalised hyper-connections around deepseek_v3's layers.
The family's config keys and refusals, its checkpoint names through the
loader, every forward path against the plain reference
(`benchmark/reference/xing4_0.py`): logits, not tokens; the shared short
step and a prefix-cache hit; the Sinkhorn steps and the clip; the tie to
the accepted family; each fault the comparison must catch; the step's
`hc_res_err_ppm`; the layouts that refuse the family; the benchmark's count
and trace placement.  Tiny sizes, float32, seeded weights, CPU."""

import asyncio
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import ModelConfig, init_params, llama
from dynamo_tpu.models.loader import load_params
from dynamo_tpu.ops import hyper_connections as hc

import test_deepseek_v3 as ds
from test_deepseek_v3 import (BENCH, PAGE, ROOT, TOL, bench_module,
                              engine_of, generate, logp, prompt)

CELL = "xing4.0-29b-h8"

TINY = dict(
    ds.TINY, model_type="xing4_0", num_hidden_layers=4,
    first_k_dense_replace=2, n_routed_experts=8, ep_size=1, n_group=1,
    topk_group=1, num_experts_per_tok=2, routed_scaling_factor=2,
    hc_mult=4, hc_sinkhorn_iters=8, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30)
# 8 Sinkhorn steps, not the published 20: the steps are unrolled into one
# elementwise fusion (`ops/hyper_connections.py` `sinkhorn`), which the CPU
# backend compiles in 2 s a mixer at 8 steps and in 12-20 s at 20, four
# mixers a program; the model and the reference run the same count, and the
# tests of `sinkhorn` itself run 20.
HC_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
           "mhc_h_res_clamp_max")


@pytest.fixture(scope="module")
def ref():
    return bench_module("reference", "xing4_0")


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig.from_hf_config(TINY, name="tiny-xing4-0")


@pytest.fixture(scope="module")
def params(cfg):
    """Mixers at unit scale (`init_params`): logits with a spread near 1, so
    that R is far from uniform and a transposed R shows."""
    return init_params(cfg, jax.random.PRNGKey(37), dtype=jnp.float32)


# One compiled program a (configuration, chunk shape) for the whole file: the
# eager forward compiles its layer scans anew at every call, and a scan body
# of this family holds two mixers' unrolled Sinkhorn steps.
_PROGRAMS = {}


def jitted(forward, cfg):
    """`forward(params, cfg, ...)` jitted over everything but `cfg` (which
    holds a dict and cannot be a static argument)."""
    key = (forward.__name__, repr(cfg))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(
            lambda params, *args: forward(params, cfg, *args))
    return _PROGRAMS[key]


def prefill_all(cfg, params, tokens, chunk=None):
    """`test_deepseek_v3.prefill_all` through the jitted forward: chunked
    prefill of one prompt through the paged latent cache -> the next-token
    logprobs after each chunk's last token [(position, lp)], the cache and
    the table."""
    T = len(tokens)
    chunk = chunk or T
    kv = ds.KVCache.create(cfg, 2 + -(-T // PAGE) + 8, PAGE, jnp.float32)
    table = ds.table_for(T + 8 * PAGE)
    out = []
    for s in range(0, T, chunk):
        part = tokens[s:s + chunk]
        logits, kv = jitted(llama.forward_prefill, cfg)(
            params, kv, jnp.asarray([part], jnp.int32), table,
            jnp.asarray([s], jnp.int32), jnp.asarray([len(part)], jnp.int32))
        out.append((s + len(part) - 1, logp(logits)[0]))
    return out, kv, table


def stacks_of(params, cfg):
    k = cfg.first_k_dense
    return [(params["dense_layers"], i) if i < k
            else (params["layers"], i - k)
            for i in range(cfg.num_hidden_layers)]


def reader_of(params, cfg):
    """`read(name)` over a param tree under the family's tensor names:
    deepseek_v3's (`test_deepseek_v3.reader_of`) and the mixers', each `fn`
    back to a Linear's [out, streams x hidden]."""
    inner = ds.reader_of(params, cfg)
    flat = {}
    for i, (lay, j) in enumerate(stacks_of(params, cfg)):
        for half, key in (("hc_attn", "hc_attn"), ("hc_ffn", "hc_mlp")):
            p = f"model.layers.{i}.{half}."
            phi = np.asarray(lay[key + "_phi"][j])
            flat[p + "fn"] = phi.reshape(-1, phi.shape[-1]).T
            flat[p + "scale"] = lay[key + "_scale"][j]
            flat[p + "base"] = lay[key + "_base"][j]
    phi = np.asarray(params["hc_head_phi"])
    flat["model.hc_head.fn"] = phi.reshape(-1, phi.shape[-1]).T
    flat["model.hc_head.scale"] = params["hc_head_scale"]
    flat["model.hc_head.base"] = params["hc_head_base"]
    return lambda name: (np.asarray(flat[name], np.float32) if name in flat
                         else inner(name))


def ref_logp(ref, cfg, params, tokens, **controls):
    """Reference next-token logprobs after every position: [T, vocab]."""
    return ref.forward(reader_of(params, cfg), TINY, [np.asarray([tokens])],
                       len(tokens), **controls)[0][0]


def with_mixers(params, fn):
    """The tree with `fn(name, array)` applied to every mixer tensor."""
    def walk(tree):
        return {k: (walk(v) if isinstance(v, dict)
                    else fn(k, v) if k.startswith("hc_") else v)
                for k, v in tree.items()}
    return walk(params)


def published():
    with open(os.path.join(BENCH, "configs", CELL + ".json")) as f:
        return json.load(f)


# -- configuration ------------------------------------------------------------- #

def test_from_hf_config_reads_the_published_keys():
    """The catalog row's keys as published (40 layers) and as run (8)."""
    run = published()
    model = dict(run["model"])
    model.update({k: v["published"] for k, v in run["reduced"].items()})
    c = ModelConfig.from_hf_config(model)
    assert c.model_type == "xing4_0" and c.is_latent
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps, c.hc_res_clamp) == (
        4, 20, 1e-6, (-30.0, 30.0))
    assert c.hc_mixer_width == 24
    assert (c.kv_lora_rank, c.q_lora_rank, c.hidden_size) == (512, 768, 3584)
    assert (c.num_experts, c.router_width, c.num_experts_per_tok) == (
        64, 64, 4)
    assert (c.moe_n_group, c.moe_topk_group, c.moe_routed_scale) == (1, 1, 2.0)
    assert c.first_k_dense == 2 and c.num_moe_layers == 38
    assert c.num_params() == 29_505_562_613  # "29B"; 59.0 GB in bf16
    cut = ModelConfig.from_hf_config(run["model"])
    assert cut.num_hidden_layers == 8 and cut.num_moe_layers == 6
    assert cut.num_params() == 5_665_913_141  # the issue's count
    assert cut.cache_spec.bytes_per_token_layer(2) * 8 == 12_288
    assert cut.residual_report == {
        "kind": "hyper_connections", "streams": 4, "sinkhorn_iters": 20,
        "res_clamp": [-30.0, 30.0]}
    assert ModelConfig.from_hf_config(ds.TINY).residual_report == {
        "kind": "add", "streams": 1}


def test_the_file_states_each_published_key_once_for_each_reader():
    """Top-level keys (what the driver's check reads) equal `model` (what
    the program gets); `num_hidden_layers` alone differs from the source."""
    run = published()
    model = dict(run["model"])
    assert model.pop("architectures") == ["Xing4_0ForCausalLM"]
    assert model.pop("torch_dtype") == "bfloat16"
    assert {k: run[k] for k in model} == model
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [c for c in json.load(f)["configs"] if c["name"] == CELL]
    assert entry["reduced"] == list(run["reduced"]) == ["num_hidden_layers"]
    cut = run["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["run"], run["num_hidden_layers"]) == (
        40, 8, 8)
    assert (run["first_k_dense_replace"], run["n_routed_experts"],
            run["ep_size"], run["vocab_size"]) == (2, 64, 1, 131072)
    memory = run["memory"]
    assert memory["kv_bytes_per_token"] == 12_288
    assert memory["kv_pool_tokens"] == run["worker_flags"]["--num-pages"] * 16
    assert memory["kv_pool_tokens"] >= 131_072
    assert memory["kv_pool_bytes"] == memory["kv_pool_tokens"] * 12_288


@pytest.mark.parametrize("bad,key", [
    *(({k: None}, k) for k in HC_KEYS),
    ({"hc_mult": 1}, "hc_mult"),
    ({"hc_sinkhorn_iters": 0}, "hc_sinkhorn_iters"),
    ({"hc_eps": 0}, "hc_eps"),
    ({"mhc_h_res_clamp_min": 30}, "mhc_h_res_clamp_min"),
    ({"topk_method": "greedy"}, "xing4_0: topk_method"),
    ({"q_lora_rank": None}, "xing4_0: q_lora_rank"),
    ({"model_type": "llama", "kv_lora_rank": None}, "hc_mult"),
], ids=[*HC_KEYS, "one-stream", "no-sinkhorn-step", "no-eps", "empty-clamp",
        "a-layer-key-under-this-family's-name", "full-rank-q",
        "another-family-with-streams"])
def test_from_hf_config_refuses_what_it_cannot_compute(bad, key):
    """By the key it cannot compute: every hyper-connection key is needed,
    deepseek_v3's refusals hold under this family's name, and a config with
    `hc_mult` of a family that is not implemented does not fall to the
    llama branch."""
    model = {k: v for k, v in dict(TINY, **bad).items() if v is not None}
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config(model)


def test_num_params_is_the_checkpoints_element_count(cfg, params):
    """At the tiny size against the tree, and at the cell's size against the
    benchmark's checkpoint layout, tensor by tensor."""
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))
    run = published()
    layout = bench_module("checkpoints", "xing4_0")
    count, kinds = 0, {}
    for name, shape, kind in layout.tensors(run["model"]):
        count += int(np.prod(shape))
        if ".hc_" in name:
            kinds[name.rsplit(".", 1)[1]] = kind
    assert count == ModelConfig.from_hf_config(run["model"]).num_params()
    assert count * 2 == run["memory"]["weights_bytes_bf16"]
    # a scale drawn as a weight would leave every mixer flat
    assert kinds == {"fn": "weight", "scale": "ones", "base": "weight"}


# -- checkpoint names through the loader ----------------------------------------- #

def test_written_checkpoint_loads_and_agrees_with_the_reference(tmp_path, ref):
    """`benchmark/lib/checkpoint.py` + `checkpoints/xing4_0.py` write the
    family's tensors; `models/loader.py` reads them, the mixers as float32
    whatever the file's dtype; a chunked prefill over the loaded tree agrees
    with the reference reading the same file."""
    from safetensors import safe_open

    ckpt = bench_module("lib", "checkpoint")
    out = str(tmp_path / "ckpt")
    ckpt.write({"model": TINY, "weights_seed": 5, "checkpoint": "xing4_0"},
               out)
    c = ModelConfig.from_pretrained(out)
    assert c.model_type == "xing4_0" and c.hc_mult == 4
    loaded = load_params(out, c, dtype=jnp.bfloat16)
    for stack, n in ((loaded["dense_layers"], 2), (loaded["layers"], 2)):
        for half in ("hc_attn", "hc_mlp"):
            assert stack[half + "_phi"].shape == (n, 4, 64, 24)
            assert stack[half + "_phi"].dtype == jnp.float32
            assert np.array_equal(np.asarray(stack[half + "_scale"]),
                                  np.ones((n, 3), np.float32))
            assert stack[half + "_base"].shape == (n, 24)
    assert loaded["hc_head_phi"].shape == (4, 64, 4)
    assert loaded["hc_head_scale"].dtype == jnp.float32
    assert loaded["embed"].dtype == jnp.bfloat16
    loaded = load_params(out, c, dtype=jnp.float32)
    reader = safe_open(os.path.join(out, "model.safetensors"), framework="np")
    toks = prompt(24, 1)
    want = ref.tail_logprobs(
        lambda n: reader.get_tensor(n).astype(np.float32), TINY,
        [np.asarray([toks])], 1)[0][0, 0]
    steps, _, _ = prefill_all(c, loaded, toks, chunk=16)
    assert np.abs(steps[-1][1] - want).max() < TOL


# -- the forward paths against the reference ------------------------------------- #

@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_chunked_prefill_agrees_with_the_reference(cfg, params, ref, chunks):
    toks = prompt(40)
    want = ref_logp(ref, cfg, params, toks)
    steps, _, _ = prefill_all(cfg, params, toks, chunk=40 // chunks)
    assert len(steps) == chunks
    for pos, got in steps:
        assert np.abs(got - want[pos]).max() < TOL, pos


@pytest.mark.parametrize("path", ["decode", "block", "verify"])
def test_decode_through_the_latent_cache_agrees_with_the_reference(
        cfg, params, ref, path):
    """Prefill 24 tokens in two chunks, then 8 greedy tokens through the
    latent cache by the per-step decode, the block scan and the speculative
    verify: each step's LOGITS against the reference's full forward over
    the text so far (the four streams ride every one of these loops)."""
    P, N = 24, 8
    toks = prompt(P, 2)
    steps, kv, table = prefill_all(cfg, params, toks, chunk=16)
    text = toks + [int(steps[-1][1].argmax())]
    for _ in range(N - 1):  # the reference's own greedy path
        text.append(int(ref_logp(ref, cfg, params, text)[-1].argmax()))
    want = ref_logp(ref, cfg, params, text)
    if path == "decode":
        got = []
        for i in range(N):
            logits, kv = jitted(llama.forward_decode, cfg)(
                params, kv, jnp.asarray([text[P + i]], jnp.int32),
                jnp.asarray([P + i], jnp.int32), table)
            got.append(logp(logits)[0])
    elif path == "block":
        forced = jnp.asarray(text[P + 1:] + [0], jnp.int32)

        def sample_step(carry, logits, tok_prev, step):
            return carry, forced[step][None], logits

        _, ys, _, _, kv = llama.decode_block_scan(
            params, cfg, kv, jnp.asarray([text[P]], jnp.int32),
            jnp.asarray([P], jnp.int32), table, N, 512, sample_step, ())
        got = list(logp(ys)[:, 0])
    else:
        logits, kv = llama.forward_verify(
            params, cfg, kv, jnp.asarray([text[P:]], jnp.int32), table,
            jnp.asarray([P], jnp.int32), jnp.asarray([N], jnp.int32))
        got = list(logp(logits)[0])
    for i, g in enumerate(got):
        assert np.abs(g - want[P + i]).max() < TOL, (path, i)
        if i + 1 < N:  # the served path walks the reference's greedy path
            assert int(g.argmax()) == text[P + i + 1]


def test_forward_embed_carries_the_streams(cfg, params, ref):
    """The pooled embedding is the mean of the head's REDUCTION of the
    streams after the final norm: equal to the same pooling over a prefill
    whose lm_head is the identity's stand-in (the final norm's output)."""
    toks = prompt(12, 4)
    got = llama.forward_embed(params, cfg, jnp.asarray([toks], jnp.int32),
                              jnp.asarray([12], jnp.int32))
    assert got.shape == (1, 64)
    assert abs(float(jnp.linalg.norm(got)) - 1.0) < 1e-5
    other = llama.forward_embed(params, cfg, jnp.asarray([toks], jnp.int32),
                                jnp.asarray([11], jnp.int32))
    assert float(jnp.abs(got - other).max()) > 1e-4


# -- the engine: shared short steps, the prefix cache, every decode path ---------- #

@pytest.mark.parametrize("how", [
    {}, {"decode_steps": 4}, {"speculative_ngram_k": 3},
    {"mixed_prefill_tokens": 16},
], ids=["default", "block-of-4", "speculative", "mixed"])
async def test_engine_decodes_what_the_reference_decodes(cfg, params, ref,
                                                         how):
    """Chunked prefill, the prefix cache over latent pages (the second and
    third requests share 32 tokens: a hit gives the cold run's answer) and
    the decode paths a server can reach: the logprob of every greedy token
    against the reference's full forward pass over the text so far."""
    engine = engine_of(cfg, params, **how)
    try:
        shared = prompt(32, 6)
        for tail in (prompt(5, 7), prompt(9, 8), prompt(5, 7)):
            toks = shared + tail
            got, lps = await generate(engine, toks, 4)
            text = list(toks)
            for t, lp_t in zip(got, lps):
                want = ref_logp(ref, cfg, params, text)[-1]
                assert t == int(want.argmax()), (how, len(text))
                assert abs(lp_t - want.max()) < 5 * TOL
                text.append(t)
        admits = [e for e in engine.events.dump()["events"]
                  if e["kind"] == "admit"]
        assert [e["cached"] > 0 for e in admits] == [False, True, True]
    finally:
        await engine.shutdown()


async def test_a_shared_short_step_gives_each_row_its_lone_answer(cfg, params,
                                                                  ref):
    """Four short prompts that are ready together ride ONE `prefill_step` of
    four rows (PR 36); each row's token and logprob are the reference's for
    that prompt alone."""
    engine = engine_of(cfg, params, max_prefill_tokens=128, max_model_len=256,
                       num_pages=256, max_num_seqs=8)
    try:
        prompts = [prompt(9 + i, 20 + i) for i in range(4)]
        done = await asyncio.wait_for(asyncio.gather(*(
            generate(engine, p, 1) for p in prompts)), 120)
        for _ in range(200):  # a slice is recorded AFTER its token's delivery
            chunks = [e for e in engine.events.dump()["events"]
                      if e["kind"] == "prefill_chunk"]
            if chunks:
                break
            await asyncio.sleep(0.01)
        assert [e["batch"] for e in chunks] == [4]
        assert "hc_res_err_ppm" in chunks[0]
        for p, (toks, lps) in zip(prompts, done):
            want = ref_logp(ref, cfg, params, p)[-1]
            assert toks == [int(want.argmax())]
            assert abs(lps[0] - want.max()) < 5 * TOL
    finally:
        await engine.shutdown()


async def test_steps_carry_hc_res_err_ppm_and_the_gauge_keeps_the_largest(
        cfg, params):
    """Every prefill-path step slice carries `hc_res_err_ppm`, the largest
    |row or column sum of R - 1| over the step's tokens and halves, beside
    the moe stats it carried; `/metrics.json` keeps the largest so far.
    Mixers scaled by 100 clip their logits to +-30 and the Sinkhorn steps
    do not converge there: the gauge says so (tens of percent off after
    TINY's 8 steps, where the unit-scale mixers read about 1%; after the
    published 20 steps some 7% against 0.1%)."""
    engine = engine_of(cfg, params)
    try:
        await generate(engine, prompt(40, 9), 1)
        for _ in range(200):
            chunks = [e for e in engine.events.dump()["events"]
                      if e["kind"] == "prefill_chunk"]
            if len(chunks) == 3:
                break
            await asyncio.sleep(0.01)
        assert len(chunks) == 3
        for e in chunks:
            assert 0 < e["hc_res_err_ppm"] < 60_000  # unit logits
            assert e["experts_hit"] <= cfg.num_moe_layers * cfg.num_experts
            assert "moe_local" not in e  # every expert is held: no share
        m = vars(engine.metrics())
        assert m["hc_res_err_ppm_max"] == max(
            e["hc_res_err_ppm"] for e in chunks)
        assert m["moe_assignments_total"] == (
            40 * cfg.num_experts_per_tok * cfg.num_moe_layers)
    finally:
        await engine.shutdown()
    hot = with_mixers(params, lambda k, a: a * 100 if k.endswith("_scale")
                      else a)
    engine = engine_of(cfg, hot)
    try:
        await generate(engine, prompt(40, 9), 1)
        assert vars(engine.metrics())["hc_res_err_ppm_max"] > 200_000
    finally:
        await engine.shutdown()


def test_the_stats_gain_one_column_and_merge_it_as_a_maximum(cfg):
    assert llama.moe_stats_width(cfg) == 4 and llama.moe_stats_columns(cfg) == 3
    share = dataclasses.replace(cfg, moe_ep_size=2, num_experts=4)
    assert llama.moe_stats_width(share) == 5
    assert llama.moe_stats_width(ModelConfig.from_hf_config(ds.TINY)) == 4
    merged = llama.merge_moe_stats(jnp.asarray(
        [[8, 3, 2, 40], [8, 1, 5, 7], [0, 0, 0, 90]]), hc_err=True)
    assert [int(v) for v in merged] == [16, 4, 5, 90]


@pytest.mark.parametrize("how,match", [
    ({"parallel": {"tp": 2}}, "multi-stream residual"),
    ({"parallel": {"pp": 2}, "max_prefill_tokens": 128}, "serving mesh"),
    ({"parallel": {"sp": 2}, "max_prefill_tokens": 128}, "serving mesh"),
    ({"parallel": {"dp": 2}, "kv_partition": True}, "serving mesh"),
], ids=["tp", "pp", "sp", "partitioned-pool"])
def test_layouts_that_cannot_carry_the_streams_refuse_the_family(cfg, params,
                                                                 how, match):
    from dynamo_tpu.parallel import ParallelConfig

    how = dict(how, parallel=ParallelConfig(**how["parallel"]))
    with pytest.raises(ValueError, match=match):
        engine_of(cfg, params, **how)


@pytest.mark.parametrize("layers", [7, 8, 16])
def test_a_pool_of_eight_layers_lands_its_rows_where_seven_do(layers):
    """`write_kv_layers` lands a pool whose layers would fill a tile's
    sublanes (8 latent layers: this cell) through one flat row axis; the
    rows land where the scatter over the layer axis (7 layers) lands them,
    padding in trash slot 0, nothing else touched."""
    from dynamo_tpu.ops import paged_attention as pa

    assert pa._layers_would_move_to_sublanes(layers, (2, 128)) is (
        layers != 7)
    assert not pa._layers_would_move_to_sublanes(8, (8, 64))
    P, page, B, S = 6, 4, 2, 5
    rng = np.random.default_rng(layers)
    pools = [rng.standard_normal((layers, P, page, *plane)).astype(np.float32)
             for plane in ((2, 128), (4, 128))]
    new = [rng.standard_normal((layers, B, S, *pool.shape[3:])).astype(
        np.float32) for pool in pools]
    table = np.asarray([[1, 2, 0], [3, 4, 5]], np.int32)
    start = np.asarray([3, 6], np.int32)
    valid = np.arange(S)[None, :] < np.asarray([5, 3])[:, None]
    got = pa.write_kv_layers(*map(jnp.asarray, (*pools, *new, table, start,
                                                valid)))
    for pool, rows, out in zip(pools, new, got):
        want = pool.copy()
        for b in range(B):
            for t in range(S):
                if valid[b, t]:
                    pos = start[b] + t
                    want[:, table[b, pos // page], pos % page] = rows[:, b, t]
        out = np.asarray(out)
        assert np.array_equal(out[:, 1:], want[:, 1:])
        assert np.array_equal(out[:, 0, 1:], want[:, 0, 1:])  # but slot 0


# -- the mixers -------------------------------------------------------------------- #

def test_sinkhorn_reaches_doubly_stochastic_from_unit_logits(ref):
    """R after 20 steps from logits at unit scale (what a mixer at scale 1
    makes): the column sums are 1 to rounding (the last step divides by
    them), the row sums within 1e-3 of 1 for every token and within 1e-4
    for all but one in a hundred, and `ops/hyper_connections.py` and the
    reference's numpy agree entry for entry.  At scale 3 twenty steps have
    NOT converged (the issue expected 1e-4 there; the median token reads
    2e-4 and the worst of 256 some 3e-2): what `hc_res_err_ppm` is for."""
    draw = np.random.default_rng(0).standard_normal((4, 4, 256)).astype(
        np.float32)
    res = np.asarray(hc.sinkhorn(jnp.asarray(draw), 20, 1e-6, (-30., 30.)))
    rows = np.abs(res.sum(1) - 1).max(0)
    assert np.abs(res.sum(0) - 1).max() < 1e-5
    assert rows.max() < 1e-3 and np.mean(rows < 1e-4) >= 0.99
    want = ref.sinkhorn(np, np.moveaxis(draw, -1, 0), 20, np.float32(1e-6),
                        (-30, 30))
    assert np.abs(np.moveaxis(res, -1, 0) - want).max() < 1e-6
    one = np.asarray(hc.sinkhorn(jnp.asarray(draw), 1, 1e-6, (-30., 30.)))
    assert np.abs(one.sum(1) - 1).max() > 0.05  # one step is not enough
    wide = np.asarray(hc.sinkhorn(jnp.asarray(3 * draw), 20, 1e-6,
                                  (-30., 30.)))
    assert np.abs(wide.sum(0) - 1).max() < 1e-5
    rows = np.abs(wide.sum(1) - 1).max(0)
    assert 1e-5 < np.median(rows) < 1e-3 and rows.max() > 1e-3
    want = ref.sinkhorn(np, np.moveaxis(3 * draw, -1, 0), 20,
                        np.float32(1e-6), (-30, 30))
    assert np.abs(np.moveaxis(wide, -1, 0) - want).max() < 1e-6


def test_mix_pre_post_and_head_are_the_equations(cfg, params, ref):
    """`mix`, `pre`, `post` and `head_reduce` over random streams against
    the reference's numpy, bf16 streams too (phi's three bf16 pieces give
    what the float32 product gives)."""
    lay = jax.tree.map(lambda a: a[1], params["layers"])
    kw = dict(iters=TINY["hc_sinkhorn_iters"], eps=1e-6, clamp=(-30., 30.),
              rms_eps=1e-6)
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-5)):
        X = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 4, 64),
                              jnp.float32).astype(dtype)
        y = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 64),
                              jnp.float32).astype(dtype)
        m = hc.mix(X, lay["hc_mlp_phi"], lay["hc_mlp_scale"],
                   lay["hc_mlp_base"], **kw)
        Xn, yn = (np.asarray(a, np.float32) for a in (X, y))
        phi = np.asarray(lay["hc_mlp_phi"])
        pre, post, R = ref.mixer(np, TINY, Xn, (
            phi.reshape(-1, 24).T, np.asarray(lay["hc_mlp_scale"]),
            np.asarray(lay["hc_mlp_base"])))
        assert np.abs(np.asarray(m.pre) - pre).max() < tol
        assert np.abs(np.asarray(m.post) - post).max() < tol
        assert np.abs(np.asarray(m.res) - R).max() < tol
        rows = np.abs(R.sum(-1) - 1).max(-1)  # the last step: columns
        assert np.abs(np.asarray(m.err) - rows).max() < tol
        if dtype == jnp.bfloat16:
            continue  # the sums below round to the streams' dtype
        u = hc.pre(X, m.pre)
        assert np.abs(np.asarray(u) - ref.read_in(np, Xn, pre)).max() < 1e-5
        out = hc.post(X, y, m.post, m.res)
        assert np.abs(np.asarray(out)
                      - ref.write_back(np, Xn, yn, post, R)).max() < 1e-5
        got = hc.head_reduce(X, params["hc_head_phi"],
                             params["hc_head_scale"], params["hc_head_base"],
                             eps=1e-6, rms_eps=1e-6)
        hphi = np.asarray(params["hc_head_phi"]).reshape(-1, 4).T
        w = 1 / (1 + np.exp(-(np.asarray(params["hc_head_scale"])[0]
                              * ref.mix_logits(np, Xn, hphi, 1e-6)
                              + np.asarray(params["hc_head_base"])))) + 1e-6
        assert np.abs(np.asarray(got)
                      - ref.read_in(np, Xn, w.astype(np.float32))).max() < 1e-5


def test_where_the_clip_binds_the_model_clips_as_the_reference_does(
        cfg, params, ref):
    """Mixers scaled by 100 push the logits of R far past +-30: the model
    agrees with the reference as written and differs from the reference
    without its clip."""
    hot = with_mixers(params, lambda k, a: a * 100 if k.endswith("_scale")
                      else a)
    toks = prompt(24, 12)
    (_, got), = prefill_all(cfg, hot, toks)[0]
    assert np.abs(got - ref_logp(ref, cfg, hot, toks)[-1]).max() < 5 * TOL
    unclipped = ref_logp(ref, cfg, hot, toks, faults=("no_clip",))[-1]
    assert np.abs(got - unclipped).max() > 50 * TOL


def test_with_unit_mixers_stream_0_is_the_accepted_family(cfg, params):
    """The tie to deepseek_v3: with pre = post = w = e_0 and R = I (phi 0 and
    large biases) stream 0 is the plain residual x + f(x), and the model
    gives the `deepseek_v3` path's logits on the same weights, within eps."""
    n = cfg.hc_mult
    e0 = np.where(np.arange(n) == 0, 40.0, -40.0)
    base = np.concatenate([e0, np.where(np.arange(n) == 0, 0.0, -40.0),
                           np.where(np.eye(n, dtype=bool), 30.0,
                                    -30.0).ravel()]).astype(np.float32)

    def unit(key, a):
        if key.endswith("_phi"):
            return jnp.zeros_like(a)
        if key.endswith("_scale"):
            return a
        want = e0.astype(np.float32) if key == "hc_head_base" else base
        return jnp.broadcast_to(jnp.asarray(want), a.shape)

    tied = with_mixers(params, unit)
    plain_cfg = ModelConfig.from_hf_config(dict(TINY, model_type="deepseek_v3"))
    assert not plain_cfg.hc_mult

    def strip(tree):
        return {k: strip(v) if isinstance(v, dict) else v
                for k, v in tree.items() if not k.startswith("hc_")}

    toks = prompt(40, 13)
    got, _, _ = prefill_all(cfg, tied, toks, chunk=16)
    want, _, _ = prefill_all(plain_cfg, strip(params), toks, chunk=16)
    for (pos, g), (_, w) in zip(got, want):
        assert np.abs(g - w).max() < 1e-3, pos
    loose, _, _ = prefill_all(cfg, params, toks, chunk=16)
    assert np.abs(loose[-1][1] - want[-1][1]).max() > 0.05


def test_one_group_runs_through_the_grouped_router_as_it_is(cfg, params, ref):
    """`n_group` 1 / `topk_group` 1: one group, always kept, through
    `_route_grouped_sigmoid` unchanged: the k best biased scores of all."""
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    lp = dict(lp, router_bias=lp["router_bias"] * 20)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 64), jnp.float32)
    w, sel = llama._route(lp, x, cfg)
    logits = (np.asarray(x) @ np.asarray(lp["router"])).astype(np.float32)
    biased = 1 / (1 + np.exp(-logits)) + np.asarray(lp["router_bias"])
    assert np.array_equal(np.sort(np.asarray(sel), -1),
                          np.sort(np.argsort(-biased, -1)[..., :2], -1))
    idx, wts = ref.base.route(np, TINY, logits, np.asarray(lp["router_bias"]))
    assert np.array_equal(np.sort(np.asarray(sel), -1), np.sort(idx, -1))
    assert np.allclose(np.asarray(w).sum(-1), 2.0, atol=1e-5)


# -- what the comparison rests on ------------------------------------------------- #

@pytest.mark.parametrize("control", [
    {"lower_precision": True}, {"faults": ("res_transposed",)},
    {"faults": ("one_sinkhorn_step",)}, {"faults": ("post_without_2",)},
    {"faults": ("head_mean",)}, {"faults": ("no_shared",)},
    {"faults": ("no_routed_scale",)},
], ids=["lower-precision", "R-transposed", "one-sinkhorn-step",
        "post-without-its-2", "head-reduce-a-mean", "dropped-shared-expert",
        "missing-routed-scale"])
def test_the_comparison_catches(cfg, params, ref, control):
    """What the benchmark's `correct` rests on, at the tiny size: against
    the reference computed with one thing wrong, the model is out of the
    tolerance that it meets against the reference as written (the dropped
    clip has its own case above: it shows only where the clip binds)."""
    assert set(ref.FAULTS) >= set(control.get("faults", ())) - {
        "no_shared", "no_routed_scale"}
    toks = prompt(48, 3)
    (_, got), = prefill_all(cfg, params, toks)[0]
    assert np.abs(got - ref_logp(ref, cfg, params, toks)[-1]).max() < TOL
    wrong = ref_logp(ref, cfg, params, toks, **control)
    assert np.abs(got - wrong[-1]).max() > 10 * TOL, control


def test_the_reference_needs_every_hyper_connection_key(cfg, params, ref):
    for key in HC_KEYS:
        with pytest.raises(ValueError, match=key):
            ref.check_model({k: v for k, v in TINY.items() if k != key})


# -- the benchmark's count and its trace reader ------------------------------------- #

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def bench_lib():
    import sys

    sys.path.insert(0, BENCH)
    try:
        from lib import hc_trace, roofline
    finally:
        sys.path.remove(BENCH)
    return hc_trace, roofline


def test_the_roofline_counts_what_every_step_must_do(bench_lib):
    _, roofline = bench_lib
    run = published()
    model = run["model"]
    fam = roofline.family(run)
    attn, dense, layer, expert = fam._dims(model)
    assert (attn, dense, expert) == (28_411_136 - 768 - 512, 99_090_432,
                                     11_010_048)
    assert layer == 229_376 + 11_010_048  # router (no bias) + shared expert
    served, mixers = fam.every_step_params(model)
    assert mixers == 8 * 2 * 4 * 3584 * 24
    # every expert is held: each token multiplies by exactly 4 of them
    assert served == 8 * attn + 2 * dense + 6 * (layer + 4 * expert)
    share = dict(model, ep_size=2)
    assert fam.every_step_params(share)[0] == (
        8 * attn + 2 * dense + 6 * (layer + 3584 * 64))
    secs, bound = fam.prefill_step_floor_s(model, PEAKS, 512)
    assert bound == "compute"
    assert secs == pytest.approx(2 * 512 * (served + mixers) / 197e12)
    secs, bound = fam.prefill_step_floor_s(model, PEAKS, 16)
    assert bound == "memory"
    assert secs == pytest.approx((2 * served + 4 * mixers) / 819e9)
    # the mixers' traffic: 10 rows of 3584 bf16 values a token and half
    secs, bound = fam.hyper_conn_floor_s(model, PEAKS, 512)
    assert bound == "memory"
    assert secs == pytest.approx(10 * 3584 * 2 * 16 * 512 / 819e9)
    assert 0.70e-3 < secs < 0.73e-3
    assert fam.experts_floor_s(model, PEAKS, 512, 384)[0] == pytest.approx(
        2 * 384 * expert / 819e9)


@pytest.mark.parametrize("name,mixers", [
    ("%hc.mix.3 = f32[24,512]{1,0} custom-call(bf16[512,4,3584]{2,1,0} %x)",
     True),
    ("%fusion.1088 = f32[1,512,4,3584]{3,1,2,0} fusion(f32[4,512]{1,0} %a, "
     "bf16[1,512,3584]{2,1,0} %y, bf16[1,512,4,3584]{3,2,1,0} %x)", True),
    ("%bitcast_multiply_fusion.27 = f32[24,512]{1,0} fusion(bf16[512,4,3584]"
     "{2,0,1} %x, bf16[4,3584,24]{2,1,0} %phi, f32[512]{0} %r)", True),
    ("%divide_reduce_fusion.184 = (f32[4,512]{1,0}, f32[4,4,512]{2,1,0}) "
     "fusion(f32[24,512]{1,0} %m)", True),
    ("%fusion.9 = bf16[4,64,3584]{2,1,0} fusion(bf16[4,64,4,3584]{3,2,1,0} "
     "%x, f32[4,64,4]{2,1,0} %pre, bf16[3584]{0} %norm)", True),
    ("%fusion.10 = bf16[1,512,4,3584]{3,2,1,0} fusion(bf16[1,512,4096]{2,1,0}"
     " %o, bf16[6,4096,3584]{2,1,0} %wo, bf16[1,512,4,3584]{3,2,1,0} %x)",
     False),
    ("%fusion.11 = bf16[1,512,768]{2,1,0} fusion(bf16[1,512,3584]{2,1,0} %u,"
     " bf16[6,3584,768]{2,1,0} %wq_a, s32[] %i)", False),
    ("%fusion.12 = bf16[4,131072]{1,0} fusion(bf16[4,3584]{1,0} %x, "
     "bf16[3584,131072]{1,0} %head)", False),
    ("%fusion.13 = bf16[4,3584]{1,0} fusion(bf16[4,3584]{1,0} %x, "
     "bf16[3584]{0} %norm)", False),
    ("%while.2 = (bf16[1,512,4,3584]{3,2,1,0}, s32[]) while(%t)", False),
], ids=["a-scope's-kernel", "post", "the-mixer's-product", "sinkhorn",
        "pre-with-the-half's-norm", "a-product-that-writes-the-streams",
        "attention's-projection", "the-head's-four-rows", "four-rows-of-a-"
        "shared-step", "the-layer-loop"])
def test_the_trace_reader_places_the_mixers_ops(bench_lib, name, mixers):
    hc_trace, _ = bench_lib
    assert hc_trace.is_mixer_op(name, published()["model"]) is mixers


def test_the_readers_return_nothing_without_the_family_or_a_trace(bench_lib):
    """On the parent (no `hc_mult` in the model it can load) and on a run
    without a trace both metrics are left out of the line."""
    hc_trace, _ = bench_lib
    ckpt = bench_module("lib", "checkpoint")
    run = {"config": {"model": dict(ds.TINY), "checkpoint": "deepseek_v3"},
           "trace": None, "events": [], "t0": 0.0, "t1": 1.0, "peaks": PEAKS}
    assert not hc_trace.is_family(run["config"]["model"])
    for metric in ("step.hyper_conn_device_pct", "kernel.hyper_conn_roofline"):
        import sys

        sys.path.insert(0, BENCH)
        try:
            read = ckpt.load_module("layer_metrics", metric).read
            assert read(run) is None
            assert read(dict(run, config=published())) is None
        finally:
            sys.path.remove(BENCH)


def test_the_spec_lists_the_cell_where_its_readers_find_something():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = "xing4.0-29b.docqa-1tok"
    entry, = [w for w in spec["workloads"] if w["name"] == cell]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CELL, "docqa-1tok", 1)
    listed = {m["name"] for m in spec["per_layer"]
              if cell in m.get("workloads", ())}
    assert {"step.hyper_conn_device_pct", "kernel.hyper_conn_roofline",
            "step.latent_attn_device_pct", "step.expert_share_device_pct",
            "kernel.expert_share_roofline",
            "kernel.prefill_rows_step_roofline"} <= listed
    assert not listed & {"engine.moe_held_hit_pct", "step.moe_device_pct",
                         "kernel.moe_experts_roofline",
                         "engine.moe_load_max_over_mean"}
    for name in ("step.hyper_conn_device_pct", "kernel.hyper_conn_roofline"):
        m, = [m for m in spec["per_layer"] if m["name"] == name]
        assert m["workloads"] == [cell] and m["moves"] == "ttft_p95_ms"
    # by name: the benchmark grows a configuration and a cell at a time
    assert CELL in [c["name"] for c in spec["configs"]]
    assert [w["config"] for w in spec["workloads"]
            if w["name"] == cell] == [CELL]

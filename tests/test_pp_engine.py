"""Pipeline parallelism in the SERVING ENGINE with the real model:
`ParallelConfig(pp=N)` stages the llama layer stack (params + KV layer
axis) over a pp mesh axis — GPipe prefill, ring-full decode
(parallel/pp_engine.py).  Greedy outputs must equal a single-device
engine bit for bit (VERDICT r2 item 4)."""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.parallel import ParallelConfig


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config()  # 2 layers
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def make_engine(setup, parallel=None, **over):
    cfg, params = setup
    defaults = dict(
        page_size=8, num_pages=96, max_num_seqs=8,
        max_prefill_tokens=32, max_model_len=128, decode_steps=2,
    )
    defaults.update(over)
    return JaxEngine(cfg, params, EngineConfig(**defaults),
                     eos_token_ids=[], kv_dtype=jnp.float32,
                     parallel=parallel)


def req(tokens, max_tokens=6, **so):
    return {
        "token_ids": tokens,
        "sampling_options": {"temperature": 0.0, **so},
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
    }


async def collect(engine, request):
    out = []
    async for d in engine.generate(request):
        assert d.get("finish_reason") != "error", d
        out.extend(d["token_ids"])
    return out


PROMPTS = [
    [1, 2, 3, 4, 5],
    [(7 * j) % 101 + 1 for j in range(40)],  # chunked prefill
    [9, 8, 7],
    [(3 * j) % 97 + 1 for j in range(18)],
    [11] * 12,
]


async def _run_all(engine):
    return await asyncio.gather(*[collect(engine, req(p)) for p in PROMPTS])


async def test_pp_matches_single_device(setup):
    ref = make_engine(setup)
    want = await _run_all(ref)
    await ref.shutdown()

    eng = make_engine(setup, parallel=ParallelConfig(pp=2, dp=4))
    assert eng.layout.pp == 2
    got = await _run_all(eng)
    await eng.shutdown()
    assert got == want


async def test_pp_sampled_and_penalized(setup):
    """Seeded sampling AND frequency-penalized decode through the pp
    ring match the single-device engine (the penalty histogram rides
    the ring's last stage)."""
    ref = make_engine(setup)
    p = [(5 * j) % 89 + 1 for j in range(14)]
    want = await collect(ref, req(p, max_tokens=8, temperature=0.8, seed=7))
    want_pen = await collect(ref, req(p, max_tokens=8, frequency_penalty=0.5))
    await ref.shutdown()

    eng = make_engine(setup, parallel=ParallelConfig(pp=2, dp=4))
    got = await collect(eng, req(p, max_tokens=8, temperature=0.8, seed=7))
    assert got == want
    got_pen = await collect(eng, req(p, max_tokens=8, frequency_penalty=0.5))
    await eng.shutdown()
    assert got_pen == want_pen


async def test_pp_top_logprobs(setup):
    """top_logprobs through the pp decode matches single-device."""
    def r(p):
        return req(p, max_tokens=6, logprobs=True, top_logprobs=3)

    async def run(engine, p):
        toks, tops = [], []
        async for d in engine.generate(r(p)):
            assert d.get("finish_reason") != "error", d
            toks += d["token_ids"]
            tops += d.get("top_logprobs") or []
        return toks, tops

    p = [(3 * j) % 83 + 1 for j in range(11)]
    ref = make_engine(setup)
    want = await run(ref, p)
    await ref.shutdown()
    eng = make_engine(setup, parallel=ParallelConfig(pp=2, dp=4))
    got = await run(eng, p)
    await eng.shutdown()
    assert got[0] == want[0]
    for (g, w) in zip(got[1], want[1]):
        assert [i for i, _ in g] == [i for i, _ in w]
        for (_, lg), (_, lw) in zip(g, w):
            assert abs(lg - lw) < 1e-4


async def test_pp_tp_matches_single_device(setup):
    """dp×pp×tp: each stage's params/KV shard over tp inside the
    manual-over-pp program (VERDICT r3 item 2 — 70B needs tp×pp).
    Greedy + penalized outputs equal the single-device engine."""
    ref = make_engine(setup)
    want = await _run_all(ref)
    p = [(5 * j) % 89 + 1 for j in range(14)]
    want_pen = await collect(ref, req(p, max_tokens=8, frequency_penalty=0.5))
    await ref.shutdown()

    eng = make_engine(setup, parallel=ParallelConfig(dp=2, pp=2, tp=2))
    assert eng.layout.pp == 2
    from jax.sharding import PartitionSpec as P

    assert eng.kv.k.sharding.spec == P("pp", None, None, "tp", None)
    got = await _run_all(eng)
    got_pen = await collect(eng, req(p, max_tokens=8, frequency_penalty=0.5))
    await eng.shutdown()
    assert got == want
    assert got_pen == want_pen


async def test_pp_kv_partition_matches_and_scales(setup):
    """pp × kv_partition (VERDICT r4 item 8): the KV layer axis (pp)
    and page axis (dp) shard ORTHOGONALLY — pp=2×dp=2 with the pool
    partitioned over dp is greedy-equal to single-device, aggregate
    capacity scales with dp, and concurrent load overflowing one rank's
    pool still serves."""
    from jax.sharding import PartitionSpec as P

    ref = make_engine(setup)
    want = await _run_all(ref)
    await ref.shutdown()

    eng = make_engine(setup, parallel=ParallelConfig(pp=2, dp=2, tp=2),
                      kv_partition=True)
    assert (eng.layout.pp == 2 and eng.layout.pooled
            and eng.layout.pool_ranks == 2)
    assert eng.kv.k.sharding.spec == P("pp", "dp", None, "tp", None)
    got = await _run_all(eng)
    await eng.shutdown()
    assert got == want

    # capacity ∝ dp on top of pp's layer slicing: per-rank pool of 16
    # pages (15 usable) must NOT bound the aggregate
    eng2 = make_engine(setup, parallel=ParallelConfig(pp=2, dp=2, tp=2),
                       kv_partition=True, num_pages=16, max_model_len=64,
                       watermark=0.0)
    assert eng2.metrics().kv_total_pages == 2 * 15
    prompts = [[(5 * j + i) % 90 + 1 for j in range(40)] for i in range(4)]
    outs = await asyncio.gather(
        *[collect(eng2, req(p, max_tokens=8)) for p in prompts]
    )
    assert all(len(o) == 8 for o in outs)
    assert 4 * (48 // 8) > 15, "load must overflow a single rank's pool"
    await eng2.shutdown()


async def test_pp_kvbm_tiering_offload_onboard(setup, tmp_path):
    """KVBM tiering on a pp engine (plain AND kv_partition): offload
    drains to the host pool, the device cache is cleared, and the next
    run onboards from host with identical output (the gpt-oss-120b +
    KVBM configuration, SURVEY §2.2/§6)."""
    from dynamo_tpu.kvbm import DiskTier, HostBlockPool, TieredKvCache

    cfg, params = setup

    async def one(parallel, kv_partition, sub):
        tiered = TieredKvCache(
            HostBlockPool(capacity_bytes=64 << 20),
            DiskTier(str(tmp_path / sub)),
        )
        eng = JaxEngine(
            cfg, params, EngineConfig(
                page_size=8, num_pages=96, max_num_seqs=8,
                max_prefill_tokens=32, max_model_len=128, decode_steps=2,
                kv_partition=kv_partition,
            ), eos_token_ids=[], kv_dtype=jnp.float32,
            parallel=parallel, tiered=tiered,
        )
        prompt = list(range(1, 41))  # 5 full pages
        want = await collect(eng, req(prompt, max_tokens=4))
        deadline = asyncio.get_running_loop().time() + 20
        while tiered.offload_backlog or len(tiered.host) == 0:
            assert asyncio.get_running_loop().time() < deadline, "no offload"
            await asyncio.sleep(0.05)
        assert len(tiered.host) >= 5
        eng.clear_kv_blocks()
        got = await collect(eng, req(prompt, max_tokens=4))
        assert got == want, (sub, got, want)
        assert tiered.onboarded_blocks >= 4
        await eng.shutdown()

    await one(ParallelConfig(pp=2, dp=4), False, "plain")
    await one(ParallelConfig(pp=2, dp=2, tp=2), True, "pooled")


async def test_pp_pooled_disagg_handoff(setup):
    """Disagg prefill→decode between two pp×kv_partition engines: the
    full-layer export blob stitches pp stage slices, the import slices
    them back per stage — outputs equal a local run."""
    ref = make_engine(setup)
    p = [(7 * j) % 101 + 1 for j in range(20)]
    want = await collect(ref, req(p, max_tokens=8))
    await ref.shutdown()

    pre = make_engine(setup, parallel=ParallelConfig(pp=2, dp=2, tp=2),
                      kv_partition=True)
    dec = make_engine(setup, parallel=ParallelConfig(pp=2, dp=2, tp=2),
                      kv_partition=True)
    out = await pre.prefill_remote(req(p, max_tokens=8))
    assert "kv" in out, out
    toks = []
    async for d in dec.generate_with_kv(req(p, max_tokens=8),
                                        out["token_ids"][0], out["kv"]):
        assert d.get("finish_reason") != "error", d
        toks.extend(d["token_ids"])
    await pre.shutdown()
    await dec.shutdown()
    assert toks == want


async def test_pp_kv_layer_axis_sharded(setup):
    """The cache genuinely shards its layer axis over pp (each stage
    holds L/pp layers' pages — weight+cache HBM scale with pp) and its
    kv-heads over tp."""
    eng = make_engine(setup, parallel=ParallelConfig(pp=2, dp=4))
    from jax.sharding import PartitionSpec as P

    assert eng.kv.k.sharding.spec == P("pp", None, None, "tp", None)
    lay = eng.params["layers"]
    leaf = jax.tree.leaves(lay)[0]
    assert leaf.sharding.spec[0] == "pp"
    await eng.shutdown()

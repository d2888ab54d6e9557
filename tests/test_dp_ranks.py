"""Engine dp ranks: N independent engine replicas behind one endpoint,
per-rank KV events, and (instance, dp_rank) routing — the reference's
vLLM `data_parallel_size` + `WorkerWithDpRank` path
(/root/reference/components/src/dynamo/vllm/main.py:120-143,
lib/llm/src/kv_router/protocols.rs)."""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import init_params, tiny_config
from dynamo_tpu.router.worker_key import (
    DP_RANK_LIMIT,
    pack_worker,
    unpack_worker,
)
from dynamo_tpu.worker import DpRankEngine


def test_worker_key_roundtrip():
    for inst, rank in [(0, 0), (1000, 0), (1000, 1), (123456, 1023)]:
        assert unpack_worker(pack_worker(inst, rank)) == (inst, rank)
    with pytest.raises(ValueError):
        pack_worker(1, DP_RANK_LIMIT)
    with pytest.raises(ValueError):
        pack_worker(1, -1)


def _ecfg(**over):
    base = dict(page_size=8, num_pages=64, max_num_seqs=4,
                max_prefill_tokens=64, max_model_len=128)
    base.update(over)
    return EngineConfig(**base)


def _engines(n=2):
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, [
        JaxEngine(cfg, params, _ecfg(), kv_dtype=jnp.float32)
        for _ in range(n)
    ]


async def _gen(engine, prompt, dp_rank=None, max_tokens=4):
    req = {
        "token_ids": prompt,
        "sampling_options": {"temperature": 0.0},
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
    }
    if dp_rank is not None:
        req["dp_rank"] = dp_rank
    toks = []
    async for out in engine.generate(req):
        assert out.get("finish_reason") != "error", out
        toks += out["token_ids"]
    return toks


async def test_dp_rank_engine_dispatch():
    cfg, engines = _engines(2)
    dp = DpRankEngine(engines)
    p = [1, 2, 3, 4, 5]
    await _gen(dp, p, dp_rank=1)
    assert engines[1].metrics().num_requests_total == 1
    assert engines[0].metrics().num_requests_total == 0
    # rank-less requests round-robin across ranks
    await _gen(dp, p)
    await _gen(dp, p)
    assert engines[0].metrics().num_requests_total == 1
    assert engines[1].metrics().num_requests_total == 2
    # out-of-range rank errors the request, not the engine
    bad = [o async for o in dp.generate({
        "token_ids": p, "dp_rank": 7,
        "sampling_options": {}, "stop_conditions": {"max_tokens": 2},
    })]
    assert bad[-1]["finish_reason"] == "error"
    m = dp.metrics()
    assert m.num_requests_total == 3
    await dp.shutdown()


async def test_dp_rank_capacity_gauges_aggregate_on_metrics_exposition():
    """Regression (ISSUE 7 satellite): the fleet-telemetry capacity
    gauges must aggregate across dp ranks — headroom SUMS (pages are
    capacity), occupancy takes the MAX (the fullest rank blocks
    admission) — and ride the worker /metrics exposition the same way
    the decode_cc_*_total counters do."""
    from prometheus_client import CollectorRegistry, generate_latest

    from dynamo_tpu.runtime.metrics import EngineStatsCollector

    import asyncio

    cfg, engines = _engines(2)
    dp = DpRankEngine(engines)
    try:
        # hold pages on ONE rank so headroom diverges across ranks, and
        # catch a request IN FLIGHT on that rank so occupancy does too
        held = engines[1].pool.allocate(6)
        task = asyncio.ensure_future(
            _gen(dp, [1, 2, 3, 4, 5], dp_rank=1, max_tokens=48))
        deadline = asyncio.get_running_loop().time() + 20.0
        while engines[1].metrics().active_seqs == 0:
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.005)
        per = [e.metrics() for e in engines]
        agg = dp.metrics()
        assert agg.kv_watermark_headroom_pages == sum(
            m.kv_watermark_headroom_pages for m in per
        )
        assert (per[1].kv_watermark_headroom_pages
                < per[0].kv_watermark_headroom_pages), per
        assert per[0].batch_occupancy == 0.0
        assert per[1].batch_occupancy > 0.0
        assert agg.batch_occupancy == max(m.batch_occupancy for m in per)

        # ... and the exposition path (EngineStatsCollector over the
        # aggregated stats dict) exports them as worker gauges
        reg = CollectorRegistry()
        reg.register(EngineStatsCollector(
            lambda: {k: v for k, v in vars(agg).items()
                     if isinstance(v, (int, float))}))
        body = generate_latest(reg).decode()
        line = next(l for l in body.splitlines()
                    if l.startswith("dynamo_tpu_worker_kv_watermark_"
                                    "headroom_pages"))
        assert float(line.rsplit(" ", 1)[1]) == float(
            agg.kv_watermark_headroom_pages)
        occ = next(l for l in body.splitlines()
                   if l.startswith("dynamo_tpu_worker_batch_occupancy"))
        assert float(occ.rsplit(" ", 1)[1]) == agg.batch_occupancy
        await task
        engines[1].pool.free(held)
    finally:
        await dp.shutdown()


async def test_dp_rank_routing_e2e():
    """Full path: a 2-rank worker publishes per-rank KV events; the KV
    router indexes them under packed keys and repeats of a prompt stick
    to the rank that cached it; the frontend edge unpacks the key and
    stamps dp_rank on the request."""
    from dynamo_tpu.llm import ModelDeploymentCard
    from dynamo_tpu.router import KvRouter
    from dynamo_tpu.runtime import ControlPlaneServer, DistributedRuntime
    from dynamo_tpu.testing import tiny_tokenizer
    from dynamo_tpu.worker import serve_engine

    tok = tiny_tokenizer()
    cfg = tiny_config(vocab_size=tok.vocab_size)
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    control = await ControlPlaneServer().start()
    rt_w = await DistributedRuntime.connect(control.address)
    engines = [
        JaxEngine(cfg, params, _ecfg(enable_prefix_caching=True),
                  kv_dtype=jnp.float32, eos_token_ids=[])
        for _ in range(2)
    ]
    dp = DpRankEngine(engines)
    mdc = ModelDeploymentCard(
        name="dp-model", tokenizer_json=tok.to_json_str(),
    )
    served = await serve_engine(rt_w, dp, mdc)
    assert isinstance(served.kv_publisher, list) and len(served.kv_publisher) == 2

    rt_f = await DistributedRuntime.connect(control.address)
    ep = rt_f.namespace("dynamo").component("backend").endpoint("generate")
    client = await ep.client().start()
    await client.wait_for_instances()
    router = await KvRouter(
        rt_f, "dynamo", "backend", client, block_size=8,
    ).start()

    inst = served.instance.instance_id
    try:
        prompt_a = list(range(1, 33))  # 4 full blocks
        prompt_b = [(7 * j) % cfg.vocab_size for j in range(1, 33)]

        seq = [0]

        async def through_router(prompt, finish=True):
            seq[0] += 1
            req = {"token_ids": prompt, "request_id": f"r{seq[0]}",
                   "sampling_options": {"temperature": 0.0},
                   "stop_conditions": {"max_tokens": 2, "ignore_eos": True}}
            key = await router.choose(req)
            iid, rank = unpack_worker(key)
            assert iid == inst
            req["dp_rank"] = rank
            async for out in client.direct(req, iid):
                assert out.get("finish_reason") != "error", out
            if finish:
                router.mark_finished(req["request_id"])
            return rank

        rank_a = await through_router(prompt_a)

        # wait until (a) rank_a's stored events reached the index and
        # (b) BOTH ranks' post-request metrics (kv_usage back to 0 — the
        # request finished) arrived, so choose #2 sees settled state
        from dynamo_tpu.tokens import compute_block_hash_for_seq

        hashes = compute_block_hash_for_seq(prompt_a, 8)

        def settled():
            if router.index.find_matches(hashes).get(
                pack_worker(inst, rank_a), 0
            ) <= 0:
                return False
            states = router.worker_states
            return all(
                pack_worker(inst, r) in states
                and states[pack_worker(inst, r)].kv_usage == 0.0
                for r in (0, 1)
            )

        for _ in range(200):
            if settled():
                break
            await asyncio.sleep(0.05)
        assert settled(), (router.worker_states, router.index.find_matches(hashes))
        # cache affinity: the repeat must land on the rank that cached it
        # (left unfinished so its load keeps tracking in ActiveSequences)
        rank_a2 = await through_router(prompt_a, finish=False)
        assert rank_a2 == rank_a
        # load spreading: with rank_a still tracked busy, a cold prompt
        # must go to the other rank — dp ranks behave as distinct workers
        rank_b = await through_router(prompt_b)
        assert rank_b != rank_a
        router.mark_finished("r2")
    finally:
        await router.stop()
        await client.stop()
        await dp.shutdown()
        await rt_f.shutdown(graceful=False)
        await rt_w.shutdown(graceful=False)
        await control.stop()


async def test_flat_engine_honours_its_device():
    """Replicas of one process each live on the device they are given:
    parameters, KV pool and step outputs on THAT device, answers equal to
    an unpinned engine's (worker --dp-ranks on a multi-chip host)."""
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    devs = jax.devices()
    plain = JaxEngine(cfg, params, _ecfg(), kv_dtype=jnp.float32)
    pinned = [
        JaxEngine(cfg, params, _ecfg(), kv_dtype=jnp.float32, devices=[d])
        for d in (devs[2], devs[5])
    ]
    p = [1, 2, 3, 4, 5, 6, 7]
    want = await _gen(plain, p, max_tokens=6)
    for eng, d in zip(pinned, (devs[2], devs[5])):
        assert await _gen(eng, p, max_tokens=6) == want
        assert eng.kv.k.devices() == {d} and eng.kv.v.devices() == {d}
        assert all(leaf.devices() == {d}
                   for leaf in jax.tree.leaves(eng.params))
    for eng in (plain, *pinned):
        await eng.shutdown()


def test_make_mesh_takes_a_subset_of_the_devices():
    from dynamo_tpu.parallel import ParallelConfig, make_mesh

    devs = jax.devices()
    mesh = make_mesh(ParallelConfig(tp=2))
    assert list(mesh.devices.flat) == devs[:2]
    mesh = make_mesh(ParallelConfig(dp=2, tp=2), devs[4:])
    assert list(mesh.devices.flat) == devs[4:8]
    with pytest.raises(ValueError):
        make_mesh(ParallelConfig(tp=4), devs[:2])

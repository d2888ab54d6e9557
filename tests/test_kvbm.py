"""KVBM tiering: offload to host, eviction-demotion to disk, onboarding
restores exact KV (greedy output invariance after device-cache clear)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.kvbm import DiskTier, HostBlockPool, TieredKvCache
from dynamo_tpu.models import init_params, tiny_config


@pytest.fixture(scope="module")
def model_setup():
    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def make_engine(model_setup, tiered=None, **over):
    cfg, params = model_setup
    defaults = dict(page_size=8, num_pages=64, max_num_seqs=4,
                    max_prefill_tokens=64, max_model_len=256)
    defaults.update(over)
    return JaxEngine(cfg, params, EngineConfig(**defaults),
                     eos_token_ids=[], kv_dtype=jnp.float32, tiered=tiered)


def req(tokens, max_tokens=4):
    return {
        "token_ids": tokens,
        "sampling_options": {"temperature": 0.0},
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
    }


async def collect(engine, request):
    out = []
    async for d in engine.generate(request):
        out.extend(d["token_ids"])
    return out


def test_host_pool_lru_and_bytes():
    evicted = []
    pool = HostBlockPool(capacity_bytes=4 * 1024, on_evict=evicted.append)
    k = np.zeros((2, 8, 2, 4), np.float32)  # 512B each; block = 1KiB
    for h in range(100, 106):
        pool.put(h, h - 1, k, k)
    assert len(pool) <= 4
    assert evicted and evicted[0].block_hash == 100
    assert pool.get(105) is not None
    assert pool.get(100) is None


def test_host_pool_lookup_refreshes_recency():
    """A get() must move the block to MRU: a hot prefix that keeps being
    onboarded must not be the one LRU evicts."""
    pool = HostBlockPool(capacity_bytes=4 * 1024)
    k = np.zeros((2, 8, 2, 4), np.float32)  # 1KiB per block
    for h in (1, 2, 3, 4):
        pool.put(h, None, k, k)
    assert pool.get(1) is not None  # refresh 1 → LRU is now 2
    pool.put(5, None, k, k)
    assert 2 not in pool and 1 in pool
    # summary is MRU-first and capped
    assert pool.summary(2) == [5, 1]
    assert pool.hits == 1 and pool.evicted == 1


def test_host_pool_summary_order():
    pool = HostBlockPool(capacity_bytes=1 << 20)
    k = np.zeros((1, 2, 1, 2), np.float32)
    for h in (10, 11, 12):
        pool.put(h, None, k, k)
    pool.get(10)
    assert pool.summary() == [10, 12, 11]
    assert pool.summary(1) == [10]


def test_disk_tier_torn_file_is_a_miss(tmp_path):
    """Crash debris (a SIGKILLed writer's torn .npz, or garbage) must
    read as a miss and be dropped — never corrupt onboarding."""
    disk = DiskTier(str(tmp_path))
    k = np.ones((2, 8, 2, 2), np.float32)
    disk.put(0x10, None, k, k)
    # torn file under a valid final name (simulates non-atomic debris)
    torn = tmp_path / f"{0x22:016x}.npz"
    torn.write_bytes(b"PK\x03\x04 this is not a real zip")
    assert 0x22 in disk  # _discover indexes it from the shared dir...
    assert disk.get(0x22) is None  # ...but the read rejects + drops it
    assert not torn.exists()
    assert 0x22 not in disk
    # the good block is unaffected
    got = disk.get(0x10)
    np.testing.assert_array_equal(got[0], k)


def test_disk_tier_writes_are_atomic(tmp_path):
    """put() publishes via tmp+rename: no in-progress block is ever
    visible under its final name, and tmp names never index."""
    disk = DiskTier(str(tmp_path))
    k = np.ones((2, 8, 2, 2), np.float32)
    disk.put(0xA1, None, k, k)
    names = {p.name for p in tmp_path.iterdir()}
    assert names == {f"{0xA1:016x}.npz"}  # no leftover tmp files
    # a fresh scan ignores any stale tmp debris from a killed writer
    (tmp_path / ".tmp-9999-00000000000000b2.npz").write_bytes(b"junk")
    disk2 = DiskTier(str(tmp_path))
    assert len(disk2) == 1 and 0xA1 in disk2


def test_disk_tier_put_overwrites_unverified_debris(tmp_path):
    """Pre-existing torn debris under a valid final name must not block
    re-publication: put() dedups only against entries this process wrote
    or read-verified, and atomically overwrites anything else — and the
    offload drain's dedup signal (has_verified) never vouches for a
    discovered-but-unread file."""
    h = 0x77
    (tmp_path / f"{h:016x}.npz").write_bytes(b"PK\x03\x04 torn debris")
    disk = DiskTier(str(tmp_path))
    assert h in disk  # startup scan indexed it...
    assert not disk.has_verified(h)  # ...but nothing vouches for it
    k = np.ones((2, 8, 2, 2), np.float32)
    disk.put(h, None, k, k * 3)  # must overwrite, not early-return
    assert disk.has_verified(h)
    got = disk.get(h)
    np.testing.assert_array_equal(got[1], k * 3)
    assert disk.bytes_used == sum(disk._index.values())  # noqa: SLF001


def test_disk_tier_roundtrip(tmp_path):
    disk = DiskTier(str(tmp_path), capacity_bytes=1 << 20)
    k = np.arange(64, dtype=np.float32).reshape(2, 8, 2, 2)
    disk.put(0xABC, None, k, k * 2)
    got = disk.get(0xABC)
    np.testing.assert_array_equal(got[0], k)
    np.testing.assert_array_equal(got[1], k * 2)
    # restart survives
    disk2 = DiskTier(str(tmp_path))
    assert 0xABC in disk2


async def test_offload_and_onboard_preserves_output(model_setup, tmp_path):
    tiered = TieredKvCache(
        HostBlockPool(capacity_bytes=64 << 20), DiskTier(str(tmp_path))
    )
    engine = make_engine(model_setup, tiered=tiered)
    prompt = list(range(1, 41))  # 5 full pages
    want = await collect(engine, req(prompt))

    # wait for offloads to drain to host
    deadline = asyncio.get_running_loop().time() + 5
    while tiered.offload_backlog or len(tiered.host) == 0:
        assert asyncio.get_running_loop().time() < deadline, "no offload"
        await asyncio.sleep(0.05)
    assert len(tiered.host) >= 5

    # nuke the device cache: the only KV copy is now host-side
    engine.clear_kv_blocks()
    assert engine.pool.evictable_pages == 0

    got = await collect(engine, req(prompt))
    assert got == want
    # the last prompt block is never cache-hit (logits must be recomputed),
    # so 4 of the 5 full blocks onboard
    assert tiered.onboarded_blocks >= 4
    await engine.shutdown()


async def test_disk_promotion_path(model_setup, tmp_path):
    """Host tier too small to hold everything → blocks demote to disk and
    still onboard correctly."""
    tiny_host = HostBlockPool(capacity_bytes=2 << 10)  # ~1 block
    tiered = TieredKvCache(tiny_host, DiskTier(str(tmp_path)))
    engine = make_engine(model_setup, tiered=tiered)
    prompt = list(range(50, 90))  # 5 pages
    want = await collect(engine, req(prompt))
    deadline = asyncio.get_running_loop().time() + 5
    while tiered.offload_backlog:
        assert asyncio.get_running_loop().time() < deadline
        await asyncio.sleep(0.05)
    assert len(tiered.disk) >= 1  # demoted under host pressure
    engine.clear_kv_blocks()
    got = await collect(engine, req(prompt))
    assert got == want
    await engine.shutdown()


async def test_offload_completes_off_step_thread(model_setup):
    """The async pump contract: the step/executor thread only dispatches
    the jitted gather — the blocking device_get + host insert land on the
    kvbm-offload drain thread, so offload can never stretch the decode
    host gap."""
    import threading

    host = HostBlockPool(capacity_bytes=64 << 20)
    put_threads = []
    orig_put = host.put

    def spying_put(*a, **kw):
        put_threads.append(threading.current_thread().name)
        return orig_put(*a, **kw)

    host.put = spying_put
    tiered = TieredKvCache(host)
    engine = make_engine(model_setup, tiered=tiered)
    want = await collect(engine, req(list(range(1, 41))))
    assert want
    deadline = asyncio.get_running_loop().time() + 5
    while tiered.offload_backlog or len(tiered.host) == 0:
        assert asyncio.get_running_loop().time() < deadline, "no offload"
        await asyncio.sleep(0.05)
    assert put_threads, "no host copies happened"
    assert all(t.startswith("kvbm-offload") for t in put_threads), put_threads
    assert tiered.offloaded_blocks >= 4
    await engine.shutdown()


async def test_dram_and_disk_onboard_token_identity_seeded(model_setup,
                                                           tmp_path):
    """Tier round-trip identity under SEEDED sampling: a prefill served
    from DRAM-onboarded blocks — and, with a ~1-block host pool forcing
    demotion, from disk-onboarded blocks — produces the same tokens as
    the cold run (greedy identity is test_offload_and_onboard /
    test_disk_promotion_path)."""
    for host_bytes, needs_disk in ((64 << 20, False), (2 << 10, True)):
        tiered = TieredKvCache(
            HostBlockPool(capacity_bytes=host_bytes),
            DiskTier(str(tmp_path / f"g3-{host_bytes}")),
        )
        engine = make_engine(model_setup, tiered=tiered)
        prompt = list(range(7, 55))  # 6 full pages
        r = req(prompt, max_tokens=6)
        r["sampling_options"] = {"temperature": 0.8, "seed": 1234}
        want = await collect(engine, r)
        deadline = asyncio.get_running_loop().time() + 5
        while tiered.offload_backlog or len(tiered.host) == 0:
            assert asyncio.get_running_loop().time() < deadline, "no offload"
            await asyncio.sleep(0.05)
        if needs_disk:
            assert len(tiered.disk) >= 1
        engine.clear_kv_blocks()
        got = await collect(engine, r)
        assert got == want, (host_bytes, needs_disk)
        assert tiered.onboarded_blocks >= 4
        await engine.shutdown()


async def test_onboard_leaves_watermark_reserve(model_setup):
    """Onboarding must not eat the admission watermark: with a high
    watermark and a host tier holding the whole prefix, the onboarded
    run is clamped so `watermark + 1` pages stay free on the rank."""
    tiered = TieredKvCache(HostBlockPool(capacity_bytes=64 << 20))
    warm = make_engine(model_setup, num_pages=64)
    warm.attach_connector(tiered)
    prompt = list(range(30, 110))  # 10 full pages
    await collect(warm, req(prompt))
    deadline = asyncio.get_running_loop().time() + 5
    while tiered.offload_backlog or len(tiered.host) < 9:
        assert asyncio.get_running_loop().time() < deadline, "no offload"
        await asyncio.sleep(0.05)
    await warm.shutdown()

    # fresh engine, small pool, aggressive watermark: 12 usable pages,
    # watermark 0.25 → 3 reserved (+1 onboarding headroom), so the
    # 9-block host run MUST clamp (12 - 4 = 8 onboardable)
    engine = make_engine(model_setup, tiered=tiered, num_pages=13,
                         watermark=0.25)
    wm = engine.scheduler._watermark_pages()  # noqa: SLF001
    assert wm >= 2
    seen = []
    orig = engine.scheduler.onboard_fn

    def spy(hashes, rank=0):
        pages = orig(hashes, rank)
        seen.append((len(pages), engine.pool.available_on(rank)))
        return pages

    engine.scheduler.onboard_fn = spy
    got = await collect(engine, req(prompt))
    assert got  # served despite the clamp (remainder prefills)
    assert seen, "onboard hook never ran"
    for n_pages, avail_after in seen:
        assert n_pages == 0 or avail_after >= wm, (n_pages, avail_after)
    # the host tier had >= 9 blocks but the clamp kept the run short
    assert max(n for n, _ in seen) <= engine.cfg.usable_pages - wm - 1
    await engine.shutdown()


async def test_export_cached_blocks_sync_wrapper(model_setup):
    """The public sync export (the architecture.md connector API) stays
    in lockstep with the device-chunk export it is built on: same
    resolved hashes, same bytes."""
    engine = make_engine(model_setup)
    prompt = list(range(1, 41))
    await collect(engine, req(prompt))
    hashes = list(engine.pool._cached)  # noqa: SLF001 — committed hashes
    assert hashes
    out_h, k, v = engine.export_cached_blocks(hashes + [0xDEAD])
    assert set(out_h) == set(hashes)  # unknown hash skipped
    chunks = engine.export_cached_blocks_device(hashes)
    got = {}
    for hs, kd, vd in chunks:
        kh = np.asarray(jax.device_get(kd))
        vh = np.asarray(jax.device_get(vd))
        for i, h in enumerate(hs):
            got[h] = (kh[:, i], vh[:, i])
    for i, h in enumerate(out_h):
        np.testing.assert_array_equal(k[:, i], got[h][0])
        np.testing.assert_array_equal(v[:, i], got[h][1])
    await engine.shutdown()


async def test_shutdown_with_pending_offloads_does_not_deadlock(model_setup):
    """shutdown() racing an in-flight pump iteration must terminate the
    pump: the idle branch re-checks _closed before parking on _wake
    (clear-then-wait used to eat shutdown's wakeup and gather() hung
    forever when offloads were still queued — the tier-1 wedge)."""
    tiered = TieredKvCache(HostBlockPool(capacity_bytes=64 << 20))
    engine = make_engine(model_setup, tiered=tiered)
    await collect(engine, req(list(range(1, 41))))
    # deliberately NO drain barrier: offload events are still queued, so
    # shutdown lands while the pump is mid-iteration
    await asyncio.wait_for(engine.shutdown(), timeout=60)
    assert engine._pump_task.done()  # noqa: SLF001


async def test_tier_hit_ttft_ladder(model_setup):
    """The KVBM latency contract, on the clock a CPU run has: PREFILL
    STEPS, not seconds (ROADMAP D0: a wall-clock ratio read 2.7 under six
    busy workers and over 5 alone).  A warm prefix served from the DRAM
    tier costs at most twice the steps of a device(HBM)-cache hit and at
    most a fifth of a cold prefill's (ISSUE 8 acceptance), and its blocks
    came from the host tier.  The times themselves are not measured on the
    chip: no benchmark cell offloads."""
    tiered = TieredKvCache(HostBlockPool(capacity_bytes=256 << 20))
    engine = make_engine(model_setup, tiered=tiered, num_pages=128,
                         max_prefill_tokens=32, max_model_len=448)
    # 48 pages / 12 prefill chunks, inside the tiny model's 512-position
    # window and 256-token vocab
    prompt = [(i * 7) % 250 + 1 for i in range(384)]

    async def ttft(tokens):
        """Prefill steps dispatched up to the first token."""
        before, first = engine.prefill_steps_total, None
        async for d in engine.generate(req(tokens, max_tokens=2)):
            if d["token_ids"] and first is None:
                first = engine.prefill_steps_total - before
        return first

    pages = len(prompt) // engine.cfg.page_size

    async def only_copy_is_in_dram(offloaded):
        """A barrier of two counts (the deadline is its safety, not its
        verdict): the tier has taken `offloaded` blocks, and the device
        pool has every page back.  A finished stream's pages are freed
        AFTER its last delta is posted, when the step that made it is
        consumed (`Scheduler.deferred_free`): `clear_kv_blocks()` between
        the two evicts none of them, and the next pass is a device hit
        that onboards nothing (ROADMAP D0: one rep in three under six
        workers, where an empty `offload_backlog` skipped the one sleep
        that used to hide it)."""
        deadline = asyncio.get_running_loop().time() + 10
        while True:
            if tiered.offloaded_blocks >= offloaded:
                engine.clear_kv_blocks()
                if engine.pool.available_pages == engine.cfg.usable_pages:
                    return
            assert asyncio.get_running_loop().time() < deadline, (
                tiered.offloaded_blocks, offloaded,
                engine.pool.available_pages)
            await asyncio.sleep(0.02)

    await ttft([(t + 101) % 250 + 1 for t in prompt])  # compile, off-clock
    await only_copy_is_in_dram(pages)
    cold, hbm, dram = [], [], []
    for rep in range(3):
        salted = [(t + 3 * rep) % 250 + 1 for t in prompt]
        cold.append(await ttft(salted))
        hbm.append(await ttft(salted))  # device cache holds the blocks
        await only_copy_is_in_dram(pages * (rep + 2))
        onboarded = tiered.onboarded_blocks
        dram.append(await ttft(salted))
        # all but the page whose last token the prefill must compute
        assert tiered.onboarded_blocks == onboarded + pages - 1
        await only_copy_is_in_dram(pages * (rep + 2))

    cold_m, hbm_m, dram_m = (sorted(x)[1] for x in (cold, hbm, dram))
    assert dram_m <= 2.0 * hbm_m, (cold_m, hbm_m, dram_m)
    assert cold_m >= 5.0 * dram_m, (cold_m, hbm_m, dram_m)
    await engine.shutdown()


async def test_zipf_multi_tenant_goodput_offload_ab(model_setup):
    """A CPU-scale Zipf offload A/B (ISSUE 8 acceptance; not measured on
    the chip: no benchmark cell's tenant set outgrows the device pool): a Zipf-distributed multi-tenant prefix workload whose
    tenant set dwarfs the device pool.  With offload ON, HBM-evicted
    system prefixes onboard from the DRAM tier; with offload OFF they
    re-prefill cold.  Aggregate goodput on the clock a CPU run has
    (identical seeded schedule, so tokens are equal and the ratio is
    pure PREFILL STEPS, as the ladder's above: ROADMAP D0, a ratio over
    seconds read 1.25 under six busy workers and 2.1-2.8 alone) must be
    ≥ 1.5×."""
    import random

    sys_len, user_len, tenants, n_req = 192, 16, 8, 20
    rng = random.Random(0x21F)
    weights = [1.0 / (r + 1) ** 1.2 for r in range(tenants)]
    schedule = [rng.choices(range(tenants), weights=weights)[0]
                for _ in range(n_req)]

    def prompt(i, t):
        sys_tokens = [((t * 37 + j * 5) % 250) + 1 for j in range(sys_len)]
        return sys_tokens + [((i * 11 + j) % 250) + 1
                             for j in range(user_len)]

    async def wave(engine):
        sem = asyncio.Semaphore(2)

        async def one(i, t):
            async with sem:
                return await collect(engine, req(prompt(i, t), max_tokens=4))

        before = engine.prefill_steps_total
        outs = await asyncio.gather(
            *[one(i, t) for i, t in enumerate(schedule)])
        toks = sum(len(o) for o in outs)
        assert all(outs)
        return toks / (engine.prefill_steps_total - before)

    def mk(tiered):
        # 64-page pool ≈ 2 tenants' prefixes: the 8-tenant set cannot
        # stay device-resident, exactly the regime KVBM exists for
        return make_engine(model_setup, tiered=tiered, num_pages=64,
                           max_prefill_tokens=32, max_model_len=256,
                           max_num_seqs=4)

    cold_engine = mk(None)
    await wave(cold_engine)  # compile both arms' programs off the clock
    no_offload = await wave(cold_engine)
    await cold_engine.shutdown()

    tiered = TieredKvCache(HostBlockPool(capacity_bytes=256 << 20))
    warm_engine = mk(tiered)
    # TWO warm waves: the first fills the DRAM tier, the second compiles
    # every onboard-import width bucket (the jit cache the measured wave
    # runs against: warmed off the clock)
    for _ in range(2):
        await wave(warm_engine)
        deadline = asyncio.get_running_loop().time() + 15
        while tiered.offload_backlog:
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.05)
    offload = await wave(warm_engine)
    assert tiered.onboarded_blocks > 0, "no tier onboarding happened"
    await warm_engine.shutdown()

    ratio = offload / no_offload
    assert ratio >= 1.5, (offload, no_offload, ratio)


# --------------------------------------------------------------------------- #
# distributed KVBM: leader/worker bootstrap + shared tiers
# --------------------------------------------------------------------------- #


async def test_distributed_kvbm_shared_disk(model_setup, tmp_path):
    """Two workers bootstrap through the leader barrier and share a disk
    tier: blocks demoted by worker A are onboarded by worker B, with greedy
    output preserved (VERDICT item 8's done-criterion; reference
    tests/kvbm/test_determinism_agg.py)."""
    from dynamo_tpu.kvbm import KvbmConfig, KvbmLeader, KvbmWorker
    from dynamo_tpu.runtime import ControlPlaneServer, DistributedRuntime

    prompt = list(range(1, 65))  # 8 full pages
    control = await ControlPlaneServer().start()
    rt_a = await DistributedRuntime.connect(control.address)
    rt_b = await DistributedRuntime.connect(control.address)
    engine_a = make_engine(model_setup)
    engine_b = make_engine(model_setup)
    try:
        leader = asyncio.ensure_future(KvbmLeader(
            rt_a,
            KvbmConfig(disk_root=str(tmp_path / "g3"),
                       host_bytes=1),  # host evicts immediately → disk
            world=2,
        ).start())
        ta, tb = await asyncio.gather(
            KvbmWorker(rt_a, engine_a).start(),
            KvbmWorker(rt_b, engine_b).start(),
        )
        await leader
        assert engine_a.tiered is ta and engine_b.tiered is tb

        want = await collect(engine_a, req(prompt))
        # drain A's offload queue (blocks → host → demoted to shared disk)
        while ta.offload_backlog:
            await asyncio.sleep(0.05)
        await engine_a.shutdown()
        assert len(ta.disk) > 0

        # worker B never computed this prompt: it must onboard from the
        # shared tier and produce the identical continuation
        got = await collect(engine_b, req(prompt))
        assert got == want
        assert tb.onboarded_blocks > 0
    finally:
        await engine_b.shutdown()
        await rt_a.shutdown(graceful=False)
        await rt_b.shutdown(graceful=False)
        await control.stop()


async def test_distributed_kvbm_g4_object_store(model_setup):
    """No disk: demotions land in the shared control-plane object store
    (G4) and are onboarded by the second worker."""
    from dynamo_tpu.kvbm import KvbmConfig, KvbmLeader, KvbmWorker
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.testing import threaded_control_plane

    prompt = list(range(101, 165))
    # admission-time G4 reads block the runtime loop briefly; the control
    # plane must live off-loop (its own thread here, its own process in
    # production) or those reads would starve the server they talk to
    async with threaded_control_plane() as address:
        rt_a = await DistributedRuntime.connect(address)
        rt_b = await DistributedRuntime.connect(address)
        engine_a = make_engine(model_setup)
        engine_b = make_engine(model_setup)
        try:
            leader = asyncio.ensure_future(KvbmLeader(
                rt_a, KvbmConfig(g4_bucket="kvbm-test", host_bytes=1), world=2,
            ).start())
            ta, tb = await asyncio.gather(
                KvbmWorker(rt_a, engine_a).start(),
                KvbmWorker(rt_b, engine_b).start(),
            )
            await leader
            want = await collect(engine_a, req(prompt))
            while ta.offload_backlog:
                await asyncio.sleep(0.05)
            await engine_a.shutdown()

            got = await collect(engine_b, req(prompt))
            assert got == want
            assert tb.onboarded_blocks > 0
        finally:
            await engine_b.shutdown()
            await rt_a.shutdown(graceful=False)
            await rt_b.shutdown(graceful=False)


async def test_kvbm_barrier_rejects_layout_mismatch(model_setup):
    from dynamo_tpu.kvbm import KvbmConfig, KvbmLeader, KvbmWorker
    from dynamo_tpu.runtime import ControlPlaneServer, DistributedRuntime

    control = await ControlPlaneServer().start()
    rt_a = await DistributedRuntime.connect(control.address)
    rt_b = await DistributedRuntime.connect(control.address)
    engine_a = make_engine(model_setup, page_size=8)
    engine_b = make_engine(model_setup, page_size=16)  # different geometry
    try:
        leader = asyncio.ensure_future(KvbmLeader(
            rt_a, KvbmConfig(host_bytes=1 << 20), world=2,
        ).start())
        wa = asyncio.ensure_future(KvbmWorker(rt_a, engine_a).start(timeout=5))
        wb = asyncio.ensure_future(KvbmWorker(rt_b, engine_b).start(timeout=5))
        with pytest.raises(ValueError, match="layout mismatch"):
            await leader
        for t in (wa, wb):
            t.cancel()
    finally:
        await engine_a.shutdown()
        await engine_b.shutdown()
        await rt_a.shutdown(graceful=False)
        await rt_b.shutdown(graceful=False)
        await control.stop()


@pytest.mark.slow  # XLA CPU backend_compile ABORTS (SIGABRT) on this
# dp=4xtp=2 pooled program in the CI image's jaxlib, killing the whole
# pytest process and with it every alphabetically-later tier-1 test.
# Quarantined until the jaxlib bump (ROADMAP VERDICT #10 probes it);
# run explicitly with `-m slow` on a working toolchain.
async def test_kvbm_on_partitioned_pool(model_setup, tmp_path):
    """KV tiering composes with kv_partition (VERDICT r3 item 5): the
    big-mesh deployments that exhaust HBM fastest get offload too.
    Offloaded blocks may live on any pool rank (export groups by rank);
    onboarding lands on the ADMITTING sequence's rank."""
    from dynamo_tpu.parallel import ParallelConfig

    cfg, params = model_setup
    tiered = TieredKvCache(
        HostBlockPool(capacity_bytes=64 << 20), DiskTier(str(tmp_path))
    )
    engine = JaxEngine(
        cfg, params,
        EngineConfig(page_size=8, num_pages=64, max_num_seqs=8,
                     max_prefill_tokens=64, max_model_len=256,
                     kv_partition=True),
        eos_token_ids=[], kv_dtype=jnp.float32, tiered=tiered,
        parallel=ParallelConfig(dp=4, tp=2),
    )
    assert engine.layout.pooled
    # several prompts spread across partitions (admission balances)
    prompts = [[(13 * i + j) % 90 + 1 for j in range(40)] for i in range(4)]
    want = await asyncio.gather(*[collect(engine, req(p)) for p in prompts])

    deadline = asyncio.get_running_loop().time() + 8
    while tiered.offload_backlog or len(tiered.host) == 0:
        assert asyncio.get_running_loop().time() < deadline, "no offload"
        await asyncio.sleep(0.05)
    assert len(tiered.host) >= 4

    engine.clear_kv_blocks()
    assert engine.pool.evictable_pages == 0

    # spy the onboard hook: every page it returns must land on the
    # requested rank (the admitting sequence's partition)
    orig_onboard = engine.scheduler.onboard_fn
    onboard_calls = []

    def spying_onboard(hashes, rank=0):
        pages = orig_onboard(hashes, rank)
        onboard_calls.append((rank, list(pages)))
        return pages

    engine.scheduler.onboard_fn = spying_onboard

    got = await asyncio.gather(*[collect(engine, req(p)) for p in prompts])
    assert got == want
    assert tiered.onboarded_blocks >= 4
    assert any(pages for _, pages in onboard_calls)
    for rank, pages in onboard_calls:
        assert all(engine.pool.rank_of(p) == rank for p in pages), (
            rank, pages,
        )
    await engine.shutdown()


@pytest.mark.slow  # spawns two real-engine worker OS processes (~2 min
# on the 2-CPU tier-1 box) — run explicitly with `-m slow`
async def test_kvbm_stack_remote_prefix_hit():
    """scripts/kvbm_stack.py end to end: frontend + 2 real workers with
    small HBM pools and KVBM tiers; after device-cache churn the router
    directs a warm-prefix request at the worker whose HOST TIER holds it
    and that worker onboards instead of re-prefilling."""
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ))
    from kvbm_stack import run

    summary = await run()
    assert summary["passed"], summary
    assert summary["remote_prefix_hit"] and summary["onboard_delta"] > 0

"""Fleet telemetry plane (ISSUE 7): worker capacity snapshots →
lease-scoped KV keys → FleetTelemetryWatcher join with frontend SLO
windows → online knee estimation + observed PerfProfile →
Planner.plan_once() from live data — the tier-1 mock-engine sim of the
acceptance criteria, plus unit coverage for the publisher, staleness,
knee estimator and profile builder."""

import asyncio
import json
import time

import aiohttp
import pytest

from dynamo_tpu.frontend import (
    FrontendMetrics,
    HttpService,
    ModelManager,
    ModelWatcher,
)
from dynamo_tpu.llm import ModelDeploymentCard
from dynamo_tpu.mocker import MockEngine, MockEngineArgs
from dynamo_tpu.planner import (
    FleetTelemetryWatcher,
    KneeEstimator,
    Planner,
    PlannerConfig,
    SLO,
    TelemetryConnector,
)
from dynamo_tpu.planner.telemetry import _ProfileBuilder
from dynamo_tpu.runtime import ControlPlaneServer, DistributedRuntime
from dynamo_tpu.runtime.metrics import TELEMETRY_ROOT, TelemetryPublisher
from dynamo_tpu.testing import tiny_tokenizer
from dynamo_tpu.worker import serve_engine


# --------------------------------------------------------------------------- #
# Unit: publisher, staleness, knee, profiles
# --------------------------------------------------------------------------- #


async def test_telemetry_publisher_key_rates_and_lease_scope():
    control = await ControlPlaneServer().start()
    rt = await DistributedRuntime.connect(control.address)
    try:
        state = {"num_requests_total": 0, "waiting_seqs": 3}

        pub = TelemetryPublisher(rt, lambda: dict(state),
                                 namespace="ns", component="backend",
                                 interval_s=0.5)
        assert pub.key == (f"{TELEMETRY_ROOT}/ns/backend/"
                           f"{rt.primary_lease}")
        p1 = await pub.publish_once()
        assert p1["seq"] == 1 and p1["interval_s"] == 0.5
        assert "rates" not in p1  # no previous sample yet
        state["num_requests_total"] = 40
        await asyncio.sleep(0.1)
        p2 = await pub.publish_once()
        # the publisher derives per-interval rates from *_total deltas
        assert p2["rates"]["num_requests_per_s"] > 0
        assert "waiting_per_s" not in p2["rates"]  # gauges don't rate
        # lease-scoped: the key exists now and dies with the runtime
        from dynamo_tpu.runtime.transport.wire import unpack

        raw = await rt.control.get(pub.key)
        assert unpack(raw)["seq"] == 2
    finally:
        await rt.shutdown(graceful=False)
    raw = await (await DistributedRuntime.connect(control.address)
                 ).control.get(pub.key)
    assert raw is None  # lease revoked → key gone
    await control.stop()


async def test_watcher_staleness_marked_never_dropped():
    """A publisher that misses its deadline (or whose key is deleted —
    lease expiry) keeps its last snapshot visible, MARKED STALE."""
    control = await ControlPlaneServer().start()
    rt = await DistributedRuntime.connect(control.address)
    try:
        pub = TelemetryPublisher(
            rt, lambda: {"model": "m", "waiting_seqs": 1},
            namespace="dynamo", component="backend", interval_s=0.1,
        ).start()
        watcher = await FleetTelemetryWatcher(
            rt, default_interval=0.1).start()
        await watcher.wait_synced()
        deadline = asyncio.get_running_loop().time() + 5.0
        while not watcher.snapshot().fresh_workers():
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.02)
        # publisher misses its deadline → stale by age
        await pub.stop()
        await asyncio.sleep(0.4)  # > 2.5 * interval
        snap = watcher.snapshot()
        assert snap.workers and all(
            w["stale"] and w["age_s"] > 0.25 for w in snap.workers.values()
        )
        # key deleted (lease expiry / partition reconcile) → retained
        await rt.control.delete(pub.key)
        await asyncio.sleep(0.2)
        snap = watcher.snapshot()
        assert snap.workers, "deleted snapshot was dropped, not retained"
        assert all(w["stale"] for w in snap.workers.values())
        # stale workers never count toward load samples
        assert not snap.fresh_workers()
        await watcher.stop()
    finally:
        await rt.shutdown(graceful=False)
        await control.stop()


def test_watcher_retention_prunes_ancient_stale_entries():
    """Stale entries are retained (marked) for the retention horizon,
    then pruned — a long-lived frontend must not accumulate one corpse
    per worker respawn (every lease is a fresh key)."""
    w = FleetTelemetryWatcher(runtime=None, default_interval=0.1,
                              retention_s=5.0)
    w.entries["/telemetry/dynamo/backend/1"] = {
        "payload": {"interval_s": 0.1, "model": "m"},
        "received": 0.0, "deleted": True,
    }
    snap = w.snapshot(now_mono=1.0)
    assert snap.workers["backend/1"]["stale"] is True  # retained, marked
    snap = w.snapshot(now_mono=10.0)  # past retention_s
    assert not snap.workers and not w.entries


def test_watch_reconnect_replay_cannot_launder_old_payload_as_fresh():
    """A watch re-sync replays every surviving key as a put — an
    UNCHANGED seq must keep the original receipt time (age keeps
    growing), or a wedged publisher's old snapshot looks fresh again
    after every reconnect."""
    key = "/telemetry/dynamo/backend/1"
    w = FleetTelemetryWatcher(runtime=None, default_interval=0.5)
    w._on_put(key, {"interval_s": 0.5, "model": "m", "seq": 7})
    w.entries[key]["received"] = time.monotonic() - 60.0  # published long ago
    # reconnect replays the SAME seq: receipt time must not reset
    w._on_put(key, {"interval_s": 0.5, "model": "m", "seq": 7})
    snap = w.snapshot()
    assert snap.workers["backend/1"]["stale"] is True
    assert snap.workers["backend/1"]["age_s"] > 50.0
    # a genuinely NEW publish (advanced seq) refreshes it
    w._on_put(key, {"interval_s": 0.5, "model": "m", "seq": 8})
    assert w.snapshot().workers["backend/1"]["stale"] is False


def test_profile_attribution_respects_disagg_roles():
    """In a disagg fleet, prefill load divides across prefill-capable
    workers only and decode concurrency counts decode-capable workers
    only — whole-fleet division would halve the observed per-role load
    and mis-size both pools."""
    w = FleetTelemetryWatcher(runtime=None, default_interval=60.0)
    now = time.monotonic()

    def worker(instance, role, active=0):
        w.entries[f"/telemetry/dynamo/backend/{instance}"] = {
            "payload": {"interval_s": 60.0, "model": "m",
                        "disagg_role": role, "active_seqs": active,
                        "waiting_seqs": 0},
            "received": now, "deleted": False,
        }

    worker(1, "prefill")
    worker(2, "prefill")
    worker(3, "decode", active=2)
    w.entries["/telemetry/dynamo/frontend/9"] = {
        "payload": {"kind": "frontend", "interval_s": 60.0, "models": {
            "m": {"window_s": 10.0, "requests_started": 10,
                  "requests_completed": 10, "slo_met": 1.0,
                  "goodput_tok_s": 100.0, "attained_tok_s": 100.0,
                  "prompt_tok_s": 1000.0, "offered_rps": 1.0,
                  "completed_rps": 1.0,
                  "ttft": {"p50_ms": 50, "p95_ms": 100, "p99_ms": 120,
                           "mean_ms": 60},
                  "itl": {"p50_ms": 8, "p95_ms": 10, "p99_ms": 12,
                          "mean_ms": 10}},
        }},
        "received": now, "deleted": False,
    }
    w.sample()
    # prefill load: 1000 tok/s over the 2 prefill workers, not all 3
    assert w._prefill_obs["m"].obs[0][0] == 500.0
    # decode concurrency: the decode worker's 2 active seqs over 1
    # decode worker (Little's law floor 100 tok/s × 10 ms = 1.0 < 2)
    assert w._decode_obs["m"].obs[0][0] == 2.0


def test_knee_estimator_contiguous_prefix():
    est = KneeEstimator(threshold=0.9)
    for rate, met in [(1, 1.0), (2, 0.97), (4, 0.93), (8, 0.91),
                      (16, 0.5), (32, 0.1)]:
        for _ in range(4):
            est.add(rate, met)
    knee = est.estimate()
    assert knee is not None and 7.0 < knee < 9.0
    # a passing bin ABOVE the first failure is not a knee (contiguous
    # prefix only — bench's definition)
    est.add(32, 1.0)
    est.add(32, 1.0)
    knee = est.estimate()
    assert knee is not None and knee < 9.0
    # nothing passes → no knee, never a guess
    bad = KneeEstimator(threshold=0.9)
    bad.add(4, 0.2)
    assert bad.estimate() is None
    assert KneeEstimator().estimate() is None


def test_profile_builder_monotone_curves():
    b = _ProfileBuilder(min_points=3)
    b.add(10.0, 0.05, 100.0)
    b.add(30.0, 0.04, 250.0)  # latency NOISE below the lower-load point
    assert b.curves() is None  # not enough distinct loads yet
    b.add(20.0, 0.08, 180.0)
    xs, ys, ts = b.curves()
    assert xs == [10.0, 20.0, 30.0]
    assert ys == sorted(ys), "latency curve must be monotone (running max)"
    assert ys[-1] >= 0.08
    assert ts[1] == 180.0


# --------------------------------------------------------------------------- #
# The tier-1 sim: live telemetry end-to-end (acceptance criteria)
# --------------------------------------------------------------------------- #


class FakeScaler:
    def __init__(self):
        self.calls = []

    async def scale(self, kind, n):
        self.calls.append((kind, n))


async def _drive_wave(base, n_req, max_tokens, seed_base, gap_s):
    """Seeded streaming wave; returns per-request tokens counted
    CLIENT-side — the offline half of the cross-check."""
    results = []

    async def one(i, session):
        await asyncio.sleep(gap_s * i)
        body = {
            "model": "mock-model",
            "messages": [{"role": "user", "content": f"fleet probe {i}"}],
            "max_tokens": max_tokens,
            "temperature": 0,
            "seed": seed_base + i,
            "stream": True,
            "nvext": {"ignore_eos": True},
        }
        ntok = 0
        async with session.post(f"{base}/v1/chat/completions",
                                json=body) as resp:
            assert resp.status == 200
            async for raw in resp.content:
                line = raw.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                chunk = json.loads(line[len("data: "):])
                assert "error" not in chunk, chunk
                if chunk.get("choices"):
                    ntok += 1
        results.append(ntok)

    async with aiohttp.ClientSession() as session:
        await asyncio.gather(*(one(i, session) for i in range(n_req)))
    return results


@pytest.mark.timeout(180)
async def test_planner_plans_from_live_telemetry_end_to_end():
    """ISSUE 7 acceptance: mock-engine sim where Planner.plan_once()
    produces replica targets driven ENTIRELY by live telemetry (no
    hand-fed LoadSamples, no synthetic profiles), and the frontend's
    live slo_met/goodput match the bench-style offline computation for
    the same seeded run within 5%."""
    tok = tiny_tokenizer()
    control = await ControlPlaneServer().start()
    worker_rt = await DistributedRuntime.connect(control.address)
    engine = MockEngine(MockEngineArgs(
        max_num_seqs=8, speedup_ratio=25.0,
        vocab_size=tok.vocab_size,
        eos_token_id=list(tok.eos_token_ids)[0],
    ))
    mdc = ModelDeploymentCard(
        name="mock-model",
        tokenizer_json=tok.to_json_str(),
        eos_token_ids=list(tok.eos_token_ids),
        # generous SLO class: every request in the sim meets it, so the
        # live/offline classification can't flip on sub-ms timing skew
        slo_ttft_ms=30_000.0, slo_itl_ms=5_000.0,
    )
    await serve_engine(worker_rt, engine, mdc)

    def worker_snapshot():
        snap = {k: v for k, v in vars(engine.metrics()).items()
                if isinstance(v, (int, float))}
        snap["model"] = mdc.name
        snap["queue_depth"] = snap.get("waiting_seqs", 0)
        return snap

    worker_pub = TelemetryPublisher(
        worker_rt, worker_snapshot, component="backend", interval_s=0.15,
    ).start()

    front_rt = await DistributedRuntime.connect(control.address)
    metrics = FrontendMetrics()
    manager = ModelManager()
    watcher = await ModelWatcher(front_rt, manager, metrics=metrics).start()
    await watcher.wait_for_model("mock-model")
    fleet = await FleetTelemetryWatcher(
        front_rt, default_interval=0.15).start()
    fleet.start_sampling(0.15)
    front_pub = TelemetryPublisher(
        front_rt,
        lambda: {"kind": "frontend", "models": metrics.slo.snapshot()},
        component="frontend", interval_s=0.15,
    ).start()
    http = await HttpService(manager, host="127.0.0.1", port=0,
                             metrics=metrics, fleet=fleet).start()
    base = f"http://127.0.0.1:{http.port}"
    try:
        # two seeded waves at different offered rates so the observed
        # profile accumulates distinct load points and the knee
        # estimator sees more than one rate bin
        wave1 = await _drive_wave(base, n_req=6, max_tokens=24,
                                  seed_base=400, gap_s=0.25)
        wave2 = await _drive_wave(base, n_req=8, max_tokens=24,
                                  seed_base=500, gap_s=0.05)
        offline = wave1 + wave2
        await asyncio.sleep(0.5)  # let publishers + sampler tick

        # -- cross-check: live window vs offline COUNTS ------------------ #
        # the card's targets are deadlines no request of this sim comes
        # near, so every request the client counted is one the window
        # met, and the window's rate times the window's own span is the
        # tokens the client counted: no clock of the test's is compared
        slo = metrics.slo.targets_for("mock-model")
        assert slo.ttft_ms == 30_000.0, "card SLO never reached the frontend"
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{base}/fleet.json") as r:
                assert r.status == 200
                doc = await r.json()
        live = doc["models"]["mock-model"]
        assert live["requests_completed"] == len(offline)
        assert live["slo_met"] == 1.0
        assert round(live["goodput_tok_s"] * live["window_s"]) == sum(
            offline), (live["goodput_tok_s"], live["window_s"], offline)
        assert live["slo"] == {"ttft_ms": 30_000.0, "itl_ms": 5_000.0}

        # -- /fleet.json joins worker capacity + knees ------------------- #
        fleet_doc = doc["fleet"]
        workers = fleet_doc["workers"]
        assert workers and not any(w["stale"] for w in workers.values())
        w = next(iter(workers.values()))
        assert w["model"] == "mock-model"
        assert "kv_watermark_headroom_pages" in w and "batch_occupancy" in w
        assert fleet_doc["knees"].get("mock-model") is not None

        # -- the planner loop runs from live data ONLY ------------------- #
        scaler = FakeScaler()
        conn = TelemetryConnector(fleet, scaler)
        sample = await conn.collect_load()
        assert sample is not None and sample.requests_per_s > 0
        assert sample.prefill_tokens_per_s > 0
        decode_prof = fleet.observed_profile("mock-model", "decode")
        prefill_prof = fleet.observed_profile("mock-model", "prefill")
        assert decode_prof is not None and prefill_prof is not None
        assert all(t > 0 for t in decode_prof.itl_s)
        planner = Planner(
            conn,
            prefill_profile=prefill_prof,
            decode_profile=decode_prof,
            config=PlannerConfig(
                slo=SLO(ttft_s=max(prefill_prof.ttft_s) * 2,
                        itl_s=max(decode_prof.itl_s) * 2),
                predictor="constant", min_replicas=1, max_replicas=16,
            ),
        )
        planner.observe(sample)
        targets = planner.plan_once()
        assert targets["prefill"] >= 1 and targets["decode"] >= 1
        await planner.apply()
        assert scaler.calls, "planner never actuated from live telemetry"
    finally:
        await http.stop()
        await fleet.stop()
        await front_pub.stop()
        await worker_pub.stop()
        await watcher.stop()
        await engine.shutdown()
        await front_rt.shutdown(graceful=False)
        await worker_rt.shutdown(graceful=False)
        await control.stop()


def test_fleet_stack_script_import_safe():
    """scripts/fleet_stack.py must be importable without side effects
    (the _verify_harness import-safety contract its siblings follow)."""
    import importlib
    import os
    import sys

    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    sys.path.insert(0, scripts)
    try:
        mod = importlib.import_module("fleet_stack")
        assert callable(mod.run)
        assert callable(mod.main)
    finally:
        sys.path.remove(scripts)

#!/usr/bin/env python
"""Fleet telemetry driver: frontend + 2 workers + aggregator + planner.

    python scripts/fleet_stack.py [--requests N] [--timeline-dir DIR]

Stands up a control plane, TWO mock worker OS processes (each publishing
lease-scoped capacity snapshots via its CLI's TelemetryPublisher), and an
in-process frontend (discovery + HTTP + live SLO windows + a
FleetTelemetryWatcher); drives a seeded streaming traffic wave; then
emits ONE JSON LINE proving the observe side of the planner loop end to
end::

    {"passed": true, "models": {"mock-model": {"slo_met": 1.0,
     "goodput_tok_s": ...}}, "workers": 2, "stale": 0,
     "knee_rate_rps": ..., "planner_targets": {"prefill": 1, "decode": 1}}

With ``--timeline-dir`` the aggregator's counter history also merges into
a Chrome-trace/Perfetto timeline (goodput/occupancy counter tracks).
Exit status is nonzero when any invariant fails.  Import-safe (no work at
module import): drivers built on ``scripts/_verify_harness.py`` can
``from fleet_stack import run``.
"""

import argparse
import asyncio
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _setup_env() -> None:
    # CPU-only driver: this process imports jax AND starts workers, so
    # it is pinned to the CPU backend whatever the environment says (one
    # process per chip; chip_smoke.py is the driver that runs on the chip)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("PYTHONPATH", ROOT)
    os.environ.setdefault("DYN_TPU_TELEMETRY_INTERVAL", "0.3")


async def _run(tmp: str, requests: int, max_tokens: int,
               timeline_dir: str) -> dict:
    import time

    import aiohttp

    from dynamo_tpu.frontend import (
        FrontendMetrics,
        HttpService,
        ModelManager,
        ModelWatcher,
    )
    from dynamo_tpu.planner import (
        FleetTelemetryWatcher,
        Planner,
        PlannerConfig,
        SLO,
        TelemetryConnector,
    )
    from dynamo_tpu.runtime import ControlPlaneServer, DistributedRuntime
    from dynamo_tpu.runtime.metrics import TelemetryPublisher
    from _verify_harness import ProcSet, wait_ready

    control = await ControlPlaneServer().start()
    procs = ProcSet(tmp, dict(os.environ))
    summary = {"passed": False}
    front_rt = fleet = front_pub = watcher = http = None
    try:
        loop = asyncio.get_running_loop()
        for i in range(2):
            p, log = procs.spawn(
                [sys.executable, "-m", "dynamo_tpu.worker",
                 "--control", control.address, "--model", "tiny",
                 "--mock", "--platform", "cpu", "--mock-speedup", "25",
                 "--status-port", "-1"],
                f"worker{i}",
            )
            # wait_ready is a sync poll loop — run it OFF the event loop
            # (the in-process control plane must keep serving the
            # worker's connection while we wait for its READY)
            await loop.run_in_executor(
                None, lambda p=p, log=log: wait_ready(p, log,
                                                      "READY worker"))

        front_rt = await DistributedRuntime.connect(control.address)
        metrics = FrontendMetrics()
        manager = ModelManager()
        watcher = await ModelWatcher(front_rt, manager,
                                     metrics=metrics).start()
        await watcher.wait_for_model("mock-model")
        fleet = await FleetTelemetryWatcher(
            front_rt, default_interval=0.3).start()
        fleet.start_sampling(0.3)
        front_pub = TelemetryPublisher(
            front_rt,
            lambda: {"kind": "frontend", "models": metrics.slo.snapshot()},
            component="frontend", interval_s=0.3,
        ).start()
        http = await HttpService(manager, host="127.0.0.1", port=0,
                                 metrics=metrics, fleet=fleet).start()
        base = f"http://127.0.0.1:{http.port}"

        async def one(i, session):
            await asyncio.sleep(0.1 * i)
            body = {
                "model": "mock-model",
                "messages": [{"role": "user",
                              "content": f"fleet probe {i}"}],
                "max_tokens": max_tokens, "temperature": 0,
                "seed": 9000 + i, "stream": True,
                "nvext": {"ignore_eos": True},
            }
            chunks = 0
            async with session.post(f"{base}/v1/chat/completions",
                                    json=body) as resp:
                assert resp.status == 200, await resp.text()
                async for raw in resp.content:
                    if raw.startswith(b"data: {"):
                        chunks += 1
            return chunks

        t0 = time.monotonic()
        async with aiohttp.ClientSession() as session:
            chunk_counts = await asyncio.gather(
                *(one(i, session) for i in range(requests)))
        assert all(c > 0 for c in chunk_counts), chunk_counts
        await asyncio.sleep(1.0)  # publisher + sampler ticks

        snap = fleet.sample()
        models = {
            m: {k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in w.items()
                if k in ("slo_met", "goodput_tok_s", "attained_tok_s",
                         "offered_rps", "requests_completed")}
            for m, w in snap.models.items()
        }
        fresh = snap.fresh_workers()
        assert len(fresh) == 2, f"expected 2 fresh workers: {snap.workers}"
        assert "mock-model" in models, snap.models
        assert models["mock-model"]["requests_completed"] >= requests

        # the planner loop, from live telemetry only
        class _Scaler:
            calls = []

            async def scale(self, kind, n):
                self.calls.append((kind, n))

        conn = TelemetryConnector(fleet, _Scaler())
        sample = await conn.collect_load()
        assert sample is not None and sample.requests_per_s > 0
        # the planner invariant is the point of this driver — never skip
        # it: the sampler keeps ticking, so wait for the observed
        # profiles to accumulate their 3 distinct load points
        deadline = asyncio.get_running_loop().time() + 20.0
        while True:
            decode_prof = fleet.observed_profile("mock-model", "decode")
            prefill_prof = fleet.observed_profile("mock-model", "prefill")
            if decode_prof is not None and prefill_prof is not None:
                break
            assert asyncio.get_running_loop().time() < deadline, (
                "observed profiles never accumulated enough live points")
            await asyncio.sleep(0.3)
        planner = Planner(
            conn, prefill_profile=prefill_prof,
            decode_profile=decode_prof,
            config=PlannerConfig(
                slo=SLO(ttft_s=max(prefill_prof.ttft_s) * 2,
                        itl_s=max(decode_prof.itl_s) * 2),
                predictor="constant",
            ),
        )
        planner.observe(sample)
        targets = planner.plan_once()
        assert targets.get("decode", 0) >= 1 and targets.get("prefill", 0) >= 1

        if timeline_dir:
            from dynamo_tpu.runtime.timeline import (
                merge_timeline,
                validate_chrome_trace,
            )

            os.makedirs(timeline_dir, exist_ok=True)
            out = os.path.join(timeline_dir, "fleet_timeline.json")
            doc = merge_timeline(
                [], counter_dumps={"fleet": fleet.counter_samples()},
                out_path=out,
            )
            assert validate_chrome_trace(doc) == []
            summary["timeline"] = out

        summary.update({
            "passed": True,
            "models": models,
            "workers": len(fresh),
            "stale": sum(1 for w in snap.workers.values() if w["stale"]),
            "knee_rate_rps": snap.knees.get("mock-model"),
            "planner_targets": targets,
            "wave_s": round(time.monotonic() - t0, 2),
        })
    finally:
        if http:
            await http.stop()
        if fleet:
            await fleet.stop()
        if front_pub:
            await front_pub.stop()
        if watcher:
            await watcher.stop()
        if front_rt:
            await front_rt.shutdown(graceful=False)
        procs.stop()
        await control.stop()
    return summary


def run(requests: int = 8, max_tokens: int = 24, tmp: str = "",
        timeline_dir: str = "") -> dict:
    """Drive the stack once and return the summary dict."""
    _setup_env()
    import tempfile

    tmp = tmp or tempfile.mkdtemp(prefix="fleet_stack_")
    return asyncio.run(_run(tmp, requests, max_tokens, timeline_dir))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=24)
    ap.add_argument("--timeline-dir", default="")
    args = ap.parse_args(argv)
    summary = run(requests=args.requests, max_tokens=args.max_tokens,
                  timeline_dir=args.timeline_dir)
    print(json.dumps(summary))
    return 0 if summary.get("passed") else 1


if __name__ == "__main__":
    sys.exit(main())

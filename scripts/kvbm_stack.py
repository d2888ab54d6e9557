#!/usr/bin/env python
"""KVBM fleet-wide prefix reuse driver: frontend + 2 real workers.

    python scripts/kvbm_stack.py [--filler N]

Stands up a control plane, TWO real tiny-model worker OS processes with
SMALL HBM page pools and KVBM tiers attached (``--kvbm``, leader/worker
barrier, host-DRAM tier, lease-scoped tier-summary publishers), and an
in-process KV-mode frontend (ModelWatcher + KvRouter + HTTP).  It then:

1. serves a long-system-prompt chat request (the warm prefix lands on
   one worker's device cache and offloads to its DRAM tier);
2. churns both workers' device caches with filler prompts until the warm
   worker's device copy is evicted — the ONLY remaining copy is in its
   host tier, visible fleet-wide through `/kvbm/summary/…`;
3. re-issues the warm-prefix request through the frontend and proves the
   router directed it at the worker whose HOST TIER holds the prefix
   (`kvbm_onboard_total` advances on that worker: the blocks were
   onboarded, not recomputed — a router-directed remote-prefix hit).

Emits ONE JSON line::

    {"passed": true, "workers": 2, "remote_prefix_hit": true,
     "warm_worker": "...", "onboard_delta": N, "tier_overlap_seen": M,
     "ttft_warm_ms": ..., "ttft_cold_ms": ...}

Exit status is nonzero when any invariant fails.  Import-safe (no work
at module import): drivers built on ``scripts/_verify_harness.py`` can
``from kvbm_stack import run``.
"""

import argparse
import asyncio
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the tiny tokenizer is near-character-level and the stack serves a
# 256-token context: ~110 chars ≈ 14 KV blocks of shared prefix
SYSTEM = "You are a meticulous support assistant for the Dynamo fleet. Cite the runbook; escalate data loss."


def _setup_env() -> None:
    # CPU-only driver: this process imports jax AND starts workers, so
    # it is pinned to the CPU backend whatever the environment says (one
    # process per chip; chip_smoke.py is the driver that runs on the chip)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("PYTHONPATH", ROOT)
    os.environ.setdefault("DYN_TPU_KVBM_SUMMARY_INTERVAL", "0.3")


async def _metrics_json(session, port: int) -> dict:
    async with session.get(f"http://127.0.0.1:{port}/metrics.json") as r:
        return await r.json()


async def _chat(session, base: str, model: str, user: str, seed: int,
                system: str = SYSTEM):
    """One streamed chat request; returns (ttft_ms, chunks)."""
    import time

    body = {
        "model": model,
        "messages": [{"role": "system", "content": system},
                     {"role": "user", "content": user}],
        "max_tokens": 8, "temperature": 0, "seed": seed, "stream": True,
        "nvext": {"ignore_eos": True},
    }
    t0 = time.perf_counter()
    ttft_ms, chunks = None, 0
    async with session.post(f"{base}/v1/chat/completions",
                            json=body) as resp:
        assert resp.status == 200, await resp.text()
        async for raw in resp.content:
            if raw.startswith(b"data: {"):
                chunks += 1
                if ttft_ms is None:
                    ttft_ms = (time.perf_counter() - t0) * 1e3
    return ttft_ms, chunks


async def _run(tmp: str, filler: int) -> dict:
    import aiohttp

    from dynamo_tpu.frontend import (
        FrontendMetrics,
        HttpService,
        ModelManager,
        ModelWatcher,
    )
    from dynamo_tpu.router import kv_chooser_factory
    from dynamo_tpu.runtime import ControlPlaneServer, DistributedRuntime
    from _verify_harness import ProcSet, free_port, wait_ready

    control = await ControlPlaneServer().start()
    procs = ProcSet(tmp, dict(os.environ))
    summary = {"passed": False, "workers": 2}
    front_rt = http = watcher = None
    status_ports = [free_port(), free_port()]
    try:
        loop = asyncio.get_running_loop()
        for i in range(2):
            p, log = procs.spawn(
                [sys.executable, "-m", "dynamo_tpu.worker",
                 "--control", control.address, "--model", "tiny",
                 "--dtype", "float32", "--platform", "cpu",
                 "--page-size", "8", "--num-pages", "48",
                 "--max-prefill-tokens", "64", "--max-model-len", "256",
                 "--max-num-seqs", "2",
                 "--kvbm", "--kvbm-host-bytes", str(64 << 20),
                 *(["--kvbm-leader", "2"] if i == 0 else []),
                 "--status-port", str(status_ports[i])],
                f"worker{i}",
            )
        # wait AFTER spawning both: the kvbm leader barriers on both
        # workers registering, so a serial spawn-and-wait would deadlock
        for p, log in procs.procs:
            await loop.run_in_executor(
                None, lambda p=p, log=log: wait_ready(p, log,
                                                      "READY worker"))

        front_rt = await DistributedRuntime.connect(control.address)
        metrics = FrontendMetrics()
        manager = ModelManager()
        watcher = await ModelWatcher(
            front_rt, manager, metrics=metrics, router_mode="kv",
            kv_chooser_factory=kv_chooser_factory(front_rt),
        ).start()
        entry = await watcher.wait_for_model("tiny-chat")
        deadline = loop.time() + 30
        while len(entry.instances) < 2:
            assert loop.time() < deadline, "second worker never discovered"
            await asyncio.sleep(0.2)
        http = await HttpService(manager, host="127.0.0.1", port=0,
                                 metrics=metrics).start()
        base = f"http://127.0.0.1:{http.port}"

        async with aiohttp.ClientSession() as session:
            # 1. land the warm prefix somewhere (and measure cold TTFT)
            ttft_cold, chunks = await _chat(session, base, "tiny-chat",
                                            "turn zero", seed=1)
            assert chunks > 0
            summary["ttft_cold_ms"] = round(ttft_cold, 1)

            # the warm prefix's block hashes, from the router's own device
            # index: request 1 is the only traffic so far, so the warm
            # worker's indexed blocks ARE that request's stored blocks
            chooser = entry.kv_chooser
            deadline = loop.time() + 30
            while True:
                snap = chooser.index.snapshot()
                if any(hs for hs in snap.values()):
                    break
                assert loop.time() < deadline, "no KV events reached router"
                await asyncio.sleep(0.1)
            (warm_packed, warm_hashes), = [
                (w, set(hs)) for w, hs in snap.items() if hs]

            # 2. churn device caches with DISTINCT-prefix fillers until
            # the warm worker's device copy is evicted (its 47-page pool
            # can't hold the prefix + fillers) while its DRAM tier keeps
            # it; the summary publisher makes that visible to the
            # router's tier index
            deadline = loop.time() + 90
            fill = 0
            while True:
                for j in range(filler):
                    await _chat(session, base, "tiny-chat",
                                f"filler {fill}-{j} " + "pad " * 12,
                                seed=100 + fill * filler + j,
                                system=f"junk context {fill}-{j} "
                                       + "fill " * 18)
                fill += 1
                dev = set(chooser.index.snapshot().get(warm_packed, []))
                tier = set(chooser.tier_index.snapshot()
                           .get(warm_packed, []))
                if not (dev & warm_hashes) and (tier & warm_hashes):
                    break  # device copy gone, host-tier copy indexed
                assert loop.time() < deadline, (
                    "warm prefix never moved device→DRAM tier in the "
                    f"router's view (dev∩warm={len(dev & warm_hashes)}, "
                    f"tier∩warm={len(tier & warm_hashes)})")
            summary["tier_overlap_seen"] = len(tier & warm_hashes)

            # let the workers publish their idle load states: the last
            # filler's pages free asynchronously, and a stale snapshot
            # (kv_usage from mid-filler) would mis-penalize the holder
            # in the cost model for reasons unrelated to caching
            await asyncio.sleep(2.0)

            # 3. the router-directed remote-prefix hit: the warm request
            # again — wherever the router sends it, the serving worker
            # must ONBOARD from its host tier instead of re-prefilling
            # (only the warm worker's tier holds the prefix, so a cold
            # route would serve with zero onboards and fail)
            pre = [await _metrics_json(session, sp) for sp in status_ports]
            ttft_warm, chunks = await _chat(session, base, "tiny-chat",
                                            "turn zero", seed=1)
            assert chunks > 0
            post = [await _metrics_json(session, sp)
                    for sp in status_ports]
            served = [i for i in range(2)
                      if post[i].get("num_requests_total", 0)
                      > pre[i].get("num_requests_total", 0)]
            assert len(served) == 1, f"ambiguous serving worker: {served}"
            onboard_delta = (
                post[served[0]].get("kvbm_onboard_total", 0)
                - pre[served[0]].get("kvbm_onboard_total", 0))
            assert onboard_delta > 0, (
                f"worker{served[0]} served the warm-prefix request "
                "without onboarding — the router did not direct it at "
                "the host-tier holder")
            summary["warm_worker"] = f"worker{served[0]}"
            summary["remote_prefix_hit"] = True
            summary["onboard_delta"] = int(onboard_delta)
            summary["ttft_warm_ms"] = round(ttft_warm, 1)
            summary["passed"] = True
    finally:
        if http:
            await http.stop()
        if watcher:
            await watcher.stop()
        if front_rt:
            await front_rt.shutdown(graceful=False)
        procs.stop()
        await control.stop()
    return summary


async def run(filler: int = 3) -> dict:
    import tempfile

    _setup_env()
    with tempfile.TemporaryDirectory(prefix="kvbm-stack-") as tmp:
        return await _run(tmp, filler)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--filler", type=int, default=3,
                    help="filler prompts per churn round")
    args = ap.parse_args()
    summary = asyncio.run(run(filler=args.filler))
    print(json.dumps(summary))
    return 0 if summary.get("passed") else 1


if __name__ == "__main__":
    sys.exit(main())

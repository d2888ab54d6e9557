#!/usr/bin/env python
"""Chaos scenario driver: the kill/partition suite over the operator stack.

    python scripts/chaos_stack.py [--scenario NAME] [--log-dir DIR]

Runs the scenario suite from ``dynamo_tpu.chaos.scenarios`` — worker
SIGKILL mid-stream, multinode rank death → group respawn, control-plane
partition + reconnect, disagg KV-handoff drop, wedged-engine health
eviction — and emits ONE JSON LINE per scenario::

    {"scenario": "worker_kill_midstream", "passed": true,
     "client_errors": 0, "stream_mismatches": 0, "streams": 4,
     "converge_s": 1.2, "migrations_total": 4.0, "telemetry": {...}}

Exit status is nonzero if any scenario fails.  Import-safe (no work at
module import): sibling drivers — e.g. anything built on
``scripts/_verify_harness.py`` — can ``from chaos_stack import run_suite``
and embed the suite in a larger verification pass.
"""

import argparse
import asyncio
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _setup_env() -> None:
    # CPU-only driver: this process imports jax AND starts workers, so
    # it is pinned to the CPU backend whatever the environment says (one
    # process per chip; chip_smoke.py is the driver that runs on the chip)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    os.environ.setdefault("PYTHONPATH", ROOT)
    import jax

    jax.config.update("jax_platforms", "cpu")


def run_suite(scenario: str = "", log_dir: str = "",
              timeline_dir: str = "") -> list:
    """Run one named scenario (or all) and return the ScenarioResults.
    With `timeline_dir`, each scenario also writes a merged Chrome-trace
    timeline artifact (its path lands in the result's telemetry)."""
    _setup_env()
    from dynamo_tpu.chaos.scenarios import run_all, run_scenario

    if scenario:
        return [asyncio.run(run_scenario(scenario, log_dir=log_dir,
                                         timeline_dir=timeline_dir))]
    return asyncio.run(run_all(log_dir=log_dir, timeline_dir=timeline_dir))


def main(argv=None) -> int:
    _setup_env()  # before any dynamo_tpu import pulls in jax
    from dynamo_tpu.chaos.scenarios import SCENARIOS

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", default="", choices=["", *SCENARIOS],
                    help="run just one scenario (default: the whole suite)")
    ap.add_argument("--log-dir", default="",
                    help="directory for per-scenario worker-process logs")
    ap.add_argument("--timeline-dir",
                    default=os.environ.get("DYN_TPU_CHAOS_TIMELINE", ""),
                    help="also write a merged Perfetto/Chrome-trace "
                         "timeline per scenario into this directory "
                         "(default: $DYN_TPU_CHAOS_TIMELINE)")
    args = ap.parse_args(argv)
    results = run_suite(args.scenario, args.log_dir, args.timeline_dir)
    failed = 0
    for r in results:
        print(r.to_json(), flush=True)
        failed += not r.passed
    if failed:
        print(f"CHAOS FAIL ({failed}/{len(results)} scenario(s))",
              file=sys.stderr)
        return 1
    print(f"CHAOS PASS ({len(results)} scenario(s))", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Standalone frontend egress saturation driver.

    python scripts/frontend_saturation.py                 # default rungs
    python scripts/frontend_saturation.py --rungs 2500,10000 --tokens 4
    python scripts/frontend_saturation.py --mock-speedup 1000

Runs ``frontend.loadgen.frontend_saturation`` — concurrent
mock SSE streams against the REAL frontend write path (preprocess →
postprocess_stream → StreamEgress), no device, no control plane — and
prints the result as one JSON line.  See docs/frontend_dataplane.md.

``--mock-speedup`` scales the A/B burst arms' per-stream token rate
(tokens/s per stream); the concurrency rungs use ``--interval``.
"""

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(
        description="frontend egress saturation bench")
    ap.add_argument("--rungs", default="2500,5000,10000",
                    help="comma list of concurrent-stream rungs")
    ap.add_argument("--n", type=int, default=16,
                    help="choices per connection (streams multiplex as "
                         "connections x n)")
    ap.add_argument("--interval", type=float, default=4.0,
                    help="seconds between tokens per stream (rung arms)")
    ap.add_argument("--tokens", type=int, default=5,
                    help="tokens per stream (rung arms)")
    ap.add_argument("--knee-ms", type=float, default=5.0,
                    help="delta p99 threshold defining the knee")
    ap.add_argument("--mock-speedup", type=float, default=500.0,
                    help="A/B burst arms: mock tokens/s per stream")
    ap.add_argument("--no-coalesce", action="store_true",
                    help="disable delta coalescing in the fast arm")
    args = ap.parse_args()

    # CPU-only driver: this process imports jax AND starts workers, so
    # it is pinned to the CPU backend whatever the environment says (one
    # process per chip; chip_smoke.py is the driver that runs on the chip)
    os.environ["JAX_PLATFORMS"] = "cpu"
    from dynamo_tpu.frontend.loadgen import frontend_saturation

    out = asyncio.run(frontend_saturation(
        rungs=tuple(int(r) for r in args.rungs.split(",") if r),
        n=args.n, interval_s=args.interval, tokens=args.tokens,
        knee_ms=args.knee_ms, ab_speedup=args.mock_speedup,
        coalesce=not args.no_coalesce,
        log=lambda m: print(m, file=sys.stderr, flush=True),
    ))
    print(json.dumps(out))
    return 0 if out["streams_at_knee"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""Tier-1 frontend saturation gates (dynamo_tpu/frontend/loadgen.py).

Two acceptance bars from the egress data-plane work, run at reduced
duration so they fit tier-1:

- 10k concurrent mock SSE streams against ONE real frontend process
  with zero tokens lost (the 5 ms delta-p99 knee is a time, and
  `scripts/frontend_saturation.py` reports it on a quiet machine),
- the batched/coalescing writer cuts the frontend's writes per streamed
  token >= 3x vs the legacy per-delta writer on a burst shape where
  backpressure engages (the A/B arms of `scripts/frontend_saturation.py`;
  not measured on the chip: no benchmark cell streams more than one token).

Pure asyncio — no device, no control plane.  The full ramp lives in
scripts/frontend_saturation.py.
"""

import asyncio

from dynamo_tpu.frontend.loadgen import run_rung


async def test_10k_streams_under_knee():
    """Counts only: every stream is served and no token is lost.  The knee
    itself (`delta_p99_ms < 5`) is a wall-clock reading that six busy test
    workers sink at random (ROADMAP D0): `scripts/frontend_saturation.py`
    reports it on a quiet machine, and tier-1 asserts no time."""
    r = await run_rung(streams=10_000, n=16, interval_s=4.0, tokens=4)
    assert r["streams"] >= 10_000
    assert r["tokens_lost"] == 0
    # at this gentle per-stream rate queues rarely back up, so frames
    # may equal writes — batching economics are asserted by the burst
    # A/B test below, not here
    assert r["egress_frames"] >= r["egress_writes"]


async def test_burst_ab_cpu_per_token_ratio():
    """The batching economics as work: `resp.write` calls a streamed token
    (each is a syscall and a pass through the transport), fast arm
    against the per-delta arm, on the same 80,000 tokens.  The CPU
    microseconds a token, which `run_rung` reports too, are a clock's and
    are read on a quiet machine by `scripts/frontend_saturation.py`."""
    kw = dict(streams=800, n=16, interval_s=1.0 / 500.0, tokens=100)
    fast = await run_rung(coalesce=True, **kw)
    legacy = await run_rung(coalesce=False, legacy=True, **kw)
    assert fast["tokens_lost"] == 0 and legacy["tokens_lost"] == 0
    tokens = kw["streams"] * kw["tokens"]
    # legacy arm writes one frame per resp.write, one frame a token
    assert legacy["egress_writes"] == legacy["egress_frames"] == tokens
    # fast arm batches: a third of the writes at most (a fortieth, alone)
    assert 3 * fast["egress_writes"] <= legacy["egress_writes"], (
        fast["egress_writes"], legacy["egress_writes"])
    assert fast["egress_coalesced"] > 0

"""Live SLO accounting (frontend/slo.py): log-bucket histogram vs a
brute-force percentile oracle, sliding-window rotation, SLO targets +
env overrides, and the acceptance micro-bench pinning per-request
accounting under 20 µs (it rides the streaming hot path)."""

import math
import random

import numpy as np

from dynamo_tpu.frontend.slo import (
    LogBucketHistogram,
    SLOAccountant,
    SLOTargets,
    SlidingWindow,
)
from dynamo_tpu.testing import call_ceiling, counted_calls

# half-bucket geometric error bound of the quarter-power-of-two layout
_BUCKET_RATIO = 2 ** 0.25


def test_log_bucket_histogram_vs_oracle():
    """Every quantile must land within one bucket ratio of the exact
    (numpy) percentile, across distributions with very different tails."""
    rng = random.Random(7)
    cases = [
        [rng.lognormvariate(2.0, 1.0) for _ in range(4000)],
        [rng.uniform(0.5, 500.0) for _ in range(4000)],
        [rng.expovariate(0.01) + 0.1 for _ in range(4000)],
    ]
    for vals in cases:
        h = LogBucketHistogram()
        for v in vals:
            h.record(v)
        assert h.n == len(vals)
        for p in (0.10, 0.50, 0.90, 0.95, 0.99):
            est = h.percentile(p)
            ref = float(np.percentile(vals, p * 100))
            assert ref / _BUCKET_RATIO <= est <= ref * _BUCKET_RATIO, (
                f"p{p}: est {est} vs oracle {ref}"
            )
        # mean is exact (tracked outside the buckets)
        assert abs(h.mean() - np.mean(vals)) < 1e-6


def test_log_bucket_boundaries_and_degenerate_values():
    h = LogBucketHistogram()
    for v in (0.0, -1.0, float("nan"), 1e-9):
        h.record(v)  # all land in the first bucket, never throw
    assert h.counts[0] == 4
    h.record(float("inf"))  # unserved request (no first token)
    assert h.counts[-1] == 1
    # a value exactly on a bucket edge reports within one ratio of itself
    edge = math.exp(math.log(1e-3) + 40 * (math.log(2) / 4))
    h2 = LogBucketHistogram()
    h2.record(edge)
    assert edge / _BUCKET_RATIO <= h2.percentile(0.5) <= edge * _BUCKET_RATIO
    # merge is count addition
    h2.merge(h2)
    assert h2.n == 2
    # mean is over FINITE records only: errored requests (inf) must not
    # drag it toward zero
    h3 = LogBucketHistogram()
    h3.record(100.0)
    h3.record(100.0)
    h3.record(float("inf"))
    assert h3.mean() == 100.0 and h3.n == 3


def test_sliding_window_rotation():
    """Records age out after window_s; a rotated slot is reset in place
    (stale epochs can never leak into a snapshot)."""
    win = SlidingWindow(window_s=10.0, slots=5)  # 2s sub-windows
    t0 = 1000.0
    win.record_start(now=t0)
    win.record(ttft_ms=50, itl_ms=5, output_tokens=10, slo_ok=True,
               now=t0 + 0.5)
    s = win.snapshot(now=t0 + 1.0)
    assert s["requests_completed"] == 1 and s["requests_started"] == 1
    # still inside the window
    s = win.snapshot(now=t0 + 9.0)
    assert s["requests_completed"] == 1
    # past the window: everything aged out
    s = win.snapshot(now=t0 + 11.0)
    assert s["requests_completed"] == 0 and s["requests_started"] == 0
    assert s["slo_met"] is None and s["goodput_tok_s"] == 0.0
    # a new record after full rotation starts clean (the ring slot that
    # held the old epoch was reset, not accumulated into)
    win.record(ttft_ms=70, itl_ms=7, output_tokens=4, slo_ok=False,
               now=t0 + 12.0)
    s = win.snapshot(now=t0 + 12.5)
    assert s["requests_completed"] == 1 and s["slo_met"] == 0.0
    assert s["ttft"]["p50_ms"] is not None


def test_window_rates_use_covered_duration():
    """A 2-second burst inside a 60-second window divides by ~2 s, not
    60 — otherwise live goodput could never match bench's offline
    number for the same run."""
    win = SlidingWindow(window_s=60.0, slots=12)
    t0 = 5000.0
    for i in range(20):
        now = t0 + i * 0.1
        win.record_start(now=now)
        win.record(ttft_ms=10, itl_ms=2, output_tokens=16, slo_ok=True,
                   now=now)
    s = win.snapshot(now=t0 + 2.0)
    assert abs(s["goodput_tok_s"] - 20 * 16 / 2.0) / (20 * 16 / 2.0) < 0.05
    assert abs(s["offered_rps"] - 10.0) < 1.0


def test_accountant_slo_scoring_and_env_override(monkeypatch):
    acc = SLOAccountant(default=SLOTargets(ttft_ms=100.0, itl_ms=10.0))
    t = 100.0
    assert acc.observe("m", ttft_ms=50, itl_ms=5, output_tokens=8, now=t)
    assert not acc.observe("m", ttft_ms=500, itl_ms=5, output_tokens=8,
                           now=t)  # ttft breach
    assert not acc.observe("m", ttft_ms=50, itl_ms=50, output_tokens=8,
                           now=t)  # itl breach
    snap = acc.snapshot(now=t + 0.1)["m"]
    assert abs(snap["slo_met"] - 1 / 3) < 1e-9
    assert snap["slo"] == {"ttft_ms": 100.0, "itl_ms": 10.0}
    # per-model card targets
    acc.set_targets("m2", SLOTargets(ttft_ms=1000.0, itl_ms=100.0))
    assert acc.observe("m2", ttft_ms=500, itl_ms=5, output_tokens=8, now=t)
    # env override beats card targets (from_card applies from_env on top)
    monkeypatch.setenv("DYN_TPU_SLO_TTFT_MS", "10")

    class Card:
        slo_ttft_ms = 800.0
        slo_itl_ms = 25.0

    targets = SLOTargets.from_card(Card())
    assert targets.ttft_ms == 10.0 and targets.itl_ms == 25.0
    # a typo'd override is ignored WITHOUT discarding the other knob
    monkeypatch.setenv("DYN_TPU_SLO_TTFT_MS", "2000ms")
    monkeypatch.setenv("DYN_TPU_SLO_ITL_MS", "50")
    targets = SLOTargets.from_card(Card())
    assert targets.ttft_ms == 800.0  # card value kept, typo dropped
    assert targets.itl_ms == 50.0    # valid override still applied


def test_accountant_matches_bench_offline_computation():
    """The live window and bench.poisson_goodput's offline math are the
    SAME definitions: replaying a request log through both must agree."""
    rng = random.Random(3)
    slo = SLOTargets(ttft_ms=200.0, itl_ms=20.0)
    acc = SLOAccountant(default=slo)
    t0 = 50.0
    log = []
    now = t0
    for i in range(60):
        now += rng.expovariate(20.0)
        ttft = rng.uniform(20, 400)
        itl = rng.uniform(2, 40)
        toks = rng.randrange(8, 40)
        log.append((now, ttft, itl, toks))
        acc.observe_start("bench", now=now)
        acc.observe("bench", ttft_ms=ttft, itl_ms=itl, output_tokens=toks,
                    now=now)
    t_end = now
    dt = t_end - log[0][0]
    ok = [(n, tt, it, tk) for n, tt, it, tk in log
          if tt <= slo.ttft_ms and it <= slo.itl_ms]
    offline_goodput = sum(tk for *_, tk in ok) / dt
    offline_attained = sum(tk for *_, tk in log) / dt
    offline_met = len(ok) / len(log)
    live = acc.snapshot(now=t_end)["bench"]
    assert abs(live["slo_met"] - offline_met) < 1e-9
    assert abs(live["goodput_tok_s"] - offline_goodput) / offline_goodput < 0.05
    assert (abs(live["attained_tok_s"] - offline_attained)
            / offline_attained < 0.05)


def test_observe_call_budget_per_request():
    """Per-request SLO accounting (it runs once a request on the streaming
    path) as work, not time: `observe_start` and `observe` together are 24
    Python-level calls a request WITH exemplar slots armed, the production
    frontend configuration (two clock reads, two histogram records of one
    `log` each, the window's slot twice).  The ceiling is today's count."""
    acc = SLOAccountant(exemplars=True)
    rng = random.Random(11)
    samples = [(rng.uniform(1, 2000), rng.uniform(0.5, 80),
                rng.randrange(1, 200)) for _ in range(512)]
    # the window's first slot and the model's first window, off the count
    for ttft, itl, toks in samples[:64]:
        acc.observe_start("bench")
        acc.observe("bench", ttft, itl, toks, prompt_tokens=128,
                    exemplar={"trace_id": "t", "total_ms": ttft})
    n = 2_000
    with counted_calls() as c:
        for i in range(n):
            ttft, itl, toks = samples[i % len(samples)]
            acc.observe_start("bench")
            acc.observe("bench", ttft, itl, toks, prompt_tokens=128,
                        exemplar={"trace_id": "t", "total_ms": ttft})
    assert c.total // n <= call_ceiling(24), dict(c.names)
    assert c.names["monotonic"] == 2 * n


# -- exemplar slots + windowed tail ----------------------------------------- #


def test_histogram_exemplars_keep_worst_per_bucket():
    h = LogBucketHistogram(exemplars=True)
    h.record(100.0, exemplar={"trace_id": "a"})
    h.record(105.0, exemplar={"trace_id": "b"})   # same bucket, worse
    h.record(102.0, exemplar={"trace_id": "c"})   # same bucket, not worse
    h.record(8000.0, exemplar={"trace_id": "d"})  # far bucket
    worst = h.worst_exemplars(2)
    assert [ex["trace_id"] for _v, ex in worst] == ["d", "b"]
    # merge propagates the per-bucket worst
    h2 = LogBucketHistogram(exemplars=True)
    h2.record(106.0, exemplar={"trace_id": "e"})
    h.merge(h2)
    worst = h.worst_exemplars(2)
    assert [ex["trace_id"] for _v, ex in worst] == ["d", "e"]
    # a bare histogram records fine without exemplars and merge from an
    # exemplar-less peer is a no-op on the slots
    h3 = LogBucketHistogram()
    h3.record(1.0)
    h.merge(h3)
    assert h.worst_exemplars(1)[0][1]["trace_id"] == "d"


def test_window_tail_names_worst_requests():
    win = SlidingWindow(window_s=60.0, slots=6, exemplars=True)
    t0 = 9000.0
    for i, ttft in enumerate((50.0, 900.0, 200.0)):
        win.record(ttft_ms=ttft, itl_ms=5.0, output_tokens=8, slo_ok=True,
                   now=t0 + i * 0.1,
                   exemplar={"trace_id": f"r{i}", "total_ms": ttft + 100,
                             "bottleneck": "prefill"})
    tail = win.tail(2, now=t0 + 1.0)
    assert [ex["trace_id"] for ex in tail] == ["r1", "r2"]
    assert tail[0]["bottleneck"] == "prefill"
    # snapshot carries the tail only when armed
    assert "tail" in win.snapshot(now=t0 + 1.0)
    assert "tail" not in SlidingWindow(window_s=60.0).snapshot(now=t0)
    # aged-out exemplars leave the tail with the rotation
    assert win.tail(2, now=t0 + 120.0) == []


def test_accountant_tail_per_model():
    acc = SLOAccountant(exemplars=True)
    t = 300.0
    acc.observe("m1", ttft_ms=700, itl_ms=5, output_tokens=4, now=t,
                exemplar={"trace_id": "slow", "total_ms": 800,
                          "bottleneck": "queue"})
    acc.observe("m1", ttft_ms=10, itl_ms=2, output_tokens=4, now=t,
                exemplar={"trace_id": "fast", "total_ms": 20,
                          "bottleneck": "decode"})
    tail = acc.tail(1, now=t + 1.0)
    assert [ex["trace_id"] for ex in tail["m1"]] == ["slow"]
    assert tail["m1"][0]["bottleneck"] == "queue"

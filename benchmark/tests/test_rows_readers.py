"""The readers PR 36 adds beside `engine.prefill_batched_rows_pct`, on small
hand-made `run`s: device ms per ktok and the step roofline over EVERY
`prefill_chunk` slice (the steps that several sequences share among them,
which `lib/runview.py` `prefill_steps` leaves out), the time to first token
of cached and of cold requests apart, and first tokens a second.  On a
program that runs one sequence a step the first two read what the
`batch == 1` readers read."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from lib import peaks, roofline  # noqa: E402

MS = 1_000_000
NEW = {
    "step.prefill_rows_device_ms_per_ktok": (
        "model step", "device_trace", "lower", "ttft_p95_ms", "ms/ktok"),
    "kernel.prefill_rows_step_roofline": (
        "kernels", "device_trace", "higher", "ttft_p95_ms", "%"),
    "engine.ttft_cached_p50_ms": (
        "engine", "program_span", "lower", "ttft_p50_ms", "ms"),
    "engine.ttft_cold_p50_ms": (
        "engine", "program_span", "lower", "ttft_p95_ms", "ms"),
    "engine.first_tokens_per_s": (
        "engine", "program_span", "higher", "ttft_p95_ms", "req/s"),
}


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def ev(kind, t_ms, dur_ms=0, **attrs):
    return {"kind": kind, "t_ns": int(t_ms * MS), "dur_ns": int(dur_ms * MS),
            "ring": "engine", **attrs}


def qwen():
    with open(os.path.join(BENCH, "configs", "qwen2.5-7b-h14.json")) as f:
        return json.load(f)


def run_of(events, trace=None, t0=0.0, t1=10.0):
    return {"t0": t0, "t1": t1, "events": list(events), "records": [],
            "trace": trace, "config": qwen(),
            "peaks": peaks.peaks_for("TPU v5 lite")}


STEPS = [ev("prefill_chunk", 10, 70, batch=1, tokens=512, bucket=512),
         ev("prefill_chunk", 100, 30, batch=1, tokens=256, bucket=256),
         ev("prefill_chunk", 200, 30, batch=3, tokens=100, bucket=64),
         ev("prefill_chunk", 300, 30, batch=2, tokens=60, bucket=64)]
TRACE = {"modules": [[(12 * MS, 37 * MS, "jit_prefill_step(1)"),
                      (104 * MS, 121 * MS, "jit_prefill_step(2)"),
                      (205 * MS, 222 * MS, "jit_prefill_step(3)"),
                      (305 * MS, 322 * MS, "jit_prefill_step(3)")]]}


def test_ms_per_ktok_counts_the_shared_steps_and_their_rows_tokens():
    rows = reader("step.prefill_rows_device_ms_per_ktok")
    one = reader("step.prefill_device_ms_per_ktok")
    run = run_of(STEPS, TRACE)
    assert rows(run) == pytest.approx((25 + 17 + 17 + 17) / 928 * 1000)
    assert one(run) == pytest.approx((25 + 17) / 768 * 1000)  # long steps only
    # one sequence a step (the parent): both readers read the same
    parent = run_of([dict(e, batch=1) for e in STEPS], TRACE)
    assert rows(parent) == one(parent) == pytest.approx(rows(run))
    assert rows(run_of(STEPS)) is None  # no trace
    assert rows(run_of([], TRACE)) is None


def test_a_shared_step_s_floor_is_the_weights_once_and_all_its_tokens():
    rows = reader("kernel.prefill_rows_step_roofline")
    one = reader("kernel.prefill_step_roofline")
    cfg, p = qwen(), peaks.peaks_for("TPU v5 lite")
    floor = roofline.family(cfg).prefill_step_floor_s
    # a shared step of 100 tokens is bound by ONE weight read, as a lone
    # 100-token step is: rows do not multiply the bytes
    assert floor(cfg["model"], p, 100) == floor(cfg["model"], p, 33)
    assert floor(cfg["model"], p, 100)[1] == "memory"
    want = sum(floor(cfg["model"], p, e["tokens"])[0] for e in STEPS)
    run = run_of(STEPS, TRACE)
    assert rows(run) == pytest.approx(100 * want / 0.076)
    assert 0 < rows(run) < 100
    parent = run_of([dict(e, batch=1) for e in STEPS], TRACE)
    assert rows(parent) == pytest.approx(one(parent))
    assert rows(run_of(STEPS)) is None


def first(t_ms, cached, total_ms):
    return ev("first_token", t_ms, rid="r", prompt_len=1500, cached=cached,
              total_us=int(total_ms * 1000), queue_us=0, wait_us=0,
              own_us=int(total_ms * 1000), steps=1)


def test_cached_and_cold_requests_are_read_apart_and_counted_a_second():
    events = [first(100, 1472, 40), first(200, 1472, 80), first(300, 1488, 60),
              first(400, 0, 200), first(500, 0, 150),
              first(20_000, 0, 900)]  # after the window
    run = run_of(events, t1=10.0)
    assert reader("engine.ttft_cached_p50_ms")(run) == pytest.approx(60.0)
    assert reader("engine.ttft_cold_p50_ms")(run) == pytest.approx(175.0)
    assert reader("engine.first_tokens_per_s")(run) == pytest.approx(0.5)
    for name in ("engine.ttft_cached_p50_ms", "engine.ttft_cold_p50_ms",
                 "engine.first_tokens_per_s"):
        assert reader(name)(run_of([])) is None
    cold_only = run_of(events[3:5])
    assert reader("engine.ttft_cached_p50_ms")(cold_only) is None


def test_the_spec_lists_each_reader_by_name_in_every_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    cells = [w["name"] for w in spec["workloads"]]  # every cell prefills
    for name, want in NEW.items():
        m = by_name[name]
        assert (m["layer"], m["source"], m["better"], m["moves"],
                m["unit"]) == want
        assert m["workloads"] == cells
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", name + ".py"))

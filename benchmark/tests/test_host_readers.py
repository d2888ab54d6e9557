"""The host-clock readers of the engine's step events, each on a small
hand-made `run`: one with the events it reads, one without (None)."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

MS = 1_000_000


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def ev(kind, t_ms, dur_ms=0, **attrs):
    return {"kind": kind, "t_ns": int(t_ms * MS), "dur_ns": int(dur_ms * MS),
            "ring": "engine", **attrs}


def run_of(events, records=(), trace=None, t0=0.0, t1=10.0):
    return {"t0": t0, "t1": t1, "events": list(events),
            "records": list(records), "trace": trace}


def first(t_ms, prompt_len, total_us, queue_us, wait_us):
    return ev("first_token", t_ms, rid=f"r{t_ms}", prompt_len=prompt_len,
              cached=0, total_us=total_us, queue_us=queue_us, wait_us=wait_us,
              own_us=total_us - queue_us - wait_us, steps=1)


def test_ttft_wait_is_the_median_of_queue_plus_turn_wait():
    read = reader("engine.ttft_wait_p50_ms")
    events = [first(100, 64, 50_000, 1_000, 9_000),
              first(200, 64, 300_000, 5_000, 195_000),
              first(300, 64, 400_000, 0, 350_000),
              first(20_000, 64, 900_000, 0, 800_000)]  # after the window
    assert read(run_of(events)) == pytest.approx(200.0)
    assert read(run_of([ev("prefill_chunk", 5, 10, batch=1, tokens=8)])) is None


def test_step_gap_needs_a_plan_between_and_no_idle_wait():
    read = reader("engine.step_gap_ms")
    step = dict(batch=1, tokens=16)
    events = [
        ev("prefill_chunk", 0, 50, **step),
        ev("loop_yield", 50.1, 2), ev("plan", 53, 1),
        ev("prefill_chunk", 56, 40, **step),           # gap 6 ms: counted
        ev("plan", 97, 1), ev("idle_wait", 98, 500), ev("plan", 600, 1),
        ev("prefill_chunk", 602, 40, **step),          # idle between: not
        ev("plan", 643, 1),
        ev("decode_block", 652, 20, rung=1, batch=2),  # gap 10 ms: counted
        ev("decode_block", 672.5, 20, rung=1, batch=2),  # no plan: a chain
    ]
    assert read(run_of(events)) == pytest.approx(8.0)
    # the parent's ring: step slices only, nothing between them to read
    assert read(run_of([e for e in events if e["kind"] in (
        "prefill_chunk", "decode_block")])) is None


def test_prefill_host_time_is_the_slice_less_its_program():
    read = reader("step.prefill_host_ms")
    events = [ev("prefill_chunk", 10, 70, batch=1, tokens=512),
              ev("prefill_chunk", 100, 30, batch=1, tokens=32),
              ev("prefill_chunk", 200, 30, batch=2, tokens=64)]  # two rows
    trace = {"modules": [[(12 * MS, 72 * MS, "jit_prefill_step(1)"),
                          (104 * MS, 124 * MS, "jit_prefill_step(2)"),
                          (205 * MS, 225 * MS, "jit_prefill_step(3)")]]}
    assert read(run_of(events, trace=trace)) == pytest.approx(10.0)
    assert read(run_of(events, trace=None)) is None


def record(t_sent, t_first, prompt_len):
    return {"t_due": t_sent, "t_sent": t_sent, "t_first": t_first,
            "t_last": t_first, "status": 200, "finish": "length",
            "error": None, "prompt_len": prompt_len}


def test_frontend_overhead_matches_records_to_first_tokens():
    read = reader("frontend.overhead_p50_ms")
    records = [record(1.000, 1.400, 1500), record(1.010, 1.200, 1500),
               record(2.000, 2.120, 40), record(3.000, 3.300, 40)]
    events = [first(1395, 1500, 380_000, 0, 0),   # record 0: 400 - 380 = 20
              first(1190, 1500, 170_000, 0, 0),   # record 1: 190 - 170 = 20
              first(2110, 40, 100_000, 0, 0),     # record 2: 120 - 100 = 20
              first(3290, 40, 260_000, 0, 0)]     # record 3: 300 - 260 = 40
    assert read(run_of(events, records)) == pytest.approx(20.0)
    # an event outside its record's [sent, first] answers nothing: three of
    # four match, under nine in ten
    events[3] = first(3400, 40, 260_000, 0, 0)
    assert read(run_of(events, records)) is None
    assert read(run_of([], records)) is None


def test_each_new_reader_is_an_entry_of_the_spec():
    import json

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    layers = {m["name"]: m for m in spec["per_layer"]}
    for name, layer, moves in (
            ("engine.ttft_wait_p50_ms", "engine", "ttft_p50_ms"),
            ("engine.step_gap_ms", "engine", "ttft_p95_ms"),
            ("step.prefill_host_ms", "model step", "ttft_p95_ms"),
            ("frontend.overhead_p50_ms", "frontend + router + transport",
             "ttft_p50_ms")):
        m = layers[name]
        assert (m["layer"], m["moves"], m["unit"], m["better"]) == (
            layer, moves, "ms", "lower")
        assert callable(reader(name))

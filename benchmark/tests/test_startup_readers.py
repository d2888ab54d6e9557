"""The six "compile; start-up" readers on small hand-made `run`s: the
worker's start-up phases and the births of its programs as the step ring
records them (`startup.*`, `ready`, `program`, `gc_pause`, `lease_renew`:
dynamo_tpu/runtime/events.py `host_event`), and None from each on the ring
of a program that records none of them (the parent)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

S = 1_000_000_000
MS = 1_000_000
NAMES = ("setup.worker_ready_s", "setup.weights_s", "setup.ready_to_window_s",
         "setup.programs_s", "compile.in_window", "host.pause_ms")


def ev(kind, t_s, dur_s=0.0, **attrs):
    return {"kind": kind, "t_ns": int(t_s * S), "dur_ns": int(dur_s * S),
            "ring": "engine", **attrs}


def program(t_s, trace_ms, lower_ms, compile_ms=0, load_ms=0, **attrs):
    stages = dict(trace_us=trace_ms * 1000, lower_us=lower_ms * 1000,
                  compile_us=compile_ms * 1000)
    if load_ms:
        stages.update(load_us=load_ms * 1000, hit=1)
    return ev("program", t_s,
              (trace_ms + lower_ms + compile_ms + load_ms + 1) / 1000,
              fn="prefill_step", role="step", **stages, **attrs)


def readers():
    return {n: bench_run.load_reader("layer_metrics", n) for n in NAMES}


# process start at 100 s on the clock; ready at 142.5; the window is
# [170, 210]
STARTUP = [
    ev("startup.imports", 100.0, 4.0),
    ev("startup.backend", 104.0, 2.5, platform="tpu", devices=1),
    ev("startup.weights", 106.5, 33.0, bytes=8_710_000_000, tensors=14,
       read_us=12_000_000, put_us=21_000_000),
    ev("startup.engine", 139.5, 2.0, pool_bytes=3_170_000_000),
    ev("startup.serve", 141.5, 1.0),
    ev("ready", 142.5, model="m"),
]


def run_of(events, t0=170.0, t1=210.0):
    return {"t0": t0, "t1": t1, "events": list(events), "records": [],
            "trace": None}


def test_the_three_way_split_of_setup():
    r = readers()
    run = run_of(STARTUP)
    assert r["setup.worker_ready_s"](run) == pytest.approx(42.5)
    assert r["setup.weights_s"](run) == pytest.approx(33.0)
    assert r["setup.ready_to_window_s"](run) == pytest.approx(27.5)
    # the slices tile the span the first metric reads
    assert sum(e["dur_ns"] for e in STARTUP) / S == pytest.approx(
        r["setup.worker_ready_s"](run))


def test_programs_before_the_window_by_their_four_stages():
    r = readers()
    events = STARTUP + [
        program(108.0, 5, 3, compile_ms=40),           # while loading
        program(150.0, 900, 700, compile_ms=2, load_ms=300),   # a probe's
        ev("program", 160.0, 0.004, fn="", role="step", trace_us=500,
           lower_us=1500, compile_us=2000, hit=0),     # an unledgered one
        program(169.9, 50, 50, load_ms=100),           # ends at 170.101: not
        program(180.0, 100, 100, load_ms=50),          # before it; inside
        program(209.9, 60, 60, load_ms=60),            # ends after t1
    ]
    run = run_of(events)
    assert r["setup.programs_s"](run) == pytest.approx(
        (5 + 3 + 40) / 1e3 + (900 + 700 + 2 + 300) / 1e3 + 0.004)
    assert r["compile.in_window"](run) == 2            # 170.101 and 180.251
    assert r["setup.programs_s"](run_of(STARTUP)) == 0.0
    assert r["compile.in_window"](run_of(STARTUP)) == 0


def test_host_pauses_are_cut_to_the_window():
    r = readers()
    events = STARTUP + [
        ev("gc_pause", 120.0, 0.090, gen=2, collected=9),      # before
        ev("gc_pause", 169.990, 0.030, gen=2, collected=0),    # 20 ms inside
        ev("gc_pause", 175.0, 0.0015, gen=1, collected=0),
        ev("lease_renew", 190.0, 0.114, late_us=110_000, rtt_us=4_000),
        program(200.0, 40, 30, load_ms=9),                     # 80 ms slice
        ev("gc_pause", 209.995, 0.050, gen=2, collected=0),    # 5 ms inside
        ev("prefill_chunk", 180.0, 0.040, batch=1, tokens=512),  # not a pause
        ev("gc_pause", 230.0, 0.2, gen=2, collected=0),        # after
    ]
    assert r["host.pause_ms"](run_of(events)) == pytest.approx(
        20 + 1.5 + 114 + 80 + 5)
    assert r["host.pause_ms"](run_of(STARTUP)) == 0.0


def test_a_ring_without_host_events_leaves_every_metric_out():
    r = readers()
    parent = [ev("prefill_chunk", 180.0, 0.040, batch=1, tokens=512),
              ev("plan", 180.05, 0.001, waiting=0, running=3, admitted=0),
              ev("first_token", 180.06, rid="x", total_us=5)]
    for name in NAMES:
        assert r[name](run_of(parent)) is None, name
        assert r[name](run_of([])) is None, name
    # a ring that wrapped past the start-up (no `ready`) claims nothing
    # about the window either: absent is not 0
    late = parent + [program(181.0, 1, 1, load_ms=1)]
    assert r["compile.in_window"](run_of(late)) is None
    assert r["host.pause_ms"](run_of(late)) is None


def test_the_spec_lists_the_six_in_every_cell_by_name():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [w["name"] for w in spec["workloads"]]
    by_name = {m["name"]: m for m in spec["per_layer"]}
    units = dict(zip(NAMES, ("s", "s", "s", "s", "programs", "ms")))
    for name in NAMES:
        m = by_name[name]
        assert (m["layer"], m["source"], m["better"], m["unit"]) == (
            "compile; start-up", "program_span", "lower", units[name])
        assert m["workloads"] == cells
        assert m["moves"] == ("setup_s" if name.startswith("setup.")
                              else "ttft_p95_ms")

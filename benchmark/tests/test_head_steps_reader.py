"""`step.prefill_head_steps_pct` on a small hand-made `run`: the share of
the window's prefill steps with `head` 1; 100 on the ring of a program whose
slices carry no such attribute (it ran the head on every step); None where
the window holds no prefill step."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

MS = 1_000_000
NAME = "step.prefill_head_steps_pct"


def ev(kind, t_ms, dur_ms=0, **attrs):
    return {"kind": kind, "t_ns": int(t_ms * MS), "dur_ns": int(dur_ms * MS),
            "ring": "engine", **attrs}


def run_of(events, t0=0.0, t1=10.0):
    return {"t0": t0, "t1": t1, "events": list(events), "records": [],
            "trace": None}


def test_head_share_counts_the_steps_in_which_a_row_samples():
    read = bench_run.load_reader("layer_metrics", NAME)
    events = [
        ev("prefill_chunk", 0, 30, batch=1, tokens=512, head=0),
        ev("prefill_chunk", 8, 42, batch=1, tokens=512, head=0),
        ev("prefill_chunk", 31, 40, batch=1, tokens=400, head=1),
        ev("prefill_chunk", 52, 39, batch=3, tokens=96, head=1),
        ev("prefill_chunk", 80, 39, batch=1, tokens=256, head=0),
        ev("mixed_step", 120, 20, prefill_tokens=16, head=0),  # not a chunk
        ev("prefill_chunk", 9_990, 40, batch=1, tokens=512, head=0),  # outside
    ]
    assert read(run_of(events)) == pytest.approx(40.0)
    # the parent's ring: the slices are there, the attribute is not
    bare = [{k: v for k, v in e.items() if k != "head"} for e in events]
    assert read(run_of(bare)) == pytest.approx(100.0)
    assert read(run_of([ev("decode_block", 5, 10, rung=1, batch=2)])) is None


def test_the_spec_lists_the_reader_in_every_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    (m,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert (m["layer"], m["source"], m["better"], m["moves"], m["unit"]) == (
        "model step", "program_span", "lower", "ttft_p50_ms", "%")
    # every cell that prefills, by name: the cells the list held when the
    # reader came, and each one added since
    assert set(m["workloads"]) == {w["name"] for w in spec["workloads"]}

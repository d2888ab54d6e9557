"""`engine.prefill_overlap_pct` on a small hand-made `run`: the share of the
window's prefill steps with `overlapped` 1, and None on the ring of a
program that has no step in flight (no such attribute)."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

MS = 1_000_000


def ev(kind, t_ms, dur_ms=0, **attrs):
    return {"kind": kind, "t_ns": int(t_ms * MS), "dur_ns": int(dur_ms * MS),
            "ring": "engine", **attrs}


def run_of(events, t0=0.0, t1=10.0):
    return {"t0": t0, "t1": t1, "events": list(events), "records": [],
            "trace": None}


def test_overlap_share_counts_the_steps_dispatched_behind_another():
    read = bench_run.load_reader("layer_metrics", "engine.prefill_overlap_pct")
    step = dict(batch=1, tokens=512)
    events = [
        ev("prefill_chunk", 0, 30, overlapped=0, overlap_us=3, **step),
        ev("plan", 6, 1),                       # inside the open slice
        ev("prefill_chunk", 8, 42, overlapped=1, overlap_us=21_000, **step),
        ev("prefill_chunk", 31, 40, overlapped=1, overlap_us=20_000, **step),
        ev("prefill_chunk", 52, 39, overlapped=1, overlap_us=19_000, **step),
        ev("decode_block", 95, 20, rung=1, batch=2),   # no prefill step
        ev("prefill_chunk", 9_990, 40, overlapped=1, **step),  # ends outside
    ]
    assert read(run_of(events)) == pytest.approx(75.0)
    # the parent's ring: the slices are there, the attribute is not
    bare = [{k: v for k, v in e.items()
             if k not in ("overlapped", "overlap_us")} for e in events]
    assert read(run_of(bare)) is None
    assert read(run_of([])) is None


def test_the_spec_lists_the_reader_in_both_cells():
    import json

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    (m,) = [m for m in spec["per_layer"]
            if m["name"] == "engine.prefill_overlap_pct"]
    # found by its name: where it stands in the list is nobody's promise
    assert (m["layer"], m["source"], m["better"], m["moves"], m["unit"]) == (
        "engine", "program_span", "higher", "ttft_p50_ms", "%")
    assert m["workloads"] == [w["name"] for w in spec["workloads"]]

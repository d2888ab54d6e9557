"""`engine.prefill_batched_rows_pct` on a small hand-made `run`: the share
of the window's prefill rows that shared their step with another sequence,
0.0 where every step is one sequence's, None in an empty window."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

MS = 1_000_000
NAME = "engine.prefill_batched_rows_pct"


def ev(kind, t_ms, dur_ms=0, **attrs):
    return {"kind": kind, "t_ns": int(t_ms * MS), "dur_ns": int(dur_ms * MS),
            "ring": "engine", **attrs}


def run_of(events, t0=0.0, t1=10.0):
    return {"t0": t0, "t1": t1, "events": list(events), "records": [],
            "trace": None}


@pytest.fixture(scope="module")
def read():
    return bench_run.load_reader("layer_metrics", NAME)


def test_share_is_by_rows_not_by_steps_or_tokens(read):
    events = [
        ev("prefill_chunk", 0, 30, batch=1, tokens=512, bucket=512),
        ev("prefill_chunk", 31, 15, batch=3, tokens=100, bucket=64),
        ev("plan", 47, 1),
        ev("prefill_chunk", 48, 12, batch=1, tokens=40, bucket=64),
        ev("prefill_chunk", 61, 15, batch=2, tokens=70, bucket=64),
        ev("mixed_step", 80, 20, prefill_batch=1, prefill_tokens=64),
        ev("decode_block", 101, 20, rung=1, batch=4),  # not a prefill step
        ev("prefill_chunk", 9_990, 40, batch=4, tokens=200, bucket=64),
    ]  # the last one ends outside the window
    assert read(run_of(events)) == pytest.approx(100.0 * 5 / 7)
    assert read(run_of(events[1:2])) == 100.0


def test_one_sequence_a_step_reads_zero_and_an_empty_window_none(read):
    parent = [ev("prefill_chunk", 0, 30, batch=1, tokens=512),
              ev("prefill_chunk", 31, 12, batch=1, tokens=48)]
    assert read(run_of(parent)) == 0.0
    assert read(run_of([])) is None
    assert read(run_of([ev("plan", 0, 1)])) is None


def test_the_spec_lists_the_reader_by_name_in_every_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    m = by_name[NAME]
    assert (m["layer"], m["source"], m["better"], m["moves"], m["unit"]) == (
        "engine", "program_span", "higher", "ttft_p95_ms", "%")
    # every cell prefills; a cell under another mix is listed like the rest
    assert m["workloads"] == [w["name"] for w in spec["workloads"]]

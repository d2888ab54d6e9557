"""`kernel.prefill_pallas_token_pct` on a small hand-made `run`: the share
of the window's prefilled tokens whose step ran the Pallas prefill kernel,
and None where no slice says which attention program it ran."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

MS = 1_000_000
NAME = "kernel.prefill_pallas_token_pct"


def ev(kind, t_ms, dur_ms=0, **attrs):
    return {"kind": kind, "t_ns": int(t_ms * MS), "dur_ns": int(dur_ms * MS),
            "ring": "engine", **attrs}


def run_of(events, t0=0.0, t1=10.0):
    return {"t0": t0, "t1": t1, "events": list(events), "records": [],
            "trace": None}


@pytest.fixture(scope="module")
def read():
    return bench_run.load_reader("layer_metrics", NAME)


def test_share_is_by_tokens_not_by_steps(read):
    events = [
        ev("prefill_chunk", 0, 30, batch=1, tokens=512, attn="xla"),
        ev("prefill_chunk", 31, 12, batch=1, tokens=16, attn="pallas"),
        ev("prefill_chunk", 44, 12, batch=1, tokens=48, attn="pallas"),
        ev("plan", 50, 1),
        ev("prefill_chunk", 57, 17, batch=1, tokens=192, attn="pallas"),
        ev("decode_block", 95, 20, rung=1, batch=2, attn="xla"),  # no prefill
        ev("prefill_chunk", 9_990, 40, batch=1, tokens=512, attn="pallas"),
    ]  # the last one ends outside the window
    assert read(run_of(events)) == pytest.approx(100.0 * 256 / 768)
    assert read(run_of(events[:1])) == 0.0
    assert read(run_of(events[1:3])) == 100.0


def test_a_mixed_step_counts_its_prefill_tokens(read):
    events = [
        ev("prefill_chunk", 0, 30, batch=1, tokens=300, attn="xla"),
        ev("mixed_step", 31, 25, prefill_tokens=100, decode_rows=3,
           attn="pallas"),
    ]
    assert read(run_of(events)) == pytest.approx(25.0)


def test_slices_without_attn_and_an_empty_window_read_none(read):
    bare = [ev("prefill_chunk", 0, 30, batch=1, tokens=512),
            ev("mixed_step", 31, 25, prefill_tokens=100, decode_rows=3)]
    assert read(run_of(bare)) is None
    assert read(run_of([])) is None
    # slices that say it count; the ones that do not are left out
    some = bare + [ev("prefill_chunk", 60, 12, batch=1, tokens=64,
                      attn="pallas")]
    assert read(run_of(some)) == 100.0


def test_the_spec_lists_the_reader_in_both_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    names = [m["name"] for m in spec["per_layer"]]
    # appended behind what was there (a later entry may follow it)
    assert names.index(NAME) > names.index("engine.prefill_overlap_pct")
    m = spec["per_layer"][names.index(NAME)]
    assert (m["layer"], m["source"], m["better"], m["moves"], m["unit"]) == (
        "kernels", "program_span", "higher", "ttft_p95_ms", "%")
    assert m["workloads"] == [w["name"] for w in spec["workloads"]]

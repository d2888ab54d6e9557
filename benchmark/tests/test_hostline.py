"""`lib/hostline.py` and the readers over it, on a hand-made ring and program
line: every idle interval of the device is placed by hand, so each share is
known to the microsecond.  Times below are milliseconds on the ring's clock.

    step 1  hop 9-10, build 10-12, dispatch 12-14, fetch 14-34, deliver 34-36
            loop_yield 36-37, plan 37-38
    step 2  hop 38-39, build 39-43, dispatch 43-45, fetch 45-65, deliver 65-69
            loop_yield 69-72, plan 72-73
    step 3  hop 73-74, build 74-76, dispatch 76-78, fetch 78-98, deliver 98-100
            loop_yield 100-101, plan 101-103 (gc_pause 101.5-102.5),
            idle_wait 103-120, plan 120-121
    step 4  NO hop recorded (121-122 lies under nothing), build 122-124,
            dispatch 124-126, fetch 126-136, deliver 136-138

The device runs each step's program inside its slice and filler programs
(`jit_convert_element_type`) everywhere else but in six idle intervals."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from lib import hostline, opwalk  # noqa: E402

MS = 1_000_000
SHARES = ("build", "dispatch", "deliver", "loop", "launch", "pause",
          "unaccounted")
EXPOSED = (["host.exposed_ms_per_step"]
           + [f"host.exposed_{s}_pct" for s in SHARES])


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def ev(kind, t_ms, dur_ms=0.0, **attrs):
    return {"kind": kind, "t_ns": round(t_ms * MS),
            "dur_ns": round(dur_ms * MS), "ring": "engine", **attrs}


def step(seq, t_ms, build, dispatch, fetch, deliver, overlap=0.0, hop=None,
         kind="prefill_chunk", **attrs):
    us = {"build_us": build, "dispatch_us": dispatch, "overlap_us": overlap,
          "fetch_us": fetch, "deliver_us": deliver}
    if hop is not None:
        us["hop_us"] = hop
    return ev(kind, t_ms, build + dispatch + overlap + fetch + deliver,
              seq=seq, batch=1, tokens=64,
              **{k: round(v * 1000) for k, v in us.items()}, **attrs)


def ring():
    return [
        step(7, 10, 2, 2, 20, 2, hop=1, dry=1),
        ev("loop_yield", 36, 1), ev("plan", 37, 1),
        step(8, 39, 4, 2, 20, 4, hop=1, dry=0),
        ev("loop_yield", 69, 3), ev("plan", 72, 1),
        step(9, 74, 2, 2, 20, 2, hop=1, dry=1),
        ev("loop_yield", 100, 1), ev("plan", 101, 2),
        ev("gc_pause", 101.5, 1, gen=2, collected=0),
        ev("idle_wait", 103, 17), ev("plan", 120, 1),
        step(10, 122, 2, 2, 10, 2, dry=1),
    ]


FILL, STEP = "jit_convert_element_type(1)", "jit_prefill_step(2)"
# (start, end, name): busy everywhere but in 40-41 (step 2's build), 68-71
# (deliver | loop_yield), 77.5-79 (0.5 of step 3's dispatch, then 1.0 after it
# had ended: launch), 101.2-102.7 (plan | gc_pause | plan), 104-119
# (idle_wait) and 121.2-121.8 (under no record).  Step 1's program ends the
# instant its fetch returns: the clock's lower limit is exactly 0
PROGRAMS = [
    (0, 13, FILL), (13, 34, STEP), (34, 40, FILL), (41, 44, FILL),
    (44, 64.5, STEP), (64.5, 68, FILL), (71, 77.5, FILL), (79, 97.5, STEP),
    (97.5, 101.2, FILL), (102.7, 104, FILL), (119, 121.2, FILL),
    (121.8, 125, FILL), (125, 135.5, STEP), (135.5, 300, FILL)]
WANT_MS = {"build": 1.0, "dispatch": 0.5, "deliver": 1.0, "loop": 2.5,
           "launch": 1.0, "pause": 1.0, "unaccounted": 0.6}


def run_of(events, programs=PROGRAMS, off_ms=0.0, window_s=0.3):
    modules = [(round((a + off_ms) * MS), round((b + off_ms) * MS), n)
               for a, b, n in programs]
    return {"t0": 0.0, "t1": 0.3, "events": list(events), "records": [],
            "trace": {"modules": [modules], "window_s": window_s}}


def test_the_timeline_tiles_and_a_pause_overrides_what_it_overlaps():
    line = hostline.timeline(ring())
    covered, overlapped = hostline.coverage(line, 9 * MS, 138 * MS)
    assert overlapped == 0
    assert covered == (138 - 9 - 1) * MS        # all but step 4's hand-off
    assert (101.5 * MS, 102.5 * MS, "pause") in line
    assert (101 * MS, 101.5 * MS, "plan") in line
    assert (102.5 * MS, 103 * MS, "plan") in line
    assert hostline.phase_time(line, 0, 300 * MS) == {
        "hop": 3 * MS, "build": 10 * MS, "dispatch": 8 * MS,
        "fetch": 70 * MS, "deliver": 10 * MS, "loop_yield": 5 * MS,
        "plan": 4 * MS, "pause": 1 * MS, "idle_wait": 17 * MS}


def test_each_idle_interval_is_cut_at_the_timelines_boundaries():
    run = run_of(ring())
    acc = hostline.account(run)
    assert (acc["lo"], acc["hi"], acc["shift_ns"]) == (0, 1 * MS, 0)
    assert acc["steps"] == 4
    assert {k: v / MS for k, v in acc["by_share"].items()} == (
        pytest.approx(WANT_MS))
    assert acc["idle_wait_ns"] == 15 * MS       # left out of every sum
    assert acc["idle_ns"] == acc["exposed_ns"] + 15 * MS
    total = sum(WANT_MS.values())
    assert reader("host.exposed_ms_per_step")(run) == pytest.approx(total / 4)
    got = {s: reader(f"host.exposed_{s}_pct")(run) for s in SHARES}
    assert got == pytest.approx(
        {s: 100 * ms / total for s, ms in WANT_MS.items()})
    assert sum(got.values()) == pytest.approx(100.0)
    assert reader("host.clock_slack_us")(run) == pytest.approx(1000.0)


def test_the_loops_own_readers_need_no_trace():
    run = dict(run_of(ring()), trace=None)
    # hop 3 + build 10 + dispatch 8 + deliver 10 + plan 4 + loop_yield 5
    assert reader("host.cycle_ms_per_step")(run) == pytest.approx(40 / 4)
    assert reader("engine.dry_dispatch_pct")(run) == pytest.approx(75.0)
    for name in EXPOSED + ["host.clock_slack_us"]:
        assert reader(name)(run) is None


def test_a_ring_of_the_parent_reads_nothing_and_raises_nothing():
    bare = [{k: v for k, v in e.items()
             if k not in ("seq", "dry", "hop_us")} for e in ring()]
    run = run_of(bare)
    for name in EXPOSED + ["host.clock_slack_us", "engine.dry_dispatch_pct"]:
        assert reader(name)(run) is None
    # the phases the parent does record still add up, less the hand-offs
    assert reader("host.cycle_ms_per_step")(run) == pytest.approx(37 / 4)
    assert reader("host.cycle_ms_per_step")(run_of([])) is None


def test_a_trace_300_us_off_the_rings_clock_is_shifted_back():
    run = run_of(ring(), off_ms=0.3)
    acc = hostline.account(run)
    assert (acc["lo"], acc["hi"]) == (0.3 * MS, 1.3 * MS)
    assert acc["shift_ns"] == 0.3 * MS          # the nearer end of [lo, hi]
    assert {k: v / MS for k, v in acc["by_share"].items()} == (
        pytest.approx(WANT_MS))
    assert reader("host.clock_slack_us")(run) == pytest.approx(1000.0)
    # off the other way, the records still allow 0 and nothing is shifted
    early = hostline.account(run_of(ring(), off_ms=-0.3))
    assert (early["lo"], early["hi"], early["shift_ns"]) == (
        -0.3 * MS, 0.7 * MS, 0)


def test_a_contradicted_clock_reads_none_in_every_exposed_reader():
    programs = list(PROGRAMS)
    # step 2's program starts a millisecond BEFORE its jitted call began
    # while step 1's ends the instant its fetch returned: lo 0 > hi -1
    programs[3:5] = [(41, 42, FILL), (42, 64.5, STEP)]
    run = run_of(ring(), programs)
    acc = hostline.account(run)
    assert (acc["lo"], acc["hi"], acc["shift_ns"]) == (0, -1 * MS, None)
    for name in EXPOSED:
        assert reader(name)(run) is None
    assert reader("host.clock_slack_us")(run) == pytest.approx(-1000.0)


def test_a_step_is_paired_by_seq_where_the_longest_program_is_its_neighbours():
    """B's 5 ms program, C dispatched behind it, then B's delivery held for
    40 ms: C's 30 ms program lies whole inside B's slice, and the longest-
    program rule gives it to B."""
    b = step(20, 10, 2, 2, 1, 40, overlap=5, hop=1, dry=1)   # slice 10-60
    c = step(21, 15, 2, 2, 1, 1, overlap=41, hop=0, dry=0)   # slice 15-62
    own_b, own_c = (13 * MS, 18 * MS, STEP), (18.1 * MS, 48 * MS, STEP)
    modules = [(0, 13 * MS, FILL), own_b, own_c]
    b_slice = (b["t_ns"], b["t_ns"] + b["dur_ns"])
    assert opwalk.programs_of(modules, [b_slice])[b_slice] == own_c[:2]
    paired = hostline.pair([b, c], modules)
    assert paired["programs"][b["seq"] - paired["k"]] == own_b
    assert paired["programs"][c["seq"] - paired["k"]] == own_c
    assert paired["lo"] <= 0 <= paired["hi"]


def test_what_follows_a_dispatch_that_had_ended_is_launch_for_a_chain_too():
    """A chained decode block took three ordinals; the gaps between its
    programs come after its one dispatch had ended."""
    chain = step(30, 10, 2, 2, 30, 2, hop=1, dry=1, kind="decode_block",
                 blocks=3)
    after = step(33, 50, 2, 2, 10, 2, hop=4, dry=1, kind="decode_block",
                 blocks=1)
    name = "jit_decode_block(3)"
    modules = [(0, 13, FILL), (13, 20, name), (21, 28, name), (30, 37, name),
               (37, 53.5, FILL), (53.5, 63, name), (63, 300, FILL)]
    acc = hostline.account(run_of([chain, after], modules))
    assert acc["by_share"]["launch"] == 3 * MS   # 20-21 and 28-30
    assert acc["exposed_ns"] == 3 * MS
    assert acc["steps"] == 2


def test_a_capture_that_ended_early_is_charged_over_the_span_it_holds():
    acc = hostline.account(run_of(ring(), window_s=0.075))
    # 40-41 under build, 68-71 across deliver | loop_yield; no further
    assert {k: v / MS for k, v in acc["by_share"].items() if v} == (
        pytest.approx({"build": 1.0, "deliver": 1.0, "loop": 2.0}))
    assert acc["steps"] == 2
    # the loop's own readers go on over the whole window
    assert reader("host.cycle_ms_per_step")(
        run_of(ring(), window_s=0.075)) == pytest.approx(10.0)


def test_the_continuous_chain_gives_the_gap_to_the_later_slice():
    """`runtime/timeline.py` `decode_host_gaps`' rule, as phases: inside a
    `decode_chain` slice the time before an iteration's slice is its build,
    and what follows the last one is delivery."""
    def block(t, dur, seq):
        return ev("decode_block", t, dur, continuous=True, build_us=0,
                  dispatch_us=1000, fetch_us=2000,
                  deliver_us=round((dur - 3) * 1000), seq=seq, dry=0)

    events = [ev("decode_chain", 10, 40, hop_us=1000, blocks=2),
              block(14, 5, 1), block(22, 6, 2)]
    assert hostline.timeline(events) == [
        (9 * MS, 10 * MS, "hop"), (10 * MS, 14 * MS, "build"),
        (14 * MS, 15 * MS, "dispatch"), (15 * MS, 17 * MS, "fetch"),
        (17 * MS, 19 * MS, "deliver"), (19 * MS, 22 * MS, "build"),
        (22 * MS, 23 * MS, "dispatch"), (23 * MS, 25 * MS, "fetch"),
        (25 * MS, 28 * MS, "deliver"), (28 * MS, 50 * MS, "deliver")]


def test_the_rows_attention_floor_sums_a_shared_steps_rows(monkeypatch):
    """A shared step counts with its rows' own chunks and contexts, the rows
    summed by the bound each names; without the two lists it stays out."""
    read = reader("kernel.prefill_rows_attn_roofline")
    single = {"batch": 1, "tokens": 512, "ctx": 1024}
    shared = {"batch": 3, "tokens": 96, "ctx": 900, "toks": [16, 64, 16],
              "ctxs": [900, 200, 100]}
    parent = {"batch": 2, "tokens": 80, "ctx": 300}
    monkeypatch.setattr(opwalk, "attention_seconds", lambda run: [
        (single, 0.05, 0.010), (shared, 0.02, 0.004), (parent, 0.02, 0.5)])

    class Family:
        @staticmethod
        def prefill_attn_floor_s(model, peaks, tokens, ctx):
            # memory-bound rows read their context, compute-bound ones
            # (64 tokens and more here) pay for their tokens
            return ((tokens * 1e-5, "compute") if tokens >= 64
                    else (ctx * 1e-6, "memory"))

    from lib import roofline
    monkeypatch.setattr(roofline, "family", lambda config: Family)
    run = {"config": {"model": {}}, "peaks": {}}
    # single: 512e-5; shared: memory rows 900e-6 + 100e-6 against the
    # compute row's 64e-5: the larger SUM, 1.0e-3
    assert read(run) == pytest.approx(100 * (5.12e-3 + 1.0e-3) / 0.014)
    monkeypatch.setattr(opwalk, "attention_seconds", lambda run: None)
    assert read(run) is None


def test_each_new_reader_is_an_entry_of_the_spec_for_every_cell():
    import json

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [w["name"] for w in spec["workloads"]]
    layers = {m["name"]: m for m in spec["per_layer"]}
    sources = {"host.cycle_ms_per_step": ("ms", "program_span"),
               "engine.dry_dispatch_pct": ("%", "program_span"),
               "host.exposed_ms_per_step": ("ms", "device_trace"),
               "host.clock_slack_us": ("us", "device_trace"),
               **{f"host.exposed_{s}_pct": ("%", "device_trace")
                  for s in SHARES}}
    for name, (unit, source) in sources.items():
        m = layers[name]
        assert (m["layer"], m["moves"], m["unit"], m["better"],
                m["source"]) == ("engine", "ttft_p95_ms", unit, "lower",
                                 source)
        assert m["workloads"] == cells
        assert callable(reader(name))
    rows = layers["kernel.prefill_rows_attn_roofline"]
    assert rows["workloads"] == (
        layers["kernel.prefill_attn_roofline"]["workloads"])
    assert (rows["layer"], rows["unit"], rows["better"]) == (
        "kernels", "%", "higher")

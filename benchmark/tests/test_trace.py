import json
import os

import pytest

from lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def compact(ops, modules=()):
    names = sorted({n for n, _, _ in list(ops) + list(modules)})
    ix = {n: i for i, n in enumerate(names)}

    def line(name, evs):
        return {"name": name, "events": [[ix[n], s, d] for n, s, d in evs]}

    return {"clock": "mono_ns", "names": names, "planes": [
        {"name": "/device:TPU:0", "lines": [
            line("XLA Modules", modules), line("XLA Ops", ops)]}]}


def test_busy_is_the_union_and_ops_count_their_own_time():
    # a while of 100 holding two children of 30 and 50, then a lone op of 20
    t = compact([("%while.1 = (s32[]) while(...)", 0, 100),
                 ("%fusion.1 = bf16[8,128]{1,0:T(8,128)} fusion(...)", 10, 30),
                 ("%copy.2 = bf16[16]{0} copy(...)", 45, 50),
                 ("%fusion.9 = f32[4]{0} fusion(...)", 150, 20)],
                [("jit_step(1)", 0, 100), ("jit_step(1)", 150, 20)])
    steps = [{"t_ns": 100, "dur_ns": 40, "kind": "decode_block"}]
    r = trace.reduce(t, 0, 200, steps)
    assert r["busy_s"] == pytest.approx(120e-9)
    assert r["window_s"] == pytest.approx(200e-9)
    ops = dict(r["device_ops"])
    assert ops["%copy.2 bf16[16]"] == pytest.approx(50e-9)
    assert ops["%fusion.1 bf16[8,128]"] == pytest.approx(30e-9)
    assert ops["%while.1 (s32[])"] == pytest.approx(20e-9)   # self time only
    # gaps: 100..150 spanned by the decode slice's midpoint? mid=125 -> yes
    gaps = dict(r["idle_gaps"])
    assert gaps["decode_block"] == pytest.approx(50e-9)
    assert gaps["between_steps"] == pytest.approx(30e-9)
    assert r["modules"][0] == [(0, 100, "jit_step(1)"), (150, 170, "jit_step(1)")]


def test_events_are_clipped_to_the_window():
    t = compact([("%a = f32[1]{0} add(...)", -50, 100),
                 ("%b = f32[1]{0} add(...)", 90, 100)])
    r = trace.reduce(t, 0, 100, [])
    assert r["busy_s"] == pytest.approx(60e-9)


def test_a_window_without_device_work_is_refused():
    with pytest.raises(RuntimeError):
        trace.reduce(compact([("%a = f32[1]{0} add(...)", 0, 10)]), 100, 200, [])


def test_program_time_in_slices_takes_the_longest_inside():
    mods = [[(5, 9, "jit_convert(1)"), (10, 60, "jit_step(2)"),
             (100, 130, "jit_step(2)"), (300, 310, "jit_step(2)")]]
    got = trace.program_time_in_slices(mods, [(0, 70), (90, 140), (200, 250)])
    assert got == [((0, 70), 50e-9), ((90, 140), 30e-9)]


def test_short_name_keeps_result_and_shape():
    n = ("%fusion.2 = bf16[512,4096]{1,0:T(8,128)(2,1)} fusion(bf16[1,512]"
         "{1,0} %p), kind=kLoop")
    assert trace.short_name(n) == "%fusion.2 bf16[512,4096]"
    assert trace.short_name("jit_step(123)") == "jit_step(123)"


def test_recorded_v5e_trace():
    """A cut of a real capture (my chip run, PR 24: /device:TPU:0, the "XLA
    Ops" and "XLA Modules" lines, with the worker's step events of the same
    span).  Busy time is checked against an independent sweep over the op
    intervals' end points."""
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        rec = json.load(f)
    t0, t1 = rec["window_ns"]
    r = trace.reduce(rec, t0, t1, rec["step_events"])
    ops = trace.line_of(rec["planes"][0], trace.OPS_LINE)["events"]
    points = []
    for _, s, d in ops:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            points += [(a, 1), (b, -1)]
    depth = busy = 0
    last = None
    for at, step in sorted(points):
        if depth > 0:
            busy += at - last
        depth += step
        last = at
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(s for _, s in r["idle_gaps"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"], rel=1e-6)
    own = sum(s for _, s in r["device_ops"])
    assert own <= r["busy_s"] * (1 + 1e-9)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert any(m[2].startswith("jit_") for m in r["modules"][0])
    assert rec["expected"]["busy_s"] == pytest.approx(r["busy_s"], rel=1e-9)

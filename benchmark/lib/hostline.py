"""Device idle time charged to what the host was doing: the step loop's own
account as a timeline that tiles, each step paired with its own device
execution by dispatch order, and every idle interval of the device CUT at the
timeline's boundaries (`lib/trace.py` `label_gap` labels a gap at its midpoint
with the earliest slice that spans it, which since PR 32 is always the older
`prefill_chunk`).  Pure Python on `run["events"]` and `run["trace"]`.

**The timeline** (`timeline(events)`, one ring's events -> sorted, disjoint
`(start_ns, end_ns, phase)`).  docs/observability.md, "The loop's account
tiles", is the rule relied on: between a worker's `ready` and its shutdown
every instant of the step loop (pump coroutine + step thread) lies under one
phase.  A step slice gives `hop` `[t_ns - hop_us, t_ns]` (the pump's hand-off
to the step thread), `build`, `dispatch` (forward from `t_ns`), and from its
END backwards `deliver`, `fetch` and, where the pump asked for the fetch,
`hop` again (`fetch_hop_us`); its `overlap_us` in between is time given to
OTHER records and gives nothing.  The pump's `plan`, `loop_yield`,
`idle_wait`, `pump_op` slices are phases as they stand.  Under a
`decode_chain` slice (the continuous decode loop; `hop_us` before it) the time
before a `continuous` `decode_block` slice is that slice's `build`
(`runtime/timeline.py` `decode_host_gaps`' rule: the LATER slice owns the
gap), and what follows the last one is `deliver`.  Microsecond attributes are
floors, so placed phases never overlap and leave holes under a microsecond.  A
`gc_pause`, `program` or `lease_renew` event overrides what it overlaps as
`pause`.

**The pairing** (`pair(steps, modules)`).  An engine's step programs run on
its device in the order they were handed over, and a step slice's `seq` is the
ordinal of its first program in that order (`JaxEngine._note_dispatch`; it
took the ordinals up to the next slice's `seq`: a fused decode chain, a
chained decode block).  On the trace's `XLA Modules` line the step programs
are the executions named `jit_prefill_step*`, `jit_decode_step*`,
`jit_decode_block*`, `jit_mixed_step*` and `jit_verify_step*`; the five
`jit_convert_element_type` a step (the sampling operands' casts, dispatched
while the step is built) and anything else are not.  So the i-th step program
of the line took ordinal i + k for ONE k, found once: of the few k that put a
mid-window step's program near its slice, the one under which the records
agree (`lo <= hi` below) on the offset nearest to none.  Nothing is matched
by duration.

**The clock.**  Write d for what must be added to the ring's clock to get
the trace's.  A program cannot start on the device before its jitted call
began (d <= device start - call start) nor end after its `device_get` returned
(d >= device end - return): `hi` is the smallest of the first over the paired
steps, `lo` the largest of the second.  The trace is shifted by 0 where `lo
<= 0 <= hi`, else by the nearer end of `[lo, hi]`; `lo > hi` means the two
records contradict each other, and nothing is charged.

**The charge** (`account(run)`).  The device's idle intervals are the
complement of all its program executions inside the captured span
(`run["trace"]["window_s"]` from `t0`).  The part of an interval after the
NEXT step program's dispatch had ended (`t_ns` + `build_us` + `dispatch_us` of
its slice) is `launch`: the host was in time, and the runtime, the transfer or
the device was not.  The rest is cut at the timeline's boundaries; parts
under no phase are `unaccounted`, parts under `idle_wait` are want of work and
are kept apart."""

import bisect

from . import runview
from .trace import STEP_KINDS

PUMP_KINDS = ("plan", "loop_yield", "idle_wait", "pump_op")
PAUSE_KINDS = ("gc_pause", "program", "lease_renew")
STEP_PROGRAMS = ("jit_prefill_step", "jit_decode_step", "jit_decode_block",
                 "jit_mixed_step", "jit_verify_step")
# the loop's time that is NOT waiting for the device, nor for work
HOST_PHASES = ("hop", "build", "dispatch", "deliver", "plan", "loop_yield",
               "pump_op")
# phase -> the share it is reported under (`host.exposed_<share>_pct`)
SHARES = {"build": "build", "dispatch": "dispatch", "deliver": "deliver",
          "plan": "loop", "loop_yield": "loop", "hop": "loop",
          "pump_op": "loop", "launch": "launch", "fetch": "launch",
          "pause": "pause", "unaccounted": "unaccounted"}
SHARE_NAMES = tuple(dict.fromkeys(SHARES.values()))


def _us(e, key):
    return e.get(key, 0) * 1000


def dispatch_end(e):
    """When the step's jitted call(s) had returned, on the ring's clock."""
    return e["t_ns"] + _us(e, "build_us") + _us(e, "dispatch_us")


def _step_phases(e):
    t, end = e["t_ns"], e["t_ns"] + e["dur_ns"]
    out = [(t - _us(e, "hop_us"), t, "hop")]
    if e.get("continuous"):  # dispatch, the wait for the drain, the rest
        sent = t + _us(e, "dispatch_us")
        got = sent + _us(e, "fetch_us")
        return out + [(t, sent, "dispatch"), (sent, got, "fetch"),
                      (got, end, "deliver")]
    called = t + _us(e, "build_us")
    got = end - _us(e, "deliver_us")
    fetch = got - _us(e, "fetch_us")
    return out + [(t, called, "build"),
                  (called, called + _us(e, "dispatch_us"), "dispatch"),
                  (fetch - _us(e, "fetch_hop_us"), fetch, "hop"),
                  (fetch, got, "fetch"), (got, end, "deliver")]


def _cut(segments, pauses):
    """`segments` less what `pauses` (merged, sorted) cover, plus the pauses
    themselves as `pause` segments."""
    out = []
    starts = [p[0] for p in pauses]
    for a, b, phase in segments:
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(pauses) and pauses[i][0] < b:
            pa, pb = pauses[i]
            if pb > a:
                if pa > a:
                    out.append((a, pa, phase))
                a = max(a, pb)
            i += 1
        if b > a:
            out.append((a, b, phase))
    return sorted(out + [(a, b, "pause") for a, b in pauses])


def timeline(events):
    """One ring's events -> [(start_ns, end_ns, phase)], sorted by start."""
    segments = []
    chains = []
    for e in events:
        kind = e["kind"]
        if kind in STEP_KINDS:
            segments += _step_phases(e)
        elif kind in PUMP_KINDS:
            segments.append((e["t_ns"], e["t_ns"] + e["dur_ns"], kind))
        elif kind == "decode_chain":
            chains.append(e)
    # a continuous chain: what no iteration's slice covers is the next
    # iteration's build, and after the last one delivery
    blocks = sorted((e["t_ns"], e["t_ns"] + e["dur_ns"]) for e in events
                    if e["kind"] == "decode_block" and e.get("continuous"))
    for c in chains:
        at, end = c["t_ns"], c["t_ns"] + c["dur_ns"]
        segments.append((at - _us(c, "hop_us"), at, "hop"))
        for a, b in blocks[bisect.bisect_left(blocks, (at, at)):]:
            if a >= end:
                break
            segments.append((at, a, "build"))
            at = b
        segments.append((at, end, "deliver"))
    pauses = []
    for a, b in sorted((e["t_ns"], e["t_ns"] + e["dur_ns"]) for e in events
                       if e["kind"] in PAUSE_KINDS and e["dur_ns"] > 0):
        if pauses and a <= pauses[-1][1]:
            pauses[-1][1] = max(pauses[-1][1], b)
        else:
            pauses.append([a, b])
    return _cut([s for s in segments if s[1] > s[0]],
                [tuple(p) for p in pauses])


def coverage(segments, a, b):
    """(covered_ns, overlapped_ns) of [a, b] by a timeline: how much of the
    span lies under a phase, and how much under more than one."""
    covered = overlapped = 0
    at = a
    for s, e, _ in segments:
        s, e = max(s, a), min(e, b)
        if e <= s:
            continue
        if s < at:
            overlapped += min(e, at) - s
        if e > at:
            covered += e - max(s, at)
            at = e
    return covered, overlapped


def phase_time(segments, a, b):
    """{phase: ns} of a timeline inside [a, b]."""
    out = {}
    for s, e, phase in segments:
        s, e = max(s, a), min(e, b)
        if e > s:
            out[phase] = out.get(phase, 0) + e - s
    return out


def is_step_program(name):
    return name.startswith(STEP_PROGRAMS)


def _bounds(steps, programs, k):
    """(lo, hi) of the clock's offset if the i-th program took ordinal
    i + k; (None, None) where no step has its program on the line."""
    lo = hi = None
    for e in steps:
        i = e["seq"] - k
        if not 0 <= i < len(programs):
            continue
        start, end = programs[i][0], programs[i][1]
        upper = start - (e["t_ns"] + _us(e, "build_us"))
        hi = upper if hi is None else min(hi, upper)
        if not e.get("continuous"):  # its fetch is the drain thread's
            lower = end - (e["t_ns"] + e["dur_ns"] - _us(e, "deliver_us"))
            lo = lower if lo is None else max(lo, lower)
    return lo, hi


def pair(steps, modules):
    """Step slices with `seq` and one plane's `modules` [(start, end,
    name)] -> {"k", "lo", "hi", "programs"} or None: `programs` the line's
    step programs in order, the i-th of which took ordinal i + k."""
    programs = [m for m in modules if is_step_program(m[2])]
    steps = sorted((e for e in steps if "seq" in e), key=lambda e: e["seq"])
    if not programs or not steps:
        return None
    starts = [m[0] for m in programs]
    # a step the line holds: the middle one of those that began after the
    # line's first program and ended before its last
    held = [e for e in steps if programs[0][0] <= e["t_ns"]
            and e["t_ns"] + e["dur_ns"] <= programs[-1][1]] or steps
    probe = held[len(held) // 2]
    near = range(
        max(0, bisect.bisect_left(starts, probe["t_ns"] - 5_000_000) - 1),
        min(len(programs),
            bisect.bisect_right(starts, probe["t_ns"] + probe["dur_ns"]
                                + 5_000_000) + 1))
    best = None
    for i in near:
        k = probe["seq"] - i
        lo, hi = _bounds(steps, programs, k)
        if hi is None:
            continue
        lo = hi if lo is None else lo
        # records that agree (lo <= hi) before ones that do not; of those
        # that agree the offset nearest to none at all (the trace came
        # aligned within the profiler's own anchor), then the most room
        off = 0 if lo <= 0 <= hi else min(abs(lo), abs(hi))
        score = (lo > hi, off if lo <= hi else 0, lo - hi)
        if best is None or score < best[0]:
            best = (score, k, lo, hi)
    if best is None:
        return None
    return {"k": best[1], "lo": best[2], "hi": best[3], "programs": programs}


def shift_of(lo, hi):
    """What is taken off the trace's times: 0 where the records allow it,
    else the nearer end of [lo, hi]; None where they contradict."""
    if lo > hi:
        return None
    return 0 if lo <= 0 <= hi else (lo if lo > 0 else hi)


def _by_ring(events):
    rings = {}
    for e in events:
        rings.setdefault(e.get("ring"), []).append(e)
    return rings


_MEMO = {}


def host_cycle(run):
    """{"steps", "host_ns", "phases"} over the whole window, every ring:
    the step slices that ended in it and the loop's time in it that waited
    neither for the device nor for work.  None without a step."""
    steps = len(runview.window_events(run, *STEP_KINDS))
    if not steps:
        return None
    a, b = int(run["t0"] * 1e9), int(run["t1"] * 1e9)
    phases = {}
    for events in _by_ring(run["events"]).values():
        for phase, ns in phase_time(timeline(events), a, b).items():
            phases[phase] = phases.get(phase, 0) + ns
    return {"steps": steps, "phases": phases,
            "host_ns": sum(phases.get(p, 0) for p in HOST_PHASES)}


def account(run):
    """The charge of one run (memoized), or None where it cannot be made:
    no trace, step slices without `seq` (the parent), several rings with
    steps (which plane is whose is not recorded), no step program on the
    line.  Else {"lo", "hi", "slack_ns", "shift_ns", "steps", "idle_ns",
    "idle_wait_ns", "exposed_ns", "by_share": {share: ns}, "by_phase"};
    with a contradicted clock only the first four, `shift_ns` None."""
    key = id(run)
    if key not in _MEMO:
        _MEMO.clear()
        _MEMO[key] = (run, _account(run))
    return _MEMO[key][1]


def _account(run):
    tr = run.get("trace")
    if not tr or not tr.get("modules"):
        return None
    rings = [evs for evs in _by_ring(run["events"]).values()
             if any(e["kind"] in STEP_KINDS and "seq" in e for e in evs)]
    if len(rings) != 1:
        return None
    events, modules = rings[0], tr["modules"][0]
    paired = pair([e for e in events if e["kind"] in STEP_KINDS], modules)
    if paired is None:
        return None
    out = {"lo": paired["lo"], "hi": paired["hi"],
           "slack_ns": paired["hi"] - paired["lo"],
           "shift_ns": shift_of(paired["lo"], paired["hi"])}
    if out["shift_ns"] is None:
        return out
    shift = out["shift_ns"]
    a = int(run["t0"] * 1e9)
    b = a + int(tr["window_s"] * 1e9)
    line = timeline(events)
    line_starts = [s[0] for s in line]
    steps = sorted((e for e in events if e["kind"] in STEP_KINDS
                    and "seq" in e), key=lambda e: e["seq"])
    seqs = [e["seq"] for e in steps]

    def owner(ordinal):
        """The slice of the step that handed this program over."""
        i = bisect.bisect_right(seqs, ordinal) - 1
        return steps[i] if i >= 0 else None

    by_phase = {}

    def charge(g0, g1):
        """[g0, g1] cut at the timeline's boundaries."""
        at = g0
        i = max(0, bisect.bisect_right(line_starts, g0) - 1)
        while i < len(line) and line[i][0] < g1:
            s, e, phase = line[i]
            s, e = max(s, at), min(e, g1)
            if e > s:
                if s > at:
                    by_phase["unaccounted"] = (
                        by_phase.get("unaccounted", 0) + s - at)
                by_phase[phase] = by_phase.get(phase, 0) + e - s
                at = e
            i += 1
        if g1 > at:
            by_phase["unaccounted"] = by_phase.get("unaccounted", 0) + g1 - at

    at, ordinal = a, paired["k"]  # of the line's next step program
    for m in modules:
        start, end = m[0] - shift, m[1] - shift
        if start >= b:
            break
        own = None
        if is_step_program(m[2]):
            own = owner(ordinal)
            ordinal += 1
        if start > at:  # idle from `at` to this program's start
            late = start
            if own is not None:
                late = min(start, max(at, dispatch_end(own)))
                by_phase["launch"] = by_phase.get("launch", 0) + start - late
            if late > at:
                charge(at, late)
        at = max(at, end)
    if b > at:
        charge(at, b)
    idle_wait = by_phase.pop("idle_wait", 0)
    by_share = dict.fromkeys(SHARE_NAMES, 0)
    for phase, ns in by_phase.items():
        by_share[SHARES[phase]] += ns
    exposed = sum(by_share.values())
    out.update(steps=sum(1 for e in steps
                         if a <= e["t_ns"] + e["dur_ns"] <= b),
               idle_ns=exposed + idle_wait, idle_wait_ns=idle_wait,
               exposed_ns=exposed, by_share=by_share, by_phase=by_phase)
    return out


def exposed_share(run, share):
    """100 x the exposed idle time under `share` over all of it; None
    where nothing is charged or nothing is exposed."""
    acc = account(run)
    if not acc or not acc.get("exposed_ns"):
        return None
    return 100.0 * acc["by_share"][share] / acc["exposed_ns"]

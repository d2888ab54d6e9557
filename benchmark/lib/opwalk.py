"""One walk over a traced window's device ops, step by step, for readers
that place an op by what the trace says of it: its kernel name and, where
`lib/trace.py` found them in the profiler's file, its path of named scopes
(`tf_op`).  `lib/moe_trace.py`, `latent_trace.py`, `hc_trace.py` and
`ssm_trace.py` each repeat such a walk with a family's array shapes (PERF.md
7 (n), (u)); this one knows no family and takes the placing as a function.

`step_seconds(run, place)` -> [(step event, program seconds, {group:
seconds})] for EVERY `prefill_chunk` step of the window (`lib/rowsview.py`),
or None without a trace or its compact file: self time of the ops inside the
step's own program execution (the longest one inside its host slice, as
`lib/trace.py` `program_time_in_slices` takes it), by `place(name, scope)`
-> group or None.  A `while` (the layer loop) is nobody's: only self time is
counted, so the loop's own overhead stays outside every group."""

import bisect
import json

from . import moe_trace, rowsview, trace

_COMPACT = {}  # path -> compact trace: one file a run, several readers


def compact_of(path):
    if path not in _COMPACT:
        _COMPACT.clear()
        with open(path) as f:
            _COMPACT[path] = json.load(f)
    return _COMPACT[path]


def programs_of(modules, slices):
    """{slice: (start, end)} of the longest program execution that starts
    inside the host slice and ends by its end (1 ms of grace), on one
    plane's `modules` [(start, end, name)], sorted by start."""
    starts = [m[0] for m in modules]
    out = {}
    for s, e in slices:
        best = None
        for m in modules[bisect.bisect_left(starts, s):
                         bisect.bisect_right(starts, e)]:
            if m[1] <= e + 1_000_000 and (
                    best is None or m[1] - m[0] > best[1] - best[0]):
                best = (m[0], m[1])
        if best is not None:
            out[(s, e)] = best
    return out


def walk(compact, modules, steps, place):
    """The walk itself, on a compact trace and one plane's program
    executions.  `steps`: the step events to look inside."""
    ops = trace.line_of(compact["planes"][0], trace.OPS_LINE)
    if ops is None or not steps:
        return None
    names = compact["names"]
    scopes = compact.get("scopes") or [""] * len(names)
    by_slice = {(e["t_ns"], e["t_ns"] + e["dur_ns"]): e for e in steps}
    programs = programs_of(modules, list(by_slice))
    events = sorted(ops["events"], key=lambda ev: ev[1])
    starts = [ev[1] for ev in events]
    group_of, out = {}, []
    for sl, (a, b) in sorted(programs.items(), key=lambda kv: kv[1]):
        inside = [(s, min(s + d, b), i) for i, s, d in
                  events[bisect.bisect_left(starts, a):
                         bisect.bisect_left(starts, b)]]
        groups = {}
        for i, ns in trace.self_times(inside).items():
            if i not in group_of:
                group_of[i] = place(names[i], scopes[i])
            if group_of[i]:
                groups[group_of[i]] = groups.get(group_of[i], 0) + ns / 1e9
        out.append((by_slice[sl], (b - a) / 1e9, groups))
    return out


_MEMO = {}


def step_seconds(run, place):
    path = moe_trace.trace_path()
    key = (path, id(run), place)
    if key not in _MEMO:
        _MEMO.clear()
        timed = rowsview.prefill_steps(run)
        found = None
        if timed and path is not None:
            found = walk(compact_of(path), run["trace"]["modules"][0],
                         [e for e, _ in timed], place)
        _MEMO[key] = (run, found)
    return _MEMO[key][1]


def place_attention(name, scope):
    """"core" for the attention core (the Pallas kernel, a custom call the
    compiler names after its scope, `%attn.core.N`; XLA's own attention ops
    by the `attn.core` component of their `tf_op`), "gather" for the gather
    of a row's pages that XLA's attention reads its keys and values from
    (`kv.gather`; the kernel reads the pages themselves and has none)."""
    head = name.split(" = ", 1)[0]
    if head.startswith("%while"):
        return None
    parts = scope.split("/")
    if head.startswith("%attn.core") or "attn.core" in parts:
        return "core"
    if head.startswith("%kv.gather") or "kv.gather" in parts:
        return "gather"
    return None


def attention_seconds(run):
    """[(step event, program seconds, attention seconds)] over every prefill
    step of the window, attention = core + gather; None where nothing was
    placed (no trace, or a trace whose names and scopes tell no attention
    op from the rest)."""
    found = step_seconds(run, place_attention)
    if not found:
        return None
    out = [(e, prog, g.get("core", 0.0) + g.get("gather", 0.0))
           for e, prog, g in found]
    return out if any(a for _, _, a in out) else None

"""Reduction of a profiler trace to device busy time, the device operations
that took most time, and the idle gaps by what the host was doing.

`load_xplane` runs in a child with JAX_PLATFORMS=cpu (it needs jax to read
the .xplane.pb, and the benchmark's parent never imports jax); everything
else is pure Python on the compact form it returns, which is also the form
of the recorded trace under benchmark/tests/.

Compact form: {"clock": "mono_ns", "names": [...], "planes": [{"name",
"lines": [{"name", "events": [[name_index, start_ns, dur_ns], ...]}]}]} with
every start on the host's monotonic clock, in nanoseconds.

What a real v5e trace looks like (read by hand, PR 24; PERF.md section 3):
one plane per chip, "/device:TPU:<n>", with the lines "XLA Modules" (one
event per program execution: `jit_step(<fingerprint>)` for prefill and
decode steps, `jit_body(...)` for mixed steps), "XLA Ops" (every HLO op,
NESTED: a `while` over the layers holds its body's ops, depth up to 3; an
op's name is its whole HLO line), "Async XLA Ops" (copy-start/done) and two
empty ones.  Event times are relative to the `profile_start_time` stat (unix
ns) of the "Task Environment" plane.  The host plane carries JAX's Python
tracer, which is what makes the file hundreds of MB."""

import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(path, wall_minus_mono_ns, t0_ns, t1_ns):
    """Child-side.  Device planes only, events overlapping [t0, t1] (mono
    ns).  Event times in the file are relative to `profile_start_time`
    (unix ns, a stat of the "Task Environment" plane)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    base = None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            base = dict(plane.stats).get("profile_start_time")
    if base is None:
        raise RuntimeError("the trace has no profile_start_time")
    shift = base - wall_minus_mono_ns
    names, index, planes = [], {}, []
    summary = []
    for plane in pd.planes:
        lines = list(plane.lines)
        summary.append({"plane": plane.name, "lines": [
            ln.name for ln in lines][:12]})
        if not plane.name.startswith("/device:"):
            continue
        out_lines = []
        for ln in lines:
            evs = []
            for e in ln.events:
                s = int(e.start_ns) + shift
                d = int(e.duration_ns)
                if s + d < t0_ns or s > t1_ns:
                    continue
                i = index.get(e.name)
                if i is None:
                    i = index[e.name] = len(names)
                    names.append(e.name)
                evs.append([i, s, d])
            out_lines.append({"name": ln.name, "events": evs})
        planes.append({"name": plane.name, "lines": out_lines})
    return {"clock": "mono_ns", "names": names, "planes": planes,
            "summary": summary, "profile_start_unix_ns": base}


def load_in_child(ps, path, out_path, wall_minus_mono_ns, t0_ns, t1_ns):
    """Parent-side: run `load_xplane` in a child of the run's process owner
    (lib/procs.py `ProcSet`), held to the CPU backend."""
    rc, _, err = ps.run(
        [sys.executable, os.path.abspath(__file__), path, out_path,
         str(wall_minus_mono_ns), str(t0_ns), str(t1_ns)], "trace-loader",
        {"JAX_PLATFORMS": "cpu"}, timeout=900)
    if rc != 0:
        raise RuntimeError(f"trace reduction failed: {err[-1500:]}")
    with open(out_path) as f:
        return json.load(f)


def line_of(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln
    return None


def clipped(events, t0, t1):
    """[(start, end, name_index)] clipped to [t0, t1], sorted by start."""
    out = []
    for i, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b, i))
    out.sort()
    return out


def short_name(name, limit=72):
    """An op's name as the trace gives it is its whole HLO line; keep the
    result name and shape, drop layouts and operands."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:limit]
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    return f"{head} {shape}"[:limit]


def self_times(intervals):
    """Ops nest on the trace's op line (a `while` holds its body's ops): the
    time of each interval that none of its children covers, by name index.
    `intervals` are (start, end, name_index), sorted by start."""
    out, stack = {}, []  # stack of [end, name_index, self_ns]

    def close(top):
        out[top[1]] = out.get(top[1], 0) + max(0, top[2])

    for a, b, i in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, i, b - a])
    while stack:
        close(stack.pop())
    return out


def union(intervals):
    """Merged [(start, end)] of sorted (start, end, ...) intervals."""
    merged = []
    for iv in intervals:
        a, b = iv[0], iv[1]
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def idle_gaps(busy, t0, t1):
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def label_gap(gap, slices):
    """What the host was doing over the gap's midpoint: the kind of the step
    event (a host-clock slice) that spans it, else `between_steps`."""
    mid = (gap[0] + gap[1]) // 2
    for s, e, kind in slices:
        if s <= mid <= e:
            return kind
    return "between_steps"


def reduce(trace, t0_ns, t1_ns, step_events):
    """Device busy seconds (union of the op intervals inside the window,
    averaged over the device planes that ran anything), the top device ops,
    the idle time by host activity, and the program executions."""
    slices = sorted((e["t_ns"], e["t_ns"] + e["dur_ns"], e["kind"])
                    for e in step_events if e["dur_ns"] > 0)
    names = trace["names"]
    busy_s, op_time, gap_time, modules = [], {}, {}, []
    for plane in trace["planes"]:
        ops = line_of(plane, OPS_LINE) or line_of(plane, MODULES_LINE)
        if ops is None or not ops["events"]:
            continue
        ivs = clipped(ops["events"], t0_ns, t1_ns)
        if not ivs:
            continue
        busy = union(ivs)
        busy_s.append(sum(b - a for a, b in busy) / 1e9)
        for i, ns in self_times(ivs).items():
            label = short_name(names[i])
            op_time[label] = op_time.get(label, 0) + ns
        for gap in idle_gaps(busy, t0_ns, t1_ns):
            label = label_gap(gap, slices)
            gap_time[label] = gap_time.get(label, 0) + (gap[1] - gap[0])
        mods = line_of(plane, MODULES_LINE)
        if mods is not None:
            modules.append([(s, s + d, names[i])
                            for i, s, d in sorted(
                                mods["events"], key=lambda e: e[1])])
    if not busy_s:
        raise RuntimeError("no operation ran on a device inside the window")

    def top(table):
        return [[k, v / 1e9 / len(busy_s)] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:10]]

    return {"busy_s": sum(busy_s) / len(busy_s),
            "busy_s_per_chip": busy_s,
            "window_s": (t1_ns - t0_ns) / 1e9,
            "device_ops": top(op_time), "idle_gaps": top(gap_time),
            "modules": modules}


def program_time_in_slices(modules, slices):
    """For each host slice (start_ns, end_ns), the device seconds of the
    longest program execution that lies inside it (on the first device
    plane).  Slices with no execution inside are skipped."""
    if not modules:
        return []
    mods, out, j = modules[0], [], 0
    for s, e in sorted(slices):
        while j < len(mods) and mods[j][0] < s:
            j += 1
        k, best = j, 0
        while k < len(mods) and mods[k][0] <= e:
            if mods[k][1] <= e + 1_000_000:
                best = max(best, mods[k][1] - mods[k][0])
            k += 1
        if best:
            out.append(((s, e), best / 1e9))
    return out


if __name__ == "__main__":
    path, out_path, w, a, b = sys.argv[1:6]
    result = load_xplane(path, int(w), int(a), int(b))
    with open(out_path, "w") as f:
        json.dump(result, f)

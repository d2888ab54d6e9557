"""Reduction of a profiler trace to device busy time, the device operations
that took most time, and the idle gaps by what the host was doing.

`load_xplane` runs in a child with JAX_PLATFORMS=cpu (it needs jax to read
the .xplane.pb, and the benchmark's parent never imports jax); everything
else is pure Python on the compact form it returns, which is also the form
of the recorded trace under benchmark/tests/.

Compact form: {"clock": "mono_ns", "names": [...], "planes": [{"name",
"lines": [{"name", "events": [[name_index, start_ns, dur_ns], ...]}]}]} with
every start on the host's monotonic clock, in nanoseconds; and, where the
file gives them, "scopes": per name the op's `tf_op` (its path of named
scopes, `jit(prefill_step)/.../attn.core/dot_general`; "" where it has
none), which `jax.profiler.ProfileData` does not show and `op_scopes` reads
from the file's own bytes.

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
# step slices that hold a device program (dynamo_tpu/engine/engine.py)
STEP_KINDS = ("prefill_chunk", "decode_block", "mixed_step", "spec_round")


def _xspace_class():
    """A message class for the FEW fields of the profiler's `XSpace` that
    `op_scopes` reads (tsl/profiler/protobuf/xplane.proto: planes, their
    names, their event and stat metadata), built here from a descriptor so
    that nothing but `google.protobuf` is imported.  A plane's lines, which
    are nearly all of the file, are left undeclared and so are skipped, not
    parsed."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane",
        syntax="proto3")

    def message(name, *fields, into=fd.message_type):
        m = into.add(name=name)
        for fname, number, ftype, type_name, repeated in fields:
            m.field.add(name=fname, number=number, type=ftype,
                        type_name=type_name or None,
                        label=T.LABEL_REPEATED if repeated
                        else T.LABEL_OPTIONAL)
        return m

    def map_entry(owner, name, value_type):
        e = message(name, ("key", 1, T.TYPE_INT64, "", False),
                    ("value", 2, T.TYPE_MESSAGE, value_type, False),
                    into=owner.nested_type)
        e.options.map_entry = True

    P = ".bench_xplane."
    message("XStat", ("metadata_id", 1, T.TYPE_INT64, "", False),
            ("str_value", 5, T.TYPE_STRING, "", False),
            ("ref_value", 7, T.TYPE_UINT64, "", False))
    message("XStatMetadata", ("id", 1, T.TYPE_INT64, "", False),
            ("name", 2, T.TYPE_STRING, "", False))
    message("XEventMetadata", ("id", 1, T.TYPE_INT64, "", False),
            ("name", 2, T.TYPE_STRING, "", False),
            ("display_name", 4, T.TYPE_STRING, "", False),
            ("stats", 5, T.TYPE_MESSAGE, P + "XStat", True))
    plane = message(
        "XPlane", ("name", 2, T.TYPE_STRING, "", False),
        ("event_metadata", 4, T.TYPE_MESSAGE,
         P + "XPlane.EventMetadataEntry", True),
        ("stat_metadata", 5, T.TYPE_MESSAGE,
         P + "XPlane.StatMetadataEntry", True))
    map_entry(plane, "EventMetadataEntry", P + "XEventMetadata")
    map_entry(plane, "StatMetadataEntry", P + "XStatMetadata")
    message("XSpace", ("planes", 1, T.TYPE_MESSAGE, P + "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def op_scopes(path):
    """{op name: tf_op} over the device planes of an .xplane.pb: the `tf_op`
    stat of each event's METADATA (a string, or a reference to a stat
    metadata's name), under the metadata's name and its display name."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            for st in meta.stats:
                if stat_names.get(st.metadata_id) != "tf_op":
                    continue
                scope = st.str_value or stat_names.get(st.ref_value, "")
                for key in (meta.name, meta.display_name):
                    if key and scope:
                        out.setdefault(key, scope)
    return out


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
    out = {"clock": "mono_ns", "names": names, "planes": planes,
           "summary": summary, "profile_start_unix_ns": base}
    try:  # a file or a protobuf library that gives none: readers that need
        # scopes then place an op by its kernel name alone, and say so
        scopes = op_scopes(path)
        out["scopes"] = [scopes.get(n, "") for n in names]
        out["scopes_note"] = (f"{sum(1 for x in out['scopes'] if x)} of "
                              f"{len(names)} op names carry a tf_op")
    except Exception as e:
        out["scopes_note"] = f"no scopes: {type(e).__name__}: {e}"[:300]
    return out


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


def busy_line(plane):
    """The line busy time is read from: the ops, else the programs."""
    ops = line_of(plane, OPS_LINE)
    if ops is None or not ops["events"]:
        ops = line_of(plane, MODULES_LINE)
    return ops if ops is not None and ops["events"] else None


def captured_end(trace, t0_ns, t1_ns, step_events):
    """Where the device planes end, if the profiler stopped before the
    window did; else `t1_ns`.  The profiler keeps a bounded number of
    events (about 2.95 million ops: 23 s of a program that runs 3,800 ops a
    step), and a plane that ends there reads as an idle device from then
    on.  It is the line busy time is taken from that counts (the ops: the
    line of program executions, two events a step, goes on to the window's
    end).  A capture that ended is told from a device that really fell idle
    by the host's own record: a step slice that OPENED after the line's
    last event held a program the line does not show."""
    last = max((s + d for plane in trace["planes"]
                for _, s, d in (busy_line(plane) or {"events": ()})["events"]),
               default=None)
    if last is None or last >= t1_ns:
        return t1_ns
    later = [e for e in step_events
             if e["dur_ns"] > 0 and e["kind"] in STEP_KINDS
             and last < e["t_ns"] < t1_ns]
    return max(last, t0_ns + 1) if later else t1_ns


def reduce(trace, t0_ns, t1_ns, step_events):
    """Device busy seconds (union of the op intervals inside the window,
    averaged over the device planes that ran anything), the top device ops,
    the idle time by host activity, and the program executions.  Where the
    capture ended before the window (`captured_end`), all of it is taken
    over the span the planes hold, `window_s` is that span, and
    `capture_ended_early` says so."""
    asked_ns = t1_ns
    t1_ns = captured_end(trace, t0_ns, t1_ns, step_events)
    slices = sorted((e["t_ns"], e["t_ns"] + e["dur_ns"], e["kind"])
                    for e in step_events if e["dur_ns"] > 0)
    names = trace["names"]
    busy_s, op_time, gap_time, modules = [], {}, {}, []
    for plane in trace["planes"]:
        ops = busy_line(plane)
        if ops is None:
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
            "capture_ended_early": t1_ns < asked_ns,
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

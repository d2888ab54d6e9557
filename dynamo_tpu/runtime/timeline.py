"""Merge OTLP span files + engine step-event ring dumps into ONE
Chrome-trace / Perfetto JSON timeline.

Input surfaces:
- the OTLP/JSON line files `runtime.tracing.SpanFileExporter` writes
  (`DYN_OTEL_FILE` — every process appends to a shared file, or each to
  its own; both merge the same way), and
- `runtime.events.StepEventRecorder.dump()` payloads (the worker debug
  endpoint `/events.json`, or an in-process recorder).

Output: the Chrome Trace Event Format (the JSON flavor Perfetto and
chrome://tracing open directly) —
- one PROCESS per `service.name` (metadata `M` events name them),
- spans become complete (`X`) slices on the service's "requests" track,
  one thread per trace so concurrent requests don't stack,
- ring events become slices/instants on the service's "engine-steps"
  track (duration events carry their attrs — rung, batch, chain — in
  `args`),
- FLOW events (`s`/`f`) stitch a request across processes: every
  cross-service parent→child span edge gets a flow arrow keyed by
  trace_id, so one request reads as one connected line through
  frontend → router → worker even though each process exported
  independently.

Times: spans are wall-clock ns (OTLP); ring dumps are monotonic ns plus
a (wall_ns, mono_ns) anchor pair — `wall_ns - mono_ns` rebases them onto
the same axis.  Chrome traces want µs.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .events import HOST_EVENT_KINDS

# ring-event track id within each service's process
_RING_TID = 999
_HOST_TID = 998  # the ring's host events (runtime/events.py)


def load_otlp_spans(paths: Iterable[str]) -> List[dict]:
    """Flatten OTLP/JSON line files into span dicts tagged with their
    service name.  Tolerates torn/partial trailing lines (a killed
    process mid-write must not sink the whole merge)."""
    spans: List[dict] = []
    for path in paths:
        try:
            with open(path) as f:
                lines = f.read().splitlines()
        except OSError:
            continue
        for line in lines:
            if not line.strip():
                continue
            try:
                doc = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn write from a killed process
            for rs in doc.get("resourceSpans", []):
                service = "unknown"
                for attr in rs.get("resource", {}).get("attributes", []):
                    if attr.get("key") == "service.name":
                        service = attr["value"].get("stringValue", service)
                for sc in rs.get("scopeSpans", []):
                    for sp in sc.get("spans", []):
                        spans.append({**sp, "service": service})
    return spans


def _span_attrs(span: dict) -> Dict[str, str]:
    return {
        a["key"]: a.get("value", {}).get("stringValue", "")
        for a in span.get("attributes", [])
    }


def _flow_id(trace_id: str) -> int:
    # stable positive id from the hex trace id (Chrome flow ids are ints)
    return int(trace_id[:15] or "0", 16) if all(
        c in "0123456789abcdef" for c in trace_id[:15].lower()
    ) else abs(hash(trace_id)) % (1 << 60)


def spans_to_chrome(spans: List[dict]) -> Tuple[List[dict], Dict[str, int]]:
    """Spans → (chrome events, service→pid map).  Each trace gets its own
    tid within a service so overlapping requests render side by side."""
    events: List[dict] = []
    pids: Dict[str, int] = {}
    tids: Dict[Tuple[str, str], int] = {}

    def next_tid(key) -> int:
        if key not in tids:
            n = len(tids) + 1
            # never collide with the reserved ring tracks
            tids[key] = n if n < _HOST_TID else n + 2
        return tids[key]

    by_id: Dict[str, dict] = {s.get("spanId", ""): s for s in spans}
    for sp in spans:
        service = sp.get("service", "unknown")
        pid = pids.setdefault(service, len(pids) + 1)
        trace = sp.get("traceId", "")
        tid = next_tid((service, trace))
        start = int(sp.get("startTimeUnixNano", 0))
        end = int(sp.get("endTimeUnixNano", start))
        events.append({
            "name": sp.get("name", "?"),
            "ph": "X",
            "pid": pid,
            "tid": tid,
            "ts": start / 1e3,
            "dur": max(0.0, (end - start) / 1e3),
            "cat": "span",
            "args": {
                **_span_attrs(sp),
                "trace_id": trace,
                "span_id": sp.get("spanId", ""),
            },
        })
        # cross-process edge: the parent span was exported by a DIFFERENT
        # service — stitch with a flow arrow keyed by trace id
        parent = by_id.get(sp.get("parentSpanId", ""))
        if parent is not None and parent.get("service") != service:
            p_service = parent.get("service", "unknown")
            p_pid = pids.setdefault(p_service, len(pids) + 1)
            p_tid = next_tid((p_service, parent.get("traceId", "")))
            p_start = int(parent.get("startTimeUnixNano", 0))
            fid = _flow_id(trace)
            events.append({
                "name": "request", "ph": "s", "id": fid, "cat": "flow",
                "pid": p_pid, "tid": p_tid, "ts": p_start / 1e3,
            })
            events.append({
                "name": "request", "ph": "f", "bp": "e", "id": fid,
                "cat": "flow", "pid": pid, "tid": tid, "ts": start / 1e3,
            })
    return events, pids


def ring_to_chrome(dump: dict, service: str,
                   pids: Dict[str, int]) -> List[dict]:
    """One StepEventRecorder dump → chrome events on the service's
    engine-steps track (duration events as `X` slices, instants as `i`),
    rebased from monotonic to the spans' wall-clock axis."""
    offset_ns = dump.get("wall_ns", 0) - dump.get("mono_ns", 0)
    pid = pids.setdefault(service, len(pids) + 1)
    events: List[dict] = []
    for ev in dump.get("events", []):
        ts = (ev.get("t_ns", 0) + offset_ns) / 1e3
        dur = ev.get("dur_ns", 0) / 1e3
        args = {k: v for k, v in ev.items()
                if k not in ("t_ns", "dur_ns", "kind")}
        kind = ev.get("kind", "?")
        # the host's rare, long events overlap the steps they fell into
        # (and the start-up slices are older than the ring): their own
        # track, so the step track's slices stay nested
        base = {
            "name": kind, "pid": pid,
            "tid": _HOST_TID if kind in HOST_EVENT_KINDS else _RING_TID,
            "ts": ts, "cat": "engine", "args": args,
        }
        if dur > 0:
            events.append({**base, "ph": "X", "dur": dur})
        else:
            events.append({**base, "ph": "i", "s": "t"})
    return events


def counters_to_chrome(samples: Iterable[dict], service: str,
                       pids: Dict[str, int]) -> List[dict]:
    """Fleet telemetry snapshots → Perfetto COUNTER tracks (`ph: "C"`)
    on the service's process: each sample is ``{"ts": wall_seconds,
    "values": {name: number}}`` (FleetTelemetryWatcher.counter_samples()
    emits exactly this), and each named value renders as its own counter
    track — so a goodput dip lines up visually with the rung/host-gap
    slices that explain it."""
    pid = pids.setdefault(service, len(pids) + 1)
    events: List[dict] = []
    for sample in samples:
        ts_us = float(sample.get("ts", 0)) * 1e6
        for name, value in (sample.get("values") or {}).items():
            if not isinstance(value, (int, float)):
                continue
            events.append({
                "name": name, "ph": "C", "pid": pid, "tid": 0,
                "ts": ts_us, "cat": "telemetry",
                "args": {"value": float(value)},
            })
    return events


def _metadata(pids: Dict[str, int], ring_services: Iterable[str]) -> List[dict]:
    out = []
    for service, pid in pids.items():
        out.append({"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                    "args": {"name": service}})
        if service in set(ring_services):
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": _RING_TID, "args": {"name": "engine-steps"}})
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": _HOST_TID, "args": {"name": "host-events"}})
    return out


def merge_timeline(otlp_paths: Iterable[str],
                   ring_dumps: Optional[Dict[str, dict]] = None,
                   out_path: Optional[str] = None,
                   counter_dumps: Optional[Dict[str, List[dict]]] = None
                   ) -> dict:
    """Build the merged Chrome-trace document; write it when `out_path`
    is given.  `ring_dumps` maps service name → recorder dump;
    `counter_dumps` maps service name → telemetry counter samples
    (counters_to_chrome input)."""
    spans = load_otlp_spans(otlp_paths)
    events, pids = spans_to_chrome(spans)
    ring_dumps = ring_dumps or {}
    for service, dump in ring_dumps.items():
        events.extend(ring_to_chrome(dump, service, pids))
    for service, samples in (counter_dumps or {}).items():
        events.extend(counters_to_chrome(samples, service, pids))
    doc = {
        "traceEvents": _metadata(pids, ring_dumps) + events,
        "displayTimeUnit": "ms",
        "otherData": {
            "generator": "dynamo_tpu.runtime.timeline",
            "spans": len(spans),
            "services": sorted(pids),
            "traces": len({s.get("traceId") for s in spans}),
        },
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(doc, f)
    return doc


def validate_chrome_trace(doc: Any) -> List[str]:
    """Schema check against the Chrome Trace Event Format (the subset
    this module emits); returns a list of violations (empty = valid).
    Tests and the drivers gate the merged artifact on this so a malformed
    timeline fails loudly instead of silently refusing to load in
    Perfetto."""
    errors: List[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["document must be an object with a traceEvents array"]
    if not isinstance(doc["traceEvents"], list):
        return ["traceEvents must be an array"]
    for i, ev in enumerate(doc["traceEvents"]):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if not isinstance(ev.get("name"), str):
            errors.append(f"{where}: missing name")
        if ph not in ("X", "B", "E", "i", "s", "f", "t", "M", "C"):
            errors.append(f"{where}: unknown ph {ph!r}")
            continue
        if ph != "M":
            if not isinstance(ev.get("ts"), (int, float)):
                errors.append(f"{where}: missing numeric ts")
        for key in ("pid", "tid"):
            if not isinstance(ev.get(key), int):
                errors.append(f"{where}: missing integer {key}")
        if ph == "X" and not isinstance(ev.get("dur"), (int, float)):
            errors.append(f"{where}: X event missing dur")
        if ph in ("s", "f", "t") and "id" not in ev:
            errors.append(f"{where}: flow event missing id")
        if ph == "f" and ev.get("bp") not in ("e", None):
            errors.append(f"{where}: f event bad bp")
    return errors


def decode_host_gaps(dump: dict, continuous_only: bool = False) -> dict:
    """Inter-block HOST gap derived from a StepEventRecorder dump's
    `decode_block` slices: for consecutive slices ordered by start time,
    gap = start[k+1] - end[k], clamped at zero when the next dispatch
    was issued before the previous slice closed (the async-drain overlap
    the device-resident decode loop exists to create).

    This is the ROADMAP's "host gap between consecutive decode blocks"
    measurement (target < 0.1 ms on-chip): the continuous engine records
    one `decode_block` slice per loop iteration (dispatch + drain
    handoff + fall-out checks), so the gaps are exactly the host time
    the device could have been waiting on Python.  Gaps that span chain
    boundaries (planning, array building) are included — they are the
    host-in-the-loop cost the open-ended chain amortizes away.

    Splice iterations (a prefill chunk fed / a request spliced into the
    running chain — the engine tags those slices `splice=True`) do
    intentional host work before their dispatch, so the gap LEADING
    INTO a tagged slice is the splice handshake, not an idle stall:
    those gaps are split out as `splice_n`/`splice_p50_ms`/
    `splice_p99_ms`/`splice_max_ms`, and the headline p50/p99/max cover
    only true host gaps.

    Returns {"n", "p50_ms", "p99_ms", "max_ms", "splice_n",
    "splice_p50_ms", "splice_p99_ms", "splice_max_ms"} (Nones when the
    corresponding gap set is empty).  `continuous_only` restricts to
    blocks the continuous loop dispatched."""
    evs = [e for e in dump.get("events", [])
           if e.get("kind") == "decode_block"
           and (not continuous_only or e.get("continuous"))]
    evs.sort(key=lambda e: e.get("t_ns", 0))
    plain = []
    splice = []
    for a, b in zip(evs, evs[1:]):
        gap = max(0, b.get("t_ns", 0)
                  - (a.get("t_ns", 0) + a.get("dur_ns", 0))) / 1e6
        # the LATER slice owns the gap before it: its pre-dispatch
        # host work (splice intake, chunk planning) is what filled it
        (splice if b.get("splice") else plain).append(gap)
    plain.sort()
    splice.sort()

    def stats(gaps, prefix=""):
        if not gaps:
            return {f"{prefix}n": 0, f"{prefix}p50_ms": None,
                    f"{prefix}p99_ms": None, f"{prefix}max_ms": None}
        return {
            f"{prefix}n": len(gaps),
            f"{prefix}p50_ms": round(gaps[int(0.50 * (len(gaps) - 1))], 4),
            f"{prefix}p99_ms": round(gaps[int(0.99 * (len(gaps) - 1))], 4),
            f"{prefix}max_ms": round(gaps[-1], 4),
        }

    return {**stats(plain), **stats(splice, "splice_")}


def trace_graph(spans: List[dict]) -> Dict[str, dict]:
    """Per-trace connectivity summary used by tests and trace_stack's
    summary line: {trace_id: {spans, services, roots, orphans}}.
    An ORPHAN is a span whose parentSpanId references no exported span —
    exactly the bug class (un-propagated headers, dropped exports) the
    cross-process join tests exist to catch."""
    by_trace: Dict[str, List[dict]] = {}
    for sp in spans:
        by_trace.setdefault(sp.get("traceId", ""), []).append(sp)
    out: Dict[str, dict] = {}
    for trace, group in by_trace.items():
        ids = {sp.get("spanId") for sp in group}
        roots = [sp for sp in group if not sp.get("parentSpanId")]
        orphans = [
            sp["name"] for sp in group
            if sp.get("parentSpanId") and sp["parentSpanId"] not in ids
        ]
        out[trace] = {
            "spans": len(group),
            "services": sorted({sp.get("service", "?") for sp in group}),
            "roots": len(roots),
            "orphans": orphans,
        }
    return out

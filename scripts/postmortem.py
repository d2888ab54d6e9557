#!/usr/bin/env python
"""Black-box postmortem for a dead process tree.

    python scripts/postmortem.py --dir DUMP_DIR [--out-dir DIR] [--last-s 5]

Ingests whatever a crashed/SIGKILLed stack left behind under ``--dir``
(searched recursively):

- flight-recorder segments (``flight-<pid>-<seq>.seg``, the mmap spill
  ``DYN_TPU_FLIGHT_DIR`` arms in ``runtime/events.py``) — the step-event
  black box that survives SIGKILL; torn final records parse as a clean
  prefix;
- OTLP/JSON span files (``*.jsonl``, the ``DYN_OTEL_FILE`` sink,
  rotated generations included) — torn trailing lines are skipped;
- leak/lock-ledger dumps (``lockcheck-*.json`` and friends).

Emits a merged Chrome-trace/Perfetto timeline (``postmortem_timeline
.json``), a textual "last N seconds" report (``postmortem_report.txt`` +
stdout), and ONE summary JSON line on stdout (exit 0 iff something was
recovered and the timeline validates).  Import-safe next to
``scripts/_verify_harness.py``: ``from postmortem import run`` — the
tier-1 smoke test and the chaos scenario-1 rider both embed it.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from dynamo_tpu.runtime.events import load_flight_dir  # noqa: E402
from dynamo_tpu.runtime.timeline import (  # noqa: E402
    load_otlp_spans,
    merge_timeline,
    validate_chrome_trace,
)


def collect(dump_dir):
    """Walk the dump tree; return (ring_dumps, span_paths, ledgers).

    ring_dumps maps "service:pid" -> ring-dump-shaped dict (the
    merge_timeline input); span_paths are OTLP jsonl files; ledgers maps
    filename -> parsed ledger dump."""
    ring_dumps = {}
    span_paths = []
    ledgers = {}
    for root, _dirs, files in os.walk(dump_dir):
        if any(f.startswith("flight-") and f.endswith(".seg")
               for f in files):
            for dump in load_flight_dir(root):
                key = f"{dump['service']}:{dump['pid']}"
                ring_dumps[key] = dump
        for f in files:
            path = os.path.join(root, f)
            if f.endswith(".jsonl"):
                span_paths.append(path)
            elif f.endswith(".json") and ("ledger" in f or "check" in f):
                try:
                    with open(path) as fh:
                        ledgers[f] = json.load(fh)
                except (OSError, ValueError):
                    ledgers[f] = {"error": "unreadable"}
    return ring_dumps, sorted(span_paths), ledgers


def _fmt_attrs(ev):
    skip = ("t_ns", "dur_ns", "kind")
    parts = [f"{k}={v}" for k, v in ev.items() if k not in skip]
    return " ".join(parts)


def last_seconds_report(ring_dumps, spans, last_s=5.0, max_lines=40):
    """Textual "what was everyone doing at the end" report.

    Event times rebase monotonic -> wall via each dump's anchor pair;
    the window is [t_end - last_s, t_end] where t_end is the latest
    event/span timestamp seen anywhere in the dump tree."""
    rows = []  # (wall_end_ns, source, line)
    for key, dump in ring_dumps.items():
        offset = dump.get("wall_ns", 0) - dump.get("mono_ns", 0)
        for ev in dump.get("events", []):
            end = ev.get("t_ns", 0) + ev.get("dur_ns", 0) + offset
            rows.append((end, key, ev))
    span_rows = []
    for sp in spans:
        try:
            end = int(sp.get("endTimeUnixNano", 0))
        except (TypeError, ValueError):
            continue
        span_rows.append((end, sp.get("service", "?"), sp))
    all_ends = [r[0] for r in rows] + [r[0] for r in span_rows]
    if not all_ends:
        return "postmortem: nothing recovered (no events, no spans)\n", 0
    t_end = max(all_ends)
    lo = t_end - int(last_s * 1e9)
    lines = [f"== last {last_s:g}s before the end "
             f"(t_end = {t_end} wall ns) =="]
    in_window = [(e, k, ev) for e, k, ev in rows if e >= lo]
    for key in sorted(ring_dumps):
        mine = [(e, ev) for e, k, ev in in_window if k == key]
        kinds = {}
        for _e, ev in mine:
            kinds[ev.get("kind", "?")] = kinds.get(ev.get("kind", "?"),
                                                   0) + 1
        summary = ", ".join(f"{k}×{n}" for k, n in sorted(kinds.items()))
        lines.append(f"-- {key}: {len(mine)} event(s) "
                     f"[{summary or 'silent'}]")
        for e, ev in sorted(mine, key=lambda r: r[0])[-max_lines:]:
            dt = (e - t_end) / 1e9
            dur = ev.get("dur_ns", 0) / 1e6
            lines.append(
                f"   {dt:+9.3f}s {ev.get('kind', '?'):<16}"
                + (f" dur={dur:.3f}ms" if dur else "          ")
                + ("  " + _fmt_attrs(ev) if _fmt_attrs(ev) else ""))
    sp_window = [(e, s, sp) for e, s, sp in span_rows if e >= lo]
    if sp_window:
        lines.append(f"-- spans in window: {len(sp_window)}")
        for e, service, sp in sorted(
                sp_window, key=lambda r: r[:2])[-max_lines:]:
            dt = (e - t_end) / 1e9
            lines.append(f"   {dt:+9.3f}s [{service}] "
                         f"{sp.get('name', '?')} "
                         f"trace={sp.get('traceId', '')[:16]}")
    return "\n".join(lines) + "\n", len(in_window)


def run(dump_dir, out_dir=None, last_s=5.0):
    """Full postmortem over `dump_dir`; returns (summary, report_text).

    summary is the one-line JSON payload; ok=True iff at least one
    flight segment OR span file was recovered and the merged timeline
    validates against the Chrome-trace schema."""
    out_dir = out_dir or dump_dir
    os.makedirs(out_dir, exist_ok=True)
    ring_dumps, span_paths, ledgers = collect(dump_dir)
    spans = load_otlp_spans(span_paths)
    timeline_path = os.path.join(out_dir, "postmortem_timeline.json")
    doc = merge_timeline(span_paths, ring_dumps=ring_dumps,
                         out_path=timeline_path)
    violations = validate_chrome_trace(doc)
    report, window_events = last_seconds_report(ring_dumps, spans,
                                                last_s=last_s)
    ledger_issues = 0
    for name, led in ledgers.items():
        if isinstance(led, dict):
            for key in ("cycles", "self_deadlocks", "affinity_violations",
                        "orphans", "swallowed", "imbalance"):
                v = led.get(key)
                if isinstance(v, list):
                    ledger_issues += len(v)
        report += f"-- ledger {name}: {json.dumps(led)[:400]}\n"
    report_path = os.path.join(out_dir, "postmortem_report.txt")
    with open(report_path, "w") as f:
        f.write(report)
    total_events = sum(len(d.get("events", [])) for d in ring_dumps.values())
    summary = {
        "ok": bool((ring_dumps or spans) and not violations),
        "processes": len(ring_dumps),
        "flight_events": total_events,
        "window_events": window_events,
        "spans": len(spans),
        "ledgers": len(ledgers),
        "ledger_issues": ledger_issues,
        "timeline_violations": len(violations),
        "timeline": timeline_path,
        "report": report_path,
    }
    return summary, report


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", required=True,
                    help="dump directory of the dead process tree")
    ap.add_argument("--out-dir", default="",
                    help="artifact directory (default: --dir)")
    ap.add_argument("--last-s", type=float, default=5.0,
                    help="tail window for the textual report")
    args = ap.parse_args(argv)
    summary, report = run(args.dir, out_dir=args.out_dir or None,
                          last_s=args.last_s)
    sys.stdout.write(report)
    print(json.dumps(summary), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

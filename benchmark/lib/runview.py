"""What a per-layer metric's `read(run)` is given, and small helpers on it.

run = {
  "t0", "t1":        the measured window on the host's monotonic clock (s)
  "records":         client records of every request the loop sent
  "warmup":          the loop's account of its warm-up
  "metrics0/1":      the worker's /metrics.json at the window's start / end
  "events":          step events polled from /events.json ("t_ns" and
                     "dur_ns" on the same monotonic clock, "ring" added)
  "events_dropped":  events lost to ring wrap between polls
  "trace":           lib.trace.reduce(...) of the profiler trace, or None
  "config", "mix":   the cell's configuration and traffic files
  "peaks":           the chip's published peaks
}
A reader that finds nothing to read returns None and the metric is left out
of the line."""

from . import trace


def window_events(run, *kinds):
    """Step events of the given kinds that COMMITTED inside the window."""
    a, b = run["t0"] * 1e9, run["t1"] * 1e9
    return [e for e in run["events"]
            if e["kind"] in kinds and a <= e["t_ns"] + e["dur_ns"] <= b]


def prefill_steps(run):
    """[(step event, device seconds)] for the window's `prefill_chunk` steps
    of one sequence: the longest program execution inside the step's host
    slice (the slice opens before the host builds the step's inputs and
    closes after the result fetch: engine/engine.py `_run_prefill`).  Empty
    without a trace."""
    if run["trace"] is None:
        return []
    chunks = {(e["t_ns"], e["t_ns"] + e["dur_ns"]): e
              for e in window_events(run, "prefill_chunk")
              if e["batch"] == 1 and e["tokens"] > 0}
    return [(chunks[sl], secs) for sl, secs in trace.program_time_in_slices(
        run["trace"]["modules"], list(chunks))]

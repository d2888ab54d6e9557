"""The window's `prefill_chunk` steps by ROWS: every slice whatever its
`batch`, where `lib/runview.py` `prefill_steps` keeps the steps of one
sequence.  Since PR 36 a step may hold several sequences' short chunks
(`batch` 2-4, `tokens` summed over them: engine/scheduler.py
`_plan_prefill`), and a reader that keeps `batch == 1` no longer sees any
short step of a window.  The readers over this list read the shared program
from the first line it appears in; on a program that runs one sequence a
step they read what the `batch == 1` readers read."""

from . import runview, trace


def prefill_steps(run):
    """[(step event, device seconds)] for EVERY `prefill_chunk` step of the
    window: the longest program execution inside the step's host slice, as
    `runview.prefill_steps` takes it.  Empty without a trace."""
    if run["trace"] is None:
        return []
    chunks = {(e["t_ns"], e["t_ns"] + e["dur_ns"]): e
              for e in runview.window_events(run, "prefill_chunk")
              if e["tokens"] > 0}
    return [(chunks[sl], secs) for sl, secs in trace.program_time_in_slices(
        run["trace"]["modules"], list(chunks))]

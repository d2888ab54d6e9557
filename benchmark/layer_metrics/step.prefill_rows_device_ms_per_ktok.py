"""Model step: device milliseconds of the prefill program per thousand prompt
tokens it computed, over EVERY `prefill_chunk` step of the window, the steps
that several sequences share among them (`lib/rowsview.py`): the longest
program execution inside each step's host slice (profiler trace) over the
slice's `tokens` (summed over its rows).  `step.prefill_device_ms_per_ktok`
keeps the steps of one sequence, which since PR 36 are the 128-512-token
ones alone; this one reads the whole window on both sides of that change.
ms/ktok."""

from lib import rowsview


def read(run):
    timed = rowsview.prefill_steps(run)
    tokens = sum(e["tokens"] for e, _ in timed)
    if not tokens:
        return None
    return sum(secs for _, secs in timed) * 1e3 / tokens * 1000.0

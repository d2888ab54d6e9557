"""Model step: device milliseconds of the prefill program per thousand prompt
tokens it computed, over the window's `prefill_chunk` steps: the longest
program execution inside each step's host slice (profiler trace) over the
slice's `tokens` (step event).  Device time, so the profiler's slowing of
the host does not enter.  ms/ktok."""

from lib import runview


def read(run):
    timed = runview.prefill_steps(run)
    tokens = sum(e["tokens"] for e, _ in timed)
    if not tokens:
        return None
    return sum(secs for _, secs in timed) * 1e3 / tokens * 1000.0

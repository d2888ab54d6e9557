"""Engine: the median `ctx` (tokens of context the step's longest row sees,
its chunk among them) over the window's `prefill_chunk` steps: at what
context the line's other numbers were taken.  None where no step carries
the attribute.  tokens."""

import statistics

from lib import runview


def read(run):
    ctx = [e["ctx"] for e in runview.window_events(run, "prefill_chunk")
           if "ctx" in e]
    return float(statistics.median(ctx)) if ctx else None

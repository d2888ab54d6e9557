"""Engine: rows that ran the cross half of a decoder-hybrid-decoder's layers
over prompt tokens computed: over the window's `prefill_chunk` events, the
sum of `cross_rows` (the step's rows where any of them samples, else 0) over
the sum of `tokens`.  One row a request of thousands of tokens reads a
fraction of a percent; 100 would be a program that runs the cross half at
every position.  None on a program whose events carry no `cross_rows`.  %."""

from lib import runview


def read(run):
    steps = [e for e in runview.window_events(run, "prefill_chunk")
             if "cross_rows" in e]
    tokens = sum(e["tokens"] for e in steps)
    if not tokens:
        return None
    return 100.0 * sum(e["cross_rows"] for e in steps) / tokens

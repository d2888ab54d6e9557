"""Model step: share of the prefill programs' device time that the cross
half of a decoder-hybrid-decoder's layers takes: self time of the device ops
under the scope `cross` (gated memory units, cross-attention over the full
layer's pages and their feed-forwards, on the rows' last positions: how an op
is placed: lib/cross_trace.py) over the device time of the prefill program,
summed over EVERY `prefill_chunk` step of the window.  About 40% if every
token ran it; a few % where only the steps in which a row samples do, on one
position a row.  None on a program without that scope.  %."""

from lib import cross_trace


def read(run):
    return cross_trace.share_pct(run, "cross")

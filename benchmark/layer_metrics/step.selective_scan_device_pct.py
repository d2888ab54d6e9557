"""Model step: share of the prefill programs' device time that the Mamba-1
selective scans take: self time of the device ops under the scopes
`ssm.conv`, `ssm.scan` and `ssm.gate` of the self half (how an op is placed:
lib/cross_trace.py) over the device time of the prefill program, summed over
EVERY `prefill_chunk` step of the window.  None on a program without those
scopes.  %."""

from lib import cross_trace


def read(run):
    return cross_trace.share_pct(run, "scan")

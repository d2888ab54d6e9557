"""Model step: share of the prefill programs' device time that the gated
short convolutions take: self time of the device ops under the scopes
`sconv.in_proj`, `sconv.conv`, `sconv.out_proj`, `state.read` and
`state.write` (how an op is placed: lib/sconv_trace.py) over the device time
of the prefill program, summed over EVERY `prefill_chunk` step of the window.
None on a program without those scopes.  %."""

from lib import sconv_trace


def read(run):
    found = sconv_trace.prefill_seconds(run)
    if found is None:
        return None
    return 100.0 * sum(s for _, _, s in found) / sum(p for _, p, _ in found)

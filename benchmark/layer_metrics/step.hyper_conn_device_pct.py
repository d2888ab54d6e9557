"""Model step: share of the prefill programs' device time that the stream
mixers of hyper-connections take: self time of the device ops of `hc.mix`
(the norm, the product with the mixer's matrix, the sigmoids, the Sinkhorn
steps), `hc.pre`, `hc.post` and `hc.head` (how an op is placed, and to whom
a fusion across the boundary is counted: lib/hc_trace.py) over the device
time of the prefill program, summed over EVERY `prefill_chunk` step of the
window.  %."""

from lib import hc_trace


def read(run):
    found = hc_trace.prefill_mixer_seconds(run)
    if found is None or not found[0]:
        return None
    program_s, mixer_s, _ = found
    return 100.0 * mixer_s / program_s

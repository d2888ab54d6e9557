"""Kernels: the least time the stream mixers' traffic of the window's
prefill steps can take on this chip (the family's count,
`hyper_conn_floor_s` of benchmark/roofline/<family>.py: per token and half
of a layer the residual's streams read once and written once, the half's
input written and its output read, over the HBM peak) over the device time
of the mixers' ops (lib/hc_trace.py), summed over EVERY `prefill_chunk`
step of the window.  No kernel is behind it yet: plain XLA ops, and the
share says what a fused one could gain.  %."""

from lib import hc_trace, roofline


def read(run):
    found = hc_trace.prefill_mixer_seconds(run)
    if found is None:
        return None
    _, mixer_s, timed = found
    floor_s = getattr(roofline.family(run["config"]), "hyper_conn_floor_s",
                      None)
    if floor_s is None or not mixer_s:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    return 100.0 * sum(floor_s(model, peaks, e["tokens"])[0]
                       for e, _ in timed) / mixer_s

"""Kernels: the least time the short convolutions' mixers of the window's
prefill steps can take on this chip (the family's count,
`short_conv_floor_s(model, peaks, tokens)` of benchmark/roofline/<family>.py:
a conv layer's two projections read once over the HBM peak, or two operations
a parameter a token over the bf16 peak; the larger, over the conv layers) over
the device time `step.short_conv_device_pct` counts (lib/sconv_trace.py: the
mixers and the windows moved from and to the slots, so the two cannot
disagree about which ops count), summed over EVERY `prefill_chunk` step of
the window, a shared step as one step of all its rows' tokens.  The mixers
are XLA's fusions (no kernel); the share says what the gates, the taps and
the windows cost beside the two products.  None for a family whose roofline
file has no such function, or on a program without those scopes.  %."""

from lib import roofline, sconv_trace


def read(run):
    found = sconv_trace.prefill_seconds(run)
    floor_s = getattr(roofline.family(run["config"]), "short_conv_floor_s",
                      None)
    if found is None or floor_s is None:
        return None
    measured = sum(s for _, _, s in found)
    if not measured:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    return 100.0 * sum(floor_s(model, peaks, e["tokens"])[0]
                       for e, _, _ in found) / measured

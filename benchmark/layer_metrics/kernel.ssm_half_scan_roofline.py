"""Kernels: the least time the convolution, the scan and the gated norm of
the window's prefill steps can take on this chip (the family's count,
`ssm_scan_floor_s` of benchmark/roofline/falcon_h1.py: per token and layer
the scan's inputs read and its output written once, per row and layer the
carried state read and written once, over the HBM peak, or the recurrence's
own operations over the bf16 peak; the larger) over the device time of the
ops under `ssm.conv`, `ssm.scan` and `ssm.gate_norm` (lib/halves_trace.py),
summed over EVERY `prefill_chunk` step of the window.  No kernel is behind
it: plain XLA ops of the blocked scan `kernel.ssm_scan_roofline` reads in
nemotron_h's cell, here at 128-wide heads and 256 states, and the share says
what a fused one could gain.  None for another family.  %."""

from lib import halves_trace, roofline


def read(run):
    found = halves_trace.prefill_seconds(run)
    floor_s = getattr(roofline.family(run["config"]), "ssm_scan_floor_s",
                      None)
    if found is None or floor_s is None:
        return None
    measured = sum(g.get("ssm.scan", 0.0) for _, _, g in found)
    if not measured:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    return 100.0 * sum(floor_s(model, peaks, e["tokens"], e["batch"])[0]
                       for e, _, _ in found) / measured

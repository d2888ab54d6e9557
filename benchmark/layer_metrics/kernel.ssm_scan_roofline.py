"""Kernels: the least time the convolution, the scan and the gated norm of
the window's prefill steps can take on this chip (the family's count,
`ssm_scan_floor_s` of benchmark/roofline/<family>.py: per token and layer the
scan's inputs read and its output written once, per row and layer the carried
state read and written once, over the HBM peak, or the recurrence's own
operations over the bf16 peak; the larger) over the device time of the ops
under `ssm.conv`, `ssm.scan` and `ssm.gate_norm` (lib/ssm_trace.py), summed
over EVERY `prefill_chunk` step of the window.  No kernel is behind it yet:
plain XLA ops of a chunked scan, and the share says what a fused one could
gain.  %."""

from lib import roofline, ssm_trace


def read(run):
    found = ssm_trace.prefill_seconds(run)
    if found is None:
        return None
    _, by_kind, timed = found
    floor_s = getattr(roofline.family(run["config"]), "ssm_scan_floor_s",
                      None)
    if floor_s is None or not by_kind["ssm.scan"]:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    return 100.0 * sum(floor_s(model, peaks, e["tokens"], e["batch"])[0]
                       for e, _ in timed) / by_kind["ssm.scan"]

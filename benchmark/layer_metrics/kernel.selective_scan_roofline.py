"""Kernels: the least time the convolution, the selective scan and the gate
of the window's prefill steps can take on this chip (the family's count,
`selective_scan_floor_s(model, peaks, tokens, rows)` of benchmark/roofline/
<family>.py: per token and layer the scan's inputs read and its output
written once, per row and layer the carried state read and written once, over
the HBM peak, or the recurrence's own operations over the bf16 peak; the
larger) over the device time of the ops under `ssm.conv`, `ssm.scan` and
`ssm.gate` (lib/cross_trace.py), summed over EVERY `prefill_chunk` step of
the window.  Since PR 48 the scan itself is one Pallas kernel a layer and
chunk (`ops/pallas_ssm.py`, placed by its kernel's name); the projections to
the step size, B and C, the convolution and the gate around it are XLA's,
and the share says what fusing them into it could gain.  None for a family
whose roofline file has no such function, or on a program without those
scopes.  %."""

from lib import cross_trace, roofline


def read(run):
    found = cross_trace.prefill_seconds(run)
    floor_s = getattr(roofline.family(run["config"]),
                      "selective_scan_floor_s", None)
    if found is None or floor_s is None:
        return None
    measured = sum(g.get("scan", 0.0) for _, _, g in found)
    if not measured:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    return 100.0 * sum(floor_s(model, peaks, e["tokens"], e["batch"])[0]
                       for e, _, _ in found) / measured

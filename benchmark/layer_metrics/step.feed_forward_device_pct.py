"""Model step: share of the prefill programs' device time that the dense
feed-forward of a layer of both mixers takes (falcon_h1): self time of the
device ops under the scope `mlp` (how an op is placed: lib/halves_trace.py)
over the device time of the prefill program, summed over EVERY
`prefill_chunk` step of the window: what `step.ssm_half_device_pct` and
`step.attn_device_pct` are read against.  None for another family.  %."""

from lib import halves_trace


def read(run):
    found = halves_trace.prefill_seconds(run)
    if found is None:
        return None
    return 100.0 * sum(g.get("mlp", 0.0) for _, _, g in found) / sum(
        prog for _, prog, _ in found)

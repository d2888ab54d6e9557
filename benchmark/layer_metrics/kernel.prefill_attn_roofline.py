"""Kernels: the least time attention over the context can take in the
window's one-sequence `prefill_chunk` steps (the family's count,
`prefill_attn_floor_s(model, peaks, tokens, ctx)` of benchmark/roofline/
<family>.py: the operations of the keys each token of the chunk can SEE, or
the visible keys and values read once a step and layer, the larger; `tokens`
and `ctx` are the step event's own) over the device time of the ops
`step.attn_device_pct` counts (lib/opwalk.py: core and gather together, so
the two cannot disagree about which ops count) in those same steps.  A step
that several sequences share says one `ctx`, its longest row's, so its floor
is unknown and it stays out of both sums.  None for a family whose roofline
file has no such function.  %."""

from lib import opwalk, roofline


def read(run):
    found = opwalk.attention_seconds(run)
    floor_s = getattr(roofline.family(run["config"]),
                      "prefill_attn_floor_s", None)
    if found is None or floor_s is None:
        return None
    steps = [(e, a) for e, _, a in found if e["batch"] == 1 and "ctx" in e]
    measured = sum(a for _, a in steps)
    if not measured:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    floor = sum(floor_s(model, peaks, e["tokens"], e["ctx"])[0]
                for e, _ in steps)
    return 100.0 * floor / measured

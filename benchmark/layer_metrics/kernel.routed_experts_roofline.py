"""Kernels: the least time the routed experts' matmuls of the window's
prefill steps can take on this chip (the family's count,
`routed_experts_floor_s` of benchmark/roofline/<family>.py: the larger of
the weights of the experts a step really TOUCHED, `experts_hit` of its step
event, once over the HBM peak, and two operations a weight for each of its
`moe_assignments` over the bf16 peak) over the device time under the scope
`moe.experts` (lib/moe_scopes.py), summed over the window's `prefill_chunk`
steps that carry both counters.  Whichever form runs: the all-experts matmul
reads every expert and multiplies every token by it, the dispatched form may
copy the stacks; both are charged what was routed.  %."""

from lib import moe_scopes, roofline


def read(run):
    found = moe_scopes.seconds(run)
    floor_s = getattr(roofline.family(run["config"]),
                      "routed_experts_floor_s", None)
    if found is None or floor_s is None:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    steps = [(e, g.get("experts", 0.0)) for e, _, g in found
             if "experts_hit" in e and "moe_assignments" in e]
    device = sum(secs for _, secs in steps)
    if not device:
        return None
    floor = sum(floor_s(model, peaks, e["moe_assignments"],
                        e["experts_hit"])[0] for e, _ in steps)
    return 100.0 * floor / device

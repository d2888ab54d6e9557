"""Kernels: the least time the held experts' matmuls of the window's
prefill steps can take on this chip (the family's count, `experts_floor_s`
of benchmark/roofline/<family>.py: the weights of the held experts each
step really touched, `experts_hit` of its step event, once over the HBM
peak) over the device time of the held experts' matmuls
(lib/latent_trace.py), summed over the window's `prefill_chunk` steps.
%."""

from lib import latent_trace, roofline


def read(run):
    found = latent_trace.prefill_group_seconds(run)
    if found is None:
        return None
    _, by_group, timed = found
    floor_s = getattr(roofline.family(run["config"]), "experts_floor_s", None)
    steps = [e for e, _ in timed if "experts_hit" in e]
    if floor_s is None or not steps or not by_group["experts"]:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    floor = sum(floor_s(model, peaks, e["tokens"], e["experts_hit"])[0]
                for e in steps)
    return 100.0 * floor / by_group["experts"]

"""Engine: how many of the experts held here a step touches: `experts_hit`
of each step event (held experts touched, summed over the step's expert
layers) over experts held x expert layers, the mean over the window's
steps that carry `moe_local` (a chip's share of each layer: deepseek_v3
under `ep_size` > 1).  A deployment's step, with 16 chips' chunks behind
each expert, touches all; a step here that touches few reads few.  %."""

from lib import runview

STEPS = ("prefill_chunk", "mixed_step", "spec_round")


def read(run):
    model = run["config"]["model"]
    if "n_routed_experts" not in model:
        return None
    slots = model["n_routed_experts"] * (
        model["num_hidden_layers"] - model["first_k_dense_replace"])
    hits = [e["experts_hit"] for e in runview.window_events(run, *STEPS)
            if "moe_local" in e and "experts_hit" in e]
    if not hits or not slots:
        return None
    return 100.0 * sum(hits) / len(hits) / slots

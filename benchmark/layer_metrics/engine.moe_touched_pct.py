"""Engine: how many of a model's routed experts a step touches:
`experts_hit` of each step event (experts touched, summed over the step's
sparse layers) over `num_experts` x sparse layers (`mlp_layer_types`), the
mean over the window's steps that carry `moe_form`.  How far this draw's
routing is from a deployment's: a trained router spreads a 512-token step
over every expert of a layer; random weights pile it onto few (the
configuration's `assumed.routing`).  %."""

from lib import runview

STEPS = ("prefill_chunk", "mixed_step", "spec_round")


def read(run):
    model = run["config"]["model"]
    if "mlp_layer_types" not in model or "num_experts" not in model:
        return None
    slots = model["num_experts"] * model["mlp_layer_types"].count("sparse")
    hits = [e["experts_hit"] for e in runview.window_events(run, *STEPS)
            if "moe_form" in e and "experts_hit" in e]
    if not hits or not slots:
        return None
    return 100.0 * sum(hits) / len(hits) / slots

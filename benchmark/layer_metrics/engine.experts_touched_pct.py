"""Engine: how many of the model's routed experts a step touches, for a
family that names its expert layers by `num_dense_layers` (every layer after
the leading dense ones holds `num_experts`: lfm2_moe): `experts_hit` of each
step event (experts touched, summed over the step's expert layers) over
`num_experts` x (`num_hidden_layers` - `num_dense_layers`), the mean over the
window's steps that carry `moe_form`.  What the step's expert kernel has to
READ: near 100 the layers' whole stacks cross the HBM every step whatever
its tokens, and `kernel.prefill_step_roofline`'s floor (4 experts a token)
is far under what the step can reach.  As `engine.moe_touched_pct`, which
names the sparse layers by `mlp_layer_types` and reads None here.  %."""

from lib import runview

STEPS = ("prefill_chunk", "mixed_step", "spec_round")


def read(run):
    model = run["config"]["model"]
    if "num_dense_layers" not in model or "num_experts" not in model:
        return None
    slots = model["num_experts"] * (model["num_hidden_layers"]
                                    - model["num_dense_layers"])
    hits = [e["experts_hit"] for e in runview.window_events(run, *STEPS)
            if "moe_form" in e and "experts_hit" in e]
    if not hits or slots <= 0:
        return None
    return 100.0 * sum(hits) / len(hits) / slots

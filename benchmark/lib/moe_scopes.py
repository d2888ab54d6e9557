"""Device time of an expert layer's parts by the named scope an op was traced
under, for the readers `step.routed_experts_device_pct`,
`step.moe_dispatch_device_pct` and `kernel.routed_experts_roofline`: the five
scopes `models/llama.py` puts around its expert layer, whatever form it runs:
`moe.router` (logits, top-k, weights), `moe.dispatch` (the one-hot rows, or
the sort by expert and the gather of rows), `moe.experts` (the routed
experts' matmuls and activation), `moe.combine` (the weighted sum back to
tokens) and `moe.shared` (the shared expert).

An op is placed by `lib/opwalk.py`'s walk: by the kernel name where the
compiler named it after a scope (`%moe.experts.3`), else by the LAST `moe.*`
component of its path of scopes (`tf_op`, where `lib/trace.py` found them in
the profiler's file).  It knows no family and no array shape.  A program
whose ops carry no such scope (or a run without a trace) places nothing: the
readers return None and their metrics are left out of the line."""

from . import opwalk

PARTS = ("router", "dispatch", "experts", "combine", "shared")


def place(name, scope):
    """"router" | "dispatch" | "experts" | "combine" | "shared" | None."""
    head = name.split(" = ", 1)[0].lstrip("%")
    if head.startswith("while"):
        return None
    for part in reversed([head] + scope.split("/")):
        if part.startswith("moe."):
            found = part.split(".")[1]
            return found if found in PARTS else None
    return None


def seconds(run):
    """[(step event, program seconds, {part: seconds})] over every prefill
    step of the window; None where nothing was placed."""
    found = opwalk.step_seconds(run, place)
    if not found or not any(g for _, _, g in found):
        return None
    return found

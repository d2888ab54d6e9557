"""Device time of the gated short convolutions of a model's prefill programs
(dynamo_tpu/models/laguna.py: the layers `layer_types` names "conv"), for
the readers `step.short_conv_device_pct` and `kernel.short_conv_roofline`:
the scopes `sconv.in_proj`, `sconv.conv` (the two gates and the taps) and
`sconv.out_proj` of a conv layer's mixer, and `state.read` / `state.write`,
which move its window from and to the state slots.

One placing function over `lib/opwalk.py`'s walk, by what the trace says of
an op: its kernel's name where the compiler named it after a scope and its
path of named scopes (`tf_op`) where `lib/trace.py` found them.  No array
shapes and no family: a program without `sconv.*` scopes (any other family,
and the parent of the PR that brought this one) places nothing and the
readers return None, the state scopes of a state-space family among it:
they count only beside a short convolution."""

from . import opwalk

SCOPES = ("sconv.in_proj", "sconv.conv", "sconv.out_proj")
STATE = ("state.read", "state.write")


def place(name, scope):
    """"sconv" for an op under one of `SCOPES`, "state" for one under
    `state.read` or `state.write`, else None.  A `while` is nobody's: only
    self time is counted, so a loop's own overhead stays outside."""
    head = name.split(" = ", 1)[0].lstrip("%")
    if head.startswith("while"):
        return None
    parts = [head] + scope.split("/")
    if any(p.startswith(SCOPES) for p in parts):
        return "sconv"
    if any(p.startswith(STATE) for p in parts):
        return "state"
    return None


def prefill_seconds(run):
    """[(step event, program seconds, sconv + state seconds)] over every
    prefill step of the window; None where no op lies under `sconv.*`."""
    found = opwalk.step_seconds(run, place)
    if not found or not any(g.get("sconv") for _, _, g in found):
        return None
    return [(e, prog, g.get("sconv", 0.0) + g.get("state", 0.0))
            for e, prog, g in found]

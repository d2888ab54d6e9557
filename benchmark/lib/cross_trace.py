"""Device time of a decoder-hybrid-decoder's prefill programs by mechanism
(dynamo_tpu/models/phi4flash.py): the selective scans of the self half
(scopes `ssm.conv`, `ssm.scan`, `ssm.gate`) and the cross half of the layers
(everything under the scope `cross`), for the readers `step.
selective_scan_device_pct`, `kernel.selective_scan_roofline` and `step.
cross_half_device_pct`.

One placing function over `lib/opwalk.py`'s walk, by what the trace says of
an op: its kernel's name where the compiler named it after a scope
(`%ssm.scan.3 = ...`) and its path of named scopes (`tf_op`) where
`lib/trace.py` found them.  No array shapes: a program without these scopes
(any other family, and the parent of the PR that brought this one) places
nothing and the readers return None."""

from . import opwalk

SCAN_SCOPES = ("ssm.conv", "ssm.scan", "ssm.gate")


def place(name, scope):
    """"cross" for an op under the scope `cross` (the loop over its layers
    apart), "scan" for one under `ssm.conv`, `ssm.scan` or `ssm.gate`
    outside it, else None.  A `while` is nobody's: only self time is
    counted, so a loop's own overhead stays outside both."""
    head = name.split(" = ", 1)[0]
    if head.startswith("%while"):
        return None
    parts = scope.split("/")
    if "cross" in parts or head.startswith("%cross"):
        return "cross"
    if any(s in parts for s in SCAN_SCOPES) or head.startswith(
            tuple("%" + s for s in SCAN_SCOPES)):
        return "scan"
    return None


def prefill_seconds(run):
    """[(step event, program seconds, {"scan": s, "cross": s})] over every
    prefill step of the window; None where nothing was placed."""
    found = opwalk.step_seconds(run, place)
    if not found or not any(g for _, _, g in found):
        return None
    return found


def share_pct(run, group):
    """100 x the group's device seconds over the prefill programs', summed
    over every prefill step of the window; None where the group has none."""
    found = prefill_seconds(run)
    if found is None:
        return None
    secs = sum(g.get(group, 0.0) for _, _, g in found)
    if not secs:
        return None
    return 100.0 * secs / sum(prog for _, prog, _ in found)

"""Device time of a falcon_h1 model's prefill programs by mechanism (dynamo_
tpu/models/hybrid.py, the layer of BOTH mixers): the state-space half's
products (scopes `ssm.in_proj`, `ssm.out_proj`), its convolution, scan and
gated norm (`ssm.conv`, `ssm.scan`, `ssm.gate_norm`), the state slots beside
the pages (`state.read`, `state.write`) and the dense feed-forward (`mlp`),
for the readers `step.ssm_half_device_pct`, `kernel.ssm_half_scan_roofline`
and `step.feed_forward_device_pct`.  (`lib/ssm_trace.py` reads nemotron_h's
own keys, `hybrid_override_pattern` and `mamba_num_heads`, and returns None
for this family.)  It reads the family's own config.json key `mamba_d_ssm`;
on a configuration without it, and on a program without `ssm.*` scopes, the
readers return None and their metrics are left out.

One placing function over `lib/opwalk.py`'s walk.  An op is placed by what
the trace says of it, first match: its kernel's name where the compiler
named it after a scope (`%ssm.scan.3 = ...`); its path of named scopes
(`tf_op`) where `lib/trace.py` found one; else, for a product whose scope the
compiler dropped, by the weight its HLO line lists, which is unambiguous at
this family's published widths: `in_proj` [5120, 9248], `out_proj` [4096,
5120] (attention's `o_proj` is [2560, 5120], its q [5120, 2560], k and v
[5120, 512]) and the feed-forward's [5120, 21504] / [21504, 5120].  A
`while` (the layer loop) is nobody's: only self time is counted."""

import re

from . import opwalk

GROUPS = (("ssm.in_proj", "ssm.proj"), ("ssm.out_proj", "ssm.proj"),
          ("ssm.", "ssm.scan"), ("state.", "state"), ("mlp", "mlp"))
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


def is_family(model):
    return bool(model.get("mamba_d_ssm"))


def _weights(model):
    """{(rows, columns) of a weight as a product's line lists it: group}."""
    H, F = model["hidden_size"], model["intermediate_size"]
    nh, N = model["mamba_n_heads"], model["mamba_d_state"]
    d = model["mamba_d_ssm"]
    wi = 2 * d + 2 * model.get("mamba_n_groups", 1) * N + nh
    return {(H, wi): "ssm.proj", (d, H): "ssm.proj", (H, F): "mlp",
            (F, H): "mlp"}


def placer(model):
    weights = _weights(model)

    def place(name, scope):
        head = name.split(" = ", 1)[0].lstrip("%")
        if head.startswith("while"):
            return None
        for part in [head] + scope.split("/"):
            for prefix, group in GROUPS:
                if part.startswith(prefix):
                    return group
        for dims in _ARRAY.findall(name):
            tail = tuple(int(x) for x in dims.split(","))[-2:]
            if tail in weights:
                return weights[tail]
        return None

    return place


_PLACERS = {}  # one function a model: `opwalk.step_seconds` memoises by it


def prefill_seconds(run):
    """[(step event, program seconds, {group: seconds})] over EVERY
    `prefill_chunk` step of the window; None for another family's
    configuration, without a trace, or where no op lies under `ssm.*`."""
    model = run["config"]["model"]
    if not is_family(model):
        return None
    key = id(run["config"])
    if key not in _PLACERS:
        _PLACERS.clear()
        _PLACERS[key] = placer(model)
    found = opwalk.step_seconds(run, _PLACERS[key])
    if not found or not any(g.get("ssm.scan") for _, _, g in found):
        return None
    return found

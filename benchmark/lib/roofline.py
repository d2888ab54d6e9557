"""The roofline count belongs to the architecture family: the file
benchmark/roofline/<family>.py, found by the configuration's `roofline` key
or, without one, its `checkpoint` key.  Kept with the benchmark so that no PR
that claims a gain can change the yardstick.

The file's contract (README.md has it in full): `prefill_step_floor_s(model,
peaks, tokens) -> (seconds, "memory" | "compute")`, the least time one
prefill step over `tokens` prompt tokens can take on this chip, from the
bytes that EVERY such step must read and the operations that EVERY token
must do, never more.  What only some steps or some tokens need is left out,
so the figure is a floor and a share of it cannot pass 100% by
over-counting."""

import os

from . import checkpoint
from .procs import RunFailure


def family_name(config):
    return config.get("roofline", config["checkpoint"])


def family(config):
    """The family's module; a `RunFailure` that names the file it lacks."""
    name = family_name(config)
    try:
        mod = checkpoint.load_module("roofline", name)
    except FileNotFoundError:
        raise RunFailure(
            f"{config.get('name', 'the configuration')}: no roofline count "
            f"benchmark/roofline/{name}.py for its family (contract: "
            "benchmark/README.md)") from None
    if not callable(getattr(mod, "prefill_step_floor_s", None)):
        raise RunFailure(f"benchmark/roofline/{name}.py has no "
                         "prefill_step_floor_s(model, peaks, tokens)")
    return mod

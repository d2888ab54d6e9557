"""Kernels: share of the window's prefilled tokens whose step's Mamba-2 scan
was traced into the Pallas kernel (`scan` "pallas" on the `prefill_chunk` or
`mixed_step` slice: engine/engine.py `_scan_of`, from the choice
`ops.ssm.scan` noted for the step's shape), the rest being the blocks as
plain XLA ops (a short row that hands its state out inside a block).  Tokens
are the slice's `tokens` (`prefill_tokens` on a mixed step).  None where no
slice carries `scan`: a model without a Mamba-2 layer, or a program from
before the kernel.  %."""

from lib import runview


def read(run):
    by_scan = {}
    for e in runview.window_events(run, "prefill_chunk", "mixed_step"):
        if "scan" in e:
            tokens = e.get("tokens", e.get("prefill_tokens", 0))
            by_scan[e["scan"]] = by_scan.get(e["scan"], 0) + tokens
    total = sum(by_scan.values())
    if not total:
        return None
    return 100.0 * by_scan.get("pallas", 0) / total

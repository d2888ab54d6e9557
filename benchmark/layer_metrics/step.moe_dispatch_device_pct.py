"""Model step: share of the prefill programs' device time that SPARSITY costs
beside the products: self time of the device ops under `moe.dispatch` (the
one-hot rows of the all-experts form; the sort by expert, the gather of rows
and, where the compiler makes one, the copy of the expert stacks of the
dispatched form) and `moe.combine` (the weighted sum back to tokens), over
the device time of the prefill program, summed over EVERY `prefill_chunk`
step of the window (lib/moe_scopes.py).  %."""

from lib import moe_scopes


def read(run):
    found = moe_scopes.seconds(run)
    if found is None:
        return None
    return (100.0 * sum(g.get("dispatch", 0.0) + g.get("combine", 0.0)
                        for _, _, g in found)
            / sum(prog for _, prog, _ in found))

"""Model step: share of the prefill programs' device time that the expert
layers take: self time of the device ops under the scopes `moe.router`,
`moe.dispatch`, `moe.experts`, `moe.combine` and `moe.shared` (how an op is
placed: lib/moe_scopes.py) over the device time of the prefill program,
summed over EVERY `prefill_chunk` step of the window.  %."""

from lib import moe_scopes


def read(run):
    found = moe_scopes.seconds(run)
    if found is None:
        return None
    return (100.0 * sum(sum(g.values()) for _, _, g in found)
            / sum(prog for _, prog, _ in found))

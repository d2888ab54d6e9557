"""Model step: share of the prefill programs' device time that the expert
layers take: self time of the device ops under the scopes `moe.router`,
`moe.dispatch`, `moe.experts`, `moe.combine` and `moe.shared` (how an op is
placed: lib/ssm_trace.py, by this family's own keys) over the device time of
the prefill program, summed over EVERY `prefill_chunk` step of the window.
%."""

from lib import ssm_trace


def read(run):
    found = ssm_trace.prefill_seconds(run)
    if found is None or not found[0]:
        return None
    program_s, by_kind, _ = found
    return 100.0 * by_kind["moe"] / program_s

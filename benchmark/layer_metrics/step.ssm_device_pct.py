"""Model step: share of the prefill programs' device time that the
state-space layers and their state slots take: self time of the device ops
under the scopes `ssm.in_proj`, `ssm.conv`, `ssm.scan`, `ssm.gate_norm`,
`ssm.out_proj`, `state.read` and `state.write` (how an op is placed, and
what the shapes cannot tell apart: lib/ssm_trace.py) over the device time of
the prefill program, summed over EVERY `prefill_chunk` step of the window.
%."""

from lib import ssm_trace


def read(run):
    found = ssm_trace.prefill_seconds(run)
    if found is None or not found[0]:
        return None
    program_s, by_kind, _ = found
    return 100.0 * (by_kind["ssm.proj"] + by_kind["ssm.scan"]
                    + by_kind["state"]) / program_s

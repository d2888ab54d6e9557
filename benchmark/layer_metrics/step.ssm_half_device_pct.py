"""Model step: share of the prefill programs' device time that the
state-space HALF of a layer of both mixers takes (falcon_h1): self time of
the device ops under the scopes `ssm.in_proj`, `ssm.conv`, `ssm.scan`,
`ssm.gate_norm`, `ssm.out_proj`, `state.read` and `state.write` (how an op is
placed: lib/halves_trace.py) over the device time of the prefill program,
summed over EVERY `prefill_chunk` step of the window.  None for another
family or on a program without those scopes.  %."""

from lib import halves_trace


def read(run):
    found = halves_trace.prefill_seconds(run)
    if found is None:
        return None
    half = sum(g.get("ssm.proj", 0.0) + g.get("ssm.scan", 0.0)
               + g.get("state", 0.0) for _, _, g in found)
    return 100.0 * half / sum(prog for _, prog, _ in found)

"""Model step: share of the prefill programs' device time that attention
over the context takes: self time of the attention core's device ops (the
Pallas kernel `attn.core`, or XLA's ops under that scope) and of the gather
of a row's pages where the implementation makes one (`kv.gather`), over the
device time of the prefill program, summed over EVERY `prefill_chunk` step
of the window, shared ones among them (how an op is placed: lib/opwalk.py
`place_attention`).  It is the part of a step that grows with the context,
where the projections and the feed-forward half cost the same a token
whatever came before.  %."""

from lib import opwalk


def read(run):
    found = opwalk.attention_seconds(run)
    if found is None:
        return None
    program_s = sum(prog for _, prog, _ in found)
    return 100.0 * sum(a for _, _, a in found) / program_s

"""Kernels: share of the window's prefilled tokens whose step was traced into
the Pallas prefill kernel (`attn` "pallas" on the `prefill_chunk` or
`mixed_step` slice: engine/engine.py `_attn_of`, from the choice
`ops.paged_attention._adapt` noted for the step's shape), the rest being XLA
attention over the gathered table.  Tokens are the slice's `tokens`
(`prefill_tokens` on a mixed step).  None where no slice carries `attn`.  %."""

from lib import runview


def read(run):
    by_attn = {}
    for e in runview.window_events(run, "prefill_chunk", "mixed_step"):
        if "attn" in e:
            tokens = e.get("tokens", e.get("prefill_tokens", 0))
            by_attn[e["attn"]] = by_attn.get(e["attn"], 0) + tokens
    total = sum(by_attn.values())
    if not total:
        return None
    return 100.0 * by_attn.get("pallas", 0) / total

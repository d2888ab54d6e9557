"""Kernels: `kernel.prefill_attn_roofline`'s quotient over EVERY
`prefill_chunk` step of the window (`lib/rowsview.py`), the steps that several
sequences share among them: the least time attention over the context can
take (the family's `prefill_attn_floor_s(model, peaks, tokens, ctx)` of
benchmark/roofline/<family>.py) over the device time of the attention ops in
those same steps (`lib/opwalk.py` `attention_seconds`: core and gather).  A
step of one sequence counts with its slice's `tokens` and `ctx`.  A shared
step's floor is summed over its ROWS, each with its own chunk and context
(`toks` and `ctxs` on the slice, in row order: engine/engine.py
`_prefill_dispatch`; `tokens` is their sum and `ctx` the longest).  A row's
floor is the larger of a compute time and a memory time, and a sum of larger
ones could pass the step's own floor, so the rows are summed by the bound each
names and the larger SUM is the step's.  A shared step without the two lists
(the parent) stays out of both sums, as it does in the one-sequence reader.
None for a family whose roofline file has no such function.  %."""

from lib import opwalk, roofline


def read(run):
    found = opwalk.attention_seconds(run)
    floor_s = getattr(roofline.family(run["config"]),
                      "prefill_attn_floor_s", None)
    if found is None or floor_s is None:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    floor = measured = 0.0
    for e, _, attention in found:
        if e["batch"] == 1 and "ctx" in e:
            rows = [(e["tokens"], e["ctx"])]
        elif "ctxs" in e and "toks" in e:
            rows = list(zip(e["toks"], e["ctxs"]))
        else:
            continue
        by_bound = {}
        for tokens, ctx in rows:
            seconds, bound = floor_s(model, peaks, tokens, ctx)
            by_bound[bound] = by_bound.get(bound, 0.0) + seconds
        floor += max(by_bound.values())
        measured += attention
    if not measured:
        return None
    return 100.0 * floor / measured

"""Engine: share of the window's prefill tokens whose expert layers ran the
DISPATCHED form (sort by expert + `ragged_dot`) and not the all-experts
matmul: over the window's `prefill_chunk` events that carry `moe_form`, the
`tokens` of those that say "dispatched" over the `tokens` of all.  The form
is the program's choice by the step's traced shape (experts held, experts a
token, rows x bucket: `models/llama.py` `all_experts_form`).  None on a
program whose events carry no `moe_form`.  %."""

from lib import runview


def read(run):
    steps = [e for e in runview.window_events(run, "prefill_chunk")
             if "moe_form" in e]
    tokens = sum(e["tokens"] for e in steps)
    if not tokens:
        return None
    return 100.0 * sum(e["tokens"] for e in steps
                       if e["moe_form"] == "dispatched") / tokens

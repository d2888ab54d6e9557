"""Model step: share of the window's prefill steps that ran the output head:
100 x the `prefill_chunk` slices with `head` 1 / all of them (`head`:
engine/engine.py `_sampling_rows`; 1 where a row of the step samples, which
is where models/llama.py `forward_prefill` runs the last-position gather,
the final norm, the vocabulary matmul and the sampling; 0 on a mid-prompt
chunk, whose step skips them).  A slice without the attribute counts as 1:
every program before the attribute ran the head on every step, so such a
ring reads 100.  None where the window holds no prefill step.  %."""

from lib import runview


def read(run):
    heads = [e.get("head", 1)
             for e in runview.window_events(run, "prefill_chunk")]
    if not heads:
        return None
    return 100.0 * sum(1 for h in heads if h) / len(heads)

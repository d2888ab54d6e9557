"""Engine: share of the window's prefill rows (a row = one sequence's chunk
in a `prefill_chunk` step) that rode in a step with other sequences' rows:
100 x the sum of `batch` over the slices with `batch` > 1 / the sum of
`batch` over all of them (engine/scheduler.py `_plan_prefill`: short chunks
that are ready at one plan share a step, which then reads the weights once
for all of them).  0.0 for a program that runs one sequence a step; None
where the window holds no prefill step.  %."""

from lib import runview


def read(run):
    rows = [e["batch"] for e in runview.window_events(run, "prefill_chunk")]
    if not sum(rows):
        return None
    return 100.0 * sum(b for b in rows if b > 1) / sum(rows)

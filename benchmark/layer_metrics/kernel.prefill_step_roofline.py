"""Kernels: the least time the window's prefill steps can take on this chip
(the family's count, benchmark/roofline/<family>.py through lib/roofline.py:
for a dense model the layers' weights read once a step over the HBM peak, or
two operations per weight per token over the bf16 peak; the larger) over the
device time of the prefill program in the profiler trace, summed over the
window's `prefill_chunk` steps.  %."""

from lib import roofline, runview


def read(run):
    timed = runview.prefill_steps(run)
    device = sum(secs for _, secs in timed)
    if not device:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    floor_s = roofline.family(run["config"]).prefill_step_floor_s
    floor = sum(floor_s(model, peaks, e["tokens"])[0] for e, _ in timed)
    return 100.0 * floor / device

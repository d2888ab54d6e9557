"""Kernels: the least time the window's prefill steps can take on this chip
over the device time of their programs in the profiler trace, summed over
EVERY `prefill_chunk` step of the window (`lib/rowsview.py`).  A step that
several sequences share is ONE step to the family's count
(benchmark/roofline/<family>.py `prefill_step_floor_s`): the weights read
once for all its rows, the operations of all its rows' tokens (`tokens` on
the slice is their sum); pad rows and the padding of a row to the step's
bucket are work the program does and the floor does not count.
`kernel.prefill_step_roofline` keeps the steps of one sequence.  %."""

from lib import roofline, rowsview


def read(run):
    timed = rowsview.prefill_steps(run)
    device = sum(secs for _, secs in timed)
    if not device:
        return None
    model, peaks = run["config"]["model"], run["peaks"]
    floor_s = roofline.family(run["config"]).prefill_step_floor_s
    floor = sum(floor_s(model, peaks, e["tokens"])[0] for e, _ in timed)
    return 100.0 * floor / device

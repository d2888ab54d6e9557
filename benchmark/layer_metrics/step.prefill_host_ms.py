"""Model step: host time inside a prefill step.  Mean, over the window's
`prefill_chunk` steps of one sequence, of the step's host slice (opens
before the inputs are built, closes after the result fetch and the
delivery) less the device seconds of the program inside it (profiler
trace): building, dispatching, the part of the fetch that is not device
time, delivering.  ms."""

from lib import runview


def read(run):
    timed = runview.prefill_steps(run)
    if not timed:
        return None
    return sum(e["dur_ns"] / 1e6 - secs * 1e3 for e, secs in timed) / len(timed)

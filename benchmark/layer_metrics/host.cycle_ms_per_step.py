"""Engine: the step loop's own time a step, over the WHOLE window and without
a trace.  Summed over the window, the loop's time (pump coroutine and step
thread together, `lib/hostline.py` `timeline`) that waited neither for the
device (`fetch`) nor for work (`idle_wait`): `build` + `dispatch` + `deliver`
+ `plan` + `loop_yield` + the hand-offs (`hop_us`, `fetch_hop_us`) + other
pump work (`pump_op`), over the step slices that ended in the window.  Time
under a recorded pause is left out.  A step shorter than this runs the device
dry.  ms."""

from lib import hostline


def read(run):
    cycle = hostline.host_cycle(run)
    if cycle is None:
        return None
    return cycle["host_ns"] / cycle["steps"] / 1e6

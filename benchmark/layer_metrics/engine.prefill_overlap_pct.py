"""Engine: share of the window's prefill steps that were dispatched while
the step before them was still unfetched (`overlapped` 1 on the
`prefill_chunk` slice: engine/engine.py `_run_prefill`), so that the device
went from one program to the next while the host fetched, delivered, planned
and built.  None where the slices carry no such attribute (a program without
a step in flight).  %."""

from lib import runview


def read(run):
    flags = [e["overlapped"]
             for e in runview.window_events(run, "prefill_chunk")
             if "overlapped" in e]
    if not flags:
        return None
    return 100.0 * sum(1 for f in flags if f) / len(flags)

"""Engine: share of the window's steps that were dispatched to a device that
had run dry: 100 x the step slices with `dry` 1 (no earlier program of the
engine was still running once the step's own had been handed over, so the
device stood idle before it could start: engine/engine.py
`_dispatched_dry`) over those that carry the attribute.  The
program's own count, made where the work happens, so it sees the whole window
in a run whose capture ended early and in a run without one.  None where no
slice carries it (the parent).  %."""

from lib import hostline, runview


def read(run):
    flags = [e["dry"] for e in runview.window_events(run, *hostline.STEP_KINDS)
             if "dry" in e]
    if not flags:
        return None
    return 100.0 * sum(1 for f in flags if f) / len(flags)

"""Engine: how deep the window's prefix hits were against how deep their
PAGES were: over the window's `admit` events, the prompt tokens served from
cache (`cached`) over the prompt tokens whose pages were found cached
(`kv_cached`).  A model with state-space layers resumes at the deepest
state snapshot at or under its cached pages and computes the tokens past it
again (engine/scheduler.py `_shorten_to_snapshot`), so this reads 100 where
no hit was shortened for want of a snapshot, and 100 for any model without
such layers.  None where no admission found a cached page, or on a program
whose `admit` events carry no `kv_cached`.  %."""

from lib import runview


def read(run):
    admits = [e for e in runview.window_events(run, "admit")
              if "kv_cached" in e]
    found = sum(e["kv_cached"] for e in admits)
    if not found:
        return None
    return 100.0 * sum(e["cached"] for e in admits) / found

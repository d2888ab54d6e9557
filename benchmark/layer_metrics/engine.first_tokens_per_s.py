"""Engine: requests that got their first token inside the window, a second
of the window (`first_token` events over `t1` - `t0`).  In a closed loop
the clients send as fast as they are answered, so this is what the engine
sustains, and a change that serves more requests in the same device time
shows here where the percentiles of a convoy may not (PERF.md 7 (h)).
None where the window holds none.  req/s."""

from lib import runview


def read(run):
    firsts = runview.window_events(run, "first_token")
    if not firsts or run["t1"] <= run["t0"]:
        return None
    return len(firsts) / (run["t1"] - run["t0"])

"""Engine: how long the median request waited before and between its own
steps.  Median over the window's `first_token` events of `queue_us` (arrival
at the engine to first admission) + `wait_us` (admitted, and waiting for a
turn at the device or between its own chunks): time to first token less the
slices of the steps that computed the request's own tokens.  ms."""

from lib import runview, stats


def read(run):
    firsts = runview.window_events(run, "first_token")
    if not firsts:
        return None
    return stats.median([(e["queue_us"] + e["wait_us"]) / 1e3
                         for e in firsts])

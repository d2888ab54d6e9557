"""Engine: share of the device's exposed idle time (`host.exposed_ms_per_step`)
that lies under the step thread's `build` (a step slice's start to its jitted
call: the row layout, the numpy inputs).  Each idle interval is CUT at the
boundaries of the loop's timeline, not labelled at its midpoint
(`lib/hostline.py`).  The seven `host.exposed_*_pct` add up to 100; None where
nothing is exposed or nothing can be charged.  %."""

from lib import hostline


def read(run):
    return hostline.exposed_share(run, "build")

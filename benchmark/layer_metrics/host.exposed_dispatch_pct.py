"""Engine: share of the device's exposed idle time (`host.exposed_ms_per_step`)
that lies under the step thread's `dispatch` (the jitted call with its input
transfers, up to the end of the step's dispatch half).  Each idle interval is
CUT at the boundaries of the loop's timeline, not labelled at its midpoint
(`lib/hostline.py`).  The seven `host.exposed_*_pct` add up to 100; None where
nothing is exposed or nothing can be charged.  %."""

from lib import hostline


def read(run):
    return hostline.exposed_share(run, "dispatch")

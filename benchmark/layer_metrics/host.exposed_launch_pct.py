"""Engine: share of the device's exposed idle time (`host.exposed_ms_per_step`)
that lies under `launch`: after the NEXT program's dispatch had ended the host
was in time and the runtime, the transfer or the device was not; idle time
under a `fetch` counts here too (the host already waits).  Each idle interval
is CUT at the boundaries of the loop's timeline, not labelled at its midpoint
(`lib/hostline.py`).  The seven `host.exposed_*_pct` add up to 100; None where
nothing is exposed or nothing can be charged.  %."""

from lib import hostline


def read(run):
    return hostline.exposed_share(run, "launch")

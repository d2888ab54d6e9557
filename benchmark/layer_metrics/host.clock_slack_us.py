"""Engine: how finely a device idle interval can be placed on the ring's
clock: `hi - lo` of `lib/hostline.py`, the room the paired steps leave for the
offset between the two clocks (a program cannot start before its jitted call
began, nor end after its `device_get` returned).  Negative where the two
records contradict each other, and then no `host.exposed_*` metric reads.
us."""

from lib import hostline


def read(run):
    acc = hostline.account(run)
    return None if not acc else acc["slack_ns"] / 1e3

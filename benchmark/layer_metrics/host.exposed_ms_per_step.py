"""Engine: device idle time a step that was not want of work.  The idle
intervals between the device's program executions inside the captured span,
less the parts under the pump's `idle_wait`, over the step slices that ended
in that span (`lib/hostline.py` `account`).  None without a trace, on a ring
whose slices carry no `seq` (the parent), or where the ring's clock and the
trace's contradict each other.  ms."""

from lib import hostline


def read(run):
    acc = hostline.account(run)
    if not acc or acc.get("exposed_ns") is None or not acc["steps"]:
        return None
    return acc["exposed_ns"] / acc["steps"] / 1e6

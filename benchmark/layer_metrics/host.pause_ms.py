"""Compile; start-up: the host's rare, long events inside the window:
summed `dur_ns` of `program`, `gc_pause` (a generation-2 collection or any
pause of 1 ms or more) and `lease_renew` (a renewal that woke 50 ms late or
took as long) slices, each cut to [t0, t1].  They may overlap a step that is
hidden behind a running program: this is the time the interpreter or the
loop was held, not device idle time.  None where the ring has no `ready`.
ms."""

KINDS = ("program", "gc_pause", "lease_renew")


def read(run):
    if not any(e["kind"] == "ready" for e in run["events"]):
        return None
    a, b = run["t0"] * 1e9, run["t1"] * 1e9
    return sum(max(0.0, min(b, e["t_ns"] + e["dur_ns"]) - max(a, e["t_ns"]))
               for e in run["events"] if e["kind"] in KINDS) / 1e6

"""Compile; start-up: programs born INSIDE the measured window: `program`
events (a trace, a lowering and a compile or a load from the persistent
cache, on the thread that called the jitted function) that ended in
[t0, t1].  Expected 0: the warm-up has met every shape.  The step slice such
a program fell into says `compiled`.  None where the ring has no `ready` (a
program that records no such events).  programs."""


def read(run):
    if not any(e["kind"] == "ready" for e in run["events"]):
        return None
    a, b = run["t0"] * 1e9, run["t1"] * 1e9
    return sum(1 for e in run["events"] if e["kind"] == "program"
               and a <= e["t_ns"] + e["dur_ns"] <= b)

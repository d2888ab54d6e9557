"""Compile; start-up: the share of the named programs born before the window
whose lowered module came from the program store
(`dynamo_tpu/compile_cache.py` `ProgramStore`): 100 x the `program` events
with a `fn` that ended before `t0` and say `stored` 1 / all such events.  A
birth says `stored` 1 (the module was read, nothing was traced), 0 (it was
derived and written: a first start) or nothing (the program could not be
carried and was traced).  Unnamed births (`fn` "": library programs outside
any ledgered function) are not the store's and are not counted.  None where
no event carries the attribute (a program without a store).  %."""


def read(run):
    t0 = run["t0"] * 1e9
    born = [e for e in run["events"] if e["kind"] == "program"
            and e.get("fn") and e["t_ns"] + e["dur_ns"] < t0]
    if not any("stored" in e for e in born):
        return None
    return 100.0 * sum(e.get("stored") == 1 for e in born) / len(born)

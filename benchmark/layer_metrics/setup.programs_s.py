"""Compile; start-up: what the programs born before the window cost the
threads that bore them: the four stages (`trace_us`, `lower_us`,
`compile_us`, `load_us`) of every `program` event that ended before `t0`,
summed (analysis/xla_ledger.py; a warm persistent cache leaves trace,
lowering and the load).  None where the ring has no `ready` (a program that
records no such events), 0.0 where it bore none.  s."""

STAGES = ("trace_us", "lower_us", "compile_us", "load_us")


def read(run):
    if not any(e["kind"] == "ready" for e in run["events"]):
        return None
    t0 = run["t0"] * 1e9
    return sum(e.get(k, 0) for e in run["events"] for k in STAGES
               if e["kind"] == "program"
               and e["t_ns"] + e["dur_ns"] < t0) / 1e6

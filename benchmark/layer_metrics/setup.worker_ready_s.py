"""Compile; start-up: the worker's process start to its `READY` line, on the
step ring's clock: the instant `ready` minus the start of the
`startup.imports` slice (worker/__main__.py; the `startup.*` slices tile
that span: imports, backend, weights, engine, serve).  None where the ring
has no such events (a program that does not record them).  s."""


def read(run):
    at = {e["kind"]: e["t_ns"] for e in run["events"]
          if e["kind"] in ("startup.imports", "ready")}
    if len(at) < 2:
        return None
    return (at["ready"] - at["startup.imports"]) / 1e9

"""Compile; start-up: the worker's `ready` instant to the window's first
request (`t0`, the same monotonic clock): the frontend listing the model, the
wait for the reference, the 40 probes, the loop's warm-up cycle and its quiet
seconds.  With `setup.worker_ready_s` it splits `setup_s` three ways: the
rest is the harness before the worker (checkpoint, control plane, frontend).
None where the ring has no `ready`.  s."""


def read(run):
    for e in run["events"]:
        if e["kind"] == "ready":
            return run["t0"] - e["t_ns"] / 1e9
    return None

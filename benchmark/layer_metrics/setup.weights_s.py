"""Compile; start-up: the worker's `startup.weights` slice: from the
backend's first touch through the last array of the checkpoint on the device
(configuration, tokenizer and the engine's imports are in it; its `read_us`
and `put_us` tell reading from placing).  None where the ring has no such
slice.  s."""


def read(run):
    for e in run["events"]:
        if e["kind"] == "startup.weights":
            return e["dur_ns"] / 1e9
    return None

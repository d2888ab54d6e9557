"""Engine: host time between two engine steps while work is waiting.  Mean,
over consecutive step slices of the window that have a `plan` slice between
them and no `idle_wait` (the engine was not out of work), of the next
slice's start minus the last one's end: the hop back to the loop thread, the
worker's other coroutines, intake and scheduling.  ms."""

from lib import runview

STEPS = ("prefill_chunk", "decode_block", "mixed_step", "spec_round")


def read(run):
    by_ring = {}
    for e in runview.window_events(run, "plan", "idle_wait", *STEPS):
        by_ring.setdefault(e.get("ring"), []).append(e)
    gaps = []
    for events in by_ring.values():
        last_end, planned, idled = None, False, False
        for e in sorted(events, key=lambda e: e["t_ns"]):
            if e["kind"] == "plan":
                planned = True
            elif e["kind"] == "idle_wait":
                idled = True
            else:
                if last_end is not None and planned and not idled:
                    gaps.append(e["t_ns"] - last_end)
                last_end = e["t_ns"] + e["dur_ns"]
                planned = idled = False
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6

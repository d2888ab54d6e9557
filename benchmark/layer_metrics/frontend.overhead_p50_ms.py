"""Frontend + router + transport: what the path around the engine adds to
the median request.  Median over the window's client records of (`t_first`
- `t_sent`) less `total_us` (arrival at the engine to first token) of the
`first_token` event matched to the record: same `prompt_len`, engine arrival
and first token both inside [`t_sent`, `t_first`] (the benchmark's parent
and the worker share CLOCK_MONOTONIC).  Records that match no event are
left out; None if fewer than nine in ten match.  ms."""

from lib import stats


def read(run):
    records = stats.window(run["records"], run["t0"], run["t1"])["ok"]
    by_len = {}
    for e in run["events"]:
        if e["kind"] == "first_token":
            by_len.setdefault(e["prompt_len"], []).append(e)
    if not records or not by_len:
        return None
    over = []
    for r in sorted(records, key=lambda r: r["t_sent"]):
        sent, first = r["t_sent"] * 1e9, r["t_first"] * 1e9
        pool = by_len.get(r["prompt_len"], [])
        for i, e in enumerate(pool):
            if sent <= e["t_ns"] - e["total_us"] * 1e3 and e["t_ns"] <= first:
                over.append((first - sent) / 1e6 - e["total_us"] / 1e3)
                del pool[i]  # an event answers one record
                break
    if len(over) < 0.9 * len(records):
        return None
    return stats.median(over)

"""Engine: the median time to first token, inside the engine, of the
requests that HIT the prefix cache (`cached` > 0 on their `first_token`
event: in the docqa mix the questions on a document already served, two
requests of three): median of `total_us`, arrival at the engine to first
token.  The cell's `ttft_p50_ms` is the median of cached and cold requests
together and can fall while this one rises (PERF.md finding 18).  None
where the window holds no such request.  ms."""

from lib import runview, stats


def read(run):
    hits = [e["total_us"] / 1e3
            for e in runview.window_events(run, "first_token")
            if e["cached"] > 0]
    return stats.median(hits) if hits else None

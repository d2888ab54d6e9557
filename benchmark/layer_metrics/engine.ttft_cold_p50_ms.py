"""Engine: the median time to first token, inside the engine, of the
requests that found NOTHING in the prefix cache (`cached` 0 on their
`first_token` event: in the docqa mix the cold documents, which take
several prefill steps and set the tail): median of `total_us`, arrival at
the engine to first token.  Beside `engine.ttft_cached_p50_ms`.  None where
the window holds no such request.  ms."""

from lib import runview, stats


def read(run):
    cold = [e["total_us"] / 1e3
            for e in runview.window_events(run, "first_token")
            if e["cached"] == 0]
    return stats.median(cold) if cold else None

"""95th percentile, nearest rank, over the window's successful requests of
the time from when the request was due (closed loop: sent) to its first
token.  ms."""

from lib import stats


def read(w):
    if not w["ok"]:
        return None
    return stats.percentile([stats.ttft_ms(r) for r in w["ok"]], 95)

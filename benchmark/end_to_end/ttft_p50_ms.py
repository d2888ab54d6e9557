"""Median over the window's successful requests of the time from when the
request was due (closed loop: sent) to its first token.  ms."""

from lib import stats


def read(w):
    return stats.median([stats.ttft_ms(r) for r in w["ok"]]) if w["ok"] else None

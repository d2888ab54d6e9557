"""Metric arithmetic on client records.  Pure Python, no clock, no I/O."""

import math


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def median(values):
    xs = sorted(values)
    n = len(xs)
    if not n:
        raise ValueError("median of no values")
    mid = n // 2
    return xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def sse_frame(line):
    """One line of a streamed completion -> None (not a data frame, or
    [DONE]), {"error": ...}, or {"finish": <reason or None>} for a frame
    that carries a choice.

    How many tokens a frame carries is NOT knowable from the stream (the
    frontend coalesces, and most random-weight ids detokenize to nothing),
    so no metric depends on the number of frames.  A request's token count
    is the `max_tokens` it asked for, valid iff it finished with `length`."""
    import json

    if isinstance(line, bytes):
        line = line.decode()
    if not line.startswith("data: ") or line.startswith("data: [DONE]"):
        return None
    ev = json.loads(line[6:])
    if "error" in ev:
        return {"error": ev["error"]}
    finish = None
    for ch in ev.get("choices", ()):
        finish = ch.get("finish_reason") or finish
    return {"finish": finish} if ev.get("choices") else None


def parse_sse(lines):
    """(token-bearing frames, finish_reason, error) of one whole stream."""
    frames, finish, error = 0, None, None
    for line in lines:
        f = sse_frame(line)
        if f is None:
            continue
        if "error" in f:
            error = f["error"]
        else:
            frames += 1
            finish = f["finish"] or finish
    return frames, finish, error


def request_ok(rec):
    """A measured request succeeded iff HTTP 200, finish_reason `length`,
    a first and a last token time, and no error frame."""
    return (rec.get("status") == 200 and rec.get("finish") == "length"
            and rec.get("error") is None
            and rec.get("t_first") is not None
            and rec.get("t_last") is not None)


def ttft_ms(rec):
    """From when the request was due (open loop) or sent (closed loop; there
    due == sent) to its first token."""
    return (rec["t_first"] - rec["t_due"]) * 1e3


def window(records, t0, t1):
    """What an end-to-end reader (benchmark/end_to_end/<metric>.py) is
    given.  `records`: every request the loop sent, warm-up and requests in
    flight at the window's edges included; `measured`: those due inside
    [t0, t1); `ok`: the measured ones that succeeded.  A failed request is
    in no percentile and in `failed`."""
    measured = [r for r in records if t0 <= r["t_due"] < t1]
    return {"records": records, "measured": measured,
            "ok": [r for r in measured if request_ok(r)],
            "t0": t0, "t1": t1}

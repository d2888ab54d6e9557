import pytest

from lib import stats

SSE = """data: {"id": "c1", "object": "text_completion", "choices": [{"index": 0, "text": "", "finish_reason": null}]}

data: {"id": "c1", "object": "text_completion", "choices": [{"index": 0, "text": "ab", "finish_reason": null}]}

: keep-alive

data: {"id": "c1", "object": "text_completion", "choices": [{"index": 0, "text": "", "finish_reason": "length"}]}

data: [DONE]
""".splitlines()


@pytest.mark.parametrize("values,q,want", [
    ([5], 95, 5), (list(range(1, 101)), 95, 95), (list(range(1, 101)), 50, 50),
    ([3, 1, 2], 95, 3), (list(range(1, 21)), 95, 19), ([1, 2, 3, 4], 25, 1),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_and_median_refuse_nothing():
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    with pytest.raises(ValueError):
        stats.median([])


@pytest.mark.parametrize("values,want", [([1, 3], 2.0), ([1, 2, 9], 2),
                                         ([4, 1, 3, 2], 2.5)])
def test_median(values, want):
    assert stats.median(values) == want


def test_canned_stream_counts_frames_not_tokens():
    frames, finish, error = stats.parse_sse(SSE)
    assert (frames, finish, error) == (3, "length", None)


def test_error_frame_is_an_error():
    frames, finish, error = stats.parse_sse(
        ['data: {"id": "x", "error": {"message": "boom"}}', "data: [DONE]"])
    assert frames == 0 and finish is None and error == {"message": "boom"}


def rec(due, first, last, n, finish="length", status=200, error=None):
    return {"t_due": due, "t_sent": due, "t_first": first, "t_last": last,
            "max_tokens": n, "finish": finish, "status": status,
            "error": error, "prompt_len": 10}


def window_of(records, t0, t1):
    return stats.window(records, t0, t1)


def reader(name):
    from lib import checkpoint

    return checkpoint.load_module("end_to_end", name).read


def test_ttft_is_from_due_to_first_token():
    assert stats.ttft_ms(rec(0.0, 0.25, 1.25, 11)) == pytest.approx(250.0)


def test_window_counts_failures_and_membership():
    records = [
        rec(-1.0, -0.5, 0.5, 1),               # due before the window
        rec(0.1, 0.2, 0.2, 1),
        rec(0.2, 0.5, 0.5, 1),
        rec(0.3, 0.4, None, 8, finish=None),   # never finished: failed
        rec(0.4, None, None, 8, status=503, error="overloaded"),
        rec(9.0, 9.1, 11.0, 50),               # completes after the window
    ]
    w = window_of(records, 0.0, 10.0)
    assert (len(w["measured"]), len(w["ok"])) == (5, 3)
    assert reader("ttft_p50_ms")(w) == pytest.approx(100.0)
    assert reader("ttft_p95_ms")(w) == pytest.approx(300.0)


@pytest.mark.parametrize("name", ["ttft_p50_ms", "ttft_p95_ms"])
def test_a_window_without_a_success_reads_nothing(name):
    assert reader(name)(window_of(
        [rec(0.1, None, None, 1, status=503)], 0.0, 1.0)) is None

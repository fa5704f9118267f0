"""Window arithmetic: rate over the whole window, percentiles over every call."""

import itertools

import numpy as np
import pytest

from bench import window


def test_rate_is_work_over_window():
    assert window.rate(2560, 0.5) == 5120.0
    with pytest.raises(ValueError):
        window.rate(1, 0.0)


@pytest.mark.parametrize("p, want", [(50, 5), (99, 10), (90, 9), (1, 1), (100, 10)])
def test_nearest_rank_percentile(p, want):
    assert window.percentile([7, 1, 10, 2, 9, 3, 8, 4, 6, 5], p) == want


def test_closed_loop_counts_every_call_and_the_overrun():
    tick = itertools.count()
    clock = lambda: float(next(tick))  # noqa: E731  one second per reading
    batches = [np.zeros((4, 2)) for _ in range(3)]

    def search(q):
        return np.zeros((len(q), 1), np.float32), np.zeros((len(q), 1), np.int64)

    calls, failed, seconds = window.run(search, batches, 5.0, clock=clock)
    # start=0; each call reads t0, t1: calls end at 2, 4, 6 (>= 5 stops).
    assert [c.batch for c in calls] == [0, 1, 2] and not failed
    assert seconds == 6.0
    s = window.summary(calls, seconds, 4)
    assert s["queries"] == 12 and s["qps"] == 2.0
    assert s["latency_p50_ms"] == 1000.0 and s["latency_p99_ms"] == 1000.0


def test_a_failed_call_is_counted_and_the_window_goes_on():
    n = {"i": 0}

    def search(q):
        n["i"] += 1
        if n["i"] == 2:
            raise RuntimeError("boom")
        return np.zeros((1, 1), np.float32), np.zeros((1, 1), np.int64)

    tick = itertools.count()
    calls, failed, _ = window.run(search, [np.zeros((1, 2))], 5.0,
                                  clock=lambda: float(next(tick)))
    assert len(failed) == 1 and len(calls) == 2


def test_repeat_mismatches():
    a = window.Call(0, 0, 1, np.ones((2, 3), np.float32), np.arange(6).reshape(2, 3))
    b = a._replace(scores=a.scores.copy())
    c = a._replace(scores=a.scores + np.float32(1e-7))
    d = window.Call(1, 0, 1, a.scores, a.ids)
    assert window.repeat_mismatches([a, b, d]) == 0
    assert window.repeat_mismatches([a, b, c, d]) == 1

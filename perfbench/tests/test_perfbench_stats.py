"""The window's arithmetic: quantiles, rates over a window with a stall,
span self times, and the closed loop's window."""
import statistics
from concurrent.futures import Future

import numpy as np
import pytest

from perfbench import loadgen, stats


@pytest.mark.parametrize("n", [1, 2, 5, 10, 101])
def test_quantile_matches_statistics_inclusive(n):
    xs = list(np.random.default_rng(n).exponential(size=n))
    assert stats.quantile(xs, 0.5) == pytest.approx(statistics.median(xs))
    if n > 1:
        deciles = statistics.quantiles(xs, n=10, method="inclusive")
        assert stats.quantile(xs, 0.9) == pytest.approx(deciles[8])


def _window_with_stall():
    # one request answered every 0.1 s for 1 s, then nothing for 4 s, then
    # the request caught in the stall answers at 5.1 s
    reqs = []
    for j in range(10):
        reqs.append(loadgen.Request(0, j, 0, t_submit=0.1 * j,
                                    t_done=0.1 * (j + 1)))
    reqs.append(loadgen.Request(0, 10, 0, t_submit=1.0, t_done=5.1))
    return loadgen.Window(start=0.0, close=1.05, requests=reqs)


def test_rate_counts_the_stall():
    w = _window_with_stall()
    assert w.end == pytest.approx(5.1)
    macs = [1e9] * len([q for q in w.requests if q.answered])
    # 11 requests over the whole window, the stall included
    assert stats.rate(macs, w.start, w.end) == pytest.approx(11e9 / 5.1)


def test_quantiles_see_the_stall():
    lat = [q.latency_s for q in _window_with_stall().requests]
    assert stats.quantile(lat, 0.5) == pytest.approx(0.1)
    assert stats.quantile(lat, 1.0) == pytest.approx(4.1)
    assert stats.quantile(lat, 0.9) == pytest.approx(0.1)


def test_rate_of_an_empty_window_is_an_error():
    with pytest.raises(ValueError):
        stats.rate([], 1.0, 1.0)


def test_unanswered_request_does_not_end_the_window():
    w = _window_with_stall()
    w.requests.append(loadgen.Request(1, 0, 0, t_submit=0.5, error="lost"))
    assert w.end == pytest.approx(5.1)
    assert sum(1 for q in w.requests if not q.answered) == 1


def test_self_time_subtracts_children():
    spans = [
        {"trace_id": "a", "name": "outer", "t_start": 0.0, "t_end": 1.0,
         "tags": {}},
        {"trace_id": "a", "name": "inner", "t_start": 0.2, "t_end": 0.5,
         "tags": {"parent": "outer"}},
        {"trace_id": "a", "name": "inner", "t_start": 0.4, "t_end": 0.6,
         "tags": {"parent": "outer"}},
        {"trace_id": "b", "name": "outer", "t_start": 0.0, "t_end": 2.0,
         "tags": {}},
    ]
    assert stats.self_times(spans) == pytest.approx([0.6, 0.3, 0.2, 2.0])
    assert sorted(stats.per_trace_sum(spans, "outer")) == pytest.approx(
        [0.6, 2.0])


def test_closed_loop_waits_for_each_answer_and_drains():
    sent = []

    def submit(A, B):
        sent.append(int(A[0, 0, 0]))
        f = Future()
        f.set_result(A)
        return f

    As = [np.full((1, 1, 1), k, np.uint32) for k in range(8)]
    w = loadgen.closed_loop(submit, As, None, clients=4, seconds=0.05)
    assert w.requests and all(q.answered for q in w.requests)
    assert w.end >= w.close
    # client c's j-th request uses operand set (c + 4 j) mod 8
    for q in w.requests:
        assert q.operand == (q.client + 4 * q.seq) % 8
        assert int(q.result[0, 0, 0]) == q.operand


def test_closed_loop_counts_a_request_that_raises():
    def submit(A, B):
        f = Future()
        f.set_exception(RuntimeError("worker died"))
        return f

    w = loadgen.closed_loop(submit, [np.zeros((1, 1, 1))], None, clients=2,
                            seconds=0.05)
    assert len(w.requests) == 2
    assert all(not q.answered and "worker died" in q.error
               for q in w.requests)

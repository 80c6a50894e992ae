"""End-to-end arithmetic on synthetic stamps."""
import math

import pytest

from bench import latency


def rec(arrival, first, last, n, done=True, admit=None):
    return {"arrival": arrival, "first": first, "last": last, "n_tokens": n,
            "done": done, "t_admit": arrival if admit is None else admit}


def test_ttft_from_due_time_and_tail_over_all_requests():
    reqs = [rec(0.0, 0.1 * (i + 1), 1.0, 10) for i in range(20)]
    # the 95th percentile of 0.1 .. 2.0 s, interpolated over all 20
    assert latency.ttft_ms(reqs, 10.0, 95) == pytest.approx(1905.0)
    assert latency.ttft_ms(reqs, 10.0, 50) == pytest.approx(1050.0)


def test_unfinished_request_counts_as_infinite():
    reqs = [rec(0.0, 0.1, 1.0, 10) for _ in range(9)]
    reqs.append(rec(0.0, None, None, 0, done=False))
    assert math.isinf(latency.ttft_ms(reqs, 10.0, 95))
    assert math.isinf(latency.tpot_ms(reqs, 10.0, 95))
    assert latency.ttft_ms(reqs, 10.0, 50) == pytest.approx(100.0)


def test_requests_arriving_after_the_window_are_left_out():
    reqs = [rec(1.0, 1.2, 2.0, 5), rec(11.0, 99.0, 100.0, 5)]
    assert latency.ttft_ms(reqs, 10.0, 95) == pytest.approx(200.0)


def test_tpot_is_last_minus_first_over_tokens_minus_one():
    reqs = [rec(0.0, 1.0, 2.0, 11), rec(0.0, 1.0, 1.5, 6),
            rec(0.0, 1.0, 1.0, 1)]          # one token: no gap, left out
    assert latency.tpot_ms(reqs, 10.0, 50) == pytest.approx(100.0)
    assert latency.tpot_ms(reqs, 10.0, 95) == pytest.approx(100.0)


def test_rate_is_over_the_whole_window():
    assert latency.rate(1500, 30.0) == pytest.approx(50.0)


def test_queue_wait_from_scheduler_stamps():
    reqs = [rec(0.0, 0.5, 1.0, 3, admit=0.2), rec(1.0, 1.5, 2.0, 3,
                                                  admit=1.1)]
    assert latency.queue_wait_ms(reqs, 10.0, 50) == pytest.approx(150.0)


def test_percentile_of_nothing_is_none():
    assert latency.percentile([], 95) is None
    assert latency.ttft_ms([], 10.0, 95) is None

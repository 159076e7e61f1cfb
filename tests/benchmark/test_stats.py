"""The metric arithmetic: pure functions of client-side stamps."""

import math

import pytest

from perfbench import stats


def test_tpot_mean_is_token_weighted_not_a_mean_of_requests():
    # a 3-token request at 10 ms a gap and a 101-token request at 1 ms a gap:
    # 120 ms of span over 102 gaps; a mean over REQUESTS would say 5.5 ms
    reqs = [
        {"n_out": 3, "t_first": 0.0, "t_last": 0.020},
        {"n_out": 101, "t_first": 1.0, "t_last": 1.100},
    ]
    assert stats.tpot_mean_ms(reqs) == pytest.approx(120.0 / 102.0)
    assert sorted(stats.per_request_tpot_ms(reqs)) == pytest.approx([1.0, 10.0])


def test_tpot_mean_ignores_one_token_answers_and_empty_samples():
    assert stats.tpot_mean_ms([{"n_out": 1, "t_first": 0.0, "t_last": 0.0}]) is None
    assert stats.tpot_mean_ms([]) is None


def test_ttft_is_timed_from_the_due_time_not_the_send_time():
    # due at 1.0, sent late at 1.4, first token at 1.5: the caller waited 500 ms
    reqs = [{"t_due": 1.0, "t_sent": 1.4, "t_first": 1.5}, {"t_due": 2.0, "t_sent": 2.0, "t_first": None}]
    got = stats.ttft_ms(reqs)
    assert got[0] == pytest.approx(500.0)
    assert got[1] is None


def test_a_request_without_an_answer_counts_as_missing_any_limit():
    ttfts = [10.0] * 8 + [None, None]
    assert stats.tail_with_missing(ttfts, 50) == 10.0
    assert math.isinf(stats.tail_with_missing(ttfts, 90))


@pytest.mark.parametrize("n, q", [(19, None), (20, 50), (100, 90), (199, 90), (200, 95), (1000, 99)])
def test_highest_percentile_with_ten_samples_beyond(n, q):
    assert stats.highest_percentile_with_ten_beyond(n) == q


def test_percentile_interpolates_like_numpy():
    np = pytest.importorskip("numpy")
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    for q in (0, 25, 50, 90, 100):
        assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))
    assert stats.percentile([], 50) is None


def test_tokens_in_window_counts_both_edges():
    assert stats.tokens_in_window([0.9, 1.0, 1.5, 2.0, 2.1], 1.0, 2.0) == 3


def test_tpot_spans_add_up_over_requests():
    reqs = [{"n_out": 9, "t_first": float(i), "t_last": i + 0.080} for i in range(5)]
    assert stats.tpot_mean_ms(reqs) == pytest.approx(10.0)


# (t0, t1, left queued) per engine step; the window's last part is [10, 20]
@pytest.mark.parametrize("rows, expected", [
    ([], 0.0),                                                        # an idle engine queues nothing
    ([(9.0, 9.5, 0), (9.5, 10.0, 0), (15.0, 15.5, 0)], 0.0),
    ([(9.0, 10.0, 4), (10.0, 20.0, 4)], 4.0),                         # a standing queue
    ([(11.0, 12.0, 6), (12.0, 14.0, 0)], 1.2),                        # a 2 s stall: 6 x 2 / 10
    ([(5.0, 8.0, 3), (18.0, 19.0, 0)], 2.7),                          # holds from before the part until the next step ends
    ([(19.0, 19.5, 20)], 1.0),                                        # many at the close, for half a second
    ([(10.0 + i, 11.0 + i, 4 * (i + 1)) for i in range(10)], 18.0),   # growing all through: 4, 8, .. 36 for a second each
])
def test_mean_left_queued_is_weighted_by_time(rows, expected):
    assert stats.mean_left_queued(rows, 10.0, 20.0) == pytest.approx(expected)


def test_mean_left_queued_of_no_time_is_none():
    assert stats.mean_left_queued([(0.0, 1.0, 3)], 5.0, 5.0) is None

"""Percentile, gap and rate arithmetic on hand-made timelines."""

import math
import statistics

import pytest

from benchmarks.harness import stats


def test_percentile_interpolates_between_ranks():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_burst_of_k_tokens_gives_k_samples_of_gap_over_k():
    # request 1: first token at 1.0, then a burst of 8 at 1.8, then 1 at 1.9
    log = [(1.0, 1, 1), (1.8, 1, 8), (1.9, 1, 1)]
    gaps = stats.token_gaps(log, 0.0, 10.0)
    assert len(gaps) == 9
    assert gaps[:8] == [pytest.approx(0.1)] * 8
    assert gaps[8] == pytest.approx(0.1)


def test_gaps_are_per_request_and_only_inside_the_window():
    log = [(0.5, 1, 1), (0.6, 2, 1), (1.5, 1, 2), (1.7, 2, 1), (9.0, 1, 4)]
    gaps = stats.token_gaps(log, 1.0, 2.0)
    assert sorted(gaps) == [pytest.approx(0.5), pytest.approx(0.5),
                            pytest.approx(1.1)]


def test_first_delivery_gives_no_gap():
    assert stats.token_gaps([(1.0, 1, 3)], 0.0, 2.0) == []


def test_tokens_in_window_is_half_open():
    log = [(0.0, 1, 2), (1.0, 1, 3), (2.0, 1, 5)]
    assert stats.tokens_in_window(log, 0.0, 2.0) == 5


def test_ttft_counts_from_the_scheduled_arrival():
    # request 7 was due at 1.0 but the engine was stalled until 1.4
    log = [(1.4, 7, 1), (1.5, 7, 8)]
    assert stats.ttfts(log, {7: 1.0}, 0.0, 2.0) == [pytest.approx(0.4)]


def test_a_request_with_no_token_at_the_close_stands_at_its_wait_so_far():
    # 8's first token comes after the window closes at 2.0, 9 never gets
    # one, 10 is due after the close: 8 and 9 count with a lower bound
    # of their wait, so pushing a request out of the window cannot
    # improve the median; 10 is not of this window
    log = [(1.4, 7, 1), (1.5, 7, 8), (3.0, 8, 1)]
    due = {7: 1.0, 8: 1.9, 9: 0.5, 10: 2.5}
    tt = stats.ttfts(log, due, 0.0, 2.0)
    assert tt == [pytest.approx(0.4), pytest.approx(0.1), pytest.approx(1.5)]
    on_time = stats.ttfts([(1.4, 7, 1), (1.95, 8, 1), (0.6, 9, 1)], due, 0.0, 2.0)
    assert stats.percentile(tt, 50) > stats.percentile(on_time, 50)


def test_quantile_multiset_is_fixed_bounded_and_heavy_headed():
    a = stats.quantile_multiset(64, 256, 64, 1.6)
    assert a == stats.quantile_multiset(64, 256, 64, 1.6)
    assert min(a) >= 64 and max(a) <= 256 and len(a) == 64
    assert statistics.median(a) < (64 + 256) / 2   # mass near the low end
    assert stats.quantile_multiset(0, 10, 5, 1.0) == [1, 3, 5, 7, 9]


def test_exponential_gaps_sum_to_n_means():
    g = stats.exponential_gaps(0.5, 40)
    assert sum(g) == pytest.approx(20.0)
    assert min(g) > 0 and max(g) / statistics.median(g) > 3   # a tail


def test_spread_is_iqr_over_median_by_statistics_quantiles():
    xs = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    q = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q[2] - q[0]) / 102.5)
    assert math.isclose(stats.spread([5.0] * 6), 0.0)

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


def test_the_closed_loop_rate_is_the_mean_over_the_windows_last_tenth():
    """One form for every cell that reports it: no mix chooses the share."""
    line = [(0.63 * k + s, d, n) for k in range(70)
            for s, d, n in [(0.0, 0.39, 32), (0.39, 0.12, 192), (0.51, 0.12, 192)]]
    assert stats.RATE_OVER_LAST == 0.1
    assert stats.closed_loop_rate(line, 0.0, 40.0) == pytest.approx(
        stats.mean_rate_over_closes(line, 0.0, 36.0, 40.0))
    assert stats.closed_loop_rate(line, 2.0, 42.0) == pytest.approx(
        stats.mean_rate_over_closes(line, 2.0, 38.0, 42.0))
    assert stats.closed_loop_rate([], 0.0, 40.0) == 0.0


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


# a closed loop's steps as (start, seconds, tokens): gather steps that hand
# out 32 tokens in 0.39 s between decode bursts that hand out 192 in 0.12 s
TIMELINE = [(0.00, 0.39, 32), (0.40, 0.12, 192), (0.53, 0.12, 192),
            (0.66, 0.39, 32), (1.06, 0.12, 192)]


def test_the_step_that_straddles_the_close_counts_for_its_share_inside():
    whole = 32 + 192 + 192
    assert stats.tokens_prorated(TIMELINE, 0.0, 0.655) == pytest.approx(whole)
    # the close in the middle of the second burst: half of its tokens
    assert stats.tokens_prorated(TIMELINE, 0.0, 0.59) == pytest.approx(
        32 + 192 + 96)
    # between two steps nothing is added; past the last, everything
    assert stats.tokens_prorated(TIMELINE, 0.0, 0.652) == pytest.approx(whole)
    assert stats.tokens_prorated(TIMELINE, 0.0, 9.0) == 32 + 192 * 3 + 32
    assert stats.tokens_prorated(TIMELINE, 0.0, 0.0) == 0.0


@pytest.mark.parametrize("end,tokens,seconds", [
    (0.65, 192, 0.12),          # the end of a decode burst
    (1.05, 32, 0.39),           # the end of a gather step
    (0.52, 192, 0.12)])
def test_moving_the_close_by_a_millisecond_moves_the_count_by_a_milliseconds_work(
        end, tokens, seconds):
    """Across a step's end the count moves by that step's tokens x 1 ms /
    its duration, not by the step: the rate is continuous in the close.
    Whole deliveries jump by the burst."""
    before = stats.tokens_prorated(TIMELINE, 0.0, end - 0.001)
    after = stats.tokens_prorated(TIMELINE, 0.0, end)
    assert after - before == pytest.approx(tokens * 0.001 / seconds)
    assert stats.tokens_prorated(TIMELINE, 0.0, end + 0.001) == pytest.approx(after)
    whole = lambda t1: sum(n for s, d, n in TIMELINE if s + d < t1)  # noqa: E731
    assert whole(end + 1e-9) - whole(end - 0.001) == tokens


def test_prorating_holds_at_the_open_too_and_skips_empty_steps():
    assert stats.tokens_prorated(TIMELINE, 0.195, 0.40) == pytest.approx(16)
    assert stats.step_share_inside(0.4, 0.12, 0.0, 0.46) == pytest.approx(0.5)
    assert stats.step_share_inside(2.0, 0.1, 0.0, 1.0) == 0.0
    assert stats.step_share_inside(0.5, 0.0, 0.0, 1.0) == 1.0   # no duration
    assert stats.tokens_prorated([(0.9, 0.2, 0)], 0.0, 1.0) == 0.0


def test_the_mean_over_closes_of_a_steady_timeline_is_its_rate():
    steady = [(0.1 * i, 0.1, 50) for i in range(400)]         # 500 tokens/s
    assert stats.mean_rate_over_closes(steady, 0.0, 36.0, 40.0) == pytest.approx(500.0)
    for c_lo in (0.0, 40.0):            # from the open itself; one close alone
        with pytest.raises(ValueError):
            stats.mean_rate_over_closes(steady, 0.0, c_lo, 40.0)


def test_the_mean_over_closes_is_the_integral_of_the_prorated_rate():
    closes = [0.9 + 0.0001 * i for i in range(2001)]           # 0.9 .. 1.1
    by_hand = sum(stats.tokens_prorated(TIMELINE, 0.0, c) / c for c in closes) / len(closes)
    assert stats.mean_rate_over_closes(TIMELINE, 0.0, 0.9, 1.1) == pytest.approx(by_hand, rel=1e-4)


def test_a_lag_reads_once_in_the_mean_over_closes_and_threefold_at_one_close():
    """Gather steps of 0.39 s (32 tokens) and bursts of 0.12 s (192): the
    same timeline 0.05 s late. One close inside a burst loses the burst's
    rate x the lag; the mean over 4 s of closes loses the mean rate x the lag."""
    cycle = [(0.0, 0.39, 32), (0.39, 0.12, 192), (0.51, 0.12, 192)]      # 0.63 s, 416 tokens
    line = [(s + 0.63 * k, d, n) for k in range(70) for s, d, n in cycle]
    late = [(s + 0.05, d, n) for s, d, n in line]
    mean_rate = 416 / 0.63
    close = 63 * 0.63 + 0.45                                      # inside a burst
    one = (stats.tokens_prorated(line, 0.0, close) - stats.tokens_prorated(late, 0.0, close))
    assert one == pytest.approx(0.05 * 192 / 0.12)                # 80 tokens: 2.4 x the mean
    many = (stats.mean_rate_over_closes(line, 0.0, close - 4.0, close)
            - stats.mean_rate_over_closes(late, 0.0, close - 4.0, close)) * (close - 2.0)
    assert many == pytest.approx(0.05 * mean_rate, rel=0.1)       # 33 tokens

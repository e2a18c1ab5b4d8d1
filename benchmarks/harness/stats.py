"""Percentile, gap and rate arithmetic on plain lists: no JAX, no clock.

The serving drivers keep one ``Delivery`` log (when, which request, how
many tokens); everything a user would feel is reduced from it here, so a
hand-made timeline can pin each rule in a test.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def token_gaps(deliveries: Iterable[Tuple[float, int, int]], t0: float,
               t1: float) -> List[float]:
    """Per-token gaps a client sees. ``deliveries`` are ``(time, request,
    tokens)`` in time order. For every delivery inside ``[t0, t1)`` after a
    request's first: the time since that request's previous delivery over
    the tokens of this one, once per token (K tokens -> K samples of
    gap/K). A request's first delivery gives samples only for its tokens
    beyond the first, at gap 0 measured from itself: none."""
    last: Dict[int, float] = {}
    gaps: List[float] = []
    for t, rid, n in deliveries:
        prev = last.get(rid)
        last[rid] = t
        if prev is None or n <= 0 or not (t0 <= t < t1):
            continue
        gaps.extend([(t - prev) / n] * n)
    return gaps


def tokens_in_window(deliveries: Iterable[Tuple[float, int, int]], t0: float,
                     t1: float) -> int:
    return sum(n for t, _, n in deliveries if t0 <= t < t1)


def first_token_times(deliveries: Iterable[Tuple[float, int, int]]
                      ) -> Dict[int, float]:
    first: Dict[int, float] = {}
    for t, rid, n in deliveries:
        if n > 0 and rid not in first:
            first[rid] = t
    return first


def ttfts(deliveries, scheduled: Dict[int, float], t0: float, t1: float
          ) -> List[float]:
    """Time to first token of *every* request due in ``[t0, t1)``: the
    first-token time minus the scheduled arrival, and for a request with no
    token yet when the window closes (not yet sent, queued, or still in
    prefill) its wait so far, ``t1 - scheduled`` — a lower bound, so a
    request pushed past the window makes the median and the tail worse,
    never better."""
    first = first_token_times(deliveries)
    return [(first[rid] if first.get(rid, t1) < t1 else t1) - due
            for rid, due in scheduled.items() if t0 <= due < t1]


def quantile_multiset(lo: float, hi: float, n: int, shape: float) -> List[int]:
    """``n`` lengths: the mid-quantiles of a distribution on ``[lo, hi]``
    whose density falls towards ``hi`` (``shape`` > 1: heavier head, long
    tail; 1: uniform). x = lo + (hi - lo) * u**shape for u = (i + .5)/n.
    A fixed multiset: every seed gets these n values, in the mix's one order."""
    return [int(round(lo + (hi - lo) * ((i + 0.5) / n) ** shape))
            for i in range(n)]


def exponential_gaps(mean: float, n: int) -> List[float]:
    """``n`` gaps: the mid-quantiles of an exponential with that mean,
    rescaled so that they sum to ``n * mean`` exactly."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    k = n / sum(raw)
    return [mean * k * r for r in raw]


def spread(values: Sequence[float]) -> float:
    """The contract's spread: interquartile distance over the median, by
    ``statistics.quantiles(values, n=4)``."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)

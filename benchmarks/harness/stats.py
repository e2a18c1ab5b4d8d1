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


def step_share_inside(start: float, duration: float, t0: float,
                      t1: float) -> float:
    """The share of the step ``[start, start + duration]`` that lies in
    ``[t0, t1]``: 1 for a step inside, 0 for one outside, and for the step
    that straddles a border the share of its duration on the inside."""
    if duration <= 0.0:
        return 1.0 if t0 <= start < t1 else 0.0
    inside = min(start + duration, t1) - max(start, t0)
    return min(1.0, max(0.0, inside / duration))


def tokens_prorated(steps: Iterable[Tuple[float, float, int]], t0: float,
                    t1: float) -> float:
    """Tokens delivered in ``[t0, t1]`` as a *continuous* function of the
    borders. ``steps`` are ``(start, duration, tokens)``: a step hands out
    its tokens when it ends, and they are the work of its whole duration,
    so the step that straddles the close counts for the share of its
    duration inside. Counting whole deliveries instead makes the rate step
    by one delivery (a decode burst hands out 192-256 tokens at once) as
    the close moves across a step's end by a millisecond."""
    return sum(n * step_share_inside(s, d, t0, t1) for s, d, n in steps if n)


# the closes a closed loop's rate is the mean over: the window's last tenth.
# A constant of the yardstick, not of a mix: every cell that reports the
# rate reports the same quantity.
RATE_OVER_LAST = 0.1


def mean_rate_over_closes(steps: Sequence[Tuple[float, float, int]], t0: float,
                          c_lo: float, c_hi: float) -> float:
    """The mean, over every close ``c`` in ``[c_lo, c_hi]``, of the rate of
    the window ``[t0, c]``: ``tokens_prorated(steps, t0, c) / (c - t0)``.

    Every member is all the tokens over all the time of a window that
    opens at ``t0``. One close alone reads the timeline at one instant,
    where tokens come at 1,500 a second inside a decode burst and at 85
    inside a gather step, so a run that is 0.1 s behind (a stall inside one
    step) reads up to three times that lag; the mean over a few seconds of
    closes reads the lag once. Exact: the prorated count is linear between
    step borders, and the integral of (a + b x) / x is a ln x + b x.
    ``steps`` do not overlap (one engine thread)."""
    if not t0 < c_lo < c_hi:
        raise ValueError(f"closes [{c_lo}, {c_hi}] of a window opening at {t0}")
    borders = sorted({c_lo, c_hi} | {p for s, d, _ in steps for p in (s, s + d)
                                     if c_lo < p < c_hi})
    total = 0.0
    for a, b in zip(borders, borders[1:]):
        na, nb = tokens_prorated(steps, t0, a), tokens_prorated(steps, t0, b)
        slope = (nb - na) / (b - a)
        at_zero = na - slope * (a - t0)
        total += at_zero * math.log((b - t0) / (a - t0)) + slope * (b - a)
    return total / (c_hi - c_lo)


def closed_loop_rate(steps: Sequence[Tuple[float, float, int]], t0: float,
                     t1: float) -> float:
    """``serve_tokens_per_s`` of the window ``[t0, t1]``: the mean over the
    closes in its last ``RATE_OVER_LAST``. There is no other form of it."""
    return mean_rate_over_closes(steps, t0, t1 - RATE_OVER_LAST * (t1 - t0), t1)


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


def floored_gaps(gaps: Sequence[float], floor: float) -> List[float]:
    """``gaps`` with none under ``floor``, their count and their sum as
    they were: every shorter gap is raised to the floor, and what that
    adds is taken off the longest gaps, which are lowered to one common
    ceiling. The order of the gaps is kept."""
    total, n = sum(gaps), len(gaps)
    if floor * n > total:
        raise ValueError(f"{n} gaps of at least {floor} s do not fit into "
                         f"{total} s")
    out = [max(g, floor) for g in gaps]
    excess = sum(out) - total
    # lower the longest to the ceiling c with sum(max(0, g - c)) = excess
    desc = sorted(out, reverse=True)
    taken, c = 0.0, desc[0]
    for k, g in enumerate(desc[1:] + [floor], start=1):
        step = (c - g) * k
        if taken + step >= excess:
            c -= (excess - taken) / k
            break
        taken, c = taken + step, g
    return [min(g, c) for g in out]


def spread(values: Sequence[float]) -> float:
    """The contract's spread: interquartile distance over the median, by
    ``statistics.quantiles(values, n=4)``."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)

"""Operations and bytes the grouped expert products of one expert layer
need (``grouped_matmul``: gate, up and down projections of the experts held
here), from what was *routed*, not from what is held.

Bytes: the three matrices of every held expert **that got a row** once
(an expert no token chose is not read: counting all the held experts would
put the share over 100% whenever routing leaves some idle), and each routed
(token, expert) pair's rows in and out. Operations: three products of
hidden x expert-width a pair. At a few rows an expert the call is
memory-bound; at hundreds it turns compute-bound: the floor is the larger.
"""

from __future__ import annotations

from typing import Tuple

from benchmarks.harness.trace import kernel_name


def classify(event_name: str):
    """"gmm" for the ``grouped_matmul`` kernel's device events, else None."""
    return "gmm" if kernel_name(event_name) == "grouped_matmul" else None


def layer_calls(pairs: float, experts_hit: float, hidden: int, width: int,
                itemsize: int = 2) -> Tuple[float, float]:
    """``pairs`` (token, expert) pairs routed to held experts and
    ``experts_hit`` held experts with at least one row, summed over any
    number of layers and steps (means over steps may be fractions)."""
    flops = 3 * 2.0 * hidden * width * pairs
    weights = 3 * hidden * width * itemsize * experts_hit
    # a pair: x in twice, gate and up out, their product in, the result out
    rows = pairs * itemsize * (2 * hidden + 3 * width + hidden)
    return flops, float(weights + rows)

"""Operations and bytes the grouped products of *ungated* experts
(``down(act(up x))``: two matrices an expert, the ``nemotron_h`` family's
squared-ReLU experts) need in a training step, forward and backward, from
what was routed, not from what is held and not from how the program lays its
rows out. ``kernels/moe_grouped_train.py`` counts a SwiGLU expert's three
forward and six backward products; a floor counted so for these experts would
read half again too high.

A routed (token, expert) pair goes through two products of hidden x
expert-width forward (up, down) and four backward: each needs the gradient of
its input and of its matrix. What a checkpoint policy recomputes is not
counted. Bytes: the two matrices of every held expert **that got a row** read
in the forward, read in the backward and their gradients written once, and
each product's row operands read and its result written once.

``classify`` is ``moe_grouped_train``'s: the same two kernels run these
products (``"gmm"``: forward products, recomputed ones and input gradients;
``"dw"``: the matrices' gradients).
"""

from __future__ import annotations

from typing import Tuple

from benchmarks.kernels.moe_grouped_train import classify  # noqa: F401

FORWARD_PRODUCTS, BACKWARD_PRODUCTS = 2, 4


def step_calls(pairs: float, experts_hit: float, hidden: int, width: int,
               itemsize: int = 2) -> Tuple[float, float]:
    """``pairs`` (token, expert) pairs routed to held experts and
    ``experts_hit`` held experts with at least one row, summed over any
    number of expert blocks and steps (means may be fractions)."""
    products = FORWARD_PRODUCTS + BACKWARD_PRODUCTS
    flops = products * 2.0 * hidden * width * pairs
    # a matrix: read forward, read for the input gradient, gradient written
    weights = 3 * 2 * hidden * width * itemsize * experts_hit
    # a product reads one row operand and writes one row result, the
    # matrices' gradients read two row operands: per pair, forward 2 hidden
    # + 2 width, backward 4 hidden + 4 width
    rows = pairs * itemsize * 6 * (hidden + width)
    return flops, float(weights + rows)

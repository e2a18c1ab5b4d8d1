"""Operations and bytes the grouped expert products of expert layers need
in a *training* step (forward and backward), from what was routed, not from
what is held and not from how the program lays its rows out.

A routed (token, expert) pair goes through three products of hidden x
expert-width forward (gate, up, down) and six backward: each of the three
needs the gradient of its input and of its matrix. (ISSUE 43 writes "five
backward products", the count of flash attention's backward; a SwiGLU
expert's is six, and a floor counted with five would read 8% lower.) What a
checkpoint policy recomputes is not counted: the program's choice, not the
work's. Bytes: the three matrices of every held expert **that got a row**
read in the forward, read in the backward and their gradients written once
(an expert no token chose does nothing), and each product's row operands
read and its result written once. At a thousand rows an expert the products
are compute-bound; the floor is the larger of the two.

``classify``: ``"gmm"`` for the ``grouped_matmul`` kernel's device events
(forward products, recomputed ones and input gradients), ``"dw"`` for
``grouped_matmul_dw`` (the matrices' gradients); another implementation of
the same work is read by giving its kernels these classes.
"""

from __future__ import annotations

from typing import Tuple

from benchmarks.harness.trace import kernel_name

KERNELS = {"grouped_matmul": "gmm", "grouped_matmul_dw": "dw"}
FORWARD_PRODUCTS, BACKWARD_PRODUCTS = 3, 6


def classify(event_name: str):
    return KERNELS.get(kernel_name(event_name))


def step_calls(pairs: float, experts_hit: float, hidden: int, width: int,
               itemsize: int = 2) -> Tuple[float, float]:
    """``pairs`` (token, expert) pairs routed to held experts and
    ``experts_hit`` held experts with at least one row, summed over any
    number of expert layers and steps (means may be fractions)."""
    products = FORWARD_PRODUCTS + BACKWARD_PRODUCTS
    flops = products * 2.0 * hidden * width * pairs
    # a matrix: read forward, read for the input gradient, gradient written
    weights = 3 * 3 * hidden * width * itemsize * experts_hit
    # a product reads one row operand and writes one row result, the
    # matrices' gradients read two row operands: per pair, forward 3 hidden
    # + 3 width, backward 6 hidden + 6 width
    rows = pairs * itemsize * 9 * (hidden + width)
    return flops, float(weights + rows)

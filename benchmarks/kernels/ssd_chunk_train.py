"""Operations and bytes the Mamba-2 scan needs in a *training* step (forward
and backward), from the tokens, heads, head size, groups, state and chunk:
the work, whatever implements it.

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t

Counted in the chunked form at the causal half, the cheapest way known to
do it with matrix products: within a chunk ``C . B`` a group and the masked
product with ``x`` a head, each over ``(chunk + 1) / 2`` earlier tokens a
token; a chunk's state ``x (x) B`` and its read-out ``S C``, ``head x
state`` a head a token each. What carries the states from chunk to chunk is
not counted (a loop does it with no product), nor the elementwise decays.
The backward of a product is two products: backward twice the forward.
What a checkpoint policy recomputes is not counted: the program's choice,
not the work's.

Bytes: forward ``x``, ``B``, ``C`` read in the activations' type and ``dt``
in float32, ``y`` written in float32, and every chunk's state (float32)
written once and read once; backward the same operands and ``dy`` read, the
states read, the four gradients written and the states' gradients written
and read. The scan is bound by these, not by its operations.

``classify`` reads an operation's *scope path* (the ``op_name`` the compiler
keeps), not a kernel's name: whatever runs under ``jax.named_scope
("ssd_chunk")`` — XLA's fusions today, a Pallas kernel later — is the scan,
forward, recomputed or backward by JAX's own marks.
"""

from __future__ import annotations

from typing import Tuple

from benchmarks.harness.train_step import scope_elements

SCOPE = "ssd_chunk"


def classify(op_name: str):
    """"fwd", "recompute" or "bwd" for an operation under the scan's scope,
    None for any other."""
    if not op_name:
        return None
    els = scope_elements(op_name)
    if SCOPE not in els:
        return None
    if "rematted_computation" in els:
        return "recompute"
    return "bwd" if "transpose(" in op_name else "fwd"


def step_calls(tokens: float, heads: int, head_dim: int, groups: int,
               state: int, chunk: int, itemsize: int = 2
               ) -> Tuple[float, float]:
    """``tokens`` tokens through one scan, forward and backward (summed
    over any number of blocks and steps by the caller)."""
    half = (chunk + 1) / 2.0
    macs = half * (groups * state + heads * head_dim) \
        + 2.0 * heads * head_dim * state
    flops = 3 * 2.0 * macs * tokens
    inner, bc = heads * head_dim, 2 * groups * state
    operands = (inner + bc) * itemsize + heads * 4      # x, B, C; dt
    states = heads * head_dim * state * 4 / chunk       # a chunk's, a token
    fwd = operands + inner * 4 + 2 * states
    bwd = (operands + inner * 4 + states) + (operands + 2 * states)
    return flops, float((fwd + bwd) * tokens)

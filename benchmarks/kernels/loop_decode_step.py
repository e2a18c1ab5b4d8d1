"""Operations and bytes one decode token step of a looped stack needs: every
live sequence's new token through ``total_ut_steps`` passes of the same
``num_hidden_layers`` layers, then the head.

Counted from the model and not from an implementation. Bytes: the layers'
matrices once a **pass** (a pass cannot start before the one before it has
ended, so what a pass reads of the weights the next reads again: nothing on
the chip holds 4.9 GB between them), the head once, and the keys and values
of every context token in every one of the token's ``cache_layers`` layer
slots (one a pass and layer) once. Norm gains, the embedding's rows, the
step's own activations and the exit gate (which the step programs at
threshold 1 do not compute) are not counted: together under a thousandth.
Operations: 2 a weight and row in every pass, 2 a head weight and row, and
per pass, layer and query head two products of ``head_dim`` a context token.
At about one operation a byte the floor is the memory's.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def layer_weights(a) -> int:
    """One layer's matrices: four attention projections and three of the
    feed-forward."""
    h, d = a.hidden_size, a.head_dim
    return (h * d * (2 * a.num_attention_heads + 2 * a.num_key_value_heads)
            + 3 * h * a.intermediate_size)


def step(a, context_lens: Iterable[int], itemsize: int = 2
         ) -> Tuple[float, float]:
    """``(operations, bytes)`` of one token step over the sequences whose
    contexts (the new token included) are ``context_lens``; dead slots (0)
    cost nothing."""
    ctx = [int(c) for c in context_lens if c > 0]
    rows, tokens = len(ctx), sum(ctx)
    stack = a.total_ut_steps * a.num_hidden_layers * layer_weights(a)
    head = a.hidden_size * a.vocab_size
    flops = 2.0 * rows * (stack + head) + (
        2 * 2.0 * a.head_dim * a.num_attention_heads * tokens
        * a.cache_layers)
    kv = 2 * tokens * a.cache_layers * a.num_key_value_heads * a.head_dim
    return flops, float(itemsize * (stack + head + kv))

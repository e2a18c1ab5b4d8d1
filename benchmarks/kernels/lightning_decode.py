"""Operations and bytes one ``lightning_decode`` call needs: one token of
lightning attention (``S <- a S + k v^T; o = S^T q``) for each live sequence
of a batch, one lightning layer.

Bytes: each live sequence's state read once and written once (heads x
head_dim x head_dim float32, twice), the vectors in (q, k, v and the head's
decay) and the output out. A kernel that also moves the rows of the batch
that hold no sequence (they go to the scratch slot), or that takes the
decay as a lane vector, moves more and shows a lower share. Operations: per
head the decay, the rank-one update and ``S^T q``: three passes of two
operations over head_dim x head_dim. The call is memory-bound by two orders
of magnitude.
"""

from __future__ import annotations

from typing import Tuple

from benchmarks.harness.trace import kernel_name


def classify(event_name: str):
    """"decode" for the ``lightning_decode`` kernel's device events."""
    return "decode" if kernel_name(event_name) == "lightning_decode" else None


def call(live_seqs: int, heads: int, head_dim: int) -> Tuple[float, float]:
    state = heads * head_dim * head_dim
    flops = 3 * 2.0 * state * live_seqs
    vectors = heads * (4 * head_dim + 1) * 4
    return flops, float(live_seqs * (2 * state * 4 + vectors))


def lightning_layers(arch) -> int:
    return sum(m == "lightning-attn" for m in arch.mixers)

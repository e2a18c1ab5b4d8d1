"""Operations and bytes one ``gdn_decode`` call needs: one token of the
gated delta rule for each live sequence of a batch, one recurrent layer.

Bytes: each live sequence's state read once and written once (heads x
key_dim x value_dim float32, twice), the vectors in (q, k, v and the two
per-head scalars) and the output out. A kernel that also moves the rows of
the batch that hold no sequence (they go to the scratch slot), or that
takes per-head scalars as lane vectors, moves more and shows a lower
share. Operations: per head, the decay, ``S^T k``, the rank-one update and
``S^T q``: four passes of two operations over key_dim x value_dim. The call
is memory-bound by two orders of magnitude.
"""

from __future__ import annotations

from typing import Tuple

from benchmarks.harness.trace import kernel_name


def classify(event_name: str):
    """"decode" for the ``gdn_decode`` kernel's device events, else None."""
    return "decode" if kernel_name(event_name) == "gdn_decode" else None


def call(live_seqs: int, heads: int, key_dim: int, value_dim: int
         ) -> Tuple[float, float]:
    state = heads * key_dim * value_dim
    flops = 4 * 2.0 * state * live_seqs
    vectors = heads * (2 * key_dim + 2 * value_dim + 2) * 4
    return flops, float(live_seqs * (2 * state * 4 + vectors))


def recurrent_layers(arch) -> int:
    return arch.num_hidden_layers - (arch.num_hidden_layers
                                     // arch.full_attention_interval)

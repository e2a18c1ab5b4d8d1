"""Operations and bytes one token step of the learned selector needs in one
full layer: every head's indexer query against the indexer key of each token
the query can see, and the choice of the highest.

A visible token costs its key's ``index_head_dim * itemsize`` bytes **once**
(a program that gathers a sequence's keys into a copy first, reads the pages
behind the context's end, or writes the heads' scores out before it adds
them up reads more and shows a lower share) and ``2 * index_n_heads *
index_head_dim`` operations (one product a head; the ReLU, the heads' weights
and the choice itself are not counted: a perfect selection costs no pass of
its own). Queries in (``index_n_heads * index_head_dim`` and the heads'
weights) besides. At 64 operations a byte against the chip's ridge of 240
the floor is memory's.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def call(context_lens: Iterable[int], heads: int, dim: int,
         itemsize: int = 2) -> Tuple[float, float]:
    flops = bytes_ = 0.0
    for n in (int(c) for c in context_lens if c > 0):
        flops += 2.0 * heads * dim * n
        bytes_ += n * dim * itemsize + heads * (dim * itemsize + 4)
    return flops, float(bytes_)


def sizes(arch) -> Tuple[int, int]:
    """(indexer heads, key width) of an architecture with the selector."""
    return arch.index_n_heads, arch.index_head_dim


def full_layers(arch) -> int:
    return sum(arch.is_full(l) for l in range(arch.num_hidden_layers))

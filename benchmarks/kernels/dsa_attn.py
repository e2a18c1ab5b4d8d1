"""Operations and bytes one token step of latent attention over the
*selected* tokens needs in one full layer, in its absorbed form.

A context of ``n`` tokens attends over ``min(n, index_topk)`` of them. A
chosen token costs its latent's ``latent_dim * itemsize`` bytes **once** (576
values: not the pool's lane-padded row of 640, not a gathered copy written
out and read back, and not the tokens that were not chosen, which a program
that walks the whole context under a mask reads too) and ``2 * heads * (latent_dim +
value_dim)`` operations (a score over the whole latent, a weighted sum over
its compressed part). Queries in and outputs out a sequence besides. The
absorb products are not the kernel's. Memory-bound.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmarks.kernels import mla_decode


def rows_read(n: int, topk: int) -> int:
    return min(int(n), topk)


def call(context_lens: Iterable[int], topk: int, heads: int, latent_dim: int,
         value_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    return mla_decode.call([rows_read(c, topk) for c in context_lens],
                           heads, latent_dim, value_dim, itemsize)


def sizes(arch) -> Tuple[int, int, int, int]:
    """(index_topk, heads, latent_dim, value_dim) of the full layers."""
    return (arch.index_topk,) + mla_decode.sizes(arch)

"""Operations and bytes one token step of block-sparse decode attention
needs in one ``minicpm4`` layer: selection against the compressed keys, then
attention over the pages chosen.

A context of ``n`` tokens whose newest position is past ``dense_len`` reads,
for each KV head, the compressed keys of its whole windows (``(n - kernel) //
stride + 1`` vectors) and the keys and values of the ``min(topk, blocks
visible)`` blocks it chooses (the newest block counted whole: at most one
page over); a context below ``dense_len`` reads all its keys and values and
no compressed key. Queries in and outputs out besides. A program that reads
a chosen page once for each KV head (both heads' halves each time), or the
compressed keys of windows that are not whole, reads more and shows a lower
share. Operations: two products per (query head, token read) and one per
(query head, window). Memory-bound.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def blocks_read(n: int, sizes) -> Tuple[int, int]:
    """(blocks chosen, blocks visible) of a context of ``n`` tokens, each
    for one KV head; ``sizes`` in the reference's order (kernel, stride,
    block, init, window, topk, dense_len)."""
    _, _, block, _, _, topk, dense_len = sizes
    visible = -(-n // block)
    return (min(topk, visible) if n - 1 >= dense_len else visible), visible


def call(context_lens: Iterable[int], sizes, n_q: int, n_kv: int, d: int,
         itemsize: int = 2) -> Tuple[float, float]:
    kernel, stride, block = sizes[:3]
    dense_len = sizes[6]
    flops = bytes_ = 0.0
    for n in (int(c) for c in context_lens if c > 0):
        sparse = n - 1 >= dense_len
        tokens = blocks_read(n, sizes)[0] * block if sparse else n
        windows = max(0, (n - kernel) // stride + 1) if sparse else 0
        flops += 2.0 * d * n_q * (2 * tokens + windows)
        bytes_ += (2 * tokens + windows) * n_kv * d * itemsize
        bytes_ += 2 * n_q * d * itemsize
    return flops, float(bytes_)


def sparse_layers(arch) -> int:
    return sum(m == "minicpm4" for m in arch.mixers)

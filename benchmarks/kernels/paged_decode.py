"""Operations and bytes one paged decode-attention call needs: one new
query token per sequence against that sequence's cached context.

Bytes: the keys and values of every context token once (what the pages
hold for the batch's contexts), the queries in and the outputs out. A
kernel that reads whole pages, or pads the batch, reads more and shows a
lower share. Operations: two products per (query head, context token).
"""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmarks.harness.trace import kernel_name


def classify(event_name: str):
    """"decode" for the ``paged_decode`` kernel's device events (by the
    name the program gives the kernel), else None."""
    return "decode" if kernel_name(event_name) == "paged_decode" else None


def call(context_lens: Iterable[int], n_q: int, n_kv: int, d: int,
         itemsize: int = 2) -> Tuple[float, float]:
    ctx = [int(c) for c in context_lens if c > 0]
    tokens = sum(ctx)
    flops = 2 * 2.0 * d * n_q * tokens
    kv = 2 * tokens * n_kv * d * itemsize
    qo = 2 * len(ctx) * n_q * d * itemsize
    return flops, float(kv + qo)

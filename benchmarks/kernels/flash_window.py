"""Operations and bytes one *windowed* flash-attention call needs, from its
shapes: causal attention under a sliding window, where key j is visible to
query i iff 0 <= i - j < window.

Counted as ``kernels/flash.py`` counts the full kernel: what the algorithm
needs, not what an implementation does. The pairs are the band's, not the
triangle's: ``sum_i min(i + 1, window)`` (query, key) pairs a head of one
sequence. The forward is two matrix products a pair, the backward of
memory-efficient attention five. A kernel that multiplies the masked corners
of its blocks, or a whole block the window does not reach, reads a lower
share, never a higher. Bytes: every operand read once and every result
written once (a window shorter than the sequence does not shorten them:
every key is in some query's window).

``classify`` tells the windowed kernels' device events from the full ones'
by the name the program gives them (``flash_window_fwd``, and the two halves
of a backward, ``flash_window_bwd_dkdv`` and ``flash_window_bwd_dq``).
"""

from __future__ import annotations

from typing import Tuple

from benchmarks.harness.trace import kernel_name
from benchmarks.kernels import flash

KERNELS = {"flash_window_fwd": "fwd", "flash_window_bwd_dkdv": "bwd",
           "flash_window_bwd_dq": "bwd"}


def classify(event_name: str):
    """"fwd", "bwd" (one of its two kernels) or None."""
    return KERNELS.get(kernel_name(event_name))


def band_pairs(seq: int, window: int) -> float:
    """(query, key) pairs one head of one sequence attends over."""
    w = min(window, seq)
    return w * (w + 1) / 2.0 + float(seq - w) * w


def _scaled(call: Tuple[float, float], seq: int, window: int
            ) -> Tuple[float, float]:
    """The full causal call's operations scaled to the band; its bytes."""
    ops, nbytes = call
    return ops * band_pairs(seq, window) / (seq * (seq + 1) / 2.0), nbytes


def fwd(batch: int, seq: int, n_q: int, n_kv: int, d: int, window: int,
        itemsize: int = 2) -> Tuple[float, float]:
    return _scaled(flash.fwd(batch, seq, n_q, n_kv, d, True, itemsize), seq,
                   window)


def bwd(batch: int, seq: int, n_q: int, n_kv: int, d: int, window: int,
        itemsize: int = 2) -> Tuple[float, float]:
    return _scaled(flash.bwd(batch, seq, n_q, n_kv, d, True, itemsize), seq,
                   window)

"""Operations and bytes one flash-attention call needs, from its shapes.

Counted: what the algorithm needs, not what an implementation does. The
forward is two matrix products per (query, visible key) pair; the backward
of memory-efficient attention is five (scores again, dV, dP, dQ, dK). A
kernel that recomputes more than that reads a lower share, never a higher.
Bytes: every operand read once and every result written once.

``classify`` tells the kernels' device events apart in a trace by the
name the program gives each kernel (``flash_fwd``, and the two halves of a
backward, ``flash_bwd_dkdv`` and ``flash_bwd_dq``), so that a program with
other kernels beside them is read the same.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmarks.harness.trace import kernel_name

KERNELS = {"flash_fwd": "fwd", "flash_bwd_dkdv": "bwd", "flash_bwd_dq": "bwd"}


def classify(event_name: str):
    """"fwd", "bwd" (one of its two kernels) or None."""
    return KERNELS.get(kernel_name(event_name))


def _pairs(seq: int, causal: bool) -> float:
    """(query, key) pairs one head of one sequence attends over."""
    return seq * (seq + 1) / 2.0 if causal else float(seq) * seq


def fwd(batch: int, seq: int, n_q: int, n_kv: int, d: int, causal: bool = True,
        itemsize: int = 2) -> Tuple[float, float]:
    flops = 2 * 2.0 * d * _pairs(seq, causal) * n_q * batch
    q = o = batch * seq * n_q * d * itemsize
    kv = 2 * batch * seq * n_kv * d * itemsize
    lse = batch * seq * n_q * 4
    return flops, float(q + kv + o + lse)


def bwd(batch: int, seq: int, n_q: int, n_kv: int, d: int, causal: bool = True,
        itemsize: int = 2) -> Tuple[float, float]:
    flops = 5 * 2.0 * d * _pairs(seq, causal) * n_q * batch
    q = batch * seq * n_q * d * itemsize          # q, o, do read; dq written
    kv = 2 * batch * seq * n_kv * d * itemsize    # k, v read; dk, dv written
    lse = batch * seq * n_q * 4
    return flops, float(4 * q + 2 * kv + lse)


def floor_seconds(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc, tm = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")

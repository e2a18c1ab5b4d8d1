"""Compiled-HLO collective wire-byte accounting.

The qgZ claim (reference blogs/zeropp: ~4x less gradient-reduction
traffic via int8/int4 wire, runtime/comm/coalesced_collectives.py:31)
should be checkable from the program XLA actually compiled, not from one
instruction match. This module parses an HLO text dump and sums the
output bytes of every cross-device collective, keyed by op kind and
element type — tests and docs divide full-width vs quantized totals.

Byte accounting uses the collective's OUTPUT tensor(s): for all-to-all,
all-gather, collective-permute and all-reduce the output is the moved
payload (within a constant factor per algorithm); comparing two programs
of the same structure cancels the constant.
"""

from __future__ import annotations

import re
from typing import Dict, Tuple

_DTYPE_BITS = {
    "pred": 8, "s4": 4, "u4": 4, "s8": 8, "u8": 8, "s16": 16, "u16": 16,
    "f16": 16, "bf16": 16, "s32": 32, "u32": 32, "f32": 32,
    "s64": 64, "u64": 64, "f64": 64, "c64": 64, "c128": 128,
}

_COLLECTIVES = ("all-to-all", "all-reduce", "reduce-scatter",
                "all-gather", "collective-permute")

# one tensor type like  f32[8,128]{1,0:T(8,128)}  (layout suffix optional)
_TENSOR_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _tensor_bytes(dtype: str, dims: str) -> float:
    bits = _DTYPE_BITS.get(dtype)
    if bits is None:
        return 0.0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * bits / 8.0


def collective_wire_bytes(hlo_text: str) -> Dict[Tuple[str, str], float]:
    """Sum output bytes of every collective instruction in an HLO dump.

    Returns {(op_kind, dtype): bytes}. ``op_kind`` ∈ all-to-all /
    all-reduce / reduce-scatter / all-gather / collective-permute
    (``-start`` variants fold into their base kind; ``-done`` ops carry
    no new payload and are skipped).
    """
    out: Dict[Tuple[str, str], float] = {}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        _, _, rhs = line.partition("=")
        rhs = rhs.strip()
        # HLO line shape: `name = TYPE opcode(operands), attrs`; TYPE is
        # a tensor type or a tuple of them, between '=' and the opcode
        kind, op_pos, started = None, -1, False
        for c in _COLLECTIVES:
            m = re.search(rf"(?:^|\s){c}(-start)?\(", rhs[:400])
            if m and (op_pos == -1 or m.start() < op_pos):
                kind, op_pos, started = c, m.start(), bool(m.group(1))
        if kind is None:
            continue
        if re.search(r"-done\(", rhs[:400]):
            continue
        type_decl = rhs[:op_pos]
        tensors = [(d, dims) for d, dims in _TENSOR_RE.findall(type_decl)
                   if d in _DTYPE_BITS]
        if started:
            # async `-start` declares a tuple (operands..., results...,
            # u32 context...); summing all entries would double-count the
            # payload ~2x vs the sync form. Context tensors are scalar
            # u32[] — drop those (a genuinely scalar u32 *payload*, e.g.
            # a digest psum, is miscounted by 4 bytes; acceptable), then
            # keep the result half (operands and results pair up, so the
            # last half of the remaining entries — handles coalesced
            # variadic forms with N>1 operand/result pairs; a bare
            # non-tuple result, length 1, is kept whole).
            non_ctx = [(d, dims) for d, dims in tensors
                       if not (d == "u32" and not dims)]
            tensors = non_ctx[len(non_ctx) // 2:]
        for dtype, dims in tensors:
            key = (kind, dtype)
            out[key] = out.get(key, 0.0) + _tensor_bytes(dtype, dims)
    return out


def total_bytes(acct: Dict[Tuple[str, str], float],
                kinds: Tuple[str, ...] = _COLLECTIVES) -> float:
    return sum(v for (k, _), v in acct.items() if k in kinds)


def program_costs(compiled) -> Dict[str, float]:
    """Full cost picture of a compiled executable.

    Combines XLA's cost analysis (flops / bytes accessed /
    transcendentals — the roofline inputs) with this module's
    collective wire-byte accounting over the compiled HLO text. Any
    piece that a backend can't produce is reported as 0.0 rather than
    raising, so callers can always roofline what they have.
    """
    out = {"flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0,
           "collective_bytes": 0.0}
    try:
        cost = compiled.cost_analysis() or {}
        out["flops"] = float(cost.get("flops", 0.0))
        out["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
        out["transcendentals"] = float(cost.get("transcendentals", 0.0))
    except Exception:
        pass
    try:
        out["collective_bytes"] = total_bytes(
            collective_wire_bytes(compiled.as_text()))
    except Exception:
        pass
    return out


def quantized_fraction(acct: Dict[Tuple[str, str], float]) -> float:
    """Fraction of collective bytes moved at <=8-bit element width."""
    tot = total_bytes(acct)
    if tot == 0:
        return 0.0
    narrow = sum(v for (_, d), v in acct.items()
                 if _DTYPE_BITS.get(d, 32) <= 8)
    return narrow / tot

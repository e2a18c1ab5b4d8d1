"""Blockwise quantization kernels (INT8/INT4) + quantized collectives.

TPU-native equivalent of the reference's quantizer CUDA library
(csrc/quantization/{quantize.cu,quant_reduce.cu,swizzled_quantize.cu,
dequantize.cu} — 2,925 LoC) that powers ZeRO++:

  qwZ  — INT8 blockwise-quantized weight all-gather
         (docs/_tutorials/zeropp.md; partition_parameters.py:1446
         quantized all_gather_coalesced)
  qgZ  — quantized gradient reduce via all-to-all
         (runtime/comm/coalesced_collectives.py:31 all_to_all_quant_reduce)

Scheme: symmetric per-block scale (absmax / qmax), block along the last
dim. INT4 packs two nibbles per int8 byte. The Pallas kernel does
quantize + pack in VMEM (one HBM round-trip); a jnp path provides the
CPU/interpret fallback and the reference for tests.

The collectives (quantized_all_gather / quantized_psum_scatter) run
inside shard_map: quantize shard-locally → move int8 over ICI → dequant,
cutting wire bytes ~2x (bf16→int8) or ~4x (int4), the ZeRO++ headline.
(EQuARX, arXiv:2506.17615, is the published XLA analog of this design.)
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 256


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# jnp reference path (also the grad/fallback path)
# ---------------------------------------------------------------------------


def _quantize_ref(x, bits: int, block: int):
    orig_shape = x.shape
    n = x.shape[-1]
    assert n % block == 0, f"last dim {n} must divide block {block}"
    xb = x.reshape(*x.shape[:-1], n // block, block).astype(jnp.float32)
    qmax = (1 << (bits - 1)) - 1  # 127 / 7
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax / qmax)
    q = jnp.clip(jnp.round(xb / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q.reshape(orig_shape), scale[..., 0]


def _dequantize_ref(q, scale, bits: int, block: int, dtype):
    n = q.shape[-1]
    qb = q.reshape(*q.shape[:-1], n // block, block).astype(jnp.float32)
    out = qb * scale[..., None]
    return out.reshape(q.shape).astype(dtype)


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------


def _quant_kernel(x_ref, q_ref, s_ref, *, bits: int, block: int):
    x = x_ref[:].astype(jnp.float32)  # [rows, block]
    qmax = float((1 << (bits - 1)) - 1)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
    q_ref[:] = q.astype(jnp.int8)
    s_ref[:] = jnp.broadcast_to(scale, s_ref.shape)


def _dequant_kernel(q_ref, s_ref, out_ref, *, block: int):
    q = q_ref[:].astype(jnp.float32)
    out_ref[:] = (q * s_ref[:, :1]).astype(out_ref.dtype)


def quantize_blockwise(x: jax.Array, bits: int = 8,
                       block: int = DEFAULT_BLOCK
                       ) -> Tuple[jax.Array, jax.Array]:
    """x [..., N] → (int8 values [..., N], fp32 scales [..., N/block]).

    INT4 values occupy int8 storage in [-8, 7]; pack with pack_int4 for
    wire transport.
    """
    assert bits in (4, 8)
    orig_shape = x.shape
    n = x.shape[-1]
    if n % block != 0 or x.size % block != 0:
        return _quantize_ref(x, bits, min(block, n))
    rows = x.size // block
    x2 = x.reshape(rows, block)
    if _interpret() or rows % 8 != 0 or block % 128 != 0:
        q, s = _quantize_ref(x2, bits, block)
        return (q.reshape(orig_shape),
                s.reshape(*orig_shape[:-1], n // block))
    tile = min(rows, 256)
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, bits=bits, block=block),
        name="quantize_blockwise",
        # cdiv: a row count off the 256 grid keeps its tail rows (the
        # last tile is partial)
        grid=(pl.cdiv(rows, tile),),
        in_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((tile, block), lambda i: (i, 0)),
            pl.BlockSpec((tile, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, block), jnp.int8),
            jax.ShapeDtypeStruct((rows, 128), jnp.float32),
        ],
    )(x2)
    return (q.reshape(orig_shape),
            s[:, 0].reshape(*orig_shape[:-1], n // block))


def dequantize_blockwise(q: jax.Array, scale: jax.Array, bits: int = 8,
                         block: int = DEFAULT_BLOCK,
                         dtype=jnp.bfloat16) -> jax.Array:
    n = q.shape[-1]
    blk = block if n % block == 0 else min(block, n)
    return _dequantize_ref(q, scale, bits, blk, dtype)


def pack_int4(q: jax.Array) -> jax.Array:
    """[..., N] int8 nibbles → [..., N/2] packed bytes."""
    lo = q[..., 0::2].astype(jnp.uint8) & 0x0F
    hi = (q[..., 1::2].astype(jnp.uint8) & 0x0F) << 4
    return (lo | hi).astype(jnp.uint8)


def unpack_int4(p: jax.Array) -> jax.Array:
    lo = (p & 0x0F).astype(jnp.int8)
    hi = ((p >> 4) & 0x0F).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*p.shape[:-1], p.shape[-1] * 2)


# ---------------------------------------------------------------------------
# KV-cache helpers (per-head_dim-vector scale granularity)
# ---------------------------------------------------------------------------


def kv_quantize(x: jax.Array, bits=8) -> Tuple[jax.Array, jax.Array]:
    """Quantize a KV tensor [..., head_dim] with one fp32 scale per head
    vector: (int8 payload [..., head_dim], fp32 scales [...]).

    Block = head_dim so every (layer, block, row, k/v, head) vector carries
    its own scale — the granularity the paged cache stores alongside the
    int8 payload. Reuses the blockwise dispatch (Pallas on TPU when the
    tiling constraints hold, jnp reference on CPU CI).

    ``bits="fp8"`` stores e4m3 values instead of an integer grid — the
    quality midpoint between int8 and int4, via the fp_quantizer cast
    path (per-vector scale maps the absmax to the format's max normal).
    """
    hd = x.shape[-1]
    if bits == "fp8":
        from deepspeed_tpu.ops.fp_quantizer import fp_quantize

        q, s = fp_quantize(x, fmt="e4m3", group_size=hd)
        return q, s[..., 0]
    q, s = quantize_blockwise(x, bits=bits, block=hd)
    return q, s[..., 0]


def kv_dequantize(q: jax.Array, scale: jax.Array, bits=8,
                  dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of kv_quantize: (int8/fp8 [..., head_dim], fp32 [...]) →
    dtype — value-times-scale either way (fp8 payloads upcast exactly)."""
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


def kv_pack(q: jax.Array, bits) -> jax.Array:
    """Storage codec for the quantized KV pool: int8/fp8 values pass
    through; int4 packs two per byte (uint8 payload, last dim head_dim//2
    — the same nibble codec the disagg handoff wire uses)."""
    return pack_int4(q) if bits == 4 else q


def kv_unpack(p: jax.Array, bits) -> jax.Array:
    """Inverse of kv_pack: uint8 nibble payload → int8 values in [-8, 7];
    int8/fp8 payloads pass through."""
    return unpack_int4(p) if bits == 4 else p


# ---------------------------------------------------------------------------
# quantized collectives (shard_map bodies)
# ---------------------------------------------------------------------------


def quantized_all_gather(x: jax.Array, axis: str, bits: int = 8,
                         block: int = DEFAULT_BLOCK) -> jax.Array:
    """qwZ: all-gather with int8/int4 wire format (reference quantized
    weight all-gather, partition_parameters.py:1446). Call inside a
    shard_map body; gathers along dim 0."""
    dtype = x.dtype
    q, s = quantize_blockwise(x, bits=bits, block=block)
    if bits == 4:
        q = pack_int4(q)
    qg = lax.all_gather(q, axis, axis=0, tiled=True)
    sg = lax.all_gather(s, axis, axis=0, tiled=True)
    if bits == 4:
        qg = unpack_int4(qg)
    return dequantize_blockwise(qg, sg, bits=bits, block=block, dtype=dtype)


def quantized_psum_scatter(x: jax.Array, axis: str, bits: int = 8,
                           block: int = DEFAULT_BLOCK) -> jax.Array:
    """qgZ: gradient reduce with quantized wire format via all-to-all +
    local reduce (reference all_to_all_quant_reduce,
    runtime/comm/coalesced_collectives.py:31). Inside shard_map; scatters
    dim 0. Returns the mean-reduced shard in x.dtype."""
    n = jax.lax.axis_size(axis)
    shard = x.shape[0] // n
    q, s = quantize_blockwise(x, bits=bits, block=block)
    if bits == 4:
        q = pack_int4(q)
    # all-to-all: each rank receives its output-shard's slice from everyone
    qt = lax.all_to_all(q.reshape(n, shard, *q.shape[1:]), axis,
                        split_axis=0, concat_axis=0, tiled=False)
    st = lax.all_to_all(s.reshape(n, shard, *s.shape[1:]), axis,
                        split_axis=0, concat_axis=0, tiled=False)
    if bits == 4:
        qt = unpack_int4(qt)
    vals = _dequantize_ref(
        qt, st, bits, block if x.shape[-1] % block == 0 else min(block, x.shape[-1]),
        jnp.float32)
    return (vals.sum(axis=0) / n).astype(x.dtype)


def quantized_all_reduce(x: jax.Array, axis: str, bits: int = 8,
                         block: int = DEFAULT_BLOCK) -> jax.Array:
    """EQuARX-style quantized all-reduce (arXiv:2506.17615): quantize
    shard-local → int8 reduce-scatter with fp32 accumulation → int8
    all-gather of the reduced shards → dequant. Composes
    quantized_psum_scatter + quantized_all_gather so both wire phases move
    int8/int4 instead of bf16/fp32. Inside shard_map; reduces over `axis`
    and returns the full mean-reduced tensor on every rank.

    Pads dim 0 to a multiple of the axis size so arbitrary leading shapes
    reduce-scatter cleanly; padding is stripped after the gather.
    """
    n = jax.lax.axis_size(axis)
    d0 = x.shape[0]
    pad = (-d0) % n
    xp = x if pad == 0 else jnp.concatenate(
        [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0)
    shard = quantized_psum_scatter(xp, axis, bits=bits, block=block)
    full = quantized_all_gather(shard, axis, bits=bits, block=block)
    return full[:d0] if pad else full

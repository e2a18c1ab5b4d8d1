"""Domino: tensor parallelism with communication hidden behind compute.

Reference: ``deepspeed/runtime/domino/transformer.py:411``
(``DominoTransformer``) + ``domino/async_linear.py:47``
(``DominoAsyncColumnParallelLinear``) — row-split the batch into two
micro-chunks; launch chunk k's TP allreduce asynchronously and overlap it
with chunk k+1's compute, hiding up to 100% of TP communication.

TPU-native: XLA's latency-hiding scheduler overlaps a collective with any
compute that doesn't depend on it — what Domino engineers with CUDA
streams falls out of *graph structure* here. This module provides the
structure: the layer processes ``num_chunks`` independent batch slices
whose collective/compute chains don't depend on each other, so while
chunk 0's psum (after the row-parallel matmul) is on the ICI wire, chunk
1's column-parallel matmuls occupy the MXU. The explicit shard_map +
psum form (rather than GSPMD constraints) pins the collective placement
to exactly the Domino schedule.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel import topology
from deepspeed_tpu.utils.comms_logging import get_comms_logger

BATCH_SPEC = P(("dp", "fsdp", "ep"))


def _layer_norm(x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _chunk_attention(q, k, v, causal: bool):
    # local heads only (column-sharded qkv): plain sdpa per chunk
    d = q.shape[-1]
    scores = jnp.einsum("bsnd,btnd->bnst", q, k) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    if causal:
        s, t = scores.shape[-2:]
        mask = jnp.tril(jnp.ones((s, t), bool))
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", probs, v)


def domino_layer_params(rng, hidden: int, ffn: int, num_heads: int,
                        dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Weights for one Domino transformer layer ([in, out] layout)."""
    ks = jax.random.split(rng, 4)
    s = hidden ** -0.5
    return {
        "wqkv": (jax.random.normal(ks[0], (hidden, 3 * hidden)) * s
                 ).astype(dtype),
        "wo": (jax.random.normal(ks[1], (hidden, hidden)) * s).astype(dtype),
        "w1": (jax.random.normal(ks[2], (hidden, ffn)) * s).astype(dtype),
        "w2": (jax.random.normal(ks[3], (ffn, hidden)) * (ffn ** -0.5)
               ).astype(dtype),
    }


def _local_layer(params, x, *, num_heads: int, num_chunks: int,
                 causal: bool, tp_axis: str):
    """Runs inside shard_map: x [B_loc, S, H] full hidden; weights are the
    local TP shards (wqkv/w1 column = [H, 3H/p | F/p], wo/w2 row =
    [H/p, H | F→H])."""
    tp = jax.lax.psum(1, tp_axis)
    del tp
    B = x.shape[0]
    n_local = params["wqkv"].shape[1] // 3 // (x.shape[-1] // num_heads)
    hd = x.shape[-1] // num_heads

    chunks = jnp.split(x, num_chunks, axis=0)
    # phase 1: per-chunk attention up to the row-parallel projection —
    # each chunk ends in its own psum; chunks are mutually independent so
    # XLA overlaps chunk k+1's matmuls with chunk k's psum (the Domino
    # async-allreduce schedule).
    attn_out = []
    for cx in chunks:
        y = _layer_norm(cx)
        qkv = y @ params["wqkv"]  # column-parallel: [b, s, 3*Hl]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(*q.shape[:2], n_local, hd)
        k = k.reshape(*k.shape[:2], n_local, hd)
        v = v.reshape(*v.shape[:2], n_local, hd)
        o = _chunk_attention(q, k, v, causal)
        o = o.reshape(*o.shape[:2], n_local * hd)
        partial = o @ params["wo"]  # row-parallel partial sums
        full = jax.lax.psum(partial, tp_axis)
        attn_out.append(cx + full)

    # phase 2: per-chunk MLP, same overlap structure
    out = []
    for cx in attn_out:
        y = _layer_norm(cx)
        h = jax.nn.gelu(y @ params["w1"])  # column-parallel
        partial = h @ params["w2"]  # row-parallel
        full = jax.lax.psum(partial, tp_axis)
        out.append(cx + full)
    return jnp.concatenate(out, axis=0)


def domino_transformer_layer(params, x, *, num_heads: int,
                             num_chunks: int = 2, causal: bool = True,
                             tp_axis: str = "tp",
                             mesh=None) -> jax.Array:
    """One TP transformer layer with the Domino chunked schedule.

    params: domino_layer_params output, *unsharded* (global); x: [B, S, H]
    batch-sharded. The weights are sharded here (column specs for
    wqkv/w1, row specs for wo/w2) and the body runs under shard_map with
    explicit psums.
    """
    mesh = mesh or topology._GLOBAL_MESH
    if mesh is None or mesh.shape.get(tp_axis, 1) == 1:
        # single-chip fallback: same math, no collectives
        return _single_device_layer(params, x, num_heads=num_heads,
                                    causal=causal)
    get_comms_logger().record(
        "all_reduce", 2 * x.size * x.dtype.itemsize, tp_axis,
        log_name="domino_layer_allreduce")
    wspecs = {"wqkv": P(None, tp_axis), "wo": P(tp_axis, None),
              "w1": P(None, tp_axis), "w2": P(tp_axis, None)}
    fn = jax.shard_map(
        functools.partial(_local_layer, num_heads=num_heads,
                          num_chunks=num_chunks, causal=causal,
                          tp_axis=tp_axis),
        mesh=mesh,
        in_specs=(wspecs, BATCH_SPEC),
        out_specs=BATCH_SPEC,
        check_vma=False)
    return fn(params, x)


def _single_device_layer(params, x, *, num_heads: int, causal: bool):
    hd = x.shape[-1] // num_heads
    y = _layer_norm(x)
    qkv = y @ params["wqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(*q.shape[:2], num_heads, hd)
    k = k.reshape(*k.shape[:2], num_heads, hd)
    v = v.reshape(*v.shape[:2], num_heads, hd)
    o = _chunk_attention(q, k, v, causal).reshape(x.shape)
    x = x + o @ params["wo"]
    y = _layer_norm(x)
    return x + jax.nn.gelu(y @ params["w1"]) @ params["w2"]


class DominoTransformer:
    """Stack of Domino layers (reference DominoTransformer
    domino/transformer.py:411)."""

    def __init__(self, num_layers: int, hidden: int, ffn: int,
                 num_heads: int, num_chunks: int = 2, causal: bool = True,
                 dtype=jnp.bfloat16):
        self.num_layers = num_layers
        self.hidden = hidden
        self.ffn = ffn
        self.num_heads = num_heads
        self.num_chunks = num_chunks
        self.causal = causal
        self.dtype = dtype

    def init(self, rng):
        return [domino_layer_params(k, self.hidden, self.ffn,
                                    self.num_heads, self.dtype)
                for k in jax.random.split(rng, self.num_layers)]

    def apply(self, params, x, mesh=None):
        for layer in params:
            x = domino_transformer_layer(
                layer, x, num_heads=self.num_heads,
                num_chunks=self.num_chunks, causal=self.causal, mesh=mesh)
        return x

    __call__ = apply

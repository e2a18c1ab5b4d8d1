"""Ring attention: blockwise context parallelism over the sp mesh axis.

The reference has NO ring attention (SURVEY.md §5: long context is
Ulysses/ALST/FPDT only) — but Ulysses caps the sequence-parallel degree at
the head count (sequence/layer.py head-scatter). Ring attention removes
that cap: KV blocks rotate around the sp axis via ``ppermute`` on ICI
while each chip keeps its resident Q block, accumulating the exact
softmax online (flash-attention style), so sp can exceed num_heads and
sequence length scales with the ring size. This is the TPU-native
long-context path that complements parallel/ulysses.py:

  * Ulysses: 2 all-to-alls, full-sequence local attention — best when
    sp <= heads and the sequence fits one chip's HBM.
  * Ring: p-1 ppermute hops overlapped with per-block attention compute —
    best when sp > heads or S/p is all that fits.

Causality is handled by global position masking, so the math matches
dense causal attention bit-for-bit in fp32 accumulation. Gradients flow
through ``lax.scan`` + ``ppermute`` (transpose of a permute is the
inverse permute), giving the exact backward without a hand-written
kernel.

The sp axis must already shard the sequence dim of q/k/v (the engine's
sharding plan does this when sequence_parallel.size > 1).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import comm
from deepspeed_tpu.parallel import topology

BATCH = ("dp", "fsdp", "ep")


def _ring_attn_local(q, k, v, seg, *, axis: str, causal: bool,
                     s_global: int):
    """Runs INSIDE shard_map: q,k,v are the local [B, S/p, N_loc, D]
    blocks; rotates kv (and its segment-id block, for packed batches)
    around ``axis`` accumulating exact softmax (shared numerics in
    parallel/_blockwise.py). ``seg`` is the local [B, S/p] segment-id
    block or a [B, 0] placeholder when the batch is unpacked."""
    from deepspeed_tpu.parallel._blockwise import (
        block_attn_partial, finalize, init_accumulators, online_merge)

    p_size = jax.lax.axis_size(axis)
    my_idx = lax.axis_index(axis)
    s_loc = q.shape[1]
    q_pos = my_idx * s_loc + jnp.arange(s_loc)
    has_seg = seg.shape[1] > 0

    dt = q.dtype
    B, _, N, D = q.shape
    o_acc, m_acc, l_acc = init_accumulators(B, N, s_loc, D)

    # remat the per-step block: the ring scan's backward would otherwise
    # stack every step's [S/p, S/p] softmax block as a residual —
    # [p, B, N, S/p, S/p] fp32, the O(S^2/p) memory blowup this path
    # exists to avoid (same leak class as fpdt's inner tile scan)
    ck_block = jax.checkpoint(
        lambda q_, k_, v_, qp, kp, sq, sk: block_attn_partial(
            q_, k_, v_, qp, kp, causal, s_global, seg_q=sq, seg_k=sk))

    def body(carry, step):
        k_blk, v_blk, seg_blk, o_acc, m_acc, l_acc = carry
        kv_idx = (my_idx - step) % p_size
        k_pos = kv_idx * s_loc + jnp.arange(s_loc)
        blk = ck_block(q, k_blk, v_blk, q_pos, k_pos,
                       seg if has_seg else None,
                       seg_blk if has_seg else None)
        o_acc, m_acc, l_acc = online_merge(o_acc, m_acc, l_acc, blk)
        # rotate kv forward around the ring (device i -> i+1) — via the
        # traced comm facade so each hop gets a flight-recorder span and
        # a chrome-trace collective-lane slice (bytes are per-hop local
        # block size; the scan dispatches the hop once at trace time)
        perm = [(i, (i + 1) % p_size) for i in range(p_size)]
        k_blk = comm.ppermute(k_blk, axis, perm,
                              log_name="ring_attention_kv")
        v_blk = comm.ppermute(v_blk, axis, perm,
                              log_name="ring_attention_kv")
        if has_seg:
            seg_blk = comm.ppermute(seg_blk, axis, perm,
                                    log_name="ring_attention_seg")
        return (k_blk, v_blk, seg_blk, o_acc, m_acc, l_acc), None

    (k, v, seg, o_acc, m_acc, l_acc), _ = lax.scan(
        body, (k, v, seg, o_acc, m_acc, l_acc), jnp.arange(p_size))

    return finalize(o_acc, l_acc, dt)  # [B,S/p,N,D]


def ring_attention(q, k, v, causal: bool = True, axis: str = "sp",
                   segment_ids: Optional[jax.Array] = None):
    """Context-parallel attention; drop-in for multi_head_attention when
    the sequence dim is sharded over ``axis``.

    q,k,v: [B, S, N, D] global (kv heads already repeated for GQA, same
    contract as ops/attention.py multi_head_attention). segment_ids
    [B, S] mask cross-segment attention for packed batches — the id
    block rotates around the ring with its KV block.
    """
    from deepspeed_tpu.ops.attention import multi_head_attention

    mesh = topology._GLOBAL_MESH
    if mesh is None or axis not in mesh.shape or mesh.shape[axis] == 1:
        from deepspeed_tpu.utils import telemetry

        telemetry.count(
            "ring_attention.dense_fallback",
            f"no mesh axis '{axis}' > 1 — running dense attention")
        return multi_head_attention(q, k, v, causal=causal,
                                    segment_ids=segment_ids)

    p_size = mesh.shape[axis]

    # pad S to a multiple of the ring size; padded KV positions are masked
    # inside the blockwise compute, padded Q rows are sliced off
    S = q.shape[1]
    pad = (-S) % p_size
    if pad:
        widths = [(0, 0), (0, pad), (0, 0), (0, 0)]
        q, k, v = (jnp.pad(t, widths) for t in (q, k, v))
    if segment_ids is None:
        # zero-width placeholder: shard_map wants a concrete operand, the
        # local body skips segment masking when it sees width 0
        seg = jnp.zeros((q.shape[0], 0), jnp.int32)
    else:
        # padded keys are masked by position already; -1 also keeps them
        # out of any real segment
        seg = jnp.pad(segment_ids.astype(jnp.int32), [(0, 0), (0, pad)],
                      constant_values=-1)

    batch_axes = tuple(a for a in BATCH if a in mesh.shape)
    spec = P(batch_axes, axis, "tp" if "tp" in mesh.shape else None, None)
    seg_spec = P(batch_axes, None if seg.shape[1] == 0 else axis)
    fn = jax.shard_map(
        partial(_ring_attn_local, axis=axis, causal=causal, s_global=S),
        mesh=mesh, in_specs=(spec, spec, spec, seg_spec), out_specs=spec,
        check_vma=False)
    out = fn(q, k, v, seg)
    return out[:, :S] if pad else out

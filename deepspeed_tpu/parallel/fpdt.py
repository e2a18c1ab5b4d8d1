"""FPDT-style chunked attention + host activation offload for multi-M-token
sequences.

Reference: sequence/fpdt_layer.py — ``_FPDTGPUOffloadingAttentionImpl_``
(:545) processes the sequence in chunks, double-buffering chunk
activations through pinned host memory, and chunked FFN/logits (:1126,
:1207) cap the rest of the activation footprint; 16x longer sequences at
~55% MFU (blogs/ulysses-offload).

TPU-native decomposition of the same capability:

  * ``chunked_attention`` — a ``lax.scan`` over Q chunks, each chunk
    scanning KV tiles with exact online-softmax accumulation and
    ``jax.checkpoint`` around the chunk: peak attention memory is one
    [chunk × kv_tile] score block instead of [S × S]. XLA pipelines the
    loops; no custom kernel needed (the Pallas flash kernel covers the
    unchunked case).
  * host offload — instead of FPDT's hand-rolled pinned-buffer double
    buffering, the remat policy ``offload_dots_host``
    (models/transformer.py _REMAT_POLICIES) uses XLA memory kinds
    (device → pinned_host) to spill checkpointed activations to host RAM
    and stream them back in backward, overlapped by XLA's latency-hiding
    scheduler.

Composes with Ulysses/ring: those shard S across chips; this bounds the
per-chip footprint of the resident S/p slice.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _read_bisect() -> str:
    """DSTPU_FPDT_BISECT debug modes (noctx/outonly/novjp/devout/
    dummybwd) amputate parts of the hosted-layer computation to bisect
    TPU host-offloading failures — gradients (and for some modes the
    outputs) are WRONG. Shout once and count, so a bisect var leaking
    into a real run cannot pass silently."""
    mode = os.environ.get("DSTPU_FPDT_BISECT", "")
    if mode:
        from deepspeed_tpu.utils import telemetry
        from deepspeed_tpu.utils.logging import logger

        telemetry.count("fpdt.bisect_active", mode)
        if ("fpdt.bisect", mode) not in _BISECT_WARNED:
            _BISECT_WARNED.add(("fpdt.bisect", mode))
            logger.warning(
                f"DSTPU_FPDT_BISECT={mode!r} is ACTIVE: this is a debug "
                "bisection mode — fpdt numerics/gradients are "
                "intentionally wrong. Unset it for real runs.")
    return mode


_BISECT_WARNED: set = set()


def _chunk_vs_kv_tiles(q, k_tiles, v_tiles, q_pos0, causal: bool,
                       s_kv: int):
    """One Q chunk against all KV tiles with online softmax (shared
    numerics in parallel/_blockwise.py).

    q: [B,C,N,D]; k_tiles/v_tiles: [T,B,kv_tile,N,D]; q_pos0: global
    position of the chunk's first query; s_kv: real (unpadded) KV length.
    """
    from deepspeed_tpu.parallel._blockwise import (
        block_attn_partial, finalize, init_accumulators, online_merge)

    B, C, N, D = q.shape
    q_pos = q_pos0 + jnp.arange(C)
    kv_tile = k_tiles.shape[2]
    T = k_tiles.shape[0]
    o, m, l = init_accumulators(B, N, C, D)

    # remat the per-tile block: without this the INNER scan's backward
    # saves every tile's [C, kv_tile] softmax block as a residual —
    # stacked to [T, B, N, C, kv_tile] fp32, which is exactly the O(S^2)
    # memory this path exists to avoid (observed: 8GB temp at 128K)
    ck_block = jax.checkpoint(
        lambda q_, k_, v_, qp, kp: block_attn_partial(
            q_, k_, v_, qp, kp, causal, s_kv))

    def body(carry, xs):
        o, m, l = carry
        k_t, v_t, t_idx = xs
        k_pos = t_idx * kv_tile + jnp.arange(kv_tile)
        blk = ck_block(q, k_t, v_t, q_pos, k_pos)
        return online_merge(o, m, l, blk), None

    (o, m, l), _ = lax.scan(body, (o, m, l),
                            (k_tiles, v_tiles, jnp.arange(T)))
    return finalize(o, l, q.dtype)


def chunked_attention(q, k, v, causal: bool = True, q_chunks: int = 4,
                      kv_tile: Optional[int] = None):
    """Exact attention with O(chunk × kv_tile) score memory.

    q,k,v: [B, S, N, D] (equal q/kv head counts — the head-split chunking
    needs them; callers repeat GQA KV first. Same contract as
    ops/attention.py multi_head_attention). ``q_chunks``: number of query
    chunks scanned sequentially, each rematted. ``kv_tile``: KV tile
    length (default S/q_chunks rounded up).
    """
    B, S, N, D = q.shape
    if q_chunks <= 1:
        from deepspeed_tpu.ops.attention import multi_head_attention

        return multi_head_attention(q, k, v, causal=causal)

    pad_q = (-S) % q_chunks
    Sp = S + pad_q
    kv_tile = kv_tile or max(Sp // q_chunks, 1)
    pad_kv = (-S) % kv_tile
    Skv = S + pad_kv

    if pad_q:
        q = jnp.pad(q, [(0, 0), (0, pad_q), (0, 0), (0, 0)])
    if pad_kv:
        k = jnp.pad(k, [(0, 0), (0, pad_kv), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, pad_kv), (0, 0), (0, 0)])

    C = Sp // q_chunks
    T = Skv // kv_tile
    q_t = jnp.moveaxis(q.reshape(B, q_chunks, C, N, D), 1, 0)
    k_t = jnp.moveaxis(k.reshape(B, T, kv_tile, N, D), 1, 0)
    v_t = jnp.moveaxis(v.reshape(B, T, kv_tile, N, D), 1, 0)

    def chunk_body(_, xs):
        q_c, q_pos0 = xs

        def run(q_c, k_t, v_t, q_pos0):
            return _chunk_vs_kv_tiles(q_c, k_t, v_t, q_pos0, causal, S)

        return None, jax.checkpoint(run)(q_c, k_t, v_t, q_pos0)

    q_pos0s = jnp.arange(q_chunks) * C
    _, out = lax.scan(chunk_body, None, (q_t, q_pos0s))
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sp, N, D)
    return out[:, :S] if pad_q else out


# ---------------------------------------------------------------------------
# host-KV streaming attention block (beyond-HBM sequence lengths)
# ---------------------------------------------------------------------------


def _to_host(x):
    """Move to pinned host memory inside jit (no-op placement on CPU)."""
    from deepspeed_tpu.utils import memspace

    return memspace.put(x, "pinned_host")


def _to_device(x):
    from deepspeed_tpu.utils import memspace

    return memspace.put(x, "device")


def _fetch_tile(stacked, t_idx):
    """Stream one [B, kv_tile, Nkv, D] tile of a host-resident stack to
    the device."""
    return _to_device(lax.dynamic_index_in_dim(stacked, t_idx,
                                               keepdims=False))


def _masked_scores(q_c, k_rep, q_pos, k_pos, causal: bool, s_valid: int):
    """Scaled masked scores [B, N, C, kv_tile] — must match the forward
    numerics exactly (same einsum + mask as _blockwise)."""
    d = q_c.shape[-1]
    s = jnp.einsum("bqnd,bknd->bnqk", q_c, k_rep).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(d, jnp.float32))
    mask = k_pos[None, :] < s_valid
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    else:
        mask = jnp.broadcast_to(mask, (q_pos.shape[0], k_pos.shape[0]))
    return jnp.where(mask[None, None, :, :], s, -jnp.inf)


def _repeat_tile(tile, g: int):
    return jnp.repeat(tile, g, axis=2) if g > 1 else tile


def _unrepeat_grad(grad_rep, g: int):
    """[B, kv_tile, Nkv*g, D] cotangent → summed back to kv heads."""
    if g == 1:
        return grad_rep
    B, T, NG, D = grad_rep.shape
    return grad_rep.reshape(B, T, NG // g, g, D).sum(axis=3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _stream_attn(q_c, k_t, v_t, q_pos, n_tiles, g, s_valid, causal,
                 kv_tile):
    """One q-chunk against host-resident KV tiles, flash-style exact
    softmax. The custom VJP recomputes per-tile probabilities from the
    saved logsumexp instead of differentiating through the online-merge
    scan — without it the scan's backward stacks every tile's fp32
    (o, m, l) carry, an O(S * N * D) residual that is exactly the memory
    this path exists to avoid (observed: 2x8GB at 512K)."""
    ctx, _ = _stream_attn_fwd_impl(q_c, k_t, v_t, q_pos, n_tiles, g,
                                   s_valid, causal, kv_tile)
    return ctx


def _stream_attn_fwd_impl(q_c, k_t, v_t, q_pos, n_tiles, g, s_valid,
                          causal, kv_tile):
    B, C, N, D = q_c.shape
    T = k_t.shape[0]

    def _untile(flat):
        # host stacks are [T, B*kv_tile*Nkv*D] (2-D dodges an XLA
        # async-copy layout bug on 5-D host moves)
        return flat.reshape(B, kv_tile, N // g, D)

    o = jnp.zeros((B, N, C, D), jnp.float32)
    m = jnp.full((B, N, C), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, N, C), jnp.float32)

    def tile_body(carry, t_idx):
        o, m, l = carry
        k_rep = _repeat_tile(_untile(_fetch_tile(k_t, t_idx)), g)
        v_rep = _repeat_tile(_untile(_fetch_tile(v_t, t_idx)), g)
        k_pos = t_idx * kv_tile + jnp.arange(kv_tile)
        s = _masked_scores(q_c, k_rep, q_pos, k_pos, causal, s_valid)
        m_blk = jnp.max(s, axis=-1)
        valid = jnp.isfinite(m_blk)
        m_safe = jnp.where(valid, m_blk, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        l_blk = jnp.where(valid, jnp.sum(p, axis=-1), 0.0)
        o_blk = jnp.einsum("bnqk,bknd->bnqd", p,
                           v_rep.astype(jnp.float32))
        m_new = jnp.maximum(m, jnp.where(valid, m_blk, -jnp.inf))
        m_new_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new_safe), 0.0)
        beta = jnp.where(valid, jnp.exp(m_blk - m_new_safe), 0.0)
        o = o * alpha[..., None] + o_blk * beta[..., None]
        l = l * alpha + l_blk * beta
        return (o, m_new, l), None

    def guarded(carry, t_idx):
        return lax.cond(t_idx < n_tiles,
                        lambda c: tile_body(c, t_idx)[0],
                        lambda c: c, carry), None

    (o, m, l), _ = lax.scan(guarded, (o, m, l), jnp.arange(T))
    l_safe = jnp.maximum(l, 1e-30)
    ctx = jnp.transpose(o / l_safe[..., None], (0, 2, 1, 3)) \
        .astype(q_c.dtype)                                   # [B,C,N,D]
    lse = jnp.where(l > 0, jnp.where(jnp.isfinite(m), m, 0.0)
                    + jnp.log(l_safe), 0.0)                  # [B,N,C]
    return ctx, lse


def _stream_attn_fwd(q_c, k_t, v_t, q_pos, n_tiles, g, s_valid, causal,
                     kv_tile):
    ctx, lse = _stream_attn_fwd_impl(q_c, k_t, v_t, q_pos, n_tiles, g,
                                     s_valid, causal, kv_tile)
    return ctx, (q_c, k_t, v_t, q_pos, n_tiles, ctx, lse)


def _stream_attn_bwd(g, s_valid, causal, kv_tile, res, dctx):
    import numpy as np

    q_c, k_t, v_t, q_pos, n_tiles, ctx, lse = res
    B, C, N, D = q_c.shape
    T = k_t.shape[0]

    def _untile(flat):
        return flat.reshape(B, kv_tile, N // g, D)

    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    dctx32 = jnp.transpose(dctx.astype(jnp.float32), (0, 2, 1, 3))
    ctx32 = jnp.transpose(ctx.astype(jnp.float32), (0, 2, 1, 3))
    delta = jnp.sum(dctx32 * ctx32, axis=-1)                 # [B,N,C]

    dq = jnp.zeros((B, N, C, D), jnp.float32)
    dk_t = jnp.zeros_like(k_t)
    dv_t = jnp.zeros_like(v_t)

    def tile_body(carry, t_idx):
        dq, dk_t, dv_t = carry
        k_tile = _untile(_fetch_tile(k_t, t_idx))
        v_tile = _untile(_fetch_tile(v_t, t_idx))
        k_rep = _repeat_tile(k_tile, g)
        v_rep = _repeat_tile(v_tile, g)
        k_pos = t_idx * kv_tile + jnp.arange(kv_tile)
        s = _masked_scores(q_c, k_rep, q_pos, k_pos, causal, s_valid)
        p = jnp.exp(s - lse[..., None])                      # [B,N,C,kt]
        # dv[k] = sum_q p * dctx ; dp = dctx . v ; ds = p (dp - delta)
        dv_rep = jnp.einsum("bnqk,bnqd->bknd", p, dctx32)
        dp = jnp.einsum("bnqd,bknd->bnqk", dctx32,
                        v_rep.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bnqk,bknd->bnqd", ds,
                             k_rep.astype(jnp.float32)) * scale
        dk_rep = jnp.einsum("bnqk,bnqd->bknd", ds,
                            q_c.astype(jnp.float32).transpose(0, 2, 1, 3)
                            ) * scale
        dk_tile = _unrepeat_grad(dk_rep, g).astype(k_t.dtype)
        dv_tile = _unrepeat_grad(dv_rep, g).astype(v_t.dtype)
        dk_t2 = lax.dynamic_update_index_in_dim(
            dk_t, dk_tile.reshape(dk_t.shape[1:]), t_idx, 0)
        dv_t2 = lax.dynamic_update_index_in_dim(
            dv_t, dv_tile.reshape(dv_t.shape[1:]), t_idx, 0)
        return (dq, dk_t2, dv_t2), None

    def guarded(carry, t_idx):
        return lax.cond(t_idx < n_tiles,
                        lambda c: tile_body(c, t_idx)[0],
                        lambda c: c, carry), None

    (dq, dk_t, dv_t), _ = lax.scan(guarded, (dq, dk_t, dv_t),
                                   jnp.arange(T))
    dq_out = jnp.transpose(dq, (0, 2, 1, 3)).astype(q_c.dtype)
    zero_pos = np.zeros(q_pos.shape, dtype=jax.dtypes.float0)
    zero_nt = np.zeros((), dtype=jax.dtypes.float0)
    return dq_out, dk_t, dv_t, zero_pos, zero_nt


_stream_attn.defvjp(_stream_attn_fwd, _stream_attn_bwd)


def fpdt_attention_block(y, ap, positions, *, num_heads: int,
                         kv_heads: int, head_dim: int,
                         rope_theta: Optional[float], q_chunks: int,
                         kv_tile: Optional[int] = None, causal: bool = True,
                         use_biases: bool = False,
                         norm_fn: Optional[callable] = None,
                         post_fn: Optional[callable] = None,
                         hosted: bool = False,
                         seq_len: Optional[int] = None,
                         sp_axis: Optional[str] = None,
                         sp_size: int = 1) -> jax.Array:
    """Full FPDT attention sub-layer with host-resident KV streaming —
    the reference ``_FPDTGPUOffloadingAttentionImpl_``'s pinned
    double-buffered sequence chunks (sequence/fpdt_layer.py:545,
    ``SequenceChunk`` :497) as XLA memory-space movement.

    y: [B, S, H] layer input (device) — pre-norm when ``norm_fn`` is
    given (the norm then applies per chunk inside the scans, so neither
    the normed full-S activation nor its fp32 intermediate ever
    materializes — the reference chunks the whole layer pass the same
    way, fpdt_layer.py:1126). Returns the attention branch output
    [B, S, H] (wo applied). Device never holds a full-S [B, S, Nq, D]
    query/output tensor or repeated-KV tensor:

      * K/V build scans sequence tiles: per tile (norm→) project at
        kv_heads width (the GQA-narrow 1/g footprint), rotate, and
        write into pinned-host stacks;
      * the q-chunk scan projects each chunk's queries on the fly and
        streams KV tiles back one at a time, accumulating each chunk's
        wo-contracted output into a carried [B, Sp, H] buffer (scan
        in-places the carry — no stacked-ys + reshape double buffer);
      * the backward replays chunk bodies (remat), re-streaming tiles
        from host, so residuals are O(B*S*H) rather than O(B*S*Nq*D).

    ``hosted=True`` is the residual-stream-offload mode (VERDICT r4 #5,
    reference fpdt_layer.py:545's SequenceChunk applied to the residual
    itself): ``y`` is a HOST stack [q_chunks, B*C, H] (the padded
    sequence pre-split on the chunk grid), ``seq_len`` gives the real S,
    and the return value is the same-shaped host stack of layer outputs
    — the device never holds any full-S [B, S, H] buffer, only one
    chunk (+ one KV-build tile) at a time. The KV tile grid is forced
    onto the chunk grid so both scans fetch the same host tiles.

    ``sp_axis`` is the sequence-parallel composition mode: the call runs
    INSIDE ``shard_map`` over that mesh axis with ``y``/``positions``
    holding this rank's LOCAL [B, S/p, ...] shard (rank r owns the
    contiguous global span [r·S/p, (r+1)·S/p)). Each rank builds its
    local KV tile stacks, all-gathers them over ``sp_axis`` (rank-major
    tiled gather ⇒ the gathered tile order is position-sorted, so tile j
    still starts at global position j·kv_tile), spills the GLOBAL stacks
    to host, and streams them through its local q chunks with
    shard-offset query positions. ``sp_size`` must be the static degree
    of ``sp_axis`` (the global valid length S·p is a nondiff argument of
    the streaming kernel).
    """
    if sp_axis is not None and hosted:
        raise ValueError("fpdt sp composition does not support the "
                         "hosted-residual mode (fpdt_host_residual)")
    if hosted:
        if seq_len is None:
            raise ValueError(
                "hosted fpdt requires seq_len (the host stack is padded "
                "on the chunk grid, so the real sequence length cannot "
                "be recovered from y.shape)")
        T_res, BC, H = y.shape
        if q_chunks != T_res:
            raise ValueError(
                f"hosted fpdt: q_chunks={q_chunks} must equal the host "
                f"stack's chunk count {T_res}")
        S = seq_len
        C = -(-S // q_chunks)  # ceil
        # the stack is padded on the chunk grid by construction
        Sp = q_chunks * C
        assert BC % C == 0, (BC, C)
        B = BC // C
        if kv_tile not in (None, C):
            raise ValueError("hosted fpdt uses the chunk grid for KV "
                             f"tiles; got kv_tile={kv_tile} != C={C}")
        kv_tile = C
        T = q_chunks
        dt = y.dtype
        g = num_heads // kv_heads
        positions = jnp.broadcast_to(positions, (B, S))
        pos_p = (jnp.pad(positions, [(0, 0), (0, Sp - S)]) if Sp > S
                 else positions)

        def _res_tile(t):
            """Fetch residual chunk t from the host stack → [B, C, H]."""
            return _to_device(lax.dynamic_index_in_dim(
                y, t, keepdims=False)).reshape(B, C, H)
    elif sp_axis is not None:
        # sp composition (runs inside shard_map): y/positions are the
        # LOCAL shard. Padding a local shard would insert pad rows
        # mid-sequence GLOBALLY and break the position math, so the
        # chunk/tile grids must divide the shard exactly — the planner
        # (parallel/auto_sp.py) only ever picks divisible counts.
        B, S, H = y.shape
        dt = y.dtype
        g = num_heads // kv_heads
        positions = jnp.broadcast_to(positions, (B, S))
        if S % q_chunks:
            raise ValueError(
                f"fpdt+sp: local sequence shard {S} must be divisible "
                f"by q_chunks={q_chunks} (pad-free composition only)")
        pad_q = 0
        Sp = S
        C = S // q_chunks
        kv_tile = kv_tile or C
        if S % kv_tile:
            raise ValueError(
                f"fpdt+sp: local sequence shard {S} must be divisible "
                f"by kv_tile={kv_tile} (pad-free composition only)")
        T_loc = S // kv_tile               # tiles this rank builds
        T = sp_size * T_loc                # global tile count streamed
        y_p, pos_p = y, positions

        def _res_tile(t):
            return lax.dynamic_slice_in_dim(y_p, t * kv_tile, kv_tile, 1)
    else:
        B, S, H = y.shape
        dt = y.dtype
        g = num_heads // kv_heads
        positions = jnp.broadcast_to(positions, (B, S))

        pad_q = (-S) % q_chunks
        Sp = S + pad_q
        C = Sp // q_chunks
        kv_tile = kv_tile or C
        pad_kv = (-S) % kv_tile
        Skv = S + pad_kv
        T = Skv // kv_tile

        # one padded view serves both the q chunks and the kv tiles
        P = max(Sp, Skv)
        y_p = jnp.pad(y, [(0, 0), (0, P - S), (0, 0)]) if P > S else y
        pos_p = (jnp.pad(positions, [(0, 0), (0, P - S)]) if P > S
                 else positions)

        def _res_tile(t):
            return lax.dynamic_slice_in_dim(y_p, t * kv_tile, kv_tile, 1)

    # sp composition globals: this rank's queries live at global
    # positions shard_off + [0, S); KV/softmax masking runs against the
    # GLOBAL valid length (static — _stream_attn nondiff arg)
    if sp_axis is not None:
        shard_off = lax.axis_index(sp_axis) * S
        s_valid = sp_size * S
    else:
        shard_off = 0
        s_valid = S

    def maybe_norm(t):
        return norm_fn(t) if norm_fn is not None else t

    def proj_tile(yt, w, b):
        out = jnp.einsum("bch,hnd->bcnd", yt, w.astype(dt))
        if use_biases:
            out = out + b.astype(dt)
        return out

    # K/V build: scan tiles — per tile (norm→) project+rotate — stacking
    # on device at kv_heads width (1/g of the repeated footprint; ~2GB
    # at 512K vs 4.3GB for one full-S hidden), then one move to host.
    # Pad tiles carry norm-of-zero garbage; _masked_scores' k_pos <
    # s_valid mask keeps them out of every softmax. (Stacks can't build
    # directly into host buffers: autodiff of a host-carried
    # dynamic_update scan makes mixed-memory-space cotangents.)
    def kv_tile_fn(t):
        x_tile = _res_tile(t)
        p_tile = lax.dynamic_slice_in_dim(pos_p, t * kv_tile, kv_tile, 1)
        yt = maybe_norm(x_tile)
        kt = proj_tile(yt, ap["wk"], ap.get("bk"))
        vt = proj_tile(yt, ap["wv"], ap.get("bv"))
        if rope_theta:
            kt = _rope_chunk(kt, p_tile, rope_theta)
        # [rows, head_dim] keeps the lane dim: fully flat 1-D tiles trip
        # the TPU async dynamic-index emitter's sublane alignment CHECK
        return (kt.reshape(-1, head_dim), vt.reshape(-1, head_dim))

    # remat per tile: without it the scan's backward saves every tile's
    # norm fp32 intermediates — stacked [T, ...] f32, exactly the full-S
    # footprint this path removes. The host move stays OUTSIDE the
    # rematted region (its replay would mix memory spaces), and happens
    # per flattened tile: the stacked host result is [T, tile_elems]
    # built from 1-D per-step copies (bulk D2H of a multi-dim stack
    # trips an XLA async-copy layout-assignment mismatch on TPU);
    # _stream_attn re-shapes per fetched tile.
    kv_tile_fn = jax.checkpoint(kv_tile_fn)

    if sp_axis is not None:
        # build the LOCAL tile stacks on device, all-gather them over
        # the sp axis, then spill the GLOBAL stacks to host. The tiled
        # gather concatenates in axis-index (= rank) order and rank r's
        # tokens occupy the contiguous global span [r·S, (r+1)·S), so
        # the gathered stack is position-sorted: _stream_attn's internal
        # k_pos = t·kv_tile + arange(kv_tile) stays valid unchanged.
        # The gather's AD transpose is a reduce-scatter, which routes
        # each rank's dk/dv tile cotangents back to the owning rank.
        from deepspeed_tpu.comm import comm as _comm

        def kv_body(_, t):
            return None, kv_tile_fn(t)

        _, (k_loc, v_loc) = lax.scan(kv_body, None, jnp.arange(T_loc))
        k_t = _to_host(_comm.all_gather(k_loc, sp_axis, gather_dim=0,
                                        log_name="fpdt_sp_kv"))
        v_t = _to_host(_comm.all_gather(v_loc, sp_axis, gather_dim=0,
                                        log_name="fpdt_sp_kv"))
    else:
        def kv_body(_, t):
            kt, vt = kv_tile_fn(t)
            return None, (_to_host(kt), _to_host(vt))

        _, (k_t, v_t) = lax.scan(kv_body, None, jnp.arange(T))

    wo = ap["wo"].astype(dt)

    def chunk(x_chunk, pos_chunk, chunk_idx):
        y_chunk = maybe_norm(x_chunk)
        q_c = jnp.einsum("bch,hnd->bcnd", y_chunk, ap["wq"].astype(dt))
        if use_biases:
            q_c = q_c + ap["bq"].astype(dt)
        if rope_theta:
            q_c = _rope_chunk(q_c, pos_chunk, rope_theta)
        q_pos = shard_off + chunk_idx * C + jnp.arange(C)

        # causal: later tiles are fully masked for this chunk — skipped
        # entirely inside _stream_attn (no H2D fetch, no compute).
        # shard_off shifts the cutoff to this rank's global span in the
        # sp composition (0 otherwise).
        n_tiles = (jnp.minimum(
            (shard_off + (chunk_idx + 1) * C + kv_tile - 1) // kv_tile, T)
            if causal else jnp.asarray(T, jnp.int32))

        ctx = _stream_attn(q_c, k_t, v_t, q_pos, n_tiles, g, s_valid,
                           causal, kv_tile)
        attn_c = jnp.einsum("bcnd,ndh->bch", ctx, wo)
        if post_fn is not None:
            # fuse the rest of the transformer block into the same
            # chunk (residual add + ln2 + MLP — all position-wise): the
            # layer emits ONE full-S buffer instead of separate
            # attention-out and MLP-out full-S intermediates (reference
            # chunks the whole layer pass, fpdt_layer.py:1126)
            return post_fn(x_chunk, attn_c)
        return attn_c

    if hosted:
        # emit each chunk's result straight back to the host stack (scan
        # ys — the same pattern as the KV build; a host CARRY with
        # dynamic_update makes mixed-memory-space cotangents). The FETCH
        # stays INSIDE the rematted region: the saved residual is then
        # the (loop-invariant) host stack itself, not a per-chunk device
        # copy — stacked fetched chunks would rebuild the full-S device
        # buffer this mode exists to remove. The host EMISSION stays
        # outside (a replayed D2H would mix memory spaces).
        def hosted_chunk(idx):
            x_chunk = _res_tile(idx)
            p_chunk = lax.dynamic_slice_in_dim(pos_p, idx * C, C, axis=1)
            return chunk(x_chunk, p_chunk, idx)

        hosted_chunk = jax.checkpoint(hosted_chunk)

        def hosted_body(_, idx):
            return None, _to_host(hosted_chunk(idx).reshape(B * C, H))

        _, out_t = lax.scan(hosted_body, None, jnp.arange(q_chunks))
        return out_t

    def chunk_body(buf, idx):
        # slice the chunk in-body (a pre-split [q_chunks, B, C, H] copy
        # would be a second full-sequence buffer) and write the result
        # into the carried output buffer (scan in-places the carry — a
        # stacked-ys + moveaxis/reshape epilogue would transiently hold
        # two full-sequence copies)
        x_chunk = lax.dynamic_slice_in_dim(y_p, idx * C, C, axis=1)
        p_chunk = lax.dynamic_slice_in_dim(pos_p, idx * C, C, axis=1)
        res = jax.checkpoint(chunk)(x_chunk, p_chunk, idx)
        return lax.dynamic_update_slice_in_dim(buf, res, idx * C, 1), None

    out, _ = lax.scan(chunk_body, jnp.zeros((B, Sp, H), dt),
                      jnp.arange(q_chunks))
    return out[:, :S] if pad_q else out


def _rope_chunk(x, positions, theta: float):
    from deepspeed_tpu.models.transformer import _rope

    return _rope(x, positions, theta)


# ---------------------------------------------------------------------------
# hosted-residual fused layer with a two-pass flash-style backward
# ---------------------------------------------------------------------------


def fpdt_hosted_layer(x_t, layer_params, pos_p, *, seq_len: int,
                      q_chunks: int, num_heads: int, kv_heads: int,
                      head_dim: int, rope_theta, use_biases: bool,
                      norm_kind: str, norm_eps: float, activation: str):
    """One fused transformer block over a HOST residual chunk stack, with
    a layer-level custom VJP whose backward runs in TWO passes (the
    flash-attention backward split, applied at the host-streaming level):

      pass A (chunk-outer): per q-chunk — tail (wo/residual/ln2/MLP) vjp,
        the dq tile loop, and the q-projection/ln1 vjp; emits the partial
        d(x) chunk plus (q, d_ctx, delta) stacks for pass B.
      pass B (tile-outer): per KV tile — accumulates dk/dv from all
        later chunks (recomputing probabilities from the saved lse), then
        the KV-build vjp; adds the kv-path d(x) into pass A's partial.

    Why not plain autodiff of the chunk scan (the r4 structure): each
    chunk's KV cotangent is a full [T, ...] stack, and the scan transpose
    accumulates those across chunks — an O(S)-sized host add per chunk
    (~800 GB of hidden traffic at 512K) whose operands XLA stages
    through HBM; that accumulation is exactly what made 512K OOM at
    21.8 GB temp. Here every host object is written once and read O(1)
    or O(T) times with tile-sized buffers only.

    x_t: [q_chunks, B*C, H] host stack; pos_p: [B, Sp] int32 (device).
    Returns the same-shaped host stack. Reference:
    sequence/fpdt_layer.py:545 (chunked layer + offload), backward split
    per the standard flash-attention dq/dkv loop exchange.
    """
    import math

    from deepspeed_tpu.models.transformer import _norm, act_fn

    T, BC, H = x_t.shape
    S = seq_len
    C = -(-S // q_chunks)
    Sp = q_chunks * C
    assert T == q_chunks and BC % C == 0
    B = BC // C
    N, D = num_heads, head_dim
    g = num_heads // kv_heads
    dt = x_t.dtype
    scale = 1.0 / math.sqrt(D)

    # -- pure per-chunk pieces (jax.vjp'd in the backward) ---------------
    def head_q(x_c, p_c, params):
        ap = params["attn"]
        y = _norm(x_c, params["ln1"], norm_kind, norm_eps)
        q = jnp.einsum("bch,hnd->bcnd", y, ap["wq"].astype(dt))
        if use_biases:
            q = q + ap["bq"].astype(dt)
        if rope_theta:
            q = _rope_chunk(q, p_c, rope_theta)
        return q

    def build_kv(x_c, p_c, params):
        ap = params["attn"]
        y = _norm(x_c, params["ln1"], norm_kind, norm_eps)
        k = jnp.einsum("bch,hnd->bcnd", y, ap["wk"].astype(dt))
        v = jnp.einsum("bch,hnd->bcnd", y, ap["wv"].astype(dt))
        if use_biases:
            k = k + ap["bk"].astype(dt)
            v = v + ap["bv"].astype(dt)
        if rope_theta:
            k = _rope_chunk(k, p_c, rope_theta)
        return k, v

    def tail(x_c, ctx_c, params):
        ap = params["attn"]
        attn = jnp.einsum("bcnd,ndh->bch", ctx_c, ap["wo"].astype(dt))
        if use_biases:
            attn = attn + ap["bo"].astype(dt)
        xc2 = x_c + attn
        mp = params["mlp"]
        y2 = _norm(xc2, params["ln2"], norm_kind, norm_eps)
        if activation == "swiglu":
            gate = jnp.einsum("bch,hf->bcf", y2, mp["wg"].astype(dt))
            up = jnp.einsum("bch,hf->bcf", y2, mp["wi"].astype(dt))
            z = jax.nn.silu(gate) * up
        else:
            pre = jnp.einsum("bch,hf->bcf", y2, mp["wi"].astype(dt))
            if use_biases:
                pre = pre + mp["bi"].astype(dt)
            z = act_fn(activation)(pre)
        out = jnp.einsum("bcf,fh->bch", z, mp["wo"].astype(dt))
        if use_biases:
            out = out + mp["bo"].astype(dt)
        return xc2 + out

    def fetch_rows(stack, i, shape):
        return _to_device(lax.dynamic_index_in_dim(
            stack, i, keepdims=False)).reshape(shape)

    def pos_chunk(i):
        return lax.dynamic_slice_in_dim(pos_p, i * C, C, axis=1)

    def n_tiles_of(idx):
        return jnp.minimum(idx + 1, T).astype(jnp.int32)

    # -- forward ---------------------------------------------------------
    def _kv_build(x_t, params):
        def f(t):
            x_tile = fetch_rows(x_t, t, (B, C, H))
            k, v = build_kv(x_tile, pos_chunk(t), params)
            return k.reshape(-1, D), v.reshape(-1, D)

        f = jax.checkpoint(f)

        def body(_, t):
            kt, vt = f(t)
            return None, (_to_host(kt), _to_host(vt))

        _, (k_t, v_t) = lax.scan(body, None, jnp.arange(T))
        return k_t, v_t

    def _forward(x_t, params):
        k_t, v_t = _kv_build(x_t, params)

        def f(idx):
            x_c = fetch_rows(x_t, idx, (B, C, H))
            q_c = head_q(x_c, pos_chunk(idx), params)
            q_pos = idx * C + jnp.arange(C)
            ctx, lse = _stream_attn_fwd_impl(
                q_c, k_t, v_t, q_pos, n_tiles_of(idx), g, S, True, C)
            out_c = tail(x_c, ctx, params)
            return out_c, ctx, lse

        f = jax.checkpoint(f)

        _bisect = _read_bisect()

        def body_noctx(_, idx):
            out_c, ctx, lse = f(idx)
            return None, _to_host(out_c.reshape(BC, H))

        def body(_, idx):
            out_c, ctx, lse = f(idx)
            if "outonly" in _bisect:
                return None, (_to_host(out_c.reshape(BC, H)),
                              _to_host(ctx.reshape(B * C * N, D) * 0)[:1],
                              _to_host(lse * 0)[:1])
            # ys must be uniformly host-resident: a mixed host/device ys
            # tuple in one scan trips the TPU host-offloading pass
            # ("moved to host ... layout for this output is not set")
            return None, (_to_host(out_c.reshape(BC, H)),
                          _to_host(ctx.reshape(B * C * N, D)),
                          _to_host(lse))

        if "noctx" in _bisect:
            _, out_t = lax.scan(body_noctx, None, jnp.arange(T))
            return out_t, (k_t, v_t, out_t, out_t)
        _, (out_t, ctx_t, lse_t) = lax.scan(body, None, jnp.arange(T))
        return out_t, (k_t, v_t, ctx_t, lse_t)

    @jax.custom_vjp
    def run(x_t, params, pos_p):
        out_t, _ = _forward(x_t, params)
        return out_t

    def run_fwd(x_t, params, pos_p):
        out_t, (k_t, v_t, ctx_t, lse_t) = _forward(x_t, params)
        return out_t, (x_t, params, k_t, v_t, ctx_t, lse_t)

    def run_bwd(res, d_out_t):
        import numpy as np

        x_t, params, k_t, v_t, ctx_t, lse_t = res
        f32 = jnp.float32
        dparams0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, f32), params)

        def _untile_kv(flat):
            return flat.reshape(B, C, N // g, D)

        # ---- pass A: chunk-outer — tail vjp, dq, q-path vjp -----------
        def a_step(dparams, idx):
            x_c = fetch_rows(x_t, idx, (B, C, H))
            d_out_c = fetch_rows(d_out_t, idx, (B, C, H))
            ctx_c = fetch_rows(ctx_t, idx, (B, C, N, D))
            lse_c = _to_device(lse_t[idx])                    # [B,N,C]
            p_c = pos_chunk(idx)
            q_c = head_q(x_c, p_c, params)                    # replay
            q_pos = idx * C + jnp.arange(C)

            _, tail_vjp = jax.vjp(
                lambda xx, cc, pp: tail(xx, cc, pp), x_c, ctx_c, params)
            dx_post, d_ctx, dp_tail = tail_vjp(d_out_c)
            d_ctx32 = jnp.transpose(d_ctx.astype(f32), (0, 2, 1, 3))
            ctx32 = jnp.transpose(ctx_c.astype(f32), (0, 2, 1, 3))
            delta = jnp.sum(d_ctx32 * ctx32, axis=-1)         # [B,N,C]

            nt = n_tiles_of(idx)
            dq0 = jnp.zeros((B, N, C, D), f32)

            def dq_tile(dq, t):
                def live(dq):
                    k_rep = _repeat_tile(_untile_kv(_fetch_tile(k_t, t)), g)
                    v_rep = _repeat_tile(_untile_kv(_fetch_tile(v_t, t)), g)
                    k_pos = t * C + jnp.arange(C)
                    s = _masked_scores(q_c, k_rep, q_pos, k_pos, True, S)
                    p = jnp.exp(s - lse_c[..., None])
                    dp = jnp.einsum("bnqd,bknd->bnqk", d_ctx32,
                                    v_rep.astype(f32))
                    ds = p * (dp - delta[..., None])
                    return dq + jnp.einsum(
                        "bnqk,bknd->bnqd", ds, k_rep.astype(f32)) * scale

                return lax.cond(t < nt, live, lambda d: d, dq), None

            dq, _ = lax.scan(dq_tile, dq0, jnp.arange(T))
            dq = jnp.transpose(dq, (0, 2, 1, 3)).astype(q_c.dtype)

            _, q_vjp = jax.vjp(
                lambda xx, pp: head_q(xx, p_c, pp), x_c, params)
            dx_q, dp_q = q_vjp(dq)
            dparams = jax.tree.map(
                lambda a, b, c: a + b.astype(f32) + c.astype(f32),
                dparams, dp_tail, dp_q)
            dx_c = (dx_post + dx_q).astype(dt)
            return dparams, (_to_host(dx_c.reshape(BC, H)),
                             _to_host(q_c.reshape(B * C * N, D)),
                             _to_host(d_ctx.reshape(B * C * N, D)),
                             _to_host(delta))

        dparams, (dxa_t, q_t, dctx_t, delta_t) = lax.scan(
            a_step, dparams0, jnp.arange(T))

        # ---- pass B: tile-outer — dk/dv from all later chunks, kv vjp -
        def b_step(dparams, t):
            x_tile = fetch_rows(x_t, t, (B, C, H))
            p_tile = pos_chunk(t)
            k_rep = _repeat_tile(_untile_kv(_fetch_tile(k_t, t)), g)
            v_rep = _repeat_tile(_untile_kv(_fetch_tile(v_t, t)), g)
            k_pos = t * C + jnp.arange(C)
            dk0 = jnp.zeros((B, C, N, D), f32)  # repeated-head layout
            dv0 = jnp.zeros((B, C, N, D), f32)

            def kv_chunk(carry, c):
                dk, dv = carry

                def live(carry):
                    dk, dv = carry
                    q_c = fetch_rows(q_t, c, (B, C, N, D))
                    d_ctx = fetch_rows(dctx_t, c, (B, C, N, D))
                    d_ctx32 = jnp.transpose(d_ctx.astype(f32),
                                            (0, 2, 1, 3))
                    lse_c = _to_device(lse_t[c])
                    delta_c = _to_device(delta_t[c])
                    q_pos = c * C + jnp.arange(C)
                    s = _masked_scores(q_c, k_rep, q_pos, k_pos, True, S)
                    p = jnp.exp(s - lse_c[..., None])
                    dv2 = dv + jnp.einsum("bnqk,bnqd->bknd", p, d_ctx32)
                    dp = jnp.einsum("bnqd,bknd->bnqk", d_ctx32,
                                    v_rep.astype(f32))
                    ds = p * (dp - delta_c[..., None])
                    dk2 = dk + jnp.einsum(
                        "bnqk,bnqd->bknd", ds,
                        q_c.astype(f32).transpose(0, 2, 1, 3)) * scale
                    return dk2, dv2

                return lax.cond(c >= t, live, lambda cc: cc, (dk, dv)), None

            (dk, dv), _ = lax.scan(kv_chunk, (dk0, dv0), jnp.arange(T))
            dk_tile = _unrepeat_grad(dk, g).astype(dt)
            dv_tile = _unrepeat_grad(dv, g).astype(dt)
            _, kv_vjp = jax.vjp(
                lambda xx, pp: build_kv(xx, p_tile, pp), x_tile, params)
            dx_kv, dp_kv = kv_vjp((dk_tile, dv_tile))
            dparams = jax.tree.map(
                lambda a, b: a + b.astype(f32), dparams, dp_kv)
            dxa = fetch_rows(dxa_t, t, (B, C, H))
            dx_total = (dxa + dx_kv).astype(dt)
            return dparams, _to_host(dx_total.reshape(BC, H))

        dparams, dx_t = lax.scan(b_step, dparams, jnp.arange(T))
        dparams = jax.tree.map(lambda gg, p: gg.astype(p.dtype),
                               dparams, params)
        d_pos = np.zeros(np.shape(pos_p), jax.dtypes.float0)
        return dx_t, dparams, d_pos

    _bis = _read_bisect()
    if "novjp" in _bis:
        return _forward(x_t, layer_params)[0]
    if "devout" in _bis:
        @jax.custom_vjp
        def run_d(x_t, params, pos_p):
            out_t, _ = _forward(x_t, params)
            return _to_device(out_t)

        def run_d_fwd(x_t, params, pos_p):
            out_t, res_extra = _forward(x_t, params)
            return _to_device(out_t), (x_t, params) + res_extra

        def run_d_bwd(res, d_out):
            import numpy as np
            x_t, params, *_ = res
            dx = _to_host(jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), x_t))
            dp = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                              params)
            d_pos = np.zeros(np.shape(pos_p), jax.dtypes.float0)
            return dx, dp, d_pos

        run_d.defvjp(run_d_fwd, run_d_bwd)
        return _to_host(run_d(x_t, layer_params, pos_p))
    if "dummybwd" in _bis:
        def run_bwd_dummy(res, d_out_t):
            import numpy as np
            x_t, params, *_ = res
            dx = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), x_t)
            dx = _to_host(dx)
            dp = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params)
            d_pos = np.zeros(np.shape(pos_p), jax.dtypes.float0)
            return dx, dp, d_pos
        run.defvjp(run_fwd, run_bwd_dummy)
        return run(x_t, layer_params, pos_p)
    run.defvjp(run_fwd, run_bwd)
    return run(x_t, layer_params, pos_p)

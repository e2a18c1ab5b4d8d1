"""Paged (blocked-KV) decode attention kernel.

Reference: the ragged inference ops in
``inference/v2/kernels/ragged_ops/blocked_flash`` — CUDA flash attention
reading K/V directly from paged cache blocks via a block table, so decode
never materializes a per-token contiguous context.

TPU re-design: one Pallas kernel per sequence walks that sequence's pages
(innermost grid dim) with the block table as a scalar-prefetch operand —
the page id feeds the BlockSpec index_map, so the next page's DMA is
issued ahead of the body (the TPU analog of the reference's async-copy
pipeline). Online-softmax accumulation over pages in fp32 scratch; GQA
handled by grouping query heads per kv head (static in-kernel loop, since
Mosaic block shapes cannot tile the kv-head axis independently).

Layout matches inference/ragged/kv_cache.py: the pool is
``kv[L, num_blocks, block_size, 2, kv_heads, head_dim]`` and the kernels
take it whole, with the layer as one more scalar-prefetch operand: the
index map picks ``(layer, page)``, so a step program never slices a
layer out of the pool (a 130 MB copy per layer at mistral-7b width).
One page is fetched per grid step; the kernel reads K from plane 0 and V
from plane 1 of the same block. A 5-D one-layer pool is still accepted
(it is viewed as ``kv[None]``, layer 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fold_page(q, k, v, visible, m_ref, l_ref, acc_ref, rows: slice,
               nrows: int):
    """Fold one K/V page into the online-softmax state for one kv head.

    q [nrows, hd] fp32 (pre-scaled); k/v [bs, hd] fp32; visible
    [nrows, bs]; scratch refs indexed at ``rows``.
    """
    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    sc = jax.lax.select(visible, sc, jnp.full_like(sc, NEG_INF))

    m_prev = m_ref[rows, :1]                      # [nrows, 1]
    m_cur = jnp.max(sc, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    # explicit zero for masked columns: when every score so far is
    # the NEG_INF sentinel, exp(sc - m_new) == exp(0) would count them
    e = jnp.exp(sc - m_new)
    p = jax.lax.select(visible, e, jnp.zeros_like(e))

    l_new = alpha * l_ref[rows, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[rows, :] = acc_ref[rows, :] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[rows, :] = jnp.broadcast_to(m_new, (nrows, m_ref.shape[1]))
    l_ref[rows, :] = jnp.broadcast_to(l_new, (nrows, l_ref.shape[1]))


def _visit(q_ref, kv_ref, m_ref, l_ref, acc_ref, visible, *, bs: int,
           nkv: int, gp: int, scale: float):
    """Fold one K/V page into the online-softmax state (decode)."""
    for n in range(nkv):  # static unroll over kv heads
        q = q_ref[0, n].astype(jnp.float32) * scale   # [gp, hd]
        k = kv_ref[0, 0, :, 0, n].astype(jnp.float32)  # [bs, hd]
        v = kv_ref[0, 0, :, 1, n].astype(jnp.float32)  # [bs, hd]
        _fold_page(q, k, v, visible, m_ref, l_ref, acc_ref,
                   slice(n * gp, (n + 1) * gp), gp)


def _kernel(bt_ref, ctx_ref, layer_ref, q_ref, *refs, bs: int, nkv: int,
            gp: int, scale: float, pages: int):
    # refs = pages kv page blocks, then out_ref + 3 scratch refs. The
    # pages fold sequentially in ascending page order — the identical
    # op sequence for every pages_per_compute_block, so outputs stay
    # bit-identical across the autotuner's geometry candidates.
    kv_refs = refs[:pages]
    out_ref, m_ref, l_ref, acc_ref = refs[pages:]
    s = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[s]
    for i, kv_ref in enumerate(kv_refs):
        cols = ((j * pages + i) * bs
                + jax.lax.broadcasted_iota(jnp.int32, (gp, bs), 1))
        visible = cols < ctx

        # pages past the context: no compute (and the index_map
        # re-requests the same page: no DMA)
        @pl.when((j * pages + i) * bs < ctx)
        def _visit_page(kv_ref=kv_ref, visible=visible):
            _visit(q_ref, kv_ref, m_ref, l_ref, acc_ref, visible,
                   bs=bs, nkv=nkv, gp=gp, scale=scale)

    @pl.when(j == nj - 1)
    def _finalize():
        for n in range(nkv):
            rows = slice(n * gp, (n + 1) * gp)
            l = l_ref[rows, :1]
            l = jax.lax.select(l == 0.0, jnp.ones_like(l), l)  # dead slots
            out_ref[0, n] = (acc_ref[rows, :] / l).astype(out_ref.dtype)


def _prefill_kernel(pos0_ref, ctx_ref, bt_ref, layer_ref, q_ref, *refs,
                    bs: int, nkv: int, g: int, tq: int, scale: float,
                    pages: int):
    kv_refs = refs[:pages]
    out_ref, m_ref, l_ref, acc_ref = refs[pages:]
    s = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    rows = tq * g  # row layout per kv head: query-major, group-minor

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos0 = pos0_ref[s]
    ctx = ctx_ref[s]
    # query absolute position per row (row r = query r // g, group r % g)
    qpos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) // g
    for i, kv_ref in enumerate(kv_refs):
        cols = ((j * pages + i) * bs
                + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1))
        # causal within the segment + bounded by the segment's total
        # context; dead/padded segments have ctx == 0 -> nothing visible
        visible = jnp.logical_and(cols <= qpos, cols < ctx)

        @pl.when((j * pages + i) * bs < ctx)
        def _visit_page(kv_ref=kv_ref, visible=visible):
            for n in range(nkv):
                # q layout is [S, nkv, tq*g, hd] (wrapper pre-transposes):
                # only leading-dim integer indexing, which Mosaic supports
                q = q_ref[0, n].astype(jnp.float32) * scale  # [rows, hd]
                k = kv_ref[0, 0, :, 0, n].astype(jnp.float32)  # [bs, hd]
                v = kv_ref[0, 0, :, 1, n].astype(jnp.float32)
                _fold_page(q, k, v, visible, m_ref, l_ref, acc_ref,
                           slice(n * rows, (n + 1) * rows), rows)

    @pl.when(j == nj - 1)
    def _finalize():
        for n in range(nkv):
            rsl = slice(n * rows, (n + 1) * rows)
            l = l_ref[rsl, :1]
            l = jax.lax.select(l == 0.0, jnp.ones_like(l), l)
            out_ref[0, n] = (acc_ref[rsl, :] / l).astype(out_ref.dtype)


def _page_id(bt, ctx, s, j, bs: int, nb: int):
    """Pool page behind entry ``j`` of sequence ``s``'s block table.
    Entries beyond the context clamp to the last live page: Mosaic skips
    the DMA when consecutive grid steps request the same block."""
    last = jax.lax.max(ctx[s] - 1, 0) // bs
    return jax.lax.min(jax.lax.max(bt[s, jax.lax.min(j, last)], 0), nb - 1)


def _pool_and_layer(kv: jax.Array, layer):
    """The pool as the kernels index it, ``[L, nb, bs, 2, nkv, hd]``, and
    the layer as the ``int32[1]`` scalar-prefetch operand. A 5-D pool is
    one layer's: a free reshape to ``kv[None]``, layer 0."""
    if kv.ndim == 5:
        if layer is not None:
            raise ValueError("a 5-D KV pool is one layer's: pass the "
                             "[L, ...] pool with `layer`")
        kv, layer = kv[None], 0
    elif layer is None:
        raise ValueError("the [L, num_blocks, ...] KV pool needs `layer`")
    return kv, jnp.asarray(layer, jnp.int32).reshape(1)


def paged_prefill_attention(q: jax.Array, kv: jax.Array,
                            block_table: jax.Array, seg_pos0: jax.Array,
                            context_lens: jax.Array,
                            scale: float = None,
                            pages_per_compute_block: int = 1,
                            layer=None) -> jax.Array:
    """Chunked-prefill attention over paged KV (SplitFuse chunk step).

    Each segment is one sequence's contiguous chunk of ``Tq`` new tokens
    (queries at absolute positions pos0..pos0+Tq-1), already scattered
    into the paged cache. Queries attend their sequence's full paged
    history causally.

    q            [S, Tq, num_heads, head_dim] (padded rows have garbage;
                 their outputs are well-defined zeros only if the whole
                 segment is dead — callers slice real rows out)
    kv           [L, num_blocks, block_size, 2, kv_heads, head_dim], read
                 at ``layer`` (a traced int32 scalar); or one layer's
                 5-D pool with ``layer`` left out
    block_table  [S, max_pages]
    seg_pos0     [S] absolute position of each segment's first query
    context_lens [S] keys visible to the segment's LAST query (pos0 +
                 n_real_tokens); 0 marks a dead segment

    ``pages_per_compute_block`` (kernels config / autotuner axis) folds
    that many KV pages per grid step — fewer grid steps, more DMA in
    flight per step. Outputs are bit-identical for every legal value
    (pages fold in the same sequential order).

    Returns [S, Tq, num_heads, head_dim] in q.dtype.
    """
    S, tq, nh, hd = q.shape
    kv, layer = _pool_and_layer(kv, layer)
    _, nb, bs, _, nkv, _ = kv.shape
    Bm = block_table.shape[1]
    if nh % nkv:
        raise ValueError(f"num_heads {nh} not a multiple of kv_heads {nkv}")
    g = nh // nkv
    if (tq * g) % 8:
        raise ValueError(f"Tq*group ({tq}*{g}) must be a multiple of 8")
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    # [S, Tq, nh, hd] -> [S, nkv, Tq*g, hd]: per-kv-head rows, query-
    # major / group-minor (matches the kernel's qpos = row // g)
    qg = (q.reshape(S, tq, nkv, g, hd)
          .transpose(0, 2, 1, 3, 4)
          .reshape(S, nkv, tq * g, hd))

    P = max(1, min(int(pages_per_compute_block), Bm))

    def kv_spec(i):
        return pl.BlockSpec(
            (1, 1, bs, 2, nkv, hd),
            lambda s, j, pos0, ctx, bt, lyr: (
                lyr[0], _page_id(bt, ctx, s, j * P + i, bs, nb), 0, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, -(-Bm // P)),
        in_specs=[
            pl.BlockSpec((1, nkv, tq * g, hd),
                         lambda s, j, pos0, ctx, bt, lyr: (s, 0, 0, 0)),
        ] + [kv_spec(i) for i in range(P)],
        out_specs=pl.BlockSpec((1, nkv, tq * g, hd),
                               lambda s, j, pos0, ctx, bt, lyr: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv * tq * g, 128), jnp.float32),
            pltpu.VMEM((nkv * tq * g, 128), jnp.float32),
            pltpu.VMEM((nkv * tq * g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, bs=bs, nkv=nkv, g=g, tq=tq,
                          scale=float(scale), pages=P),
        name="paged_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nkv, tq * g, hd), q.dtype),
        interpret=_interpret(),
    )(seg_pos0.astype(jnp.int32), context_lens.astype(jnp.int32),
      block_table.astype(jnp.int32), layer, qg, *([kv] * P))
    return (out.reshape(S, nkv, tq, g, hd)
            .transpose(0, 2, 1, 3, 4)
            .reshape(S, tq, nh, hd))


def paged_decode_attention(q: jax.Array, kv: jax.Array,
                           block_table: jax.Array, context_lens: jax.Array,
                           scale: float = None,
                           pages_per_compute_block: int = 1,
                           layer=None) -> jax.Array:
    """Decode attention over a paged KV pool.

    q            [S, num_heads, head_dim] — one query token per sequence
    kv           [L, num_blocks, block_size, 2, kv_heads, head_dim], read
                 at ``layer`` (a traced int32 scalar); or one layer's
                 5-D pool with ``layer`` left out
    block_table  [S, max_pages] int32 page ids (entries past the context
                 may be stale/scratch; they are read but masked)
    context_lens [S] int32 — keys visible per sequence (including the
                 token written this step); 0 marks a dead slot (output 0)

    ``pages_per_compute_block`` folds that many KV pages per grid step
    (kernels config / autotuner axis); bit-identical for every legal
    value — the pages fold in the same sequential order.

    Returns [S, num_heads, head_dim] in q.dtype.
    """
    S, nh, hd = q.shape
    kv, layer = _pool_and_layer(kv, layer)
    _, nb, bs, _, nkv, _ = kv.shape
    Bm = block_table.shape[1]
    if nh % nkv:
        raise ValueError(f"num_heads {nh} not a multiple of kv_heads {nkv}")
    g = nh // nkv
    gp = max(8, -(-g // 8) * 8)  # pad head group to the fp32 sublane tile
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    qg = q.reshape(S, nkv, g, hd)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    P = max(1, min(int(pages_per_compute_block), Bm))

    def kv_spec(i):
        return pl.BlockSpec(
            (1, 1, bs, 2, nkv, hd),
            lambda s, j, bt, ctx, lyr: (
                lyr[0], _page_id(bt, ctx, s, j * P + i, bs, nb), 0, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, -(-Bm // P)),
        in_specs=[
            pl.BlockSpec((1, nkv, gp, hd),
                         lambda s, j, bt, ctx, lyr: (s, 0, 0, 0)),
        ] + [kv_spec(i) for i in range(P)],
        out_specs=pl.BlockSpec((1, nkv, gp, hd),
                               lambda s, j, bt, ctx, lyr: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv * gp, 128), jnp.float32),  # running max
            pltpu.VMEM((nkv * gp, 128), jnp.float32),  # running denom
            pltpu.VMEM((nkv * gp, hd), jnp.float32),   # weighted-value acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, nkv=nkv, gp=gp,
                          scale=float(scale), pages=P),
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nkv, gp, hd), q.dtype),
        interpret=_interpret(),
    )(block_table.astype(jnp.int32), context_lens.astype(jnp.int32),
      layer, qg, *([kv] * P))
    return out[:, :, :g, :].reshape(S, nh, hd)

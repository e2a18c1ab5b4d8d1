"""Paged (blocked-KV) decode attention kernel.

Reference: the ragged inference ops in
``inference/v2/kernels/ragged_ops/blocked_flash`` — CUDA flash attention
reading K/V directly from paged cache blocks via a block table, so decode
never materializes a per-token contiguous context.

TPU re-design, decode: one grid step per sequence; the pool stays in
HBM and the kernel walks the sequence's own pages in a loop whose trip
count is ``ceil(context / block_tokens)``, fetching a block of pages a
step by ``make_async_copy`` from the page ids in the scalar-prefetched
block table, double-buffered: block n+1, or the next sequence's first
block, is in flight while block n is multiplied (the TPU analog of the
reference's async-copy pipeline). Nothing past the context is visited.
A block's K (and V) of a sublane tile of KV heads is *one* MXU operand
``[tokens * heads, head_dim]``, the (token, head) rows exactly as they
lie in the pool, multiplied with those heads' query rows in one
product; a mask keeps each query row to its own head's columns, so the
score tile is lane-full and no row is moved between the fetch and the
MXU. Operands enter the MXU in the pool's dtype; scores, running max,
denominator and accumulator are float32 (online softmax across
blocks). The block's size is the kernel's own choice from the shapes
(``_decode_block_pages``). Pools Mosaic's DMA cannot slice
(``_pages_sliceable``) and the prefill kernel keep the older walk: the
grid's innermost dim steps over block-table entries, the page id feeds
the BlockSpec index_map, and pages fold one at a time, one KV head at a
time (static in-kernel loop).

Layout matches inference/ragged/kv_cache.py: the pool is
``kv[L, num_blocks, block_size, 2, kv_heads, head_dim]`` and the kernels
take it whole, with the layer as one more scalar-prefetch operand: the
fetch picks ``(layer, page)``, so a step program never slices a
layer out of the pool (a 130 MB copy per layer at mistral-7b width).
The kernel reads K from plane 0 and V from plane 1 of the same page. A
5-D one-layer pool is still accepted (it is viewed as ``kv[None]``,
layer 0).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

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


def _grid_walk_kernel(bt_ref, ctx_ref, layer_ref, q_ref, *refs, bs: int,
                      nkv: int, gp: int, scale: float, pages: int):
    """Decode over a pool whose pages a DMA cannot address
    (:func:`_pages_sliceable`): the grid walks every entry of the block
    table, ``pages`` of them a step, each page its own pipelined block
    and folded on its own, one KV head at a time."""
    # refs = pages kv page blocks, then out_ref + 3 scratch refs
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


def _walk_pages(bt_ref, ctx_ref, layer_ref, kv_hbm, buf, sem, slot_ref, *,
                bs: int, nb: int, pages: int, multiply, init,
                started_ref=None):
    """The walk the decode kernels share, one sequence a grid step: fetch
    the sequence's own pages from the pool in HBM (``kv_hbm[layer, page]``,
    any page shape) into ``buf[slot, i]``, ``pages`` of them a block, block
    n+1 (or the next sequence's first) in flight while block n is
    multiplied. ``multiply(blk, slot, carry)`` is a block's arithmetic on
    ``buf[slot]``, ``init`` its carry. Returns the carry after the
    sequence's last block. ``started_ref`` (SMEM, a slot a grid step), where
    given, counts the page copies this grid step starts."""
    s = pl.program_id(0)
    S = pl.num_programs(0)
    T = pages * bs                      # tokens a block
    layer = layer_ref[0]

    def copies(seq, blk, slot, fn):
        """Apply ``fn`` to the copy of every page of block ``blk`` of
        sequence ``seq`` that holds context: entries of the block table
        past the context are never read, let alone fetched. (A loop and
        not an unrolled run of guarded copies: the kernel is traced for
        every burst length, and a step's set-up is made of that.)"""
        seq_c = jax.lax.min(seq, S - 1)
        ctx = jnp.where(seq < S, ctx_ref[seq_c], 0)
        live = jnp.clip((ctx - blk * T + bs - 1) // bs, 0, pages)

        def page_copy(i, carry):
            page = bt_ref[seq_c, blk * pages + i]
            page = jax.lax.min(jax.lax.max(page, 0), nb - 1)
            fn(pltpu.make_async_copy(kv_hbm.at[layer, page],
                                     buf.at[slot, i], sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, live, page_copy, 0)
        return live

    def start(seq, blk, slot):
        n = copies(seq, blk, slot, lambda dma: dma.start())
        if started_ref is not None:
            started_ref[s] += n

    if started_ref is not None:
        started_ref[s] = 0

    @pl.when(s == 0)
    def _first():
        # rows a fetch never lands on still meet a zero weight in the
        # value product: they have to be finite
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(s, 0, 0)

    nblk = (ctx_ref[s] + T - 1) // T
    slot0 = slot_ref[0]
    slot_ref[0] = (slot0 + nblk) % 2    # where the next sequence starts

    @pl.when(nblk == 0)
    def _dead():
        start(s + 1, 0, slot0)

    def block(blk, carry):
        slot = (slot0 + blk) % 2
        # the next block, or the next sequence's first, flies meanwhile
        last = blk + 1 == nblk
        start(jnp.where(last, s + 1, s), jnp.where(last, 0, blk + 1),
              1 - slot)
        copies(s, blk, slot, lambda dma: dma.wait())
        return multiply(blk, slot, carry)

    return jax.lax.fori_loop(0, nblk, block, init)


def _decode_kernel(bt_ref, ctx_ref, layer_ref, q_ref, kv_hbm, out_ref,
                   buf, sem, slot_ref, *, bs: int, nb: int, c: int, g: int,
                   nchunks: int, pages: int, scale: float):
    """One sequence a grid step over its own pages (:func:`_walk_pages`).
    ``buf`` is [2, pages, bs, 2, nkv, hd]; a chunk of
    ``c`` KV heads of a block is one [pages*bs*c, hd] operand, its rows
    (token, head) pairs in the pool's own order, so nothing is moved
    between the fetch and the MXU: the product also multiplies each query
    row with the other heads' keys, and the head mask drops those."""
    T = pages * bs                      # tokens a block
    N = T * c                           # (token, head) columns a chunk
    rp = q_ref.shape[2]
    ctx = ctx_ref[pl.program_id(0)]
    col = jax.lax.broadcasted_iota(jnp.int32, (rp, N), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rp, N), 0)
    tok = col // c                      # c is a power of two
    own = (col % c) == (row // g)       # the column's head is the row's
    dt = jnp.promote_types(q_ref.dtype, buf.dtype)   # what the MXU takes

    def multiply(blk, slot, carry):
        visible = jnp.logical_and(own, tok < ctx - blk * T)
        out = []
        for n, (m_prev, l_prev, acc) in enumerate(carry):
            heads = slice(n * c, (n + 1) * c)
            k = buf[slot, :, :, 0, heads, :].reshape(N, -1)
            v = buf[slot, :, :, 1, heads, :].reshape(N, -1)
            sc = jax.lax.dot_general(
                q_ref[0, n].astype(dt), k.astype(dt),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(visible, sc, NEG_INF)
            # every live row sees a key in every block it walks, so its
            # running max is finite and exp(NEG_INF - m) is an exact 0
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(dt), v.astype(dt), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append((m_new, l_new, acc))
        return tuple(out)

    hd = out_ref.shape[-1]
    init = tuple((jnp.full((rp, 1), NEG_INF, jnp.float32),
                  jnp.zeros((rp, 1), jnp.float32),
                  jnp.zeros((rp, hd), jnp.float32))
                 for _ in range(nchunks))
    final = _walk_pages(bt_ref, ctx_ref, layer_ref, kv_hbm, buf, sem,
                        slot_ref, bs=bs, nb=nb, pages=pages,
                        multiply=multiply, init=init)
    for n, (_, l, acc) in enumerate(final):
        l = jnp.where(l == 0.0, 1.0, l)   # dead slots, padded rows
        out_ref[0, n] = (acc / l).astype(out_ref.dtype)


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
    flight per step; 0 or ``None`` is 1. Outputs are bit-identical for
    every legal value (pages fold in the same sequential order).

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

    P = max(1, min(int(pages_per_compute_block or 1), Bm))

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


# The decode kernel's block, measured on a v5e at the serving cells'
# shapes, the multi-head preset's and a tp=2 shard's (PERF.md section 6,
# PR 31). A block costs about a microsecond whatever it holds, so it
# should hold bytes: 1 MiB a fetch. But a context pays for the whole of
# its last block's products, so no more than 256 tokens (contexts of a
# few hundred tokens read 10-20% slower at 512). Two buffers live in
# VMEM beside the score tile.
_BLOCK_BYTES = 1024 * 1024
_BLOCK_TOKENS = 256
_VMEM_BUFFER_BYTES = 4 * 1024 * 1024


def _decode_block_pages(bs: int, nkv: int, hd: int, itemsize: int,
                        max_pages: int, c: int) -> int:
    """Pages the decode kernel folds a block, from the shapes alone:
    ``_BLOCK_BYTES`` of them but no more than ``_BLOCK_TOKENS`` tokens,
    at least enough (token, head) columns to fill the score tile's 128
    lanes, never more than a VMEM buffer holds or the block table has."""
    page_bytes = bs * 2 * nkv * hd * itemsize
    pages = min(_BLOCK_BYTES // page_bytes, _BLOCK_TOKENS // bs)
    pages = max(pages, -(-128 // (bs * c)))
    pages = min(pages, _VMEM_BUFFER_BYTES // page_bytes)
    return max(1, min(pages, max_pages))


def _pages_sliceable(nkv: int, hd: int, itemsize: int) -> bool:
    """Whether a DMA can take one page ``[bs, 2, nkv, hd]`` out of the
    pool: Mosaic slices a memory reference only where its last two
    dims fill whole tiles, so ``hd`` has to be a multiple of the 128
    lanes and ``nkv`` a multiple of 8 sublanes, or a power of two no
    smaller than the values a 32-bit sublane packs (2 for bf16)."""
    packing = max(1, 4 // itemsize)
    whole = nkv % 8 == 0 or (nkv & (nkv - 1) == 0 and nkv >= packing)
    return hd % 128 == 0 and whole


def paged_decode_attention(q: jax.Array, kv: jax.Array,
                           block_table: jax.Array, context_lens: jax.Array,
                           scale: float = None,
                           pages_per_compute_block: int = None,
                           layer=None) -> jax.Array:
    """Decode attention over a paged KV pool.

    q            [S, num_heads, head_dim] — one query token per sequence
    kv           [L, num_blocks, block_size, 2, kv_heads, head_dim], read
                 at ``layer`` (a traced int32 scalar); or one layer's
                 5-D pool with ``layer`` left out
    block_table  [S, max_pages] int32 page ids (entries past the context
                 may be stale, scratch or out of range: never fetched)
    context_lens [S] int32 — keys visible per sequence (including the
                 token written this step); 0 marks a dead slot (output 0)

    The pool stays in HBM; the kernel fetches each sequence's own pages,
    a block of them at a time, and stops at the sequence's last page.
    ``pages_per_compute_block`` 0 or ``None`` (the default) leaves the
    block to the kernel (:func:`_decode_block_pages`); a positive value
    sets it, for tests and the chip smoke. Outputs agree across blocks
    to float32 rounding, not bit for bit: a block is one product.

    A pool whose pages a DMA cannot address (:func:`_pages_sliceable`:
    ``head_dim`` 64, one bf16 KV head, twelve) keeps the walk over the
    whole block table, one pipelined page and one KV head at a time;
    there 0 or ``None`` is one page a grid step.

    Returns [S, num_heads, head_dim] in q.dtype.
    """
    S, nh, hd = q.shape
    kv, layer = _pool_and_layer(kv, layer)
    _, nb, bs, _, nkv, _ = kv.shape
    Bm = block_table.shape[1]
    if nh % nkv:
        raise ValueError(f"num_heads {nh} not a multiple of kv_heads {nkv}")
    g = nh // nkv
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    # (the interpreter has no tiles: every pool walks its own pages there)
    own_walk = _interpret() or _pages_sliceable(nkv, hd, kv.dtype.itemsize)

    # KV heads multiplied together in one product: a whole sublane tile
    # of them where the pool has one, so the (token, head) rows of a
    # block go to the MXU as they lie
    c = math.gcd(nkv, 8) if own_walk else 1
    nchunks = nkv // c
    rows = c * g
    rp = -(-rows // 8) * 8              # pad to the fp32 sublane tile
    qg = q.reshape(S, nchunks, rows, hd)
    if rp != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rp - rows), (0, 0)))

    if pages_per_compute_block:
        P = max(1, min(int(pages_per_compute_block), Bm))
    elif own_walk:
        P = _decode_block_pages(bs, nkv, hd, kv.dtype.itemsize, Bm, c)
    else:
        P = 1

    def rows_of(s, *_):                 # a sequence's query / output rows
        return (s, 0, 0, 0)

    if own_walk:
        kernel = functools.partial(_decode_kernel, bs=bs, nb=nb, c=c, g=g,
                                   nchunks=nchunks, pages=P,
                                   scale=float(scale))
        grid = (S,)
        kv_specs = [pl.BlockSpec(memory_space=pl.ANY)]
        scratch = [
            pltpu.VMEM((2, P, bs, 2, nkv, hd), kv.dtype),  # two blocks
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),    # buffer of the next block
        ]
    else:
        def kv_spec(i):
            return pl.BlockSpec(
                (1, 1, bs, 2, nkv, hd),
                lambda s, j, bt, ctx, lyr: (
                    lyr[0], _page_id(bt, ctx, s, j * P + i, bs, nb),
                    0, 0, 0, 0))

        kernel = functools.partial(_grid_walk_kernel, bs=bs, nkv=nkv, gp=rp,
                                   scale=float(scale), pages=P)
        grid = (S, -(-Bm // P))
        kv_specs = [kv_spec(i) for i in range(P)]
        scratch = [
            pltpu.VMEM((nkv * rp, 128), jnp.float32),  # running max
            pltpu.VMEM((nkv * rp, 128), jnp.float32),  # running denom
            pltpu.VMEM((nkv * rp, hd), jnp.float32),   # weighted-value acc
        ]

    out = pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[pl.BlockSpec((1, nchunks, rp, hd), rows_of)] + kv_specs,
            out_specs=pl.BlockSpec((1, nchunks, rp, hd), rows_of),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((S, nchunks, rp, hd), q.dtype),
        # a step starts the next sequence's first fetch, or carries the
        # running state on: in order, on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=_interpret(),
    )(block_table.astype(jnp.int32),
      jnp.minimum(context_lens.astype(jnp.int32), Bm * bs), layer, qg,
      *([kv] * len(kv_specs)))
    return out[:, :, :rows, :].reshape(S, nh, hd)


# ---------------------------------------------------------------------------
# latent (MLA) decode: the absorbed form over a latent pool
# ---------------------------------------------------------------------------

# tokens of context a block of the latent walk holds: 8 pages of 64, 640 KiB
# a buffer at 640 bf16 lanes a token; the score tile is [heads, tokens]
_MLA_BLOCK_TOKENS = 512


def _mla_decode_kernel(bt_ref, ctx_ref, layer_ref, *refs, bs: int, nb: int,
                       pages: int, vd: int, scale: float, windowed: bool,
                       chosen: bool):
    """One sequence a grid step over its own latent pages
    (:func:`_walk_pages`), every head at once: the heads' absorbed queries
    ``[heads, W]`` against a block ``[pages * bs, W]``, the values the first
    ``vd`` columns of the same rows already in VMEM. There is no KV head, so
    no head mask. ``windowed``: one more scalar operand, each sequence's
    lower bound, and one more term of the mask (a token below it is not
    seen). ``chosen``: one more blocked operand, ``[blocks, tokens]`` of the
    sequence's context, above zero where the token is attended over (a
    selector's choice): one more term of the mask too."""
    lo, keep_ref = 0, None
    if windowed:
        lo, refs = refs[0][pl.program_id(0)], refs[1:]
    if chosen:
        keep_ref, refs = refs[1], refs[:1] + refs[2:]
    (q_ref, kv_hbm, out_ref, started_ref, buf, sem, slot_ref, acc_ref) = refs
    T = pages * bs
    W = buf.shape[-1]
    rp = q_ref.shape[1]
    ctx = ctx_ref[pl.program_id(0)]
    tok = jax.lax.broadcasted_iota(jnp.int32, (rp, T), 1)
    dt = jnp.promote_types(q_ref.dtype, buf.dtype)
    q = q_ref[0].astype(dt)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def multiply(blk, slot, carry):
        m_prev, l_prev = carry
        k = buf[slot].reshape(T, W).astype(dt)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        seen = tok < ctx - blk * T
        if windowed:
            seen = jnp.logical_and(seen, tok >= lo - blk * T)
        if chosen:
            seen = jnp.logical_and(seen, keep_ref[0, pl.ds(blk, 1), :] > 0.0)
        sc = jnp.where(seen, sc, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        if windowed or chosen:  # a block with no key seen: exp(0) of none
            p = jnp.where(seen, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(dt), k[:, :vd], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new

    _, l = _walk_pages(
        bt_ref, ctx_ref, layer_ref, kv_hbm, buf, sem, slot_ref, bs=bs, nb=nb,
        pages=pages, multiply=multiply, started_ref=started_ref,
        init=(jnp.full((rp, 1), NEG_INF, jnp.float32),
              jnp.zeros((rp, 1), jnp.float32)))
    l = jnp.where(l == 0.0, 1.0, l)       # dead slots
    out_ref[0] = (acc_ref[...] / l).astype(out_ref.dtype)


def mla_decode_attention(q: jax.Array, kv: jax.Array, block_table: jax.Array,
                         context_lens: jax.Array, *, value_dim: int,
                         scale: float, layer,
                         pages_per_compute_block: int = None,
                         lower: jax.Array = None, chosen: jax.Array = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Decode attention of multi-head latent attention in its absorbed form,
    over a latent pool (``inference/ragged/kv_cache.py``, kind "latent").

    q            [S, heads, W]: each head's absorbed query (its nope part
                 through ``W_kvb^K``, then its rotated part), zero beyond
                 the latent's width up to the pool's ``W``
    kv           [L, num_blocks, block_size, W], read at ``layer``: a token's
                 row is its latent (the compressed vector, the rotary key,
                 zeros up to ``W``, whole 128-lane tiles)
    block_table  [S, max_pages]; context_lens [S] (0: a dead slot, output 0)
    value_dim    the leading columns of a row that are also its value (the
                 compressed vector's width, a multiple of 128)
    lower        [S] or None: a sequence's tokens below it are not seen (a
                 sliding window's far edge, counted along the block table;
                 the pages below it are still walked, so hand over a table
                 that starts at the window's first page)
    chosen       [S, max_pages * block_size] bool or None: the context tokens
                 a sequence attends over (a selector's choice; the others
                 are fetched with their pages and masked)

    A score is ``q . row * scale``; the output ``[S, heads, value_dim]`` is
    the softmax-weighted sum of the rows' leading ``value_dim`` columns: keys
    and values are the same bytes of a page, fetched once. Softmax state in
    float32, operands in the pool's type. Returns (output, pages ``[S]``
    int32: the page copies the kernel started in each grid step, counted
    where it starts them; their sum is what the call fetched).
    """
    S, nh, W = q.shape
    _, nb, bs, Wp = kv.shape
    if W != Wp or W % 128 or value_dim % 128 or value_dim > W:
        raise ValueError(f"queries {q.shape} against a latent pool "
                         f"{kv.shape} with values {value_dim} wide: the "
                         f"widths must agree and fill whole 128-lane tiles")
    Bm = block_table.shape[1]
    P = pages_per_compute_block or max(1, _MLA_BLOCK_TOKENS // bs)
    P = max(1, min(int(P), Bm))
    rp = -(-nh // 8) * 8
    if rp != nh:
        q = jnp.pad(q, ((0, 0), (0, rp - nh), (0, 0)))
    windowed = lower is not None
    kernel = functools.partial(_mla_decode_kernel, bs=bs, nb=nb, pages=P,
                               vd=value_dim, scale=float(scale),
                               windowed=windowed, chosen=chosen is not None)
    bounds = (lower.astype(jnp.int32),) if windowed else ()
    keep, keep_spec = (), []
    if chosen is not None:      # a row a block of the walk, whole blocks
        nblk = -(-Bm // P)
        keep = (jnp.pad(chosen.astype(jnp.float32),
                        ((0, 0), (0, nblk * P * bs - Bm * bs))).reshape(
                            S, nblk, P * bs),)
        keep_spec = [pl.BlockSpec((1, nblk, P * bs), lambda s, *_: (s, 0, 0))]
    out, started = pl.pallas_call(
        kernel,
        name="mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + windowed,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, rp, W), lambda s, *_: (s, 0, 0)),
                      *keep_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, rp, value_dim),
                                    lambda s, *_: (s, 0, 0)),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[
                pltpu.VMEM((2, P, bs, W), kv.dtype),       # two blocks
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((rp, value_dim), jnp.float32),  # weighted rows
            ]),
        out_shape=[jax.ShapeDtypeStruct((S, rp, value_dim), q.dtype),
                   jax.ShapeDtypeStruct((S,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(block_table.astype(jnp.int32),
      jnp.minimum(context_lens.astype(jnp.int32), Bm * bs),
      jnp.asarray(layer, jnp.int32).reshape(1), *bounds, q, *keep, kv)
    return out[:, :nh], started


# ---------------------------------------------------------------------------
# the learned selector's scores over a sequence's cached indexer keys
# ---------------------------------------------------------------------------

# tokens of context a block of the indexer's walk holds: 16 pages of 64, 256
# KiB a buffer at 128 bf16 lanes a key; the score tile is [heads, tokens]
_INDEX_BLOCK_TOKENS = 1024


def _index_scores_kernel(bt_ref, ctx_ref, layer_ref, q_ref, w_ref, ik_hbm,
                         out_ref, buf, sem, slot_ref, *, bs: int, nb: int,
                         pages: int):
    """One sequence a grid step over its own pages of indexer keys
    (:func:`_walk_pages`): the heads' queries ``[heads, d]`` against a block
    of keys ``[pages * bs, d]``, ReLU, the heads' weights as one more product
    ``[8, heads] x [heads, tokens]`` (row 0 is the sequence's), written to
    the block's row of the output. Rows of blocks past the context are not
    written."""
    T = pages * bs
    dt = jnp.promote_types(q_ref.dtype, buf.dtype)
    q = q_ref[0].astype(dt)
    w = w_ref[0]

    def multiply(blk, slot, carry):
        k = buf[slot].reshape(T, -1).astype(dt)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        tot = jax.lax.dot_general(w, jnp.maximum(sc, 0.0),
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32,
                                  precision=jax.lax.Precision.HIGHEST)
        out_ref[0, pl.ds(blk, 1), :] = tot[:1]
        return carry

    _walk_pages(bt_ref, ctx_ref, layer_ref, ik_hbm, buf, sem, slot_ref, bs=bs,
                nb=nb, pages=pages, multiply=multiply, init=0)


def index_scores_decode(q: jax.Array, w: jax.Array, ik: jax.Array,
                        block_table: jax.Array, context_lens: jax.Array, *,
                        layer) -> jax.Array:
    """The learned selector's scores of one query a sequence against every
    indexer key the sequence has cached: ``I[s, t] = sum_h w[s, h] relu(q[s,
    h] . ik[layer, page(t), t % bs])``.

    q            [S, heads, d] (on a chip d fills whole 128-lane tiles: the
                 page fetch slices the pool at tiles); w [S, heads] float32
    ik           [L, num_blocks, block_size, d]: the keys, page-addressed as
                 the latent pool beside them
    block_table  [S, max_pages]; context_lens [S]

    Returns float32 ``[S, max_pages * block_size]``; an entry at or past the
    sequence's context (rounded up to whole blocks of the walk) is not
    written and holds anything: mask by ``context_lens``. Each key is read
    once, nothing past the context is fetched; the heads' scores never leave
    VMEM."""
    S, nh, d = q.shape
    _, nb, bs, dk = ik.shape
    if d != dk:
        raise ValueError(f"queries {q.shape} against indexer keys {ik.shape}: "
                         f"the widths must agree")
    Bm = block_table.shape[1]
    P = max(1, min(_INDEX_BLOCK_TOKENS // bs, Bm))
    nblk = -(-Bm // P)
    T = P * bs
    if nblk * P != Bm:      # whole blocks of the walk
        block_table = jnp.pad(block_table, ((0, 0), (0, nblk * P - Bm)))
    w8 = jnp.pad(w.astype(jnp.float32)[:, None, :], ((0, 0), (0, 7), (0, 0)))
    kernel = functools.partial(_index_scores_kernel, bs=bs, nb=nb, pages=P)
    out = pl.pallas_call(
        kernel,
        name="dsa_index",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, nh, d), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((1, 8, nh), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, nblk, T), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, P, bs, d), ik.dtype),       # two blocks
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, nblk, T), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(block_table.astype(jnp.int32),
      jnp.minimum(context_lens.astype(jnp.int32), Bm * bs),
      jnp.asarray(layer, jnp.int32).reshape(1), q, w8, ik)
    return out.reshape(S, nblk * T)[:, :Bm * bs]


# ---------------------------------------------------------------------------
# chunked prefill under a block mask: a tile of queries over the pages it chose
# ---------------------------------------------------------------------------

# The block-masked chunk kernel's tile and fold, measured on a v5e at the
# learned selector's shapes (16 heads a KV group of 128, pages of 128 tokens
# and 4 KV heads, 256 KB; PERF.md section 6, PR 50). A tile holds
# ``_TILE_ROWS`` rows (queries x the heads of a KV group): its score tile
# fills the MXU's rows whatever the page. A fold of the running softmax takes
# ``_FOLD_KEYS`` keys (whole pages): every per-row term of the softmax
# (maximum, rescale, denominator) costs a pass as wide as 128 keys' scores,
# so a fold of one page read 3.8 us a page, of four 1.9, of eight 1.33 and of
# sixteen no less (and a short list pays for the pages its last fold lacks).
# One product takes ``_FOLD_ROWS`` of the tile's rows (512 reads 3% slower).
# Two folds of pages, the head's keys and values, the score tile and the
# softmax state want more than the 16 MiB a kernel is given unasked.
_TILE_ROWS = 2048
_FOLD_ROWS = 1024
_FOLD_KEYS = 1024
_CHUNK_VMEM_BYTES = 64 * 1024 * 1024


def chunk_tile(tq_all: int, g: int, bs: int) -> int:
    """Queries a tile of the block-masked chunk kernel holds, from the
    shapes alone: no more than a page (a tile that starts on a page's border
    shares the blocks every query reads: the first, its own, the one before),
    no more than ``_TILE_ROWS`` rows with the ``g`` heads of a KV group, no
    more than the chunk."""
    return max(1, min(bs, _TILE_ROWS // g, tq_all))


def tile_visits(mask: jax.Array, seg_pos0: jax.Array, context_lens: jax.Array,
                tq: int, bs: int):
    """The blocks each tile of ``tq`` queries visits under ``mask`` [S, Tq,
    nkv, Bm] (``Tq`` whole tiles): a block some real query of the tile chose
    and that holds a key (it starts below the context).

    Returns ``(live, blocks, count, visible)``: ``live`` the mask held to the
    real queries and the blocks that exist; ``blocks`` [S, nkv, tiles, Bm]
    int32 ascending, the first ``count`` [S, nkv, tiles] of them real (the
    rest ``Bm``); ``visible`` [S, tiles] the blocks the tile's last real query
    sees (0 for a tile with none)."""
    S, Tq, nkv, Bm = mask.shape
    nt = Tq // tq
    ctx = context_lens.astype(jnp.int32)[:, None]
    pos = seg_pos0.astype(jnp.int32)[:, None] + jnp.arange(Tq)[None, :]
    blk = jnp.arange(Bm, dtype=jnp.int32)
    live = (mask & (pos < ctx)[:, :, None, None]
            & (blk[None, :] * bs < ctx)[:, None, None, :])
    hit = jnp.any(live.reshape(S, nt, tq, nkv, Bm), axis=2).transpose(
        0, 2, 1, 3)                                         # [S, nkv, nt, Bm]
    blocks = jnp.sort(jnp.where(hit, blk, Bm), axis=-1)
    count = jnp.sum(hit, axis=-1).astype(jnp.int32)
    first = pos[:, ::tq]                                           # [S, nt]
    last = jnp.minimum(first + tq, ctx) - 1
    visible = jnp.where(first < ctx, last // bs + 1, 0)
    return live, blocks, count, visible


def _block_prefill_kernel(blocks_ref, count_ref, bt_ref, pos0_ref, layer_ref,
                          q_ref, mask_ref, kv_hbm, out_ref, buf, sem,
                          slot_ref, ks_ref, vs_ref, bias_ref, m_ref, l_ref,
                          acc_ref, *, bs: int, nb: int, nkv: int, tq: int,
                          g: int, gc: int, pages: int, scale: float):
    """One tile of ``tq`` queries of one KV head a grid step, over the pages
    its visit list names (``blocks_ref``, ``count_ref``: flat, a grid step's
    entries after the one before it), ``pages`` of them a fold. Pages come
    from the pool in HBM through the block table, as they lie (``kv_hbm[layer,
    page]`` is ``[bs * 2 * nkv, d]``: a row a (token, plane, head)), the next
    fold's (or the next grid step's first) in flight while this one is
    multiplied. The KV head's rows are cut out of a page by strided loads of
    32-bit words (a 16-bit pool packs two rows a word) into ``ks_ref`` /
    ``vs_ref``. Rows are group-major, query-minor (``[g, tq, d]``), so the
    mask's term -- the query chose the block, and the key is not after it --
    is one additive ``[tq, keys]`` tile for all ``g`` heads (``bias_ref``). A
    fold's scores are computed once and folded into the running softmax in
    VMEM, ``gc`` heads a product."""
    s, h, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nt = pl.num_programs(2)
    Bm = bt_ref.shape[1]
    width = -(-Bm // pages) * pages     # a grid step's entries: whole folds
    step = (s * nkv + h) * nt + t
    nsteps = pl.num_programs(0) * nkv * nt
    layer = layer_ref[0]
    d = q_ref.shape[-1]
    dt = jnp.promote_types(q_ref.dtype, buf.dtype)     # what the MXU takes
    pack = 4 // buf.dtype.itemsize      # rows of the pool a 32-bit word holds
    words = buf.bitcast(jnp.uint32) if pack > 1 else buf

    def copies(stp, fold, slot, fn):
        """Apply ``fn`` to the copy of every page of fold ``fold`` of grid
        step ``stp`` (``count_ref[stp]`` pages in all) into ``buf[slot]``."""
        live = jnp.clip(count_ref[stp] - fold * pages, 0, pages)

        def page_copy(i, carry):
            blk = jax.lax.min(blocks_ref[stp * width + fold * pages + i],
                              Bm - 1)
            page = bt_ref[stp // (nkv * nt), blk]
            page = jax.lax.min(jax.lax.max(page, 0), nb - 1)
            fn(pltpu.make_async_copy(kv_hbm.at[layer, page], buf.at[slot, i],
                                     sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, live, page_copy, 0)

    def start(stp, fold, slot):
        copies(stp, fold, slot, lambda dma: dma.start())

    n = count_ref[step]
    nfolds = (n + pages - 1) // pages
    nxt = jax.lax.min(step + 1, nsteps - 1)
    follows = step + 1 < nsteps      # (a last or empty one's copies: none)

    @pl.when(step == 0)
    def _first():
        # rows a fetch never lands on still meet a zero weight in the value
        # product: they have to be finite
        ks_ref[...] = jnp.zeros_like(ks_ref)
        vs_ref[...] = jnp.zeros_like(vs_ref)
        slot_ref[0] = 0
        start(step, 0, 0)

    slot0 = slot_ref[0]
    slot_ref[0] = (slot0 + nfolds) % 2  # where the next grid step starts

    @pl.when(jnp.logical_and(nfolds == 0, follows))
    def _dead():
        start(nxt, 0, slot0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    qpos = (pos0_ref[s] + t * tq
            + jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 0))
    key = jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tq, 128), 1)

    def head_rows(slot, i, plane, head):
        """``[bs, d]``: the rows of (plane, head) of page ``i`` of
        ``buf[slot]``, every token's: row ``plane * nkv + head`` of the ``2 *
        nkv`` a token has, ``pack`` of them a word. (The head is traced: a
        strided load takes a traced start, where an index on the pool's
        tiled head dim would have to be static.)"""
        e = plane * nkv + head
        x = words[slot, i, pl.ds(e // pack, bs, stride=2 * nkv // pack), :]
        if pack == 1:
            return x.astype(dt)
        # the word's low half is the even row; a bfloat16 is the high half of
        # the float32 of the same value
        x = (x >> (16 * (e % pack)).astype(jnp.uint32)) << 16
        return jax.lax.bitcast_convert_type(x, jnp.float32).astype(dt)

    def fold(f, carry):
        slot = (slot0 + f) % 2
        last = f + 1 == nfolds

        # the next fold, or the next grid step's first, flies meanwhile
        @pl.when(jnp.logical_or(jnp.logical_not(last), follows))
        def _ahead():
            start(jnp.where(last, nxt, step), jnp.where(last, 0, f + 1),
                  1 - slot)

        copies(step, f, slot, lambda dma: dma.wait())
        for i in range(pages):                              # static
            j = f * pages + i
            blk = jax.lax.min(blocks_ref[step * width + j], Bm - 1)
            # the tile's column of the mask: who chose this block
            chose = jnp.max(jnp.where(lane == blk % 128,
                                      mask_ref[0, 0, 0, blk // 128], 0),
                            axis=1, keepdims=True) > 0            # [tq, 1]
            seen = jnp.logical_and(
                jnp.logical_and(chose, j < n), blk * bs + key <= qpos)
            bias_ref[:, i * bs:(i + 1) * bs] = jnp.where(seen, 0.0, NEG_INF)

            # (each page's cut beside its column of the mask: all pages'
            # cuts in one block read a fifth slower)
            @pl.when(j < n)
            def _cut(i=i):
                ks_ref[i * bs:(i + 1) * bs] = head_rows(slot, i, 0, h)
                vs_ref[i * bs:(i + 1) * bs] = head_rows(slot, i, 1, h)

        k, v, bias = ks_ref[...], vs_ref[...], bias_ref[...][None]

        # static: gc heads a product, the products side by side in one block
        # so that one's softmax runs under the other's products (looped, the
        # kernel read a quarter slower)
        for g0 in range(0, g, gc):
            heads = slice(g0, g0 + gc)
            q = q_ref[0, 0, heads].reshape(gc * tq, d).astype(dt)
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            sc = sc.reshape(gc, tq, pages * bs) + bias
            m_prev = m_ref[heads]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a masked score is NEG_INF to float32 rounding; against a
            # maximum held above NEG_INF / 2 its weight is an exact 0, also
            # in a row that has seen no key yet
            p = jnp.exp(sc - jnp.maximum(m_new, 0.5 * NEG_INF))
            l_ref[heads] = alpha * l_ref[heads] + jnp.sum(p, axis=-1,
                                                          keepdims=True)
            pv = jax.lax.dot_general(
                p.reshape(gc * tq, pages * bs).astype(dt), v,
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            acc_ref[heads] = acc_ref[heads] * alpha + pv.reshape(gc, tq, d)
            m_ref[heads] = m_new
        return carry

    jax.lax.fori_loop(0, nfolds, fold, 0)
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)       # dead and padded rows, empty tiles
    out_ref[0, 0] = (acc_ref[...] / l).astype(out_ref.dtype)


def block_prefill_attention(q: jax.Array, kv: jax.Array,
                            block_table: jax.Array, mask: jax.Array,
                            seg_pos0: jax.Array, context_lens: jax.Array, *,
                            layer=None, scale: float = None):
    """Chunked-prefill attention over paged KV under a block mask: a query
    attends, causally, over the keys of the blocks it chose. A block is a
    page of the pool.

    q            [S, Tq, num_heads, head_dim]: each segment one sequence's
                 chunk, queries at positions ``seg_pos0 ..``, already written
                 to the pool
    kv           [L, num_blocks, block_size, 2, kv_heads, head_dim], float32
                 or bfloat16, read at ``layer``; or one layer's 5-D pool with
                 ``layer`` left out
    block_table  [S, max_pages]
    mask         [S, Tq, kv_heads, max_pages] bool: the blocks each (query,
                 KV head) reads (a selector's choice; all its group's heads
                 read the same)
    seg_pos0     [S]; context_lens [S] = pos0 + real tokens, 0: a dead segment

    A tile of queries (:func:`chunk_tile`) visits the blocks one of its real
    queries chose (:func:`tile_visits`) and no other; inside a visited block
    a query that did not choose it is masked, so the result is each query's
    own. Operands enter the MXU in the pool's type; scores, running maximum,
    denominator and accumulator are float32. Rows at or past the context
    (padding, dead segments) give zeros.

    Returns ``(out [S, Tq, num_heads, head_dim] in q.dtype, visited [S,
    kv_heads, tiles] int32, visible [S, tiles] int32)``: the blocks each
    tile visited and the blocks its last real query sees."""
    S, Tq, nh, d = q.shape
    kv, layer = _pool_and_layer(kv, layer)
    L, nb, bs, _, nkv, _ = kv.shape
    Bm = block_table.shape[1]
    if nh % nkv:
        raise ValueError(f"num_heads {nh} not a multiple of kv_heads {nkv}")
    if mask.shape != (S, Tq, nkv, Bm):
        raise ValueError(f"mask {mask.shape} for queries {q.shape} over "
                         f"{nkv} KV heads and a table of {Bm} pages")
    if kv.dtype not in (jnp.float32, jnp.bfloat16):
        raise NotImplementedError(
            f"the chunk kernel cuts a KV head's rows out of a page of "
            f"float32 or bfloat16, not {kv.dtype}")
    g = nh // nkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    tq = chunk_tile(Tq, g, bs)
    nt = -(-Tq // tq)
    if nt * tq != Tq:                   # whole tiles: padded rows choose none
        q = jnp.pad(q, ((0, 0), (0, nt * tq - Tq), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, nt * tq - Tq), (0, 0), (0, 0)))
    live, blocks, count, visible = tile_visits(mask, seg_pos0, context_lens,
                                               tq, bs)
    P = max(1, min(_FOLD_KEYS // bs, Bm))       # pages a fold
    blocks = jnp.pad(blocks, ((0, 0),) * 3 + ((0, -Bm % P),),
                     constant_values=Bm)        # whole folds
    C = -(-Bm // 128)
    # the mask a tile at a time, a block a lane: [S, nkv, nt, C, tq, 128]
    tiles = jnp.pad(live, ((0, 0),) * 3 + ((0, C * 128 - Bm),)).reshape(
        S, nt, tq, nkv, C, 128).transpose(0, 3, 1, 4, 2, 5).astype(jnp.int32)
    # rows group-major, query-minor: [S, nkv, g, Tq, d]
    qg = q.reshape(S, nt * tq, nkv, g, d).transpose(0, 2, 3, 1, 4)
    gc = max(1, min(g, _FOLD_ROWS // tq))
    while g % gc:
        gc -= 1

    def rows_of(s, h, t, *_):
        return (s, h, 0, t, 0)

    out = pl.pallas_call(
        functools.partial(_block_prefill_kernel, bs=bs, nb=nb, nkv=nkv, tq=tq,
                          g=g, gc=gc, pages=P, scale=float(scale)),
        name="paged_block_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(S, nkv, nt),
            in_specs=[pl.BlockSpec((1, 1, g, tq, d), rows_of),
                      pl.BlockSpec((1, 1, 1, C, tq, 128),
                                   lambda s, h, t, *_: (s, h, t, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, g, tq, d), rows_of),
            scratch_shapes=[
                pltpu.VMEM((2, P, bs * 2 * nkv, d), kv.dtype),  # two folds
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),    # buffer of the next fold
                pltpu.VMEM((P * bs, d), kv.dtype),      # the head's keys
                pltpu.VMEM((P * bs, d), kv.dtype),      # and values
                pltpu.VMEM((tq, P * bs), jnp.float32),  # the mask, additive
                pltpu.VMEM((g, tq, 1), jnp.float32),    # running max
                pltpu.VMEM((g, tq, 1), jnp.float32),    # running denom
                pltpu.VMEM((g, tq, d), jnp.float32),    # weighted values
            ]),
        out_shape=jax.ShapeDtypeStruct((S, nkv, g, nt * tq, d), q.dtype),
        # a grid step starts the next one's first fetch: in order, one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_CHUNK_VMEM_BYTES),
        interpret=_interpret(),
    )(blocks.reshape(-1), count.reshape(-1), block_table.astype(jnp.int32),
      seg_pos0.astype(jnp.int32), layer, qg, tiles,
      # a page as it lies: the pool's bytes, a (token, plane, head) a row
      kv.reshape(L, nb, bs * 2 * nkv, d))
    out = out.transpose(0, 3, 1, 2, 4).reshape(S, nt * tq, nh, d)
    return out[:, :Tq], count, visible

"""Grouped matrix multiply (Pallas TPU kernel) — dropless-MoE execution.

The role the reference's grouped-GEMM expert engine plays
(deepspeed/moe/ep_experts.py:136 ``GroupedExperts`` — experts executed as
grouped GEMMs over per-expert token counts, no capacity padding), built
megablox-style for the MXU:

  gmm(lhs [M, K], rhs [E, K, N], group_sizes [E]) -> out [M, N]

where the rows of ``lhs`` are sorted by group (group e owns the
contiguous row range [sum(sizes[:e]), sum(sizes[:e+1]))) and row m is
multiplied by ``rhs[group(m)]``. FLOPs are exactly M*K*N — independent
of how imbalanced the groups are — versus the capacity-padded einsum
dispatch whose cost is fixed at E*capacity slots and which *drops*
tokens when a group overflows.

Mechanics: group boundaries rarely align with the row tile, so
the grid iterates over *work items* — (m-tile, group) pairs that
intersect — with the per-item tile id, group id, and row range
scalar-prefetched. A tile crossed by a boundary is visited once per
group; rows outside the item's group are masked from the product and
the partial products accumulate in a VMEM scratch across the
consecutive visits. The number of slots is static: M/block_m + E - 1,
the worst case (every interior group boundary adds one extra visit). A
group with no row has no item, and the slots beyond the real items
repeat the last real item with an empty row range: such a slot does no
product, touches no accumulator and fetches no new block (its block
indices are the step's before it), so a call costs what its rows and
the groups *that got a row* cost, not what the groups held would.

The tiles are the kernel's choice from the static shapes
(:func:`choose_tiles`): a few rows a group (a serving step: 3 at 384
rows over 128 experts) is memory-bound and wants a short row tile and a
group's whole matrix in as few steps as VMEM allows; thousands of rows a
group (training) is compute-bound and wants the large tiles measured
below.

The backward is two more grouped products: dlhs = gmm(dout, rhs^T) and
drhs[e] = lhs_e^T @ dout_e (``tgmm`` below, same metadata, accumulator
keyed by group instead of by tile).

Requires sum(group_sizes) == M (callers pad rows and assign the padding
to a real group with zero combine weight — see parallel/moe.py).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# work-item metadata
# ---------------------------------------------------------------------------

def make_group_metadata(group_sizes: jax.Array, m: int, block_m: int
                        ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Static-shape work list for a grouped matmul.

    Returns (tile_ids, group_ids, row_start, row_end), each [T] int32 with
    T = m//block_m + E - 1. Work items are ordered by row, so all visits
    to one m-tile are consecutive (accumulation stays VMEM-resident) and
    all visits to one group are consecutive (for the tgmm accumulator).
    Padding items repeat the last real (tile, group) with an empty row
    range.

    Contract guard (sum(group_sizes) must equal m): sizes are clamped so
    cumulative ends never exceed ``m`` (over-sum can't index tiles out of
    range), and when sum < m the padding items are re-aimed at the
    uncovered trailing m-tiles with empty row ranges — those output
    blocks come back zero-filled instead of as uninitialized memory.
    """
    num_groups = group_sizes.shape[0]
    m_tiles = m // block_m
    t_total = m_tiles + num_groups - 1

    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.minimum(jnp.cumsum(sizes), m)    # clamp: over-sum stays in range
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    sizes = ends - starts
    first_tile = starts // block_m
    last_tile = jnp.where(sizes > 0, (ends - 1) // block_m, first_tile)
    items = jnp.where(sizes > 0, last_tile - first_tile + 1, 0)  # [E]
    item_cum = jnp.cumsum(items)
    item_base = item_cum - items
    total = item_cum[-1]

    w = jnp.arange(t_total, dtype=jnp.int32)
    gid = jnp.searchsorted(item_cum, w, side="right").astype(jnp.int32)
    gid = jnp.clip(gid, 0, num_groups - 1)
    tile = first_tile[gid] + (w - item_base[gid])

    valid = w < total
    last = jnp.maximum(total - 1, 0)
    # padding items: aim at any m-tiles left uncovered by an under-sum
    # (one each, empty row range → zero-filled output); once tiles are
    # exhausted, stay on the tile visited last — the last uncovered one,
    # or the last real item's where none was uncovered (a benign
    # re-visit). Going *back* to the last real item's tile after the
    # uncovered ones would open it anew and zero what it holds.
    first_uncovered = (ends[-1] + block_m - 1) // block_m
    pad_tile = first_uncovered + (w - total)
    use_pad_tile = jnp.logical_and(~valid, pad_tile < m_tiles)
    rest = jnp.where(first_uncovered < m_tiles, m_tiles - 1, tile[last])
    tile = jnp.where(valid, tile,
                     jnp.where(use_pad_tile, pad_tile, rest))
    tile = jnp.clip(tile, 0, max(m_tiles - 1, 0)).astype(jnp.int32)
    group = jnp.where(valid, gid, gid[last]).astype(jnp.int32)
    row_start = jnp.where(valid, starts[gid], 0).astype(jnp.int32)
    row_end = jnp.where(valid, ends[gid], 0).astype(jnp.int32)
    return tile, group, row_start, row_end


def _num_work_items(m: int, num_groups: int, block_m: int) -> int:
    return m // block_m + num_groups - 1


def work_items(metadata) -> jax.Array:
    """The slots of a work list that hold a row and so do a product — the
    (m-tile, group) pairs that share one — as an int32 scalar: what a call
    costs beside its bytes, for the serving counters."""
    _, _, row_start, row_end = metadata
    return jnp.sum(row_end > row_start).astype(jnp.int32)


# ---------------------------------------------------------------------------
# gmm: out[m] = lhs[m] @ rhs[group(m)]
# ---------------------------------------------------------------------------

def _pick_block(dim: int, want: int) -> int:
    """Largest power-of-two tile <= want that divides dim (>=128 when
    possible — HBM traffic scales inversely with the tile, see module
    docstring)."""
    b = min(want, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


def _pick_lane_block(dim: int, want: int) -> int:
    """A tile of a dim that lies on lanes (``n``, the contraction):
    :func:`_pick_block`'s where that fills more than one lane tile or is all
    that was asked for. A width that is 128 times an odd number (2688 = 128
    x 21) halves down to 128 and one that is no multiple of 128 (1856 = 64
    x 29) below it, which Mosaic refuses for a block that is not the whole
    dim: there, the largest multiple of 128 within ``want`` that divides the
    dim, or with none the whole dim."""
    b = _pick_block(dim, want)
    if b > 128 or b >= want:
        return b
    fit = [x for x in range(128, min(want, dim) + 1, 128) if dim % x == 0]
    return max(fit) if fit else dim


# a right-hand block of a memory-bound call: double-buffered, two of them
# stay well inside the 16 MiB of VMEM a v5e kernel gets by default
_RHS_BLOCK_BYTES = 2 << 20
# all the blocks of one call, of the 16 MiB a v5e kernel gets by default
# (Mosaic keeps some for itself: 13.8 MiB of blocks did not compile)
_VMEM_BLOCKS_BYTES = 12 << 20


def row_tile(m: int, num_groups: int, dtype) -> int:
    """The row tile for ``m`` rows over ``num_groups`` groups: the rows a
    group gets on average, as a power of two between the type's sublane
    packing (16 rows of bf16) and 512. A tile much taller than a group
    multiplies masked rows, one much shorter visits a group's matrix more
    often. It follows from the rows and the groups alone, so the products
    of one expert block share it, and with it one work list."""
    rows = -(-m // max(num_groups, 1))
    return min(max(pl.next_power_of_2(rows), 32 // jnp.dtype(dtype).itemsize),
               512)


def choose_tiles(m: int, kdim: int, n: int, num_groups: int, dtype,
                 block_m: int = 0, block_n: int = 0, block_k: int = 0
                 ) -> Tuple[int, int, int]:
    """(block_m, block_n, block_k) for ``[m, kdim] x [groups, kdim, n]``,
    from the static shapes alone; a positive argument is an upper bound
    on that tile (``kernels.gmm_block_*``).

    The row tile is :func:`row_tile`. At 512 rows and more a group the
    product is compute-bound and takes the tiles measured at the Mixtral
    geometry (``gmm``); below, it streams weights, and the right-hand
    block is the whole contraction by as much of ``n`` as
    ``_RHS_BLOCK_BYTES`` holds, so a group's matrix is fetched once, in
    as few steps as fit."""
    itemsize = jnp.dtype(dtype).itemsize
    bm = row_tile(m, num_groups, dtype)
    if bm == 512:
        bn, bk = 2048 // itemsize, 512      # (float32: what VMEM holds)
    else:
        bk = _pick_block(kdim, max(128, _RHS_BLOCK_BYTES // (128 * itemsize)))
        # (a power of two, so that the halving in _pick_block lands on a
        # divisor of n that fills lanes: at a contraction of 7168 the bytes
        # hold 146 columns, which would halve down to 4)
        bn = max(128, pl.next_power_of_2(
            _RHS_BLOCK_BYTES // (bk * itemsize) + 1) // 2)
    bm, bn, bk = (min(b, cap) if cap > 0 else b
                  for b, cap in ((bm, block_m), (bn, block_n), (bk, block_k)))
    whole_k = _pick_lane_block(kdim, bk)
    if whole_k > bk:
        # no tile of the contraction fills lanes and it came back whole: of
        # ``n``, what the right-hand block's bytes then hold
        bn = min(bn, pl.next_power_of_2(
            _RHS_BLOCK_BYTES // (whole_k * itemsize) + 1) // 2)
    bm, whole_n = _pick_block(m, bm), _pick_lane_block(n, bn)
    if whole_k > bk or whole_n > bn:
        # a dim that came back whole is wider than was asked for: fewer rows,
        # until the call's blocks (both operands and the result twice, for the
        # pipeline, and a float32 accumulator as large as the largest of
        # them: the backward's calls hold the same three blocks in other
        # roles) fit a kernel's VMEM
        while bm > 8 and (2 * itemsize * (bm * whole_k + whole_k * whole_n
                                          + bm * whole_n)
                          + 4 * max(bm * whole_n, bm * whole_k,
                                    whole_k * whole_n)) > _VMEM_BLOCKS_BYTES:
            bm //= 2
    return bm, whole_n, whole_k


def _gmm_kernel(tile_ids, group_ids, row_start, row_end, *refs,
                block_m: int, transpose_rhs: bool, layered: bool):
    # a layered call carries the layer as a fifth scalar-prefetch operand
    # (read by the index maps only) and a leading layer dim on the rhs block
    lhs_ref, rhs_ref, out_ref, acc_ref = refs[1:] if layered else refs
    if layered:
        rhs_ref = rhs_ref.at[0]
    t = pl.program_id(1)
    k = pl.program_id(2)
    tile = tile_ids[t]
    start, end = row_start[t], row_end[t]
    opened = jnp.logical_and(k == 0, jnp.logical_or(
        t == 0, tile != tile_ids[jnp.maximum(t - 1, 0)]))

    @pl.when(opened)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(end > start)
    def _product():
        rows = tile * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, 1), 0)
        mask = jnp.logical_and(rows >= start, rows < end)
        if transpose_rhs:  # rhs block [bn, bk], contract both k dims
            prod = jax.lax.dot_general(
                lhs_ref[...], rhs_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            prod = jnp.dot(lhs_ref[...], rhs_ref[0],
                           preferred_element_type=jnp.float32)
        acc_ref[...] += jnp.where(mask, prod, 0.0)
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    # a slot with no row does no product; one that opens a tile (an
    # uncovered one) hands it back zero-filled
    @pl.when(jnp.logical_and(opened, end <= start))
    def _fill():
        out_ref[...] = jnp.zeros_like(out_ref)


def _gmm_call(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
              block_m: int, block_n: int, block_k: int,
              transpose_rhs: bool = False, layer=None, metadata=None
              ) -> jax.Array:
    """out[m] = lhs[m] @ rhs[g(m)] (or @ rhs[g(m)].T when transpose_rhs,
    rhs then being [E, N, K] — saves materializing the swap in the
    backward). With ``layer`` (a traced int32 scalar) ``rhs`` is a stack
    ``[L, E, K, N]`` read at that layer by the index map: a step program
    that loops over layers never slices one layer's experts out of the
    stack (268 MB a projection at 128 experts of 2048 x 512).
    ``metadata`` is :func:`make_group_metadata` of these sizes at this
    row tile, for calls that share one (built here when not given)."""
    m, kdim = lhs.shape
    layered = layer is not None
    if transpose_rhs:
        num_groups, n, _ = rhs.shape[-3:]
    else:
        num_groups, _, n = rhs.shape[-3:]
    block_m = _pick_block(m, block_m)
    block_n = _pick_block(n, block_n)
    block_k = _pick_block(kdim, block_k)
    t_total = _num_work_items(m, num_groups, block_m)
    meta = metadata or make_group_metadata(group_sizes, m, block_m)
    assert meta[0].shape == (t_total,), (meta[0].shape, t_total)
    if layered:
        meta = meta + (jnp.asarray(layer, jnp.int32).reshape(1),)
    k_steps = kdim // block_k
    grid = (n // block_n, t_total, k_steps)

    def k_at(t, k, rs, re):
        # an empty slot stays on the block the step before it ended on:
        # an unchanged block index is not fetched again
        if k_steps == 1:
            return k
        return jnp.where(re[t] > rs[t], k, k_steps - 1)

    def lhs_index(n, t, k, tiles, gids, rs, re, *_):
        return tiles[t], k_at(t, k, rs, re)

    def rhs_index(n, t, k, tiles, gids, rs, re, *lyr):
        k = k_at(t, k, rs, re)
        at = (gids[t], n, k) if transpose_rhs else (gids[t], k, n)
        return (lyr[0][0],) + at if layered else at

    rhs_block = (1, block_n, block_k) if transpose_rhs \
        else (1, block_k, block_n)
    rhs_spec = pl.BlockSpec(((1,) + rhs_block) if layered else rhs_block,
                            rhs_index)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, block_m=block_m,
                          transpose_rhs=transpose_rhs, layered=layered),
        name="grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), lhs_index),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda n, t, k, tiles, *_: (tiles[t], n)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(*meta, lhs, rhs)
    return out


def _tiles(lhs, rhs, block_m: int, block_n: int, block_k: int):
    """The tiles of ``lhs @ rhs[g]``: each as given, or :func:`choose_tiles`'
    where given as 0."""
    auto = choose_tiles(lhs.shape[0], rhs.shape[-2], rhs.shape[-1],
                        rhs.shape[-3], lhs.dtype)
    return tuple(b or a for b, a in zip((block_m, block_n, block_k), auto))


def gmm_layer(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, layer,
              block_m: int = 0, block_n: int = 0, block_k: int = 0,
              metadata=None) -> jax.Array:
    """:func:`gmm` against one layer of a stack ``rhs [L, E, K, N]``, the
    layer a traced scalar (forward only: the serving path's experts).
    The products of one expert block have one row tile and may share
    their ``metadata`` (:func:`make_group_metadata` at that tile)."""
    return _gmm_call(lhs, rhs, group_sizes,
                     *_tiles(lhs, rhs, block_m, block_n, block_k), layer=layer,
                     metadata=metadata)


# ---------------------------------------------------------------------------
# tgmm: out[e] = sum over rows of group e of lhs[r]^T @ dout[r]
# ---------------------------------------------------------------------------

def _tgmm_kernel(tile_ids, group_ids, row_start, row_end,
                 lhs_ref, dout_ref, out_ref, acc_ref, *, block_m: int):
    t = pl.program_id(2)
    tile = tile_ids[t]
    group = group_ids[t]
    prev_group = group_ids[jnp.maximum(t - 1, 0)]
    first = jnp.logical_or(t == 0, group != prev_group)

    @pl.when(first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a slot with no row (the work list's padding, and with rows beyond the
    # groups' sum most of it) multiplies nothing
    @pl.when(row_end[t] > row_start[t])
    def _product():
        rows = tile * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, 1), 0)
        mask = jnp.logical_and(rows >= row_start[t], rows < row_end[t])
        lhs = jnp.where(mask, lhs_ref[...], 0)
        acc_ref[...] += jax.lax.dot_general(
            lhs, dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _tgmm_call(lhs: jax.Array, dout: jax.Array, group_sizes: jax.Array,
               block_m: int, block_n: int, block_k: int) -> jax.Array:
    """[M,K], [M,N], [E] -> [E,K,N] per-group lhs^T @ dout."""
    m, kdim = lhs.shape
    _, n = dout.shape
    num_groups = group_sizes.shape[0]
    block_m = _pick_block(m, block_m)
    block_n = _pick_block(n, block_n)
    block_k = _pick_block(kdim, block_k)
    tiles, gids, row_start, row_end = make_group_metadata(group_sizes, m,
                                                          block_m)
    # the forward's padding slots are aimed at the row tiles no group
    # covers (it has to hand them back zero-filled); here they fetch
    # nothing: they stay on the last tile a group covers
    live = row_end > row_start
    last = jnp.maximum(jnp.sum(live) - 1, 0)
    meta = (jnp.where(live, tiles, tiles[last]), gids, row_start, row_end)
    t_total = _num_work_items(m, num_groups, block_m)
    grid = (kdim // block_k, n // block_n, t_total)

    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, block_m=block_m),
        name="grouped_matmul_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k),
                             lambda k, n, t, tiles, gids, rs, re:
                             (tiles[t], k)),
                pl.BlockSpec((block_m, block_n),
                             lambda k, n, t, tiles, gids, rs, re:
                             (tiles[t], n)),
            ],
            out_specs=pl.BlockSpec((1, block_k, block_n),
                                   lambda k, n, t, tiles, gids, rs, re:
                                   (gids[t], k, n)),
            scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, kdim, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(*meta, lhs, dout)
    # groups with zero rows are never visited — their blocks are
    # undefined. Mask with the same clamped sizes the metadata uses, so
    # a group zeroed by the over-sum guard is zero-filled too.
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), m)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    return jnp.where((ends > starts)[:, None, None], out, 0)


# ---------------------------------------------------------------------------
# public entry (differentiable)
# ---------------------------------------------------------------------------

def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
        block_m: int = 0, block_n: int = 0, block_k: int = 0) -> jax.Array:
    """Grouped matmul: row m of ``lhs`` times ``rhs[group(m)]``.

    lhs [M, K] sorted by group, rhs [E, K, N], group_sizes [E] int32 with
    sum <= M. Returns [M, N] in lhs.dtype (fp32 MXU accumulation). Rows
    beyond the groups' sum belong to no group: zeros come back for them,
    the backward gives them zero gradients and the groups' gradients hold
    nothing of them.
    A block size given as 0 is the kernel's choice from the shapes
    (:func:`choose_tiles`); a positive one is that tile, snapped to a
    divisor of its dim. The backward runs the forward's tiles.
    Large blocks keep a call with many rows a group compute-bound: rhs[g]
    is re-read once per m-tile of its group and lhs once per n-tile, so
    HBM traffic scales with 1/block. Measured on v5e at Mixtral-8x7B
    geometry (M=32k, K=4096, N=14336): (512, 1024, 512) → 98 TF/s, ~50%
    of peak (149 TF/s on the rig of PERF.md, PR 34); the full no-drop
    MoE layer runs 2.7x faster than the capacity-einsum dispatch.
    """
    return _gmm(lhs, rhs, group_sizes,
                *_tiles(lhs, rhs, block_m, block_n, block_k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(lhs, rhs, group_sizes, block_m, block_n, block_k):
    return _gmm_call(lhs, rhs, group_sizes, block_m, block_n, block_k)


def _gmm_fwd(lhs, rhs, group_sizes, block_m, block_n, block_k):
    out = _gmm_call(lhs, rhs, group_sizes, block_m, block_n, block_k)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(block_m, block_n, block_k, res, dout):
    lhs, rhs, group_sizes = res
    # dlhs[m] = dout[m] @ rhs[g(m)]^T — gmm with rhs contracted on its
    # last dim (no materialized transpose)
    dlhs = _gmm_call(dout, rhs, group_sizes, block_m, block_k, block_n,
                     transpose_rhs=True)
    drhs = _tgmm_call(lhs, dout, group_sizes, block_m, block_n, block_k)
    dgs = np.zeros(group_sizes.shape, dtype=jax.dtypes.float0)
    return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), dgs


_gmm.defvjp(_gmm_fwd, _gmm_bwd)

"""The block-masked chunk kernel (``ops/pallas/paged_attention.py::
block_prefill_attention``) in interpret mode against the plain form it takes
the place of, ``ops/block_sparse.py::blocked_attention`` over the sequence's
gathered pages under the same mask, at both rules' shapes (a block of 128
with 16 heads a KV group; a block of 64 with 2 or 4), and the visit lists a
tile walks against a table made by hand. Tiny everywhere else: a head of 16
values, a handful of pages."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import block_sparse
from deepspeed_tpu.ops.pallas import paged_attention as pa


def _pool(rng, nb, bs, nkv, d, layers=2):
    return jnp.asarray(rng.standard_normal((layers, nb, bs, 2, nkv, d)),
                       jnp.bfloat16)


def _causal_mask(rng, pos, nkv, Bm, bs, keep=0.4):
    """[S, Tq, nkv, Bm]: each (query, KV head) reads block 0, its own and a
    random part of those between."""
    S, Tq = pos.shape
    blk = np.arange(Bm)
    own = (pos // bs)[..., None, None]
    mask = rng.random((S, Tq, nkv, Bm)) < keep
    mask |= (blk == 0) | (blk == own)
    return mask & (blk <= own)


def _plain(q, kv, layer, table, mask, pos0, ctx, bs):
    """``blocked_attention`` a segment, on the pages gathered by the table."""
    S, Tq, nh, d = q.shape
    nkv = kv.shape[4]
    out = []
    for s in range(S):
        pages = kv[layer, table[s]]                 # [Bm, bs, 2, nkv, d]
        keys = pages[:, :, 0].reshape(-1, nkv, d)
        values = pages[:, :, 1].reshape(-1, nkv, d)
        t = pos0[s] + jnp.arange(Tq)
        out.append(block_sparse.blocked_attention(
            q[s].reshape(Tq, nkv, nh // nkv, d), keys, values, mask[s], t,
            ctx[s], d ** -0.5, bs, step=bs).reshape(Tq, nh, d))
    return jnp.stack(out)


def _both(rng, *, bs, nkv, g, d, Tq, pos0, nreal, Bm, mask=None, layer=1,
          keep=0.4):
    S = len(pos0)
    nb = S * Bm + 1
    kv = _pool(rng, nb, bs, nkv, d)
    table = jnp.asarray(rng.permutation(nb - 1)[:S * Bm].reshape(S, Bm),
                        jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, Tq, nkv * g, d)), jnp.bfloat16)
    pos0 = np.asarray(pos0, np.int32)
    ctx = np.where(np.asarray(nreal) > 0, pos0 + np.asarray(nreal), 0).astype(
        np.int32)
    pos = pos0[:, None] + np.arange(Tq)[None, :]
    if mask is None:
        mask = _causal_mask(rng, pos, nkv, Bm, bs, keep)
    mask = jnp.asarray(mask)
    got, visited, visible = jax.jit(
        lambda *a: pa.block_prefill_attention(*a, layer=layer))(
            q, kv, table, mask, jnp.asarray(pos0), jnp.asarray(ctx))
    want = _plain(q, kv, layer, table, mask, jnp.asarray(pos0),
                  jnp.asarray(ctx), bs)
    real = pos < ctx[:, None]
    return (np.asarray(got, np.float32), np.asarray(want, np.float32), real,
            np.asarray(visited), np.asarray(visible), np.asarray(mask))


def _close(got, want, real):
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[real], want[real], atol=2e-2, rtol=2e-2)
    assert (got[~real] == 0).all()      # padded rows and dead segments


SHAPES = [dict(bs=128, nkv=1, g=16, d=16), dict(bs=64, nkv=2, g=2, d=16),
          dict(bs=64, nkv=2, g=4, d=16)]
IDS = ["block128-g16", "block64-g2", "block64-g4"]


@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_a_chunk_on_a_block_border_reads_what_its_queries_chose(shape):
    rng = np.random.default_rng(0)
    bs = shape["bs"]
    got, want, real, visited, _, mask = _both(
        rng, **shape, Tq=2 * bs, pos0=[2 * bs], nreal=[2 * bs], Bm=5)
    _close(got, want, real)
    # a tile is a page of queries: its list is the union of their choices
    tiles = mask.reshape(1, 2, bs, shape["nkv"], 5).any(axis=2)
    assert (visited == tiles.sum(-1).transpose(0, 2, 1)).all()


@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_a_chunk_that_starts_inside_a_block_and_ends_ragged(shape):
    """Queries from the middle of block 1 on, the last third of the chunk's
    rows padding: the tile straddles two blocks, padded rows give zeros."""
    rng = np.random.default_rng(1)
    bs = shape["bs"]
    got, want, real, *_ = _both(
        rng, **shape, Tq=bs, pos0=[bs + bs // 2 - 3], nreal=[2 * bs // 3],
        Bm=4)
    assert real.sum() == 2 * bs // 3
    _close(got, want, real)


@pytest.mark.parametrize("shape", SHAPES[:2], ids=IDS[:2])
def test_a_visit_list_longer_than_a_fold_and_one_that_ends_inside_one(shape):
    """A fold of the running softmax takes 1,024 keys (8 or 16 pages): the
    first sequence, two folds and two blocks into a third, walks several
    folds, the last of them part empty; the second, 10 blocks, ends inside
    its first or second."""
    rng = np.random.default_rng(5)
    bs = shape["bs"]
    far = 2 * pa._FOLD_KEYS // bs + 2
    got, want, real, visited, visible, _ = _both(
        rng, **shape, Tq=bs, pos0=[far * bs, 9 * bs], nreal=[bs, bs - 1],
        Bm=far + 1, keep=0.8)
    _close(got, want, real)
    assert pa._FOLD_KEYS // bs < visited[0].min() <= visible[0, 0] == far + 1
    assert visited[1].max() <= 10 < pa._FOLD_KEYS // bs + 3


@pytest.mark.parametrize("shape", SHAPES[:2], ids=IDS[:2])
def test_two_sequences_with_permuted_tables_and_a_dead_segment(shape):
    """Three segments of one call: two live sequences whose pages lie mixed
    in the pool, at different positions, and a dead one (context 0) between
    them, which gives zeros and visits nothing."""
    rng = np.random.default_rng(2)
    bs = shape["bs"]
    got, want, real, visited, visible, _ = _both(
        rng, **shape, Tq=bs, pos0=[3 * bs, 0, bs], nreal=[bs, 0, bs - 5],
        Bm=4, layer=0)
    _close(got, want, real)
    assert (visited[1] == 0).all() and (visible[1] == 0).all()
    assert (got[1] == 0).all()
    assert visible[:, 0].tolist() == [4, 0, 2]


def test_a_context_shorter_than_the_rules_width_reads_everything():
    """All visible: the mask is the causal one, every tile visits every block
    up to its own."""
    rng = np.random.default_rng(3)
    bs, Bm = 8, 6
    pos = np.arange(4 * bs)[None, :]
    mask = np.broadcast_to((np.arange(Bm) <= (pos // bs)[..., None, None]),
                           (1, 4 * bs, 2, Bm))
    got, want, real, visited, visible, _ = _both(
        rng, bs=bs, nkv=2, g=2, d=16, Tq=4 * bs, pos0=[0], nreal=[4 * bs],
        Bm=Bm, mask=mask)
    _close(got, want, real)
    assert visited[0].tolist() == [[1, 2, 3, 4]] * 2
    assert visible[0].tolist() == [1, 2, 3, 4]


def test_two_queries_of_a_tile_that_chose_disjoint_blocks_get_their_own():
    """Query 0 of the tile reads block 0 alone, query 1 block 1 alone (and
    each its own, block 2): the tile visits all three, and each query's
    output is what it would be alone, not the union's."""
    rng = np.random.default_rng(4)
    bs, Bm = 8, 4
    mask = np.zeros((1, bs, 1, Bm), bool)
    mask[0, :, 0, 2] = True
    mask[0, 0, 0, 0] = True
    mask[0, 1, 0, 1] = True
    got, want, real, visited, _, _ = _both(
        rng, bs=bs, nkv=1, g=2, d=16, Tq=bs, pos0=[2 * bs], nreal=[bs],
        Bm=Bm, mask=mask)
    _close(got, want, real)
    assert visited.tolist() == [[[3]]]
    alone = np.zeros_like(mask)
    alone[0, :, 0, 2] = True
    alone[0, 0, 0, 0] = True
    rng = np.random.default_rng(4)
    only0, *_ = _both(rng, bs=bs, nkv=1, g=2, d=16, Tq=bs, pos0=[2 * bs],
                      nreal=[bs], Bm=Bm, mask=alone)
    np.testing.assert_array_equal(got[0, 0], only0[0, 0])
    assert np.abs(got[0, 1] - only0[0, 1]).max() > 1e-2


def test_visit_lists_against_a_table_by_hand():
    """Two tiles of 4 queries over 6 blocks of 4 tokens, from position 8 on,
    7 real queries (context 15: blocks 0-3 exist). KV head 0: the first tile
    chose {0, 2}, the second {1, 3, 5} (5 does not exist, and its last query
    is padding); KV head 1 chose nothing but what padding asked for."""
    mask = np.zeros((1, 8, 2, 6), bool)
    mask[0, 0, 0, 0] = mask[0, 3, 0, 2] = True
    mask[0, 4, 0, 1] = mask[0, 6, 0, 3] = mask[0, 5, 0, 5] = True
    mask[0, 7, 0, 4] = mask[0, 7, 1, 0] = True          # the padded row's
    live, blocks, count, visible = pa.tile_visits(
        jnp.asarray(mask), jnp.asarray([8]), jnp.asarray([15]), 4, 4)
    assert count.tolist() == [[[2, 2], [0, 0]]]
    assert blocks[0, 0].tolist() == [[0, 2, 6, 6, 6, 6], [1, 3, 6, 6, 6, 6]]
    assert blocks[0, 1].tolist() == [[6] * 6] * 2
    assert visible.tolist() == [[3, 4]]
    assert int(live.sum()) == 4
    # a dead segment visits and sees nothing
    _, _, count, visible = pa.tile_visits(
        jnp.asarray(mask), jnp.asarray([8]), jnp.asarray([0]), 4, 4)
    assert not count.any() and not visible.any()


def test_the_tile_comes_from_the_shapes():
    assert pa.chunk_tile(2048, 16, 128) == 128      # the learned selector's
    assert pa.chunk_tile(2048, 2, 64) == 64         # the first rule's
    assert pa.chunk_tile(2048, 64, 128) == 32       # rows bound it
    assert pa.chunk_tile(16, 16, 128) == 16         # a chunk below a page

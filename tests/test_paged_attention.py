"""Paged decode attention kernel vs dense reference.

Reference behavior: inference/v2 blocked-flash ragged kernels — decode
reads K/V straight from cache pages via the block table.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention


def _dense_reference(q, keys, values):
    # q [nh, hd]; keys/values [ctx, nkv, hd] -> [nh, hd]
    nh, hd = q.shape
    nkv = keys.shape[1]
    rep = nh // nkv
    k = np.repeat(keys, rep, axis=1).astype(np.float32)
    v = np.repeat(values, rep, axis=1).astype(np.float32)
    s = np.einsum("nd,mnd->nm", q.astype(np.float32), k) / np.sqrt(hd)
    p = np.exp(s - s.max(axis=1, keepdims=True))
    p = p / p.sum(axis=1, keepdims=True)
    return np.einsum("nm,mnd->nd", p, v)


def _build_case(rng, S, nh, nkv, hd, bs, Bm, ctx_lens):
    nb = S * Bm + 2
    kv = rng.standard_normal((nb, bs, 2, nkv, hd)).astype(np.float32)
    table = np.zeros((S, Bm), np.int32)
    used = 1  # page 0 left as a decoy
    for s in range(S):
        for j in range((ctx_lens[s] + bs - 1) // bs):
            table[s, j] = used
            used += 1
    q = rng.standard_normal((S, nh, hd)).astype(np.float32)
    return q, kv, table


def _want(q, kv, table, ctx, bs):
    """Dense float32 reference over a 5-D pool, one sequence at a time;
    a dead slot's rows are zero."""
    out = np.zeros(q.shape, np.float32)
    for s in range(q.shape[0]):
        if ctx[s] == 0:
            continue
        rows = [kv[table[s, t // bs], t % bs] for t in range(ctx[s])]
        out[s] = _dense_reference(q[s], np.stack([r[0] for r in rows]),
                                  np.stack([r[1] for r in rows]))
    return out


# nh, nkv, hd, bs, Bm, fold (None: the kernel's own choice). Between them:
# groups 1 (padded to the sublane tile), 4, 8 and 71; head sizes 64, 128,
# 256; 1, 2, 8 and 32 KV heads (32: four products a block); blocks of 16
# and 32 tokens
SHAPES = [
    (8, 8, 128, 16, 8, None),      # multi-head: group 1
    (32, 8, 128, 16, 8, 2),        # group 4, eight heads one product
    (16, 2, 256, 16, 8, None),     # group 8, head 256
    (71, 1, 128, 16, 6, 3),        # multi-query: 71 rows padded to 72
    (8, 2, 64, 16, 8, None),       # head 64
    (16, 2, 128, 32, 4, 1),        # 32-token pages, a block of one page
    (32, 32, 128, 16, 4, None),    # 32 KV heads: four chunks of eight
]


def _ragged(bs, Bm, fold):
    """Contexts that end at 1 token, mid-page, on a page border, on a
    block border, at the ceiling; a dead slot between live ones."""
    block = (fold or 2) * bs
    return np.array([1, bs + 5, 0, 2 * bs, min(block, Bm * bs), Bm * bs],
                    np.int32)


@pytest.mark.parametrize("nh,nkv,hd,bs,Bm,fold", SHAPES)
def test_matches_dense_reference(nh, nkv, hd, bs, Bm, fold):
    rng = np.random.default_rng(0)
    ctx = _ragged(bs, Bm, fold)
    q, kv, table = _build_case(rng, len(ctx), nh, nkv, hd, bs, Bm, ctx)
    out = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(table),
        jnp.asarray(ctx), pages_per_compute_block=fold))
    np.testing.assert_allclose(out, _want(q, kv, table, ctx, bs),
                               rtol=2e-5, atol=2e-5)
    assert np.all(out[2] == 0.0)


@pytest.mark.parametrize("nh,nkv,hd,bs,Bm,fold", SHAPES[:4])
def test_nothing_past_the_context_is_seen(nh, nkv, hd, bs, Bm, fold):
    """Large finite garbage in the rows of the last page past the context
    and in pages no sequence owns, out-of-range ids in block-table entries
    past the context: the output does not move by a bit."""
    rng = np.random.default_rng(5)
    ctx = _ragged(bs, Bm, fold)
    q, kv, table = _build_case(rng, len(ctx), nh, nkv, hd, bs, Bm, ctx)
    clean = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(table),
        jnp.asarray(ctx), pages_per_compute_block=fold))
    dirty_kv, dirty_table = kv.copy(), table.copy()
    for s, n in enumerate(ctx):
        live = -(-n // bs)
        if n % bs:
            dirty_kv[table[s, live - 1], n % bs:] = 1e20
        dirty_table[s, live:] = rng.choice([-7, 10 ** 6, 2 ** 31 - 1],
                                           Bm - live)
    dirty_kv[0] = -1e20                      # the decoy page
    dirty = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(dirty_kv), jnp.asarray(dirty_table),
        jnp.asarray(ctx), pages_per_compute_block=fold))
    np.testing.assert_array_equal(dirty, clean)


def test_pool_at_a_traced_layer_equals_the_one_layer_pool():
    rng = np.random.default_rng(6)
    nh, nkv, hd, bs, Bm = 16, 2, 128, 16, 6
    ctx = _ragged(bs, Bm, None)
    q, kv0, table = _build_case(rng, len(ctx), nh, nkv, hd, bs, Bm, ctx)
    pool = jnp.asarray(np.stack([kv0 + 1.0, kv0, kv0 - 1.0]))   # [3, ...]
    on_pool = jax.jit(lambda l: paged_decode_attention(
        jnp.asarray(q), pool, jnp.asarray(table), jnp.asarray(ctx), layer=l))
    for l in range(3):
        want = paged_decode_attention(jnp.asarray(q), pool[l],
                                      jnp.asarray(table), jnp.asarray(ctx))
        np.testing.assert_array_equal(
            np.asarray(on_pool(jnp.asarray(l, jnp.int32))), np.asarray(want))
    np.testing.assert_allclose(np.asarray(on_pool(jnp.asarray(1, jnp.int32))),
                               _want(q, kv0, table, ctx, bs),
                               rtol=2e-5, atol=2e-5)


def test_a_pool_a_dma_cannot_slice_keeps_the_grid_walk(monkeypatch):
    """Head size 64, one bf16 KV head, twelve heads: Mosaic cannot slice
    such a page out of the pool, and the kernel walks the block table
    through pipelined blocks instead. The interpreter would take the
    new walk for any pool, so the choice is steered here."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    assert pa._pages_sliceable(8, 128, 2) and pa._pages_sliceable(2, 256, 2)
    assert pa._pages_sliceable(4, 128, 2) and pa._pages_sliceable(32, 128, 2)
    assert pa._pages_sliceable(1, 128, 4) and pa._pages_sliceable(24, 128, 2)
    assert not pa._pages_sliceable(8, 64, 2)      # half a lane tile
    assert not pa._pages_sliceable(1, 128, 2)     # half a packed sublane
    assert not pa._pages_sliceable(12, 128, 2)    # a tile and a half
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    rng = np.random.default_rng(7)
    nh, nkv, hd, bs, Bm = 12, 12, 64, 16, 4
    ctx = _ragged(bs, Bm, None)
    q, kv, table = _build_case(rng, len(ctx), nh, nkv, hd, bs, Bm, ctx)
    calls = []
    real = pa.pl.pallas_call

    def spy(kernel, **kw):
        calls.append(kernel.func.__name__)
        return real(kernel, **{**kw, "interpret": True})

    monkeypatch.setattr(pa.pl, "pallas_call", spy)
    out = np.asarray(pa.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(table),
        jnp.asarray(ctx)))
    assert calls == ["_grid_walk_kernel"]
    np.testing.assert_allclose(out, _want(q, kv, table, ctx, bs),
                               rtol=2e-5, atol=2e-5)


def test_dead_slot_outputs_zero():
    rng = np.random.default_rng(1)
    S, nh, nkv, hd, bs, Bm = 2, 8, 8, 64, 16, 2
    ctx = np.array([5, 0], np.int32)  # slot 1 is dead
    q, kv, table = _build_case(rng, S, nh, nkv, hd, bs, Bm, ctx)
    out = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(table),
        jnp.asarray(ctx)))
    assert np.all(out[1] == 0.0)
    assert np.all(np.isfinite(out))


def test_block_rule_follows_bytes_lanes_and_vmem():
    """The block is the kernel's choice from the shapes: about 1 MiB a
    fetch, no more than 256 tokens, lane-full, inside a VMEM buffer."""
    from deepspeed_tpu.ops.pallas.paged_attention import \
        _decode_block_pages as pages

    assert pages(16, 8, 128, 2, 64, 8) == 16      # mistral: 1 MiB, 256 tokens
    assert pages(16, 2, 256, 2, 64, 2) == 16      # a quarter the bytes: tokens cap
    assert pages(16, 32, 128, 2, 64, 8) == 4      # 256 KiB pages: bytes cap
    assert pages(16, 8, 128, 2, 4, 8) == 4        # a short block table
    assert pages(128, 8, 128, 2, 8, 8) == 2       # a page of 128 tokens
    assert pages(16, 1, 128, 4, 64, 1) == 16      # one head: 8 pages fill the lanes
    assert pages(128, 64, 256, 2, 8, 8) == 1      # an 8 MiB page: one, whatever


class TestPrefill:
    @pytest.mark.parametrize("nh,nkv", [(8, 8), (8, 2)])
    def test_matches_dense_causal(self, nh, nkv):
        from deepspeed_tpu.ops.pallas.paged_attention import \
            paged_prefill_attention

        rng = np.random.default_rng(3)
        S, tq, hd, bs, Bm = 2, 8, 64, 16, 4
        # segment 0: 8 fresh tokens on 11 of history; segment 1: chunk
        # starting at position 0 (no history)
        pos0 = np.array([11, 0], np.int32)
        n_real = np.array([8, 8], np.int32)
        ctx = pos0 + n_real
        q, kv, table = _build_case(rng, S, nh, nkv, hd, bs, Bm, ctx)
        qc = rng.standard_normal((S, tq, nh, hd)).astype(np.float32)

        out = np.asarray(paged_prefill_attention(
            jnp.asarray(qc), jnp.asarray(kv), jnp.asarray(table),
            jnp.asarray(pos0), jnp.asarray(ctx)))

        for s in range(S):
            rows = []
            for t in range(ctx[s]):
                page, off = table[s, t // bs], t % bs
                rows.append(kv[page, off])
            keys = np.stack([r[0] for r in rows])
            values = np.stack([r[1] for r in rows])
            for qi in range(tq):
                vis = pos0[s] + qi + 1  # causal: keys 0..pos0+qi
                want = _dense_reference(qc[s, qi], keys[:vis], values[:vis])
                np.testing.assert_allclose(
                    out[s, qi], want, rtol=2e-5, atol=2e-5,
                    err_msg=f"seg {s} q {qi}")

    def test_dead_segment_zero(self):
        from deepspeed_tpu.ops.pallas.paged_attention import \
            paged_prefill_attention

        rng = np.random.default_rng(4)
        S, nh, nkv, tq, hd, bs, Bm = 2, 8, 8, 8, 64, 16, 2
        ctx = np.array([9, 0], np.int32)
        q, kv, table = _build_case(rng, S, nh, nkv, hd, bs, Bm, ctx)
        qc = rng.standard_normal((S, tq, nh, hd)).astype(np.float32)
        out = np.asarray(paged_prefill_attention(
            jnp.asarray(qc), jnp.asarray(kv), jnp.asarray(table),
            jnp.asarray([1, 0], np.int32), jnp.asarray(ctx)))
        assert np.all(out[1] == 0.0) and np.all(np.isfinite(out))

    def test_row_alignment_validation(self):
        from deepspeed_tpu.ops.pallas.paged_attention import \
            paged_prefill_attention

        q = jnp.zeros((1, 3, 8, 64))  # Tq*g = 3 -> not sublane aligned
        kv = jnp.zeros((4, 16, 2, 8, 64))
        with pytest.raises(ValueError, match="multiple of 8"):
            paged_prefill_attention(q, kv, jnp.zeros((1, 2), jnp.int32),
                                    jnp.zeros(1, jnp.int32),
                                    jnp.ones(1, jnp.int32))


@pytest.mark.parametrize("nh,nkv,hd", [(12, 4, 64), (32, 8, 128),
                                       (16, 2, 256)])
def test_bf16_and_jit_stability(nh, nkv, hd):
    rng = np.random.default_rng(2)
    S, bs, Bm = 4, 16, 8
    ctx = np.array([3, 40, 128, 77], np.int32)
    q, kv, table = _build_case(rng, S, nh, nkv, hd, bs, Bm, ctx)
    qb, kvb = jnp.asarray(q, jnp.bfloat16), jnp.asarray(kv, jnp.bfloat16)
    out = jax.jit(paged_decode_attention)(qb, kvb, jnp.asarray(table),
                                          jnp.asarray(ctx))
    assert out.dtype == jnp.bfloat16
    assert out.shape == (S, nh, hd)
    # bf16 operands, float32 scores and accumulator, p rounded to bf16
    # for the value product: a few bf16 ulps of the values' magnitude
    want = _want(np.asarray(qb, np.float32), np.asarray(kvb, np.float32),
                 table, ctx, bs)
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               rtol=0, atol=0.03)

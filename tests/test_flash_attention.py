"""Flash attention kernel tests (interpret mode on CPU; reference analog:
tests/unit/ops kernel-level suites)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import multi_head_attention, xla_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(B=1, S=128, N=2, D=32, dtype=jnp.float32, seed=0):
    rng = jax.random.PRNGKey(seed)
    return tuple(jax.random.normal(jax.random.fold_in(rng, i), (B, S, N, D),
                                   dtype) for i in range(3))


def test_triangle_decomposition_exhaustive():
    """The packed causal grid computes (iq, ik) from the flat work-item
    index with fp32 sqrt + integer correction — must be exact for every
    item at every grid size up to 1M-token scale."""
    from deepspeed_tpu.ops.pallas.flash_attention import (_decompose_kv,
                                                          _decompose_q,
                                                          _num_items)

    for nq in (1, 2, 3, 7, 64, 1024):
        T = _num_items(nq, nq, True)
        t = jnp.arange(T, dtype=jnp.int32)
        iq, ik = jax.jit(lambda t: _decompose_q(t, nq, nq, True))(t)
        iq, ik = np.asarray(iq), np.asarray(ik)
        # q-major triangle: t = iq(iq+1)/2 + ik, 0 <= ik <= iq
        assert (iq * (iq + 1) // 2 + ik == np.arange(T)).all(), nq
        assert (ik <= iq).all() and (ik >= 0).all(), nq

        iq2, ik2 = jax.jit(lambda t: _decompose_kv(t, nq, nq, True))(t)
        iq2, ik2 = np.asarray(iq2), np.asarray(ik2)
        # k-major triangle: cum(ik) = ik*nq - ik(ik-1)/2, ik <= iq < nq
        cum = ik2 * nq - ik2 * (ik2 - 1) // 2
        assert (cum + (iq2 - ik2) == np.arange(T)).all(), nq
        assert (iq2 >= ik2).all() and (iq2 < nq).all(), nq


def test_forward_matches_xla():
    q, k, v = _qkv(B=2, S=128, N=2, D=32)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_multi_kv_blocks():
    q, k, v = _qkv(S=256)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_backward_matches_xla():
    q, k, v = _qkv(S=128)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v, causal=True) ** 2).sum()

    gr = jax.grad(loss(xla_attention), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, causal=True, block_q=64,
                                         block_k=64) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4)


def test_padded_sequence():
    q, k, v = _qkv(S=100)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_noncausal_kernel_matches_xla():
    q, k, v = _qkv(S=128)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    ref = xla_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # dispatcher path agrees too
    out = multi_head_attention(q, k, v, causal=False, impl="flash")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_noncausal_padded():
    """Non-causal with padding: padded keys must not leak into softmax."""
    q, k, v = _qkv(S=100)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    ref = xla_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("ratio", [1, 4, 8])
def test_gqa_forward_backward(ratio):
    """GQA-native kernel: KV at kv_heads, parity vs repeated-KV dense."""
    B, S, Nq, D = 2, 128, 8, 32
    Nkv = Nq // ratio
    rng = jax.random.PRNGKey(1)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (B, S, Nq, D))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, Nkv, D))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, Nkv, D))
    ref = xla_attention(q, k, v, causal=True)  # repeats kv internally
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    gr = jax.grad(lambda q, k, v: (xla_attention(q, k, v) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, causal=True, block_q=64,
                                         block_k=64) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_segment_ids_packed(causal):
    """Packed sequences stay on the kernel and mask cross-segment pairs."""
    B, S = 2, 128
    q, k, v = _qkv(B=B, S=S)
    seg = jnp.concatenate([jnp.zeros((B, 48), jnp.int32),
                           jnp.ones((B, 50), jnp.int32),
                           jnp.full((B, 30), 2, jnp.int32)], axis=1)
    ref = xla_attention(q, k, v, causal=causal, segment_ids=seg)
    out = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                          block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    gr = jax.grad(lambda q: (xla_attention(
        q, k, v, causal=causal, segment_ids=seg) ** 2).sum())(q)
    gf = jax.grad(lambda q: (flash_attention(
        q, k, v, causal=causal, segment_ids=seg,
        block_q=64, block_k=64) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=5e-4)


def test_segment_ids_gqa_padded():
    """Segments + GQA + non-block-multiple S all at once."""
    B, S, Nq, Nkv, D = 1, 100, 4, 2, 32
    rng = jax.random.PRNGKey(3)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (B, S, Nq, D))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, Nkv, D))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, Nkv, D))
    seg = (jnp.arange(S)[None, :] >= 40).astype(jnp.int32)
    ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
    out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                          block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_dispatcher_impl_flash_used_in_model():
    """attn_impl='flash' must survive a full model forward."""
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=1,
                            num_heads=2, max_seq_len=64, remat=False,
                            attn_impl="flash")
    cfg_x = TransformerConfig(**{**cfg.__dict__, "attn_impl": "xla"})
    m, mx = TransformerLM(cfg), TransformerLM(cfg_x)
    p = m.init(jax.random.PRNGKey(0))
    toks = jnp.arange(64, dtype=jnp.int32).reshape(1, 64) % 64
    np.testing.assert_allclose(np.asarray(m.apply(p, toks)),
                               np.asarray(mx.apply(p, toks)), atol=2e-2)


# -- a sliding window: the band grid ----------------------------------------


def _masked_dense(q, k, v, window):
    """The masked dense form: key j visible to query i iff 0 <= i - j <
    window; float32 throughout, no kernel."""
    B, S, Nq, D = q.shape
    g = Nq // k.shape[2]
    k, v = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) / np.sqrt(D)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    ok = (j <= i) & (i - j < window)
    return jnp.einsum("bnqk,bknd->bqnd",
                      jax.nn.softmax(jnp.where(ok, s, -1e30), -1), v)


@pytest.mark.parametrize("S,W", [
    (200, 70),      # S and W no multiples of the 64-wide block
    (256, 64),      # the window one block exactly
    (130, 300),     # a window that reaches every key: the whole triangle
    (192, 1),       # every query sees itself alone
    (192, 65)])     # one key into the next block
def test_window_matches_the_masked_dense_form_forward_and_gradients(S, W):
    ks = jax.random.split(jax.random.PRNGKey(S + W), 4)
    q = jax.random.normal(ks[0], (2, S, 4, 32))
    k = jax.random.normal(ks[1], (2, S, 2, 32))
    v = jax.random.normal(ks[2], (2, S, 2, 32))
    t = jax.random.normal(ks[3], (2, S, 4, 32))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                               window=W)

    np.testing.assert_allclose(flash(q, k, v), _masked_dense(q, k, v, W),
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * t), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_masked_dense(*a, W) * t),
                    (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)
    # the dispatcher's XLA path masks the same pairs
    np.testing.assert_allclose(
        multi_head_attention(q, k, v, impl="xla", window=W),
        _masked_dense(q, k, v, W), atol=2e-5)


def test_band_steps_cover_the_band_once_and_clamp_the_rest():
    """q-major and k-major: the live steps are exactly the blocks a window
    reaches, each once, and a step off the band's end sits on the block the
    neighbouring live step reads (nothing is fetched for it)."""
    from deepspeed_tpu.ops.pallas.flash_attention import (_band_blocks,
                                                          _band_kv, _band_q,
                                                          _num_items)

    for nq, window, block in ((16, 2048, 512), (8, 2048, 1024), (5, 100, 64),
                              (4, 9999, 64), (7, 1, 64)):
        band = _band_blocks(window, block, nq)
        T = _num_items(nq, nq, True, band)
        assert T == nq * (band + 1)
        t = jnp.arange(T, dtype=jnp.int32)
        want = {(i, j) for i in range(nq) for j in range(nq)
                if j <= i and i * block - (j * block + block - 1) < window}
        iq, ik, d, live = (np.asarray(x) for x in _band_q(t, band))
        assert {(a, b) for a, b, l in zip(iq, ik, live) if l} == want
        assert live.sum() == len(want)
        assert (ik[~live] == 0).all() and (d[~live] < band).all()
        iq, ik, d, live = (np.asarray(x) for x in _band_kv(t, band, nq))
        assert {(a, b) for a, b, l in zip(iq, ik, live) if l} == want
        assert (iq[~live] == nq - 1).all()
    # 8k under a window of 2k: 70 pairs of 512-wide blocks, 21 of 1024-wide
    assert sum(min(i, _band_blocks(2048, 512, 16)) + 1 for i in range(16)) == 70
    assert sum(min(i, _band_blocks(2048, 1024, 8)) + 1 for i in range(8)) == 21


def _pallas_calls(fn, *args):
    """(name, grid, block shapes) of every ``pallas_call`` in ``fn``'s
    jaxpr, nested calls included; nothing runs."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                out.append((eqn.params["name"], tuple(gm.grid),
                            tuple(tuple(getattr(b, "block_size", b) for b in
                                        bm.block_shape)
                                  for bm in gm.block_mappings)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def test_no_window_lowers_to_the_grids_and_block_specs_it_had():
    """The guard for the dense training cells: at their shapes (4 x 2048,
    32 query heads over 8, 128 wide, 512-wide blocks) ``window=None`` is the
    packed triangle under the old names, block for block; a window changes
    the names and the grid and nothing else."""
    B, S, HQ, HKV, D, BLK = 4, 2048, 32, 8, 128, 512
    q = jax.ShapeDtypeStruct((B, S, HQ, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, HKV, D), jnp.bfloat16)

    def loss(window):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=BLK, block_k=BLK,
            window=window).astype(jnp.float32)), (0, 1, 2))

    nq = S // BLK
    tri = nq * (nq + 1) // 2
    qb, sb = (1, BLK, D), (1, BLK, 8)
    want = {
        "flash_fwd": ((B * HQ, tri), (qb, qb, qb, qb, sb)),
        "flash_bwd_dkdv": ((B * HKV, tri, HQ // HKV),
                           (qb, qb, qb, qb, sb, sb, qb, qb)),
        "flash_bwd_dq": ((B * HQ, tri), (qb, qb, qb, qb, sb, sb, qb)),
    }
    calls = _pallas_calls(loss(None), q, kv, kv)
    assert {n: (g, b) for n, g, b in calls} == want
    default = _pallas_calls(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=BLK, block_k=BLK).astype(jnp.float32)),
        (0, 1, 2)), q, kv, kv)
    assert default == calls
    band = 2                                    # a window of 1,024
    windowed = {n: (g, b) for n, g, b in _pallas_calls(loss(1024), q, kv, kv)}
    assert windowed == {
        "flash_window_fwd": ((B * HQ, nq * (band + 1)), want["flash_fwd"][1]),
        "flash_window_bwd_dkdv": ((B * HKV, nq * (band + 1), HQ // HKV),
                                  want["flash_bwd_dkdv"][1]),
        "flash_window_bwd_dq": ((B * HQ, nq * (band + 1)),
                                want["flash_bwd_dq"][1])}


def test_a_window_is_a_causal_masks():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match="window"):
        multi_head_attention(q, k, v, causal=False, window=16)

"""Block-geometry invariance: the knob changes the schedule, never the
math.

Every block parameter promoted into the ``kernels`` config block /
autotuner axes (flash ``block_q``/``block_k``, paged
``pages_per_compute_block``, grouped-matmul tiles, blocksparse block)
must leave the kernel's output invariant across legal candidates. The
exact guarantee differs by axis and is asserted at its true strength:

- **bit-identical** where the accumulation order provably does not
  move: paged *prefill* attention for EVERY ``pages_per_compute_block``
  (pages fold sequentially in page order regardless of grid fan-in),
  flash across ``block_q`` at fixed ``block_k`` (q rows are independent
  grid cells), gmm across ``block_m``/``block_n`` at fixed ``block_k``;
- **ulp-tight allclose** where changing the k-axis tiling regroups the
  fp32 accumulation (flash ``block_k``, gmm ``block_k``, and since the
  paged *decode* kernel folds a block of pages as one product, its
  ``pages_per_compute_block``) — the result may legally differ by
  rounding in the last bit, nothing more.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.grouped_matmul import gmm
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, paged_prefill_attention)

SEQ, HD = 256, 32


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    return (mk(1, SEQ, 4, HD), mk(1, SEQ, 2, HD), mk(1, SEQ, 2, HD))


def _ulp_close(a, b, ulps=2):
    """Within ``ulps`` bf16 ulps at the output's magnitude."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    scale = max(np.abs(a).max(), 1.0)
    tol = ulps * scale * float(jnp.finfo(jnp.bfloat16).eps)
    np.testing.assert_allclose(a, b, atol=tol, rtol=0)


class TestFlashGeometry:
    def test_block_q_sweep_bit_identical(self, qkv):
        q, k, v = qkv
        base = flash_attention(q, k, v, causal=True,
                               block_q=128, block_k=128)
        for bq in (256, SEQ):
            out = flash_attention(q, k, v, causal=True,
                                  block_q=bq, block_k=128)
            assert bool(jnp.array_equal(base, out)), f"block_q={bq}"

    def test_block_k_sweep_ulp_tight(self, qkv):
        q, k, v = qkv
        base = flash_attention(q, k, v, causal=True,
                               block_q=128, block_k=128)
        for bk in (256, SEQ):
            out = flash_attention(q, k, v, causal=True,
                                  block_q=128, block_k=bk)
            _ulp_close(base, out)

    def test_full_mask_geometry(self, qkv):
        q, k, v = qkv
        base = flash_attention(q, k, v, causal=False,
                               block_q=128, block_k=128)
        out = flash_attention(q, k, v, causal=False,
                              block_q=256, block_k=128)
        assert bool(jnp.array_equal(base, out))


class TestPagedGeometry:
    def _case(self):
        rng = np.random.default_rng(1)
        S, nh, nkv, hd, bs, Bm = 3, 8, 2, 128, 16, 6
        nb = S * Bm + 2
        kv = jnp.asarray(rng.standard_normal((nb, bs, 2, nkv, hd)),
                         jnp.float32)
        ctx = np.array([5, 40, 96], np.int32)
        table = np.zeros((S, Bm), np.int32)
        used = 1
        for s in range(S):
            for j in range((ctx[s] + bs - 1) // bs):
                table[s, j] = used
                used += 1
        q = jnp.asarray(rng.standard_normal((S, nh, hd)), jnp.float32)
        return q, kv, jnp.asarray(table), jnp.asarray(ctx), Bm

    # an explicit fold, non-divisors of max_pages and more than it holds
    # among them (Bm = 6), and 0: the kernel's own choice
    @pytest.mark.parametrize("pages", [1, 2, 3, 4, 6, 9, 0])
    def test_decode_every_pages_value_close(self, pages):
        """A block of pages is one product, so a fold regroups the
        float32 sums: every fold stands a few float32 ulps from fold 1
        and all of them on the dense reference."""
        q, kv, table, ctx, Bm = self._case()
        assert Bm == 6
        out = np.asarray(paged_decode_attention(
            q, kv, table, ctx, pages_per_compute_block=pages))
        one = np.asarray(paged_decode_attention(
            q, kv, table, ctx, pages_per_compute_block=1))
        scale = max(np.abs(one).max(), 1.0)
        np.testing.assert_allclose(
            out, one, rtol=0, atol=8 * scale * float(np.finfo(np.float32).eps))
        qn, kvn, bs = np.asarray(q), np.asarray(kv), kv.shape[1]
        nkv, g = kv.shape[3], q.shape[1] // kv.shape[3]
        for s, n in enumerate(np.asarray(ctx)):
            rows = np.stack([kvn[int(table[s, t // bs]), t % bs]
                             for t in range(n)])          # [n, 2, nkv, hd]
            k = np.repeat(rows[:, 0], g, axis=1)
            v = np.repeat(rows[:, 1], g, axis=1)
            sc = np.einsum("nd,mnd->nm", qn[s], k) / np.sqrt(q.shape[2])
            p = np.exp(sc - sc.max(axis=1, keepdims=True))
            want = np.einsum("nm,mnd->nd", p / p.sum(1, keepdims=True), v)
            np.testing.assert_allclose(out[s], want, rtol=2e-5, atol=2e-5)

    def test_prefill_every_pages_value_bit_identical(self):
        rng = np.random.default_rng(2)
        S, tq, nh, nkv, hd, bs, Bm = 2, 8, 8, 2, 64, 16, 4
        nb = S * Bm + 1
        kv = jnp.asarray(rng.standard_normal((nb, bs, 2, nkv, hd)),
                         jnp.float32)
        pos0 = jnp.asarray(np.array([0, 16], np.int32))
        ctx = jnp.asarray(np.array([8, 24], np.int32))
        table = np.zeros((S, Bm), np.int32)
        used = 1
        for s in range(S):
            for j in range(Bm):
                table[s, j] = used
                used += 1
        q = jnp.asarray(rng.standard_normal((S, tq, nh, hd)), jnp.float32)
        base = paged_prefill_attention(q, kv, jnp.asarray(table), pos0,
                                       ctx, pages_per_compute_block=1)
        for p in (2, 3, Bm):
            out = paged_prefill_attention(q, kv, jnp.asarray(table),
                                          pos0, ctx,
                                          pages_per_compute_block=p)
            assert bool(jnp.array_equal(base, out)), f"pages={p}"


class TestGmmGeometry:
    def _case(self):
        rng = np.random.default_rng(3)
        lhs = jnp.asarray(rng.standard_normal((256, 128)), jnp.bfloat16)
        rhs = jnp.asarray(rng.standard_normal((4, 128, 256)), jnp.bfloat16)
        gs = jnp.asarray(np.array([64, 32, 96, 64], np.int32))
        return lhs, rhs, gs

    def test_mn_tile_sweep_bit_identical(self):
        lhs, rhs, gs = self._case()
        base = gmm(lhs, rhs, gs, 128, 128, 128)
        for bm, bn in ((256, 256), (512, 1024), (64, 128)):
            out = gmm(lhs, rhs, gs, bm, bn, 128)
            assert bool(jnp.array_equal(base, out)), f"tile={bm}x{bn}"

    def test_k_tile_sweep_ulp_tight(self):
        lhs, rhs, gs = self._case()
        base = gmm(lhs, rhs, gs, 128, 128, 128)
        for bk in (64, 512):
            out = gmm(lhs, rhs, gs, 128, 128, bk)
            _ulp_close(base, out, ulps=4)


class TestBlocksparseGeometry:
    def test_pallas_matches_xla_form(self):
        from deepspeed_tpu.ops.pallas.blocksparse_attention import (
            FixedSparsityConfig, blocksparse_attention,
            blocksparse_attention_pallas)

        rng = np.random.default_rng(4)
        mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        q, k, v = mk(1, 256, 4, 32), mk(1, 256, 4, 32), mk(1, 256, 4, 32)
        sparsity = FixedSparsityConfig(block=128, num_local_blocks=2)
        want = blocksparse_attention(q, k, v, sparsity, causal=True)
        got = blocksparse_attention_pallas(q, k, v, sparsity, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestConfigThreading:
    def test_kernel_pages_resolves_from_config(self):
        from deepspeed_tpu.config.config import KernelsConfig
        from deepspeed_tpu.inference.model_runner import _kernel_pages
        from deepspeed_tpu.ops import attention as attn_ops

        # nothing installed (a serving engine installs nothing), and an
        # installed 0, both leave the block to the kernel
        assert _kernel_pages() == 0
        attn_ops.set_kernel_config(KernelsConfig())
        assert _kernel_pages() == 0
        attn_ops.set_kernel_config(KernelsConfig(pages_per_compute_block=4))
        try:
            assert _kernel_pages() == 4
        finally:
            attn_ops.set_kernel_config(None)

    def test_engine_installs_kernel_config(self):
        # dstpu.initialize must bridge config.kernels into the
        # process-global dispatcher the attention/gmm call sites read
        import deepspeed_tpu as dstpu
        from deepspeed_tpu.models.zoo import get_model
        from deepspeed_tpu.ops import attention as attn_ops

        model = get_model("tiny")
        engine, *_ = dstpu.initialize(
            model=model,
            config={"optimizer": {"type": "adamw",
                                  "params": {"lr": 1e-4}},
                    "kernels": {"flash_block_q": 256,
                                "pages_per_compute_block": 2}})
        try:
            kcfg = attn_ops._KERNEL_CONFIG
            assert kcfg is not None
            assert kcfg.flash_block_q == 256
            assert kcfg.pages_per_compute_block == 2
        finally:
            attn_ops.set_kernel_config(None)

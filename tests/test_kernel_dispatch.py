"""Cost-driven kernel dispatch: the win/loss table, the registry
decision layer, and the attention entry point consulting both.

Load-bearing guarantees (docs/kernels.md):
- dispatch provably consults the measured table: flipping a bucket's
  entry to losing routes that bucket to XLA **bit-identically**, and a
  winning entry routes to the flash kernel with the measured blocks;
- table entries are backend-scoped — the committed TPU-measured
  ``docs/autotuned/kernel_table.json`` never changes what a CPU run
  dispatches (unmeasured on this backend → legacy heuristic);
- compat probing stays the outer guard, the table rules measured
  buckets, the FLASH_MIN_SEQ heuristic covers only unmeasured ones;
- the chosen source is exported as ``kernel.*`` hub metrics, and the
  wanted-flash-but-unavailable case is a warn-once telemetry ratio like
  ``serve.paged_fallback_ratio``.
"""

import json
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import attention as attn_ops
from deepspeed_tpu.ops import kernel_table, registry


def _write_table(path, kernel, bucket, ratio, blocks=None, backend=None):
    entry = {"kernel_ms": 1.0, "xla_ms": ratio, "ratio": ratio,
             "backend": backend or jax.default_backend()}
    if blocks:
        entry["blocks"] = blocks
    doc = {"_meta": {"schema": kernel_table.SCHEMA},
           "entries": {kernel: {bucket: entry}}}
    path.write_text(json.dumps(doc))
    kernel_table.invalidate_cache()
    return str(path)


@pytest.fixture
def table_env(tmp_path, monkeypatch):
    """Point the dispatcher at a scratch table; restore + uncache on exit."""
    path = tmp_path / "kernel_table.json"

    def install(kernel, bucket, ratio, blocks=None, backend=None):
        monkeypatch.setenv("DSTPU_KERNEL_TABLE",
                           _write_table(path, kernel, bucket, ratio,
                                        blocks=blocks, backend=backend))
        return path

    yield install
    monkeypatch.delenv("DSTPU_KERNEL_TABLE", raising=False)
    kernel_table.invalidate_cache()


@pytest.fixture
def qkv():
    rng = np.random.default_rng(0)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    return (mk(1, 256, 4, 32), mk(1, 256, 2, 32), mk(1, 256, 2, 32))


# -- kernel_table unit layer ---------------------------------------------


class TestKernelTable:
    def test_bucketing_rounds_up_pow2(self):
        assert kernel_table.bucket_pow2(1) == 128
        assert kernel_table.bucket_pow2(128) == 128
        assert kernel_table.bucket_pow2(129) == 256
        assert kernel_table.attention_bucket(2048, 128, True) == \
            "s2048_d128_causal"
        assert kernel_table.attention_bucket(1000, 64, False) == \
            "s1024_d64_full"
        assert kernel_table.gmm_bucket(300, 128, 256, 4) == \
            "m512_k128_n256_g4"

    def test_decide_win_loss_unmeasured(self, table_env):
        table_env("flash_attention", "s256_d32_causal", 2.0,
                  blocks={"block_q": 128, "block_k": 128})
        d = kernel_table.decide("flash_attention", "s256_d32_causal")
        assert d.measured and d.win and d.ratio == 2.0
        assert d.blocks == {"block_q": 128, "block_k": 128}

        table_env("flash_attention", "s256_d32_causal", 0.5)
        d = kernel_table.decide("flash_attention", "s256_d32_causal")
        assert d.measured and not d.win

        d = kernel_table.decide("flash_attention", "s512_d32_causal")
        assert not d.measured and "unmeasured" in d.reason

    def test_backend_scoped_entries(self, table_env):
        # a tpu-measured win must NOT drive a cpu run (and vice versa)
        table_env("flash_attention", "s256_d32_causal", 3.0,
                  backend="tpu" if jax.default_backend() != "tpu"
                  else "cpu")
        d = kernel_table.decide("flash_attention", "s256_d32_causal")
        assert not d.measured
        assert "measured on" in d.reason

    def test_committed_table_is_tpu_scoped(self):
        # the artifact the repo ships must be inert off-TPU: every entry
        # carries an explicit non-local backend tag (tier-1 runs on CPU)
        from pathlib import Path

        doc = json.loads(Path(kernel_table.DEFAULT_TABLE).read_text())
        assert doc["_meta"]["schema"] == kernel_table.SCHEMA
        entries = [e for buckets in doc["entries"].values()
                   for e in buckets.values()]
        assert entries
        assert all(e["backend"] == "tpu" for e in entries)
        assert all(e["ratio"] == pytest.approx(
            e["xla_ms"] / e["kernel_ms"], rel=0.01) for e in entries)
        # the real-shape train bucket must be present and winning — the
        # train path runs flash on the 8L/131k-vocab shape via this row
        real = doc["entries"]["flash_attention"]["s2048_d128_causal"]
        assert real["ratio"] >= 1.0

    def test_record_roundtrip(self, tmp_path, monkeypatch):
        path = tmp_path / "t.json"
        monkeypatch.setenv("DSTPU_KERNEL_TABLE", str(path))
        kernel_table.invalidate_cache()
        kernel_table.record("grouped_matmul", "m256_k128_n256_g4",
                            kernel_ms=2.0, xla_ms=5.0,
                            blocks={"block_m": 128})
        d = kernel_table.decide("grouped_matmul", "m256_k128_n256_g4")
        assert d.measured and d.win and d.ratio == 2.5
        monkeypatch.delenv("DSTPU_KERNEL_TABLE")
        kernel_table.invalidate_cache()

    def test_malformed_table_never_raises(self, tmp_path, monkeypatch):
        path = tmp_path / "bad.json"
        path.write_text("{not json")
        monkeypatch.setenv("DSTPU_KERNEL_TABLE", str(path))
        kernel_table.invalidate_cache()
        d = kernel_table.decide("flash_attention", "s256_d32_causal")
        assert not d.measured
        monkeypatch.delenv("DSTPU_KERNEL_TABLE")
        kernel_table.invalidate_cache()


# -- registry decision layer ---------------------------------------------


class TestRegistryDispatch:
    def test_measured_win_routes_to_kernel(self, table_env):
        table_env("flash_attention", "s256_d32_causal", 1.8,
                  blocks={"block_q": 128, "block_k": 128})
        d = registry.dispatch_op("flash_attention", "s256_d32_causal",
                                 "xla_attention", default_use=False)
        assert d.source == "pallas" and d.op_name == "flash_attention"
        assert d.blocks == {"block_q": 128, "block_k": 128}

    def test_measured_loss_overrides_heuristic(self, table_env):
        table_env("flash_attention", "s256_d32_causal", 0.6)
        d = registry.dispatch_op("flash_attention", "s256_d32_causal",
                                 "xla_attention", default_use=True)
        assert d.source == "xla" and d.op_name == "xla_attention"

    def test_unmeasured_falls_back_to_heuristic(self, table_env):
        table_env("flash_attention", "s256_d32_causal", 2.0)
        for default_use, source in ((True, "pallas"), (False, "xla")):
            d = registry.dispatch_op("flash_attention", "s999_d32_causal",
                                     "xla_attention",
                                     default_use=default_use)
            assert d.source == source and "heuristic" in d.reason

    def test_unknown_op_raises(self):
        with pytest.raises(KeyError):
            registry.dispatch_op("not_an_op", "b", "xla_attention")


# -- the acceptance-criteria test: dispatch provably consults the table --


class TestAttentionDispatch:
    def test_losing_entry_routes_to_xla_bit_identically(self, table_env,
                                                        qkv):
        q, k, v = qkv
        table_env("flash_attention", "s256_d32_causal", 0.4)
        attn_ops._reset_dispatch_stats()
        out = attn_ops.multi_head_attention(q, k, v, causal=True)
        want = attn_ops.xla_attention(q, k, v, causal=True)
        assert bool(jnp.array_equal(out, want))
        stats = attn_ops.dispatch_stats()
        assert stats["xla"] == 1 and stats["pallas"] == 0

    def test_winning_entry_routes_to_flash(self, table_env, qkv):
        q, k, v = qkv
        table_env("flash_attention", "s256_d32_causal", 2.2,
                  blocks={"block_q": 128, "block_k": 128})
        attn_ops._reset_dispatch_stats()
        out = attn_ops.multi_head_attention(q, k, v, causal=True)
        stats = attn_ops.dispatch_stats()
        assert stats["pallas"] == 1
        want = attn_ops.xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_flip_win_to_loss_flips_route(self, table_env, qkv):
        # the same bucket, measured twice: win → kernel, loss → XLA.
        # This is the contract `make bench-kernels` regression-gates.
        q, k, v = qkv
        for ratio, source in ((1.5, "pallas"), (0.9, "xla")):
            table_env("flash_attention", "s256_d32_causal", ratio,
                      blocks={"block_q": 128, "block_k": 128})
            attn_ops._reset_dispatch_stats()
            attn_ops.multi_head_attention(q, k, v, causal=True)
            assert attn_ops.dispatch_stats()[source] == 1

    def test_heuristic_mode_ignores_table(self, table_env, qkv):
        from deepspeed_tpu.config.config import KernelsConfig

        q, k, v = qkv
        table_env("flash_attention", "s256_d32_causal", 9.0)
        attn_ops.set_kernel_config(KernelsConfig(dispatch="heuristic"))
        try:
            attn_ops._reset_dispatch_stats()
            out = attn_ops.multi_head_attention(q, k, v, causal=True)
            # seq 256 < FLASH_MIN_SEQ (and CPU): heuristic says XLA even
            # though the table claims a 9x win
            if jax.default_backend() != "tpu":
                assert attn_ops.dispatch_stats()["xla"] == 1
                want = attn_ops.xla_attention(q, k, v, causal=True)
                assert bool(jnp.array_equal(out, want))
        finally:
            attn_ops.set_kernel_config(None)

    def test_dispatch_exports_hub_metrics(self, table_env, qkv):
        from deepspeed_tpu.observability.hub import get_hub, reset_hub

        q, k, v = qkv
        table_env("flash_attention", "s256_d32_causal", 0.4)
        reset_hub()
        hub = get_hub()
        attn_ops._reset_dispatch_stats()
        attn_ops.multi_head_attention(q, k, v, causal=True)
        snap = hub.snapshot()
        assert snap["gauges"]["kernel.attention.pallas"] == 0.0
        reset_hub()

    def test_unavailable_kernel_raises_not_downgrades(self, table_env,
                                                      qkv, monkeypatch):
        """A bucket routed to the kernel runs the kernel or fails: no
        silent downgrade to the O(S^2) XLA path."""
        q, k, v = qkv
        table_env("flash_attention", "s256_d32_causal", 2.0)
        monkeypatch.setitem(
            sys.modules, "deepspeed_tpu.ops.pallas.flash_attention", None)
        with pytest.raises(ImportError):
            attn_ops.multi_head_attention(q, k, v, causal=True)


class TestFlashOnAMesh:
    """On a multi-device mesh the kernel runs per shard under shard_map
    (GSPMD cannot partition a Mosaic kernel): batch over the data axes,
    heads over tp — same numbers as the unsharded XLA reference."""

    @pytest.mark.parametrize("axes,batch", [
        (dict(dp=2, fsdp=2, tp=2), 4),   # batch over dp*fsdp, heads on tp
        (dict(fsdp=8), 2),               # batch does not divide: replicate
    ])
    def test_matches_reference(self, devices, axes, batch):
        from deepspeed_tpu.parallel import topology
        from deepspeed_tpu.parallel.topology import (TopologyConfig,
                                                     build_mesh)

        mesh = build_mesh(TopologyConfig(dp=axes.get("dp", 1),
                                         fsdp=axes.get("fsdp", 1),
                                         tp=axes.get("tp", 1)))
        topology.set_global_mesh(mesh)
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((batch, 128, 4, 16)),
                        jnp.float32)
        k = jnp.asarray(rng.standard_normal((batch, 128, 2, 16)),
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((batch, 128, 2, 16)),
                        jnp.float32)
        seg = jnp.asarray(np.repeat([[0, 1]], 64, axis=1)
                          .repeat(batch, axis=0), jnp.int32)

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v) ** 2)

        def flash(q, k, v):
            return attn_ops.multi_head_attention(
                q, k, v, causal=True, impl="flash", segment_ids=seg)

        def ref(q, k, v):
            return attn_ops.xla_attention(q, k, v, causal=True,
                                          segment_ids=seg)

        if batch == 2:  # the replicated case: forward only
            got, want = jax.jit(flash)(q, k, v), ref(q, k, v)
        else:
            got = jax.jit(jax.value_and_grad(
                lambda *a: loss(flash, *a), argnums=(0, 1, 2)))(q, k, v)
            want = jax.value_and_grad(
                lambda *a: loss(ref, *a), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-4)


# -- config plumbing -----------------------------------------------------


class TestKernelsConfig:
    def test_defaults_validate(self):
        from deepspeed_tpu.config.config import KernelsConfig

        KernelsConfig().validate()

    @pytest.mark.parametrize("bad", [
        {"flash_block_q": 100}, {"gmm_block_m": 3},
        {"pages_per_compute_block": 0}, {"dispatch": "nope"},
    ])
    def test_rejects_bad_geometry(self, bad):
        from deepspeed_tpu.config.config import KernelsConfig

        with pytest.raises(ValueError):
            KernelsConfig(**bad).validate()

    def test_config_block_builds_from_dict(self):
        from deepspeed_tpu.config.config import Config

        cfg = Config.from_dict({"kernels": {
            "flash_block_q": 256, "flash_block_k": 512,
            "pages_per_compute_block": 4, "dispatch": "heuristic"}})
        assert cfg.kernels.flash_block_q == 256
        assert cfg.kernels.pages_per_compute_block == 4

    def test_block_precedence_measured_over_config(self):
        from deepspeed_tpu.config.config import KernelsConfig

        attn_ops.set_kernel_config(KernelsConfig(flash_block_q=256,
                                                 flash_block_k=256))
        try:
            # config knobs beat the seq-derived auto...
            assert attn_ops._pick_blocks(2048, None) == (256, 256)
            # ...but measured table blocks beat the config knobs
            assert attn_ops._pick_blocks(
                2048, {"block_q": 512, "block_k": 1024}) == (512, 1024)
        finally:
            attn_ops.set_kernel_config(None)
        # no config installed: seq-derived default
        assert attn_ops._pick_blocks(256, None) == (256, 256)
        assert attn_ops._pick_blocks(8192, None) == (1024, 1024)

    def test_gmm_tiles_helper(self):
        from deepspeed_tpu.config.config import KernelsConfig

        assert attn_ops.kernel_gmm_tiles() == {}
        attn_ops.set_kernel_config(KernelsConfig(gmm_block_m=256))
        try:
            tiles = attn_ops.kernel_gmm_tiles()
            assert tiles == {"block_m": 256, "block_n": 1024,
                             "block_k": 512}
        finally:
            attn_ops.set_kernel_config(None)


# -- autotuner kernel-geometry axes --------------------------------------


class TestAutotunerKernelAxes:
    def test_parse_blocks_and_legality(self):
        from deepspeed_tpu.autotuning.autotuner import (legal_flash_blocks,
                                                        parse_blocks)

        assert parse_blocks("512x512", 2) == [512, 512]
        assert parse_blocks("512x1024x512", 3) == [512, 1024, 512]
        with pytest.raises(ValueError):
            parse_blocks("512x100", 2)  # not a power of two
        with pytest.raises(ValueError):
            parse_blocks("512", 2)
        # divisor-only candidates: 4096 admits all, 1536 only 512's
        # divisors below it
        assert legal_flash_blocks(4096) == ["128x128", "256x256",
                                            "512x512", "1024x1024"]
        assert legal_flash_blocks(1536) == ["128x128", "256x256",
                                            "512x512"]

    def test_candidates_carry_kernels_block(self):
        from deepspeed_tpu.autotuning.autotuner import Autotuner

        tuner = Autotuner(
            model_factory=lambda: None, base_config={},
            batch_fn=lambda n: {},
            tuning_space={"micro_batch_sizes": [1], "zero_stages": [1],
                          "flash_blocks": ["256x256", "512x512"],
                          "gmm_tiles": ["256x256x128"],
                          "pages_per_block": [1, 4]},
            hbm_budget_bytes=1)
        cands = tuner.candidates()
        assert len(cands) == 4  # 2 flash × 1 gmm × 2 pages
        kernels = [c["kernels"] for c in cands]
        assert {k["flash_block_q"] for k in kernels} == {256, 512}
        assert all(k["gmm_block_n"] == 256 for k in kernels)
        assert {k["pages_per_compute_block"] for k in kernels} == {1, 4}
        # tuned_defaults keeps the kernels block as-is (real config keys,
        # not private underscore axes) — it persists to docs/autotuned/
        out = Autotuner.tuned_defaults(cands[0])
        assert out["kernels"]["flash_block_q"] == 256

    def test_cli_accepts_int4_kv_bits(self):
        # the serving axis now spans the packed-nibble pool
        import deepspeed_tpu.autotuning.autotuner as at

        parsed = at.parse_quant_mode("off")  # sanity: module imports
        assert parsed["zero_hpz_partition_size"] == 1
        tuner = at.Autotuner(
            model_factory=lambda: None, base_config={},
            batch_fn=lambda n: {},
            tuning_space={"micro_batch_sizes": [1], "zero_stages": [1],
                          "kv_quant_bits": [4]},
            hbm_budget_bytes=1)
        (cand,) = tuner.candidates()
        assert cand["serving"]["kv_quant_bits"] == 4

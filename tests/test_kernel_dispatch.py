"""Which attention kernel ``impl='auto'`` runs, and with what blocks.

Load-bearing guarantees (docs/kernels.md):
- one rule decides, from what the program can observe: the flash kernel
  on a TPU backend from ``FLASH_MIN_SEQ`` up, the XLA einsum elsewhere,
  the latter **bit-identically** to ``xla_attention``;
- the blocks are ``kernels.flash_block_q/_k`` where set, else
  ``_auto_block(seq)``: a value a user sets is the value that runs;
- nothing reads a file, an environment variable or a table, and no
  module of the package imports the tooling above it;
- a decision for the kernel runs the kernel or raises;
- the chosen source is exported as ``kernel.*`` hub metrics.
"""

import ast
import builtins
import os
import re
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import attention as attn_ops

PACKAGE = Path(attn_ops.__file__).resolve().parents[1]


@pytest.fixture
def qkv():
    rng = np.random.default_rng(0)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    return (mk(1, 256, 4, 32), mk(1, 256, 2, 32), mk(1, 256, 2, 32))


@pytest.fixture
def on_backend(monkeypatch):
    """Let ``jax.default_backend()`` name another backend for a trace."""

    def name(backend):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        attn_ops._flash_available.cache_clear()

    attn_ops._reset_dispatch_stats()
    yield name
    monkeypatch.undo()
    attn_ops._flash_available.cache_clear()


@pytest.fixture
def flash_calls(monkeypatch):
    """The blocks ``_flash_on_mesh`` is called with, in place of the kernel."""
    calls = []

    def record(q, k, v, causal, segment_ids, block_q, block_k):
        calls.append((block_q, block_k))
        return q

    monkeypatch.setattr(attn_ops, "_flash_on_mesh", record)
    return calls


def _trace_auto(seq, head_dim):
    """Trace ``impl='auto'`` at a shape; nothing is computed."""
    q = jax.ShapeDtypeStruct((1, seq, 4, head_dim), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, seq, 2, head_dim), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: attn_ops.multi_head_attention(
        q, k, v, causal=True), q, kv, kv)


# The first four TPU rows are the rows of the win/loss table this rule
# replaced (v5e, fwd+bwd, kernel vs XLA): a loss at 512, wins with these
# blocks at 1024, 2048 and 8192. The table is gone; what it measured is
# held here as what the rule must answer.
@pytest.mark.parametrize("backend,seq,head_dim,blocks", [
    ("tpu", 512, 64, None),
    ("tpu", 1024, 64, (512, 512)),
    ("tpu", 2048, 128, (512, 512)),      # the training cells' shape
    ("tpu", 8192, 128, (1024, 1024)),
    ("tpu", 2048, 256, (512, 512)),      # the hybrid models' head
    ("tpu", 4096, 128, (512, 512)),
    ("tpu", 1023, 64, None),
    ("cpu", 2048, 128, None),
])
def test_auto_route_and_blocks(on_backend, flash_calls, backend, seq,
                               head_dim, blocks):
    on_backend(backend)
    _trace_auto(seq, head_dim)
    assert flash_calls == ([blocks] if blocks else [])
    assert attn_ops.dispatch_stats() == {
        "pallas": int(blocks is not None), "xla": int(blocks is None)}


@pytest.mark.parametrize("seq", [1024, 2048, 8192])
def test_set_flash_blocks_run_as_set(on_backend, flash_calls, seq):
    from deepspeed_tpu.config.config import KernelsConfig

    on_backend("tpu")
    attn_ops.set_kernel_config(KernelsConfig(flash_block_q=256,
                                             flash_block_k=256))
    try:
        _trace_auto(seq, 128)
    finally:
        attn_ops.set_kernel_config(None)
    assert flash_calls == [(256, 256)]


def test_auto_attention_opens_no_file(monkeypatch, qkv):
    """The decision comes from the arguments and the backend: no file of
    the checkout, nor any outside the installation, is opened or
    stat'ed while ``impl='auto'`` traces and runs."""
    from deepspeed_tpu.observability import hub  # noqa: F401 (lazy in ops)

    installation = (str(PACKAGE), sys.prefix, sys.base_prefix)
    touched = []

    def guarded(real):
        def call(path, *a, **kw):
            if isinstance(path, (str, bytes, os.PathLike)):
                full = os.path.abspath(os.fsdecode(path))
                if not full.startswith(installation):
                    touched.append(full)
                    raise PermissionError(full)
            return real(path, *a, **kw)
        return call

    q, k, v = qkv
    with monkeypatch.context() as m:
        m.setattr(builtins, "open", guarded(builtins.open))
        m.setattr(Path, "open", guarded(Path.open))
        m.setattr(os, "stat", guarded(os.stat))
        out = attn_ops.multi_head_attention(q[:, :192], k[:, :192],
                                            v[:, :192], causal=True)
        out = np.asarray(out, np.float32)
    assert touched == []
    assert np.isfinite(out).all()


def test_package_imports_nothing_above_itself():
    """``deepspeed_tpu/`` is the lower layer: the benchmarks and the
    tools import it, never the other way round."""
    root = PACKAGE.parent
    above = {"tools", "bench", "benchmarks", "chip_smoke"} | {
        p.stem for p in (root / "tools").glob("*.py")}
    found = []
    for path in sorted(PACKAGE.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and not node.level:
                names = [node.module or ""]
            else:
                continue
            found += [(str(path.relative_to(PACKAGE)), n) for n in names
                      if n.split(".")[0] in above]
    assert found == []


# every environment variable of its own that the package names; a new one
# is an edit here
DSTPU_ENV = """
    DSTPU_AIO_BACKEND DSTPU_CACHE_DIR DSTPU_CHAOS DSTPU_CLOCK_SKEW_S
    DSTPU_DCN_GBPS DSTPU_DISPATCH_AHEAD DSTPU_ELASTIC_RESTART_COUNT
    DSTPU_ELASTIC_WORLD DSTPU_FETCH_GBPS DSTPU_FLIGHT_DIR
    DSTPU_FPDT_BISECT DSTPU_GRADS_TO_HOST DSTPU_ICI_GBPS DSTPU_LOG_LEVEL
    DSTPU_METRICS_JSONL DSTPU_METRICS_PROM DSTPU_NVME_CONFIG
    DSTPU_OVERLAP_DEPTH DSTPU_PREFETCH DSTPU_PREFETCH_DEPTH
    DSTPU_QUANT_CHAOS DSTPU_QUANT_STATS DSTPU_REQUEST_TRACE
    DSTPU_REQ_TRACE_RING DSTPU_REQ_TRACE_SAMPLE DSTPU_REQ_TRACE_SLO_MS
    DSTPU_ROOFLINE DSTPU_RUN_DIR DSTPU_SERIALIZE_FETCH DSTPU_TRACE_DIR
    DSTPU_TRACE_STEPS DSTPU_WATCHDOG DSTPU_WATCHDOG_FACTOR
    DSTPU_WATCHDOG_MIN_S DSTPU_WORLD_INFO
""".split()


def test_dstpu_env_census():
    named = set()
    for path in PACKAGE.rglob("*.py"):
        named.update(re.findall(r"\bDSTPU_[A-Z0-9_]+", path.read_text()))
    assert len(DSTPU_ENV) == 35
    assert named == set(DSTPU_ENV)


class TestAttentionDispatch:
    def test_under_the_threshold_is_xla_bit_identically(self, on_backend,
                                                        qkv):
        q, k, v = qkv
        on_backend("tpu")  # the one backend on which a length decides
        out = attn_ops.multi_head_attention(q, k, v, causal=True)
        want = attn_ops.xla_attention(q, k, v, causal=True)
        assert bool(jnp.array_equal(out, want))
        stats = attn_ops.dispatch_stats()
        assert stats["xla"] == 1 and stats["pallas"] == 0

    def test_at_the_threshold_the_kernel_runs(self, monkeypatch, qkv):
        monkeypatch.setattr(attn_ops, "_flash_available", lambda: True)
        monkeypatch.setattr(attn_ops, "FLASH_MIN_SEQ", 256)
        q, k, v = qkv
        attn_ops._reset_dispatch_stats()
        out = attn_ops.multi_head_attention(q, k, v, causal=True)
        stats = attn_ops.dispatch_stats()
        assert stats["pallas"] == 1
        want = attn_ops.xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_dispatch_exports_hub_metrics(self, monkeypatch, qkv):
        from deepspeed_tpu.observability.hub import get_hub, reset_hub

        q, k, v = qkv
        reset_hub()
        hub = get_hub()
        events = []
        monkeypatch.setattr(hub, "record_event",
                            lambda kind, **f: events.append((kind, f)))
        attn_ops.multi_head_attention(q, k, v, causal=True)
        snap = hub.snapshot()
        assert snap["gauges"]["kernel.attention.pallas"] == 0.0
        assert events == [("kernel_dispatch", {
            "region": "attention", "source": "xla",
            "reason": "no TPU backend"})]
        reset_hub()

    def test_unavailable_kernel_raises_not_downgrades(self, qkv,
                                                      monkeypatch):
        """A length routed to the kernel runs the kernel or fails: no
        silent downgrade to the O(S^2) XLA path."""
        q, k, v = qkv
        monkeypatch.setattr(attn_ops, "_flash_available", lambda: True)
        monkeypatch.setattr(attn_ops, "FLASH_MIN_SEQ", 256)
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

    def test_zero_pages_is_the_kernels_choice(self, monkeypatch):
        """0 is the default and validates; the decode kernel then sizes
        its block from the pool's shapes, as it does with nothing set."""
        from deepspeed_tpu.config.config import KernelsConfig
        from deepspeed_tpu.ops.pallas import paged_attention as pa

        assert KernelsConfig().pages_per_compute_block == 0
        KernelsConfig(pages_per_compute_block=0).validate()
        rng = np.random.default_rng(0)
        kv = jnp.asarray(rng.standard_normal((9, 16, 2, 2, 128)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((2, 4, 128)), jnp.float32)
        table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        ctx = jnp.asarray([64, 37], jnp.int32)
        seen, rule = [], pa._decode_block_pages
        monkeypatch.setattr(pa, "_decode_block_pages",
                            lambda *a: seen.append(a) or rule(*a))
        zero = pa.paged_decode_attention(q, kv, table, ctx,
                                         pages_per_compute_block=0)
        unset = pa.paged_decode_attention(q, kv, table, ctx)
        pa.paged_decode_attention(q, kv, table, ctx,
                                  pages_per_compute_block=2)
        assert len(seen) == 2          # asked for 0 and None, not for 2
        np.testing.assert_array_equal(np.asarray(zero), np.asarray(unset))

    @pytest.mark.parametrize("bad", [
        {"flash_block_q": 100}, {"gmm_block_m": 3},
        {"pages_per_compute_block": -1}, {"flash_block_k": 3},
    ])
    def test_rejects_bad_geometry(self, bad):
        from deepspeed_tpu.config.config import KernelsConfig

        with pytest.raises(ValueError):
            KernelsConfig(**bad).validate()

    def test_config_block_builds_from_dict(self):
        from deepspeed_tpu.config.config import Config

        # "dispatch" went with the table it switched off: a config that
        # still carries it loads, the key warned about and ignored
        cfg = Config.from_dict({"kernels": {
            "flash_block_q": 256, "flash_block_k": 512,
            "pages_per_compute_block": 4, "dispatch": "heuristic"}})
        assert not hasattr(cfg.kernels, "dispatch")
        assert cfg.kernels.flash_block_q == 256
        assert cfg.kernels.pages_per_compute_block == 4

    def test_block_precedence_config_over_auto(self):
        from deepspeed_tpu.config.config import KernelsConfig

        attn_ops.set_kernel_config(KernelsConfig(flash_block_q=256))
        try:
            # a set block beats the seq-derived one; an unset one (0) is
            # the seq-derived one; nothing else has a say
            assert attn_ops._pick_blocks(2048) == (256, 512)
            assert attn_ops._pick_blocks(8192) == (256, 1024)
        finally:
            attn_ops.set_kernel_config(None)
        # no config installed: seq-derived default
        assert attn_ops._pick_blocks(256) == (256, 256)
        assert attn_ops._pick_blocks(8192) == (1024, 1024)

    def test_gmm_tiles_helper(self):
        """``kernels.gmm_block_*``: 0 leaves the tile to the kernel, a set
        one is an upper bound on the kernel's choice from the shapes."""
        from deepspeed_tpu.config.config import KernelsConfig
        from deepspeed_tpu.ops.pallas.grouped_matmul import choose_tiles

        bf16 = jnp.bfloat16
        assert attn_ops.kernel_gmm_tiles() == {}
        try:
            attn_ops.set_kernel_config(KernelsConfig())
            assert attn_ops.kernel_gmm_tiles() == {}
            attn_ops.set_kernel_config(KernelsConfig(gmm_block_m=256))
            limits = attn_ops.kernel_gmm_tiles()
            assert limits == {"block_m": 256}
            assert choose_tiles(32768, 4096, 14336, 8, bf16, **limits) == (
                256, 1024, 512)
            assert choose_tiles(384, 2048, 512, 128, bf16, **limits) == (
                16, 512, 2048)
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

"""Attention ops: XLA reference impl + Pallas flash kernel dispatch.

Covers the role of the reference's fused attention kernels
(csrc/transformer/*softmax*.cu, inference flash kernels
inference/v2/kernels/ragged_ops/blocked_flash). The ``impl='auto'`` path
picks between the Pallas flash kernel (ops/pallas/flash_attention.py) and
the XLA einsum implementation from the backend and the sequence length.
A decision for the kernel runs the kernel or raises: there is no
downgrade to the O(S^2) path behind the caller's back.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@functools.lru_cache(None)
def _flash_available() -> bool:
    """A TPU backend: elsewhere the kernel exists only in interpreter
    mode (numerics-equivalent, just slow), which ``impl='flash'`` asks
    for by name."""
    return jax.default_backend() == "tpu"


def repeat_kv_heads(q, k, v):
    """Repeat KV heads up to q's head count, for attention impls that
    need equal counts (XLA einsum, blocksparse, head-split SP paths).

    Contiguous repeat (q head h ← kv head h // group) — must match the
    flash kernel's ``_kv_row`` index map (ops/pallas/flash_attention.py).
    """
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def xla_attention(q, k, v, causal: bool = True,
                  segment_ids: Optional[jax.Array] = None,
                  window: Optional[int] = None) -> jax.Array:
    """Reference attention. q: [B, S, Nq, D]; k,v: [B, S, Nkv, D] with
    Nq a multiple of Nkv (GQA repeats kv heads here).

    Softmax in fp32 regardless of input dtype (numerics parity with the
    reference's attn_softmax kernels, csrc/transformer/softmax_kernels.cu).
    """
    k, v = repeat_kv_heads(q, k, v)
    dt = q.dtype
    d = q.shape[-1]
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    Sq, Sk = scores.shape[-2], scores.shape[-1]
    if causal:
        qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
        kpos = jnp.arange(Sk)[None, :]
        scores = jnp.where(kpos <= qpos, scores, NEG_INF)
        if window is not None:
            scores = jnp.where(qpos - kpos < window, scores, NEG_INF)
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]
        scores = jnp.where(same[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


# The crossover of impl='auto'. Below this sequence length XLA's fused
# attention beats the Pallas kernel on-chip; above it flash wins AND
# avoids the [S,S] fp32 score transient. Measured on v5e (B=32,N=12,D=64,
# fwd+bwd, block 512): seq 1024 → flash 1.5x over XLA; block 128 (old
# default) was 0.6x — block size dominates.
FLASH_MIN_SEQ = 1024


# engine-configured block-sparse layout (config.sparse_attention →
# set_sparse_config at engine init); used when impl == "blocksparse"
_SPARSE_CONFIG = None

# engine-configured kernel geometry (config.kernels → set_kernel_config
# at engine init); None = defaults (seq-derived blocks)
_KERNEL_CONFIG = None

# trace-time dispatch outcomes of impl='auto': pallas/xla picks
_DISPATCH_STATS = {"pallas": 0, "xla": 0}


def set_sparse_config(sparsity) -> None:
    """Install the layout for impl='blocksparse' (engine wires the
    ds_config sparse_attention block here)."""
    global _SPARSE_CONFIG
    _SPARSE_CONFIG = sparsity


def set_kernel_config(kernels) -> None:
    """Install the ds_config ``kernels`` block (engine init): block
    geometry overrides."""
    global _KERNEL_CONFIG
    _KERNEL_CONFIG = kernels


def dispatch_stats() -> dict:
    """Copy of the trace-time dispatch counters (tests + bench)."""
    return dict(_DISPATCH_STATS)


def _reset_dispatch_stats() -> None:
    for key in _DISPATCH_STATS:
        _DISPATCH_STATS[key] = 0


def kernel_gmm_tiles() -> dict:
    """Upper bounds on the grouped product's tiles from the installed
    ``kernels`` config block: those of ``kernels.gmm_block_{m,n,k}`` that
    are set (positive). The kernel chooses its tiles from the shapes
    (``grouped_matmul.choose_tiles``); with nothing installed or set,
    nothing bounds it."""
    limits = {b: int(getattr(_KERNEL_CONFIG, f"gmm_{b}", 0))
              for b in ("block_m", "block_n", "block_k")}
    return {b: v for b, v in limits.items() if v > 0}


def _auto_block(seq: int) -> int:
    # v5e measurements (docs/roofline.md): 512 best at short seq;
    # 1024 wins from ~8K up (fewer grid steps amortize the packed
    # triangle's per-step overhead — 128K fwd 124 vs 52 TF/s)
    return 1024 if seq >= 8192 else min(512, seq)


def _pick_blocks(seq: int) -> tuple:
    """Flash block geometry: kernels.flash_block_q/_k where set (0 =
    auto), else the seq-derived default. A sliding window does not move
    it: at 8k under a window of 2k the 1024-wide blocks' band is 21 block
    pairs against 70 of 512 (1.2x the products, 0.3x the grid steps), and
    on the chip the steps decide: forward 6.9 ms against 11.1, forward and
    backward 22.9 against 27.1 (2 x 8192, 32 heads over 4; my chip run,
    PR 43; 256-wide: 21.2 and 52.3)."""
    bq = bk = _auto_block(seq)
    kcfg = _KERNEL_CONFIG
    if kcfg is not None:
        bq = getattr(kcfg, "flash_block_q", 0) or bq
        bk = getattr(kcfg, "flash_block_k", 0) or bk
    return bq, bk


def _export_dispatch(region: str, source: str, reason: str) -> None:
    """Publish the chosen source per region to the observability hub.
    Runs at trace time (once per compiled program, not per step); never
    instantiates a hub of its own."""
    try:
        from deepspeed_tpu.observability.hub import peek_hub

        hub = peek_hub()
    except Exception:
        hub = None
    if hub is None:
        return
    hub.gauge(f"kernel.{region}.pallas", 1.0 if source == "pallas" else 0.0)
    hub.record_event("kernel_dispatch", region=region, source=source,
                     reason=reason)


def _flash_on_mesh(q, k, v, causal: bool, segment_ids, block_q: int,
                   block_k: int, window: Optional[int] = None) -> jax.Array:
    """The flash kernel on whatever mesh is live.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map" — the TPU compiler refuses the program; interpret mode on
    the CPU never notices), so on a multi-device mesh each shard runs the
    kernel on its local slice: batch over the data axes that divide it,
    heads over tp and sp (the Ulysses head-scatter layout) where they
    divide the kv heads. Attention is independent per (batch, head), so
    the region needs no collective; an axis that divides neither dim
    sees replicated operands and computes redundantly. Axes an enclosing
    region already made manual (the pipeline's pp, the ZeRO++ dp step)
    are left to it."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.parallel import topology
    from deepspeed_tpu.runtime import sharding as shard_lib

    kernel = functools.partial(flash_attention, causal=causal,
                               block_q=block_q, block_k=block_k,
                               window=window)
    mesh = topology._GLOBAL_MESH
    manual = shard_lib._MANUAL_AXES
    auto = {a: n for a, n in (mesh.shape.items() if mesh is not None else ())
            if n > 1 and a not in manual}
    if not auto:
        return kernel(q, k, v, segment_ids=segment_ids)

    def dividing(axes, dim):
        took, prod = [], 1
        for a in axes:
            if a in auto and dim % (prod * auto[a]) == 0:
                took.append(a)
                prod *= auto[a]
        return tuple(took) or None

    batch = dividing(topology.BATCH_AXES, q.shape[0])
    heads = dividing(("tp", "sp"), k.shape[2])
    spec = P(batch, None, heads, None)
    args, in_specs = (q, k, v), (spec, spec, spec)
    if segment_ids is not None:
        args, in_specs = args + (segment_ids,), in_specs + (P(batch, None),)

    def local(q, k, v, seg=None):
        return kernel(q, k, v, segment_ids=seg)

    return jax.shard_map(
        local,
        # nested in a partial-manual region, shard_map takes the context
        # mesh and may only manualize the axes still under GSPMD
        mesh=jax.sharding.get_abstract_mesh() if manual else mesh,
        in_specs=in_specs, out_specs=spec,
        axis_names=frozenset(a for a in mesh.axis_names if a not in manual),
        check_vma=False)(*args)


def multi_head_attention(q, k, v, causal: bool = True, impl: str = "auto",
                         segment_ids: Optional[jax.Array] = None,
                         window: Optional[int] = None) -> jax.Array:
    """Dispatching entry point used by the model zoo.

    ``impl='auto'`` runs the flash kernel on a TPU from FLASH_MIN_SEQ
    up and the XLA einsum elsewhere; ``impl='flash'``/``'xla'`` name
    one. Flash blocks are ``kernels.flash_block_q/_k`` where set, else
    ``_auto_block(seq)``. ``window``: a sliding window under the causal
    mask (key j visible to query i iff 0 <= i - j < window), in the flash
    kernel's band grid or the XLA mask.
    """
    seq = q.shape[1]
    if window is not None and (not causal or impl == "blocksparse"):
        raise ValueError("a sliding window needs causal attention through "
                         "the flash or XLA path")
    if impl == "blocksparse":
        if _SPARSE_CONFIG is None:
            raise ValueError(
                "attn_impl='blocksparse' needs a sparse_attention config "
                "block (or ops.attention.set_sparse_config)")
        if segment_ids is not None:
            raise NotImplementedError(
                "blocksparse attention does not take segment_ids")
        from deepspeed_tpu.ops.pallas.blocksparse_attention import \
            blocksparse_attention

        k, v = repeat_kv_heads(q, k, v)  # blocksparse kernel is MHA-only
        return blocksparse_attention(q, k, v, _SPARSE_CONFIG, causal=causal)
    if impl == "auto":
        on_tpu = _flash_available()
        impl = "flash" if on_tpu and seq >= FLASH_MIN_SEQ else "xla"
        source = "pallas" if impl == "flash" else "xla"
        _DISPATCH_STATS[source] += 1
        _export_dispatch("attention", source,
                         "no TPU backend" if not on_tpu else
                         f"seq {'>=' if impl == 'flash' else '<'} "
                         "FLASH_MIN_SEQ")
    if impl == "flash":
        bq, bk = _pick_blocks(seq)
        windowed = {} if window is None else {"window": window}
        return _flash_on_mesh(q, k, v, causal, segment_ids, bq, bk, **windowed)
    return xla_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                         window=window)

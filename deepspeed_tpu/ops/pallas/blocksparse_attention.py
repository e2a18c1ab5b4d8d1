"""Block-sparse attention (fixed / bigbird / longformer / variable).

Reference: ``deepspeed/ops/sparse_attention/`` (Triton blocksparse matmul
+ softmax, ``sparsity_config.py`` layout builders) with the sparsity modes
configured at ``runtime/config.py:250-410`` — 10x longer sequences than
dense (docs/_pages/training.md:147).

TPU design: sparsity lives at *block* granularity (MXU-shaped 128x128
tiles), never element granularity. A ``SparsityConfig`` builds a boolean
``[num_q_blocks, num_k_blocks]`` layout; the kernel is the streaming-
softmax flash loop with key blocks gated by the layout (``pl.when``
skips the matmuls of masked-out blocks, so FLOPs scale with layout
density). The XLA fallback expands the layout to an element mask and is
used off-TPU and for verification.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 128
NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# layout builders (reference sparsity_config.py)
# ---------------------------------------------------------------------------

class SparsityConfig:
    """Base layout builder (reference SparsityConfig: num_heads, block)."""

    def __init__(self, block: int = DEFAULT_BLOCK):
        self.block = int(block)

    def num_blocks(self, seq_len: int) -> int:
        if seq_len % self.block:
            raise ValueError(f"seq_len {seq_len} not a multiple of "
                             f"block {self.block}")
        return seq_len // self.block

    def make_layout(self, seq_len: int) -> np.ndarray:
        raise NotImplementedError


class DenseSparsityConfig(SparsityConfig):
    """All blocks attended (sanity/testing)."""

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self.num_blocks(seq_len)
        return np.ones((n, n), bool)


class FixedSparsityConfig(SparsityConfig):
    """Reference 'fixed' mode: each query block attends its local window
    of ``num_local_blocks`` and the last block of every window is global
    (attended by everyone)."""

    def __init__(self, block: int = DEFAULT_BLOCK, num_local_blocks: int = 4,
                 num_global_blocks: int = 1):
        super().__init__(block)
        self.num_local_blocks = num_local_blocks
        self.num_global_blocks = num_global_blocks

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self.num_blocks(seq_len)
        layout = np.zeros((n, n), bool)
        for q in range(n):
            w0 = (q // self.num_local_blocks) * self.num_local_blocks
            layout[q, w0:w0 + self.num_local_blocks] = True
        # last num_global_blocks of each window are global columns
        for w0 in range(0, n, self.num_local_blocks):
            hi = min(w0 + self.num_local_blocks, n)
            lo = max(hi - self.num_global_blocks, 0)
            layout[:, lo:hi] = True
        return layout


class LongformerSparsityConfig(SparsityConfig):
    """Sliding window + global attention on the first blocks."""

    def __init__(self, block: int = DEFAULT_BLOCK,
                 num_sliding_window_blocks: int = 3,
                 num_global_blocks: int = 1):
        super().__init__(block)
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.num_global_blocks = num_global_blocks

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self.num_blocks(seq_len)
        layout = np.zeros((n, n), bool)
        half = self.num_sliding_window_blocks // 2
        for q in range(n):
            lo, hi = max(0, q - half), min(n, q + half + 1)
            layout[q, lo:hi] = True
        g = min(self.num_global_blocks, n)
        layout[:, :g] = True  # everyone reads the globals
        layout[:g, :] = True  # globals read everyone
        return layout


class BigBirdSparsityConfig(SparsityConfig):
    """Random + sliding-window + global blocks (deterministic seed)."""

    def __init__(self, block: int = DEFAULT_BLOCK,
                 num_random_blocks: int = 1,
                 num_sliding_window_blocks: int = 3,
                 num_global_blocks: int = 1, seed: int = 0):
        super().__init__(block)
        self.num_random_blocks = num_random_blocks
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.num_global_blocks = num_global_blocks
        self.seed = seed

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self.num_blocks(seq_len)
        layout = LongformerSparsityConfig(
            self.block, self.num_sliding_window_blocks,
            self.num_global_blocks).make_layout(seq_len)
        rng = np.random.default_rng(self.seed)
        for q in range(n):
            picks = rng.choice(n, size=min(self.num_random_blocks, n),
                               replace=False)
            layout[q, picks] = True
        return layout


class VariableSparsityConfig(SparsityConfig):
    """Reference 'variable' mode: explicit local windows + global
    block indices."""

    def __init__(self, block: int = DEFAULT_BLOCK,
                 local_window_blocks: Sequence[int] = (4,),
                 global_block_indices: Sequence[int] = (0,)):
        super().__init__(block)
        self.local_window_blocks = list(local_window_blocks)
        self.global_block_indices = list(global_block_indices)

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self.num_blocks(seq_len)
        layout = np.zeros((n, n), bool)
        q = 0
        windows = list(self.local_window_blocks)
        while q < n:
            w = windows[0] if len(windows) == 1 else windows.pop(0)
            hi = min(q + w, n)
            layout[q:hi, q:hi] = True
            q = hi
        for g in self.global_block_indices:
            if g < n:
                layout[:, g] = True
                layout[g, :] = True
        return layout


MODES = {"dense": DenseSparsityConfig, "fixed": FixedSparsityConfig,
         "longformer": LongformerSparsityConfig,
         "bigbird": BigBirdSparsityConfig, "variable": VariableSparsityConfig}


def make_sparsity_config(mode: str, **kwargs) -> SparsityConfig:
    """Config-block entry (reference runtime/config.py:250-410 modes)."""
    if mode not in MODES:
        raise ValueError(f"unknown sparse attention mode '{mode}' "
                         f"(choose from {sorted(MODES)})")
    return MODES[mode](**kwargs)


def from_config(cfg) -> SparsityConfig:
    """Build a layout from the engine's ``sparse_attention`` config block
    (config.SparseAttentionConfig; 'bslongformer' is the reference's name
    for the longformer mode)."""
    mode = cfg.mode
    if mode == "dense":
        return DenseSparsityConfig(cfg.block)
    if mode == "fixed":
        return FixedSparsityConfig(cfg.block, cfg.num_local_blocks,
                                   cfg.num_global_blocks)
    if mode == "bslongformer":
        return LongformerSparsityConfig(cfg.block,
                                        cfg.num_sliding_window_blocks,
                                        cfg.num_global_blocks)
    if mode == "bigbird":
        return BigBirdSparsityConfig(cfg.block, cfg.num_random_blocks,
                                     cfg.num_sliding_window_blocks,
                                     cfg.num_global_blocks)
    if mode == "variable":
        return VariableSparsityConfig(cfg.block,
                                      cfg.local_window_blocks,
                                      cfg.global_block_indices)
    raise ValueError(f"unknown sparse attention mode '{mode}'")


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _expand_mask(layout: np.ndarray, block: int, seq_q: int,
                 seq_k: int) -> np.ndarray:
    m = np.repeat(np.repeat(layout, block, axis=0), block, axis=1)
    return m[:seq_q, :seq_k]


def blocksparse_attention(q, k, v, sparsity: SparsityConfig,
                          causal: bool = True,
                          scale: Optional[float] = None) -> jax.Array:
    """Block-sparse attention. q,k,v: [B, S, N, D] (model layout).

    The layout is static (built on host from the sparsity config), so the
    compiled program's FLOPs scale with layout density; XLA's masked
    path is used off-TPU. Causal composes with any layout.
    """
    B, S, N, D = q.shape
    # layout from the block-padded length; the expanded mask trims back to
    # S (ragged tails just use a partially-filled last block)
    padded = int(np.ceil(S / sparsity.block)) * sparsity.block
    layout = sparsity.make_layout(padded)
    scale = scale if scale is not None else D ** -0.5

    mask = jnp.asarray(_expand_mask(layout, sparsity.block, S, S))
    if causal:
        mask = mask & jnp.tril(jnp.ones((S, S), bool))

    qT = jnp.swapaxes(q, 1, 2)  # [B, N, S, D]
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bnsd,bntd->bnst", qT, kT,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnst,bntd->bnsd", probs, vT,
                     preferred_element_type=jnp.float32)
    return jnp.swapaxes(out.astype(q.dtype), 1, 2)


def _sparse_fwd_kernel(layout_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_sc, m_sc, l_sc, *, scale: float, causal: bool,
                       block_q: int, block_k: int):
    """Streaming-softmax flash loop with key blocks gated by the layout:
    a masked-out (q-block, k-block) pair skips both matmuls entirely, so
    FLOPs scale with layout density."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    run = layout_ref[iq, ik] != 0
    if causal:
        run = run & (ik * block_k <= iq * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_sc[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_sc[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha + pv
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)


def blocksparse_attention_pallas(q, k, v, sparsity: SparsityConfig,
                                 causal: bool = True,
                                 scale: Optional[float] = None) -> jax.Array:
    """Pallas block-sparse forward (inference / no-grad fast path; the
    differentiable XLA form is :func:`blocksparse_attention`). q,k,v:
    [B, S, N, D]; sparsity.block must equal the kernel block (128)."""
    B, S, N, D = q.shape
    block = sparsity.block
    layout = jnp.asarray(sparsity.make_layout(S).astype(np.int32))
    scale = scale if scale is not None else D ** -0.5
    nq = nk = S // block

    def to_bh(x):  # [B, S, N, D] → [B*N, S, D]
        return jnp.swapaxes(x, 1, 2).reshape(B * N, S, D)

    kernel = functools.partial(_sparse_fwd_kernel, scale=scale,
                               causal=causal, block_q=block, block_k=block)
    o = pl.pallas_call(
        kernel,
        name="blocksparse_fwd",
        grid=(B * N, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # layout [nq, nk]
            pl.BlockSpec((1, block, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * N, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, D), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
        ],
        interpret=_interpret(),
    )(layout, to_bh(q), to_bh(k), to_bh(v))
    return jnp.swapaxes(o.reshape(B, N, S, D), 1, 2)


def sparse_self_attention(q, k, v, mode: str = "fixed", causal: bool = True,
                          block: int = DEFAULT_BLOCK, **mode_kwargs):
    """One-call form: build the layout from (mode, kwargs) and run
    (reference SparseSelfAttention module)."""
    cfg = make_sparsity_config(mode, block=block, **mode_kwargs)
    return blocksparse_attention(q, k, v, cfg, causal=causal)


def layout_density(layout: np.ndarray, causal: bool = True) -> float:
    """Fraction of the dense score matrix actually computed — the
    compute/memory saving factor."""
    n = layout.shape[0]
    if causal:
        tri = np.tril(np.ones((n, n), bool))
        return float((layout & tri).sum() / tri.sum())
    return float(layout.mean())

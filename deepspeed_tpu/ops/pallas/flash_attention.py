"""Flash attention (Pallas TPU kernel, fwd + bwd) — GQA-native + segment ids.

The training-attention kernel of the framework — the role the reference's
fused softmax/attention CUDA kernels play (csrc/transformer/
softmax_kernels.cu, general_kernels.cu) and the memory-efficient
Evoformer/blocked-flash kernels (csrc/deepspeed4science/evoformer_attn,
inference/v2/kernels/ragged_ops/blocked_flash).

Algorithm: standard streaming-softmax flash attention. O(S) memory:
softmax statistics (m, l) are carried across key blocks; the backward
recomputes P blockwise from the saved logsumexp instead of storing the
[S, S] score matrix.

GQA is native: K/V stay at ``num_kv_heads`` in HBM and every q head's
block spec index-maps to its kv head (q-head h → kv-head h // group).
No pre-repeat — for Llama-3-8B (32q/8kv) that is 4x less KV bandwidth
and HBM than repeating. dK/dV accumulate across the q-head group inside
the kernel (grid folds group × q-blocks into one accumulation loop).

Segment ids (packed sequences) mask cross-segment attention blockwise,
so packed batches keep the O(S) kernel instead of falling back to the
O(S^2) XLA path. Non-causal is supported (padding is masked via a
synthesized segment tensor when needed).

Layout: [B, H, S, D] inside the kernels (the public wrapper transposes
from the model's [B, S, H, D]). fp32 accumulation on the MXU
(preferred_element_type), bf16 streaming.

Blocks default to 128x128 (MXU-shaped); 512 measured best on v5e at
seq >= 1024 (see ops/attention.py dispatch).

Causal grids are *triangle-packed*: the kernels iterate a static work
list of live (q-block, k-block) pairs via scalar prefetch instead of a
dense nq x nk grid with a skip gate. A skipped grid step still costs
its K/V block DMA and grid overhead — at long context that is ~2x
wasted HBM bandwidth, which is exactly what bounds the kernel at D=128.

A sliding ``window`` (key j visible to query i iff 0 <= i - j < window)
makes the live pairs a *band*: each q-block meets its diagonal block and
the ``band`` blocks below it. The windowed kernels walk an nq x (band + 1)
rectangle; the few steps that fall off the band's ragged end (before
block 0 in a q-run, past the last q-block in a k-run) are clamped onto
the neighbouring live step's blocks, so nothing is fetched for them, and
skipped, so nothing is multiplied. They carry their own names
(``flash_window_*``): a trace tells a windowed call from a full one.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
# lse/delta carry one scalar per query row, broadcast across lanes for
# tiling; 8 lanes (the fp32 sublane tile) instead of 128 cuts their
# HBM traffic 16x — they otherwise write/read 2x the attention output
STAT_LANES = 8
# segment ids reach the kernels pre-broadcast, q ids as a column
# [B, S, STAT_LANES] and k ids as a row [B, SEG_SUBLANES, S]: Mosaic
# wants a block's last two dims divisible by (8, 128) or equal to the
# array's, which a (1, block) slice of a [B, S] operand is not, and the
# column/row split also spares the kernel a lane→sublane relayout
SEG_SUBLANES = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _band_blocks(window: int, block: int, nq: int) -> int:
    """Blocks below the diagonal that a ``window`` reaches: the first row
    of q-block iq sees keys from iq*block - (window - 1) on."""
    return min(-(-(window - 1) // block), nq - 1)


def _band_q(t, band: int):
    """Windowed q-major item t -> (iq, ik, d, live): step d of q-block
    iq's run over k-blocks iq - band .. iq. A step before block 0 is not
    live and sits on block 0, which the run's first live step reads."""
    iq, d = t // (band + 1), t % (band + 1)
    ik = iq - band + d
    return iq, jnp.maximum(ik, 0), d, ik >= 0


def _band_kv(t, band: int, nq: int):
    """Windowed k-major twin -> (iq, ik, d, live): step d of k-block
    ik's run over q-blocks ik .. ik + band. A step past the last q-block
    is not live and sits on the last block, which the step before it read."""
    ik, d = t // (band + 1), t % (band + 1)
    iq = ik + d
    return jnp.minimum(iq, nq - 1), ik, d, iq < nq


def _num_items(nq: int, nk: int, causal: bool, band=None) -> int:
    """Work items in the (triangle-)packed grid. Causal requires
    block_q == block_k, giving the exact lower triangle nq*(nq+1)/2.

    Guard: the packed decomposition runs in int32 with an fp32 sqrt
    seed + double ±1 correction (_decompose_q/_decompose_kv) — exact
    while the item count fits int32. nq = 2^15 (S = 32M at block 1024)
    is still ~5e8 items; anything larger must raise, not corrupt."""
    t_total = nq * (nq + 1) // 2 if causal else nq * nk
    if band is not None:
        t_total = nq * (band + 1)
    if t_total >= 2 ** 31:
        raise ValueError(
            f"flash grid item count {t_total} overflows the int32 packed "
            f"decomposition (nq={nq}, nk={nk}); use a larger block size")
    return t_total


def _decompose_q(t, nq: int, nk: int, causal: bool):
    """Work item t → (iq, ik), q-block-major (all k-blocks of one
    q-block consecutive — the o/lse accumulation run). Causal packs the
    lower triangle: t = iq(iq+1)/2 + ik. Closed form (fp32 sqrt + ±1
    correction — exact for t < 2^23, i.e. any S the scalar core can
    count): no SMEM work lists, so sequence length is unbounded."""
    if not causal:
        return t // nk, t % nk
    tf = t.astype(jnp.float32)
    iq = jnp.floor((jnp.sqrt(8.0 * tf + 1.0) - 1.0) * 0.5).astype(jnp.int32)
    # two ±1 corrections each way (matching _decompose_kv): one fp32 ulp
    # at large t can put the closed form two integers off; a silently
    # wrong (iq, ik) would corrupt attention with no error
    iq = jnp.where(iq * (iq + 1) // 2 > t, iq - 1, iq)
    iq = jnp.where(iq * (iq + 1) // 2 > t, iq - 1, iq)
    iq = jnp.where((iq + 1) * (iq + 2) // 2 <= t, iq + 1, iq)
    iq = jnp.where((iq + 1) * (iq + 2) // 2 <= t, iq + 1, iq)
    ik = t - iq * (iq + 1) // 2
    return iq, ik


def _decompose_kv(t, nq: int, nk: int, causal: bool):
    """k-block-major twin (the dk/dv accumulation run). Causal: for
    k-block ik the q-blocks ik..nq-1 are live; cum(ik) = ik*nq -
    ik(ik-1)/2 items precede it."""
    if not causal:
        return t % nq, t // nq
    a = 2 * nq + 1
    tf = t.astype(jnp.float32)
    disc = jnp.maximum(a * a - 8.0 * tf, 0.0)
    ik = jnp.floor((a - jnp.sqrt(disc)) * 0.5).astype(jnp.int32)
    ik = jnp.clip(ik, 0, nq - 1)

    def cum(i):
        return i * nq - i * (i - 1) // 2

    ik = jnp.where(cum(ik) > t, ik - 1, ik)
    ik = jnp.where(cum(ik) > t, ik - 1, ik)
    ik = jnp.where(cum(ik + 1) <= t, ik + 1, ik)
    ik = jnp.where(cum(ik + 1) <= t, ik + 1, ik)
    iq = ik + (t - cum(ik))
    return iq, ik


def _q_run(t, nq: int, nk: int, causal: bool, band):
    """A q-major step -> (iq, ik, first of its q-block's run, last of it,
    live); ``live`` is None where every step is (no window)."""
    if band is not None:
        iq, ik, d, live = _band_q(t, band)
        return iq, ik, d == 0, d == band, live
    iq, ik = _decompose_q(t, nq, nk, causal)
    return iq, ik, ik == 0, (ik == iq) if causal else (ik == nk - 1), None


def _kv_row(b, hq: int, hkv: int):
    """GQA index map: flattened q row b = batch*hq + h → kv row for
    kv head h // (hq // hkv). The load-bearing GQA invariant — forward
    and backward must share it."""
    return (b // hq) * hkv + (b % hq) // (hq // hkv)


def _mask(s, *, iq, ik, causal: bool, seg_q, seg_k,
          block_q: int, block_k: int, window=None):
    """Apply causal and/or segment masks to a [BQ, BK] score block.
    ``seg_q`` is a [BQ, 1] column, ``seg_k`` a [1, BK] row."""
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
        if window is not None:
            s = jnp.where(qpos - kpos < window, s, NEG_INF)
    if seg_q is not None:
        s = jnp.where(seg_q == seg_k, s, NEG_INF)
    return s


def _when(live):
    """Decorator for a grid step's work: always (``live`` None: every
    step of a packed grid is live) or only on a live step of a band."""
    return (lambda step: step()) if live is None else pl.when(live)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale: float, causal: bool,
                has_segments: bool, block_q: int, block_k: int,
                nq: int, nk: int, window=None, band=None):
    if has_segments:
        q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref, \
            acc_sc, m_sc, l_sc = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc = refs
        sq_ref = sk_ref = None
    t = pl.program_id(1)
    # triangle-packed grid: every step is live; q-major ordering means a
    # q-block's run starts at its first k-block and ends at the diagonal
    iq, ik, first, last, live = _q_run(t, nq, nk, causal, band)

    @pl.when(first)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @_when(live)
    def _step():
        q = q_ref[0]  # [BQ, D]
        k = k_ref[0]  # [BK, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        s = _mask(s, iq=iq, ik=ik, causal=causal,
                  seg_q=sq_ref[0][:, :1] if has_segments else None,
                  seg_k=sk_ref[0][:1, :] if has_segments else None,
                  block_q=block_q, block_k=block_k, window=window)

        # a row whose keys in this block are all masked (the band's lower
        # edge) adds exp(0) terms at m = NEG_INF; the first block with a
        # visible key rescales them by exp(NEG_INF - m) = 0, and the
        # diagonal block always has one
        m_prev = m_sc[:, :1]  # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # [BQ, 1]
        p = jnp.exp(s - m_new)  # [BQ, BK]
        l_new = l_sc[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [BQ, D]
        acc_sc[:] = acc_sc[:] * alpha + pv
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(last)
    def _finalize():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m_sc[:] + jnp.log(l_safe)).astype(jnp.float32)


def _flash_fwd(q, k, v, seg_q, seg_k, scale: float, causal: bool,
               hq: int, hkv: int, block_q: int, block_k: int,
               window=None) -> Tuple[jax.Array, jax.Array]:
    """q: [B*Hq, S, D]; k,v: [B*Hkv, S, D]; seg_q: [B, S, STAT_LANES],
    seg_k: [B, SEG_SUBLANES, S], or both None.

    Returns (o [B*Hq, S, D], lse [B*Hq, S, STAT_LANES]).
    """
    BHq, S, D = q.shape
    nq, nk = S // block_q, S // block_k
    has_segments = seg_q is not None
    band = None if window is None else _band_blocks(window, block_q, nq)

    def kv_row(b):
        return _kv_row(b, hq, hkv)

    def d_q(t):
        return _q_run(t, nq, nk, causal, band)[:2]

    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, t: (b, d_q(t)[0], 0)),
        pl.BlockSpec((1, block_k, D),
                     lambda b, t: (kv_row(b), d_q(t)[1], 0)),
        pl.BlockSpec((1, block_k, D),
                     lambda b, t: (kv_row(b), d_q(t)[1], 0)),
    ]
    args = [q, k, v]
    if has_segments:
        in_specs += [
            pl.BlockSpec((1, block_q, STAT_LANES),
                         lambda b, t: (b // hq, d_q(t)[0], 0)),
            pl.BlockSpec((1, SEG_SUBLANES, block_k),
                         lambda b, t: (b // hq, 0, d_q(t)[1])),
        ]
        args += [seg_q, seg_k]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, has_segments=has_segments,
        block_q=block_q, block_k=block_k, nq=nq, nk=nk, window=window,
        band=band)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd" if window is None else "flash_window_fwd",
        grid=(BHq, _num_items(nq, nk, causal, band)),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, t: (b, d_q(t)[0], 0)),
            pl.BlockSpec((1, block_q, STAT_LANES),
                         lambda b, t: (b, d_q(t)[0], 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, STAT_LANES), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHq, S, D), q.dtype),
            jax.ShapeDtypeStruct((BHq, S, STAT_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(*args)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dkdv_kernel(*refs, scale: float, causal: bool,
                     has_segments: bool, block_q: int, block_k: int,
                     nq: int, nk: int, window=None, band=None):
    if has_segments:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref, \
            dk_ref, dv_ref, dk_sc, dv_sc = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, \
            dk_ref, dv_ref, dk_sc, dv_sc = refs
        sq_ref = sk_ref = None
    # grid: (B*Hkv, T, group) — T iterates the k-block-major packed
    # triangle, the inner dim the q-head group, so dk/dv accumulate over
    # (GQA group x live q-blocks) in scratch per k-block run.
    t, mem = pl.program_id(1), pl.program_id(2)
    g = pl.num_programs(2)
    if band is None:
        iq, ik = _decompose_kv(t, nq, nk, causal)
        run_start = ik if causal else 0
        first = jnp.logical_and(mem == 0, iq == run_start)
        last = jnp.logical_and(mem == g - 1, iq == nq - 1)
        live = None
    else:
        iq, ik, d, live = _band_kv(t, band, nq)
        first = jnp.logical_and(mem == 0, d == 0)
        last = jnp.logical_and(mem == g - 1, d == band)

    @pl.when(first)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    @_when(live)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]  # [BQ, 1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        s = _mask(s, iq=iq, ik=ik, causal=causal,
                  seg_q=sq_ref[0][:, :1] if has_segments else None,
                  seg_k=sk_ref[0][:1, :] if has_segments else None,
                  block_q=block_q, block_k=block_k, window=window)
        p = jnp.exp(s - lse)  # [BQ, BK]
        # dv += p^T @ do
        dv_sc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do @ v^T ; ds = p * (dp - delta)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_sc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale: float, causal: bool,
                   has_segments: bool, block_q: int, block_k: int,
                   nq: int, nk: int, window=None, band=None):
    if has_segments:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref, \
            dq_ref, dq_sc = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, \
            dq_ref, dq_sc = refs
        sq_ref = sk_ref = None
    t = pl.program_id(1)
    iq, ik, first, last, live = _q_run(t, nq, nk, causal, band)

    @pl.when(first)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    @_when(live)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask(s, iq=iq, ik=ik, causal=causal,
                  seg_q=sq_ref[0][:, :1] if has_segments else None,
                  seg_k=sk_ref[0][:1, :] if has_segments else None,
                  block_q=block_q, block_k=block_k, window=window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_sc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, seg_q, seg_k, o, lse, do, scale, causal,
               hq, hkv, block_q, block_k, window=None):
    BHq, S, D = q.shape
    BHkv = k.shape[0]
    g = hq // hkv
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)  # [B*Hq, S]
    delta = jnp.broadcast_to(delta[..., None], (BHq, S, STAT_LANES))

    nq, nk = S // block_q, S // block_k
    has_segments = seg_q is not None
    band = None if window is None else _band_blocks(window, block_q, nq)

    def d_kv(t):
        if band is None:
            return _decompose_kv(t, nq, nk, causal)
        return _band_kv(t, band, nq)[:2]

    # --- dk/dv: one pass per kv head, accumulating over its q-head group
    def q_row(b, m, t=None):
        if band is not None:
            # a step off the band's end stays on the q head it follows
            # (the group's last), so that its blocks are not fetched anew
            m = jnp.where(_band_kv(t, band, nq)[3], m, g - 1)
        return (b // hkv) * hq + (b % hkv) * g + m

    dkdv_in_specs = [
        pl.BlockSpec((1, block_q, D),
                     lambda b, t, m: (q_row(b, m, t), d_kv(t)[0], 0)),
        pl.BlockSpec((1, block_k, D),
                     lambda b, t, m: (b, d_kv(t)[1], 0)),  # k
        pl.BlockSpec((1, block_k, D),
                     lambda b, t, m: (b, d_kv(t)[1], 0)),  # v
        pl.BlockSpec((1, block_q, D),
                     lambda b, t, m: (q_row(b, m, t), d_kv(t)[0], 0)),
        pl.BlockSpec((1, block_q, STAT_LANES),
                     lambda b, t, m: (q_row(b, m, t), d_kv(t)[0], 0)),
        pl.BlockSpec((1, block_q, STAT_LANES),
                     lambda b, t, m: (q_row(b, m, t), d_kv(t)[0], 0)),
    ]
    dkdv_args = [q, k, v, do, lse, delta]
    if has_segments:
        dkdv_in_specs += [
            pl.BlockSpec((1, block_q, STAT_LANES),
                         lambda b, t, m: (b // hkv, d_kv(t)[0], 0)),
            pl.BlockSpec((1, SEG_SUBLANES, block_k),
                         lambda b, t, m: (b // hkv, 0, d_kv(t)[1])),
        ]
        dkdv_args += [seg_q, seg_k]
    dkdv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          has_segments=has_segments,
                          block_q=block_q, block_k=block_k, nq=nq, nk=nk,
                          window=window, band=band),
        name="flash_bwd_dkdv" if window is None else "flash_window_bwd_dkdv",
        grid=(BHkv, _num_items(nq, nk, causal, band), g),
        in_specs=dkdv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D),
                         lambda b, t, m: (b, d_kv(t)[1], 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, t, m: (b, d_kv(t)[1], 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, S, D), k.dtype),
            jax.ShapeDtypeStruct((BHkv, S, D), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(*dkdv_args)
    dk, dv = dkdv

    # --- dq: one pass per q head, kv blocks via the GQA index map
    def kv_row(b):
        return _kv_row(b, hq, hkv)

    def d_q(t):
        return _q_run(t, nq, nk, causal, band)[:2]

    dq_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, t: (b, d_q(t)[0], 0)),
        pl.BlockSpec((1, block_k, D),
                     lambda b, t: (kv_row(b), d_q(t)[1], 0)),
        pl.BlockSpec((1, block_k, D),
                     lambda b, t: (kv_row(b), d_q(t)[1], 0)),
        pl.BlockSpec((1, block_q, D), lambda b, t: (b, d_q(t)[0], 0)),
        pl.BlockSpec((1, block_q, STAT_LANES),
                     lambda b, t: (b, d_q(t)[0], 0)),
        pl.BlockSpec((1, block_q, STAT_LANES),
                     lambda b, t: (b, d_q(t)[0], 0)),
    ]
    dq_args = [q, k, v, do, lse, delta]
    if has_segments:
        dq_in_specs += [
            pl.BlockSpec((1, block_q, STAT_LANES),
                         lambda b, t: (b // hq, d_q(t)[0], 0)),
            pl.BlockSpec((1, SEG_SUBLANES, block_k),
                         lambda b, t: (b // hq, 0, d_q(t)[1])),
        ]
        dq_args += [seg_q, seg_k]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          has_segments=has_segments,
                          block_q=block_q, block_k=block_k, nq=nq, nk=nk,
                          window=window, band=band),
        name="flash_bwd_dq" if window is None else "flash_window_bwd_dq",
        grid=(BHq, _num_items(nq, nk, causal, band)),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda b, t: (b, d_q(t)[0], 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((BHq, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(*dq_args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, seg_q, seg_k, causal: bool, hq: int, hkv: int,
           block_q: int, block_k: int, window=None):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    o, _ = _flash_fwd(q, k, v, seg_q, seg_k, scale, causal, hq, hkv,
                      block_q, block_k, window)
    return o


def _flash_vjp_fwd(q, k, v, seg_q, seg_k, causal, hq, hkv,
                   block_q, block_k, window):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    o, lse = _flash_fwd(q, k, v, seg_q, seg_k, scale, causal, hq, hkv,
                        block_q, block_k, window)
    return o, (q, k, v, seg_q, seg_k, o, lse)


def _flash_vjp_bwd(causal, hq, hkv, block_q, block_k, window, res, do):
    q, k, v, seg_q, seg_k, o, lse = res
    scale = 1.0 / (q.shape[-1] ** 0.5)
    dq, dk, dv = _flash_bwd(q, k, v, seg_q, seg_k, o, lse, do, scale,
                            causal, hq, hkv, block_q, block_k, window)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    segment_ids: Optional[jax.Array] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    window: Optional[int] = None) -> jax.Array:
    """Public entry. q: [B, S, Nq, D]; k, v: [B, S, Nkv, D] (GQA-native —
    Nq must be a multiple of Nkv; no pre-repeat needed or wanted).

    ``segment_ids``: optional [B, S] int array; attention is masked to
    same-segment pairs (packed sequences). Causal and non-causal both
    run in the kernel.

    ``window``: optional sliding window under the causal mask: key j is
    visible to query i iff 0 <= i - j < window. Only the band of blocks
    the window reaches is visited, forward and backward.

    Pads S up to a block multiple. Padding is always masked: under a
    causal mask padded queries only attend the real prefix and are
    dropped on exit; otherwise padded keys are excluded by segment ids
    (synthesized when the caller passed none).
    """
    B, S, Nq, D = q.shape
    Nkv = k.shape[2]
    if Nq % Nkv != 0:
        raise ValueError(f"q heads ({Nq}) not a multiple of kv heads ({Nkv})")
    if window is not None and (not causal or window < 1):
        raise ValueError(f"a sliding window ({window}) is a causal mask's: "
                         f"causal={causal}")
    bq = min(block_q, _round_pow2(S))
    bk = min(block_k, _round_pow2(S))
    if causal and bq != bk:
        # the packed triangle grid's closed-form (iq, ik) decomposition
        # assumes square blocks
        bq = bk = min(bq, bk)
    Sp = -(-S // max(bq, bk)) * max(bq, bk)

    if segment_ids is None and not causal and Sp != S:
        # non-causal padding must be masked out: synthesize one segment
        segment_ids = jnp.zeros((B, S), jnp.int32)

    def prep(x):
        n = x.shape[2]
        x = jnp.swapaxes(x, 1, 2).reshape(B * n, S, D)
        if Sp != S:
            x = jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0)))
        return x

    seg_q = seg_k = None
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)
        # distinct pad values so padded q rows match nothing at all
        seg_q = jnp.pad(seg, ((0, 0), (0, Sp - S)), constant_values=-2)
        seg_k = jnp.pad(seg, ((0, 0), (0, Sp - S)), constant_values=-1)
        seg_q = jnp.broadcast_to(seg_q[:, :, None], (B, Sp, STAT_LANES))
        seg_k = jnp.broadcast_to(seg_k[:, None, :], (B, SEG_SUBLANES, Sp))

    o = _flash(prep(q), prep(k), prep(v), seg_q, seg_k,
               causal, Nq, Nkv, bq, bk, window)
    o = o[:, :S].reshape(B, Nq, S, D)
    return jnp.swapaxes(o, 1, 2)


def _round_pow2(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p

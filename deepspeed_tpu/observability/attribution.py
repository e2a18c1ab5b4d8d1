"""Per-region roofline attribution for a training step.

Splits the step's cost into the five buckets that matter at the real
shape (8L · 131k vocab on one chip, docs/roofline.md): **attn**,
**mlp**, **vocab_head**, **optimizer**, **param_fetch**.

The three compute buckets are measured, not modeled: each region is a
small jitted closure over the model's own block functions
(``models.transformer._layer`` / ``_layer_mlp`` / the fused
final-norm+unembed+CE tail), lowered + compiled on abstract
``ShapeDtypeStruct`` inputs and read back through XLA's cost analysis —
so the numbers track whatever the compiler actually emits (remat, fp8,
tiling) and the pass runs anywhere jax compiles, including CPU CI.
The attn bucket is the full-block cost minus the MLP-half cost
(the block is fused end-to-end; XLA cannot attribute a residual add to
one side, and the subtraction is exact for the matmul-dominated terms).

The two non-compute buckets are analytic transfer models:

- ``optimizer``: fused-Adam HBM (or host-RAM, under offload) traffic —
  reads master+m+v (12 B/param) + the grad, writes master+m+v + the
  bf16 model cast.
- ``param_fetch``: ZeRO-Infinity layer streaming — per-layer param
  bytes × layers × (fwd + bwd), against the host link bandwidth
  (``DSTPU_FETCH_GBPS``; the 3.3 GB/s default is builder-reported,
  from before the current installation — pass a measured rate).
  This traffic *overlaps* compute via the prefetch ring
  (``performance.param_prefetch_depth``); its row reports the bandwidth
  floor it needs to stay hidden, not an additive cost.

The long-context bench tier adds two more transfer regions —
**sp_comm** (sequence-parallel collectives on ICI) and
**host_kv_stream** (FPDT host-KV D2H/H2D) — modeled analytically by
:func:`attribute_longctx_step` (a compiled step at 256k tokens is
O(S²)-infeasible on the CPU sim). All three transfer regions share the
``DMA_REGIONS`` exposed/hidden machinery.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.observability.roofline import roofline_summary

REGIONS = ("attn", "mlp", "vocab_head", "optimizer", "param_fetch")

# the BENCH_LONGCTX tier's analytic regions (attribute_longctx_step)
LONGCTX_REGIONS = ("attn", "sp_comm", "host_kv_stream")

# Transfer (DMA) regions: their roofline time is bytes/bandwidth on the
# link they ride, not flops/bytes against HBM. sp_comm rides ICI; the
# host streams ride the host link; grad_reduce (the qgZ region,
# attribute_quant_step) rides ICI/DCN per its level structure.
DMA_REGIONS = frozenset({"param_fetch", "sp_comm", "host_kv_stream",
                         "grad_reduce"})

# builder-reported sustained host-to-device rate of an earlier rig
# (docs/roofline.md), not measured on the current installation; a pod's
# per-layer bf16 all-gather over ICI is ≥20x this
_DEFAULT_FETCH_GBPS = 3.3

# one v5e ICI link direction (sustained, docs/roofline.md); override
# with DSTPU_ICI_GBPS for other topologies
_DEFAULT_ICI_GBPS = 45.0

# inter-slice data-center network per chip (the link hpZ keeps gathers
# off); override with DSTPU_DCN_GBPS
_DEFAULT_DCN_GBPS = 6.25


def _dma_gbps(region: str, fetch_gbps: Optional[float] = None,
              ici_gbps: Optional[float] = None) -> float:
    """Bandwidth a DMA region's bytes divide by: sp collectives ride
    ICI, param/KV streams ride the host link."""
    if region == "sp_comm":
        return (ici_gbps if ici_gbps is not None
                else float(os.environ.get("DSTPU_ICI_GBPS",
                                          _DEFAULT_ICI_GBPS)))
    return (fetch_gbps if fetch_gbps is not None
            else float(os.environ.get("DSTPU_FETCH_GBPS",
                                      _DEFAULT_FETCH_GBPS)))


@dataclasses.dataclass
class RegionCost:
    region: str
    flops: float            # total for the step (already × num_layers)
    bytes_accessed: float
    note: str = ""
    overlapped: bool = False  # traffic hidden behind compute when true
    # DMA regions only: pin the link this region's bytes divide by
    # (attribute_quant_step sets these — e.g. grad_reduce's effective
    # bandwidth over its ICI+DCN level mix). None falls back to the
    # region-name default in _dma_gbps.
    gbps: Optional[float] = None
    link: Optional[str] = None

    @property
    def intensity(self) -> float:
        if self.bytes_accessed <= 0:
            return float("inf")
        return self.flops / self.bytes_accessed

    def to_dict(self) -> Dict[str, Any]:
        return {**dataclasses.asdict(self),
                "arithmetic_intensity": (
                    None if self.bytes_accessed <= 0
                    else round(self.intensity, 3))}


def _grad_cost(fn, *abstract_args,
               argnums: Optional[tuple] = None) -> Dict[str, float]:
    """Compile grad-of-sum of ``fn`` on abstract inputs; return XLA cost
    analysis (fwd+bwd flops / bytes — the shape a train step pays).
    ``argnums`` defaults to every non-integer argument."""
    from deepspeed_tpu.profiling.flops_profiler import profile_compiled

    def total(*a):
        out = fn(*a)
        if isinstance(out, tuple):
            out = out[0]
        return jnp.sum(out.astype(jnp.float32))

    if argnums is None:
        argnums = tuple(
            i for i, a in enumerate(abstract_args)
            if not all(jnp.issubdtype(jnp.dtype(s.dtype), jnp.integer)
                       for s in jax.tree.leaves(a)))
    g = jax.jit(jax.grad(total, argnums=argnums))
    return profile_compiled(g, *abstract_args)


def _abstract_params(cfg):
    """ShapeDtypeStruct tree of the full model params (no compute)."""
    from deepspeed_tpu.models.transformer import init_params

    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))


def _per_layer_shapes(stacked_layers):
    """Strip the leading stacked-layer dim: [L, ...] -> [...]."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
        stacked_layers)


def _tree_bytes(tree) -> int:
    return int(sum(
        int(jnp.prod(jnp.asarray(s.shape))) * jnp.dtype(s.dtype).itemsize
        for s in jax.tree.leaves(tree)))


def _head_fn(cfg):
    """Fused final-norm + unembed + CE tail (mirrors loss_fn's tiled and
    plain branches; the qwz fetch hooks are identity when unconfigured)."""
    from deepspeed_tpu.models.transformer import _norm
    from deepspeed_tpu.runtime.sharding import effective_dtype

    dt = effective_dtype(cfg.dtype)

    def head(hidden, head_params, labels):
        unembed = head_params["unembed"].astype(dt)
        if cfg.tiled_logits > 1:
            from deepspeed_tpu.parallel.tiled_compute import \
                tiled_logits_loss

            def fnorm_tile(h):
                return _norm(h, head_params["final_norm"], cfg.norm,
                             cfg.norm_eps)

            nll_sum, total = tiled_logits_loss(
                hidden, unembed, labels, None, cfg.tiled_logits,
                transpose_unembed=cfg.tie_embeddings,
                tile_transform=fnorm_tile)
            return nll_sum / jnp.maximum(total, 1.0)
        normed = _norm(hidden, head_params["final_norm"], cfg.norm,
                       cfg.norm_eps)
        eq = ("bsh,vh->bsv" if cfg.tie_embeddings else "bsh,hv->bsv")
        logits = jnp.einsum(eq, normed.astype(dt), unembed).astype(
            jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    return head


def attribute_step(cfg, micro_batch: int, seq: int, *,
                   fetch_gbps: Optional[float] = None,
                   optimizer: str = "adamw",
                   optimizer_on_host: Optional[bool] = None,
                   grad_bytes_per_param: int = 2) -> List[RegionCost]:
    """Measure/model the five region costs for one fwd+bwd+update step.

    ``cfg`` is a TransformerConfig; compute regions are compiled at
    [micro_batch, seq, hidden] activations and scaled by ``num_layers``.
    """
    from deepspeed_tpu.models.transformer import _layer, _layer_mlp
    from deepspeed_tpu.runtime.sharding import effective_dtype

    dt = effective_dtype(cfg.dtype)
    H, L = cfg.hidden_size, cfg.num_layers
    x = jax.ShapeDtypeStruct((micro_batch, seq, H), dt)
    pos = jax.ShapeDtypeStruct((micro_batch, seq), jnp.int32)
    labels = jax.ShapeDtypeStruct((micro_batch, seq), jnp.int32)

    params = _abstract_params(cfg)
    lp = _per_layer_shapes(params["layers"])

    layer_cost = _grad_cost(
        lambda lp_, x_, pos_: _layer(cfg, x_, lp_, pos_), lp, x, pos)
    mlp_cost = _grad_cost(
        lambda lp_, x_, attn_: _layer_mlp(cfg, x_, attn_, lp_),
        lp, x, x)

    unembed = (params["embed"]["tokens"] if cfg.tie_embeddings
               else params["unembed"]["kernel"])
    head_params = {"final_norm": params["final_norm"], "unembed": unembed}
    head_cost = _grad_cost(
        lambda h_, hp_, lab_: _head_fn(cfg)(h_, hp_, lab_),
        x, head_params, labels)

    regions = [
        RegionCost(
            "attn",
            max(0.0, (layer_cost["flops"] - mlp_cost["flops"])) * L,
            max(0.0, (layer_cost["bytes_accessed"]
                      - mlp_cost["bytes_accessed"])) * L,
            note="block minus MLP-half, x num_layers"),
        RegionCost(
            "mlp", mlp_cost["flops"] * L,
            mlp_cost["bytes_accessed"] * L,
            note=("fp8 GEMMs" if cfg.fp8_mlp else "bf16 GEMMs")
                 + ", x num_layers"),
        RegionCost(
            "vocab_head", head_cost["flops"],
            head_cost["bytes_accessed"],
            note=(f"tiled_logits={cfg.tiled_logits}"
                  if cfg.tiled_logits > 1 else "untiled logits")),
    ]

    # -- optimizer: analytic fused-Adam traffic -------------------------
    n_params = cfg.num_params()
    model_bytes = jnp.dtype(dt).itemsize
    if optimizer.lower() in ("adam", "adamw"):
        opt_reads = 12 + grad_bytes_per_param    # master+m+v + grad
        opt_writes = 12 + model_bytes            # master+m+v + cast
    else:                                        # sgd-class
        opt_reads = 4 + grad_bytes_per_param
        opt_writes = 4 + model_bytes
    on_host = (optimizer_on_host if optimizer_on_host is not None
               else bool(cfg.prefetch_stream))
    regions.append(RegionCost(
        "optimizer", float(n_params) * 4,        # ~4 flop/param update
        float(n_params) * (opt_reads + opt_writes),
        note=("host-RAM traffic (offload_optimizer)" if on_host
              else "HBM traffic, overlapped with backward"),
        overlapped=not on_host))

    # -- param_fetch: ZeRO-Infinity layer streaming ---------------------
    layer_bytes = _tree_bytes(lp)
    fetch = (fetch_gbps if fetch_gbps is not None
             else float(os.environ.get("DSTPU_FETCH_GBPS",
                                       _DEFAULT_FETCH_GBPS)))
    depth = cfg.prefetch_depth if cfg.prefetch_depth else 1
    regions.append(RegionCost(
        "param_fetch", 0.0,
        float(layer_bytes) * L * 2,              # fwd + bwd passes
        note=(f"host->device @ ~{fetch:g} GB/s, prefetch ring depth "
              f"{depth}" if cfg.prefetch_stream
              else "params resident (no streaming)"),
        overlapped=True))
    return regions


# ---------------------------------------------------------------------------
# Analytic long-context attribution (BENCH_LONGCTX tier)
# ---------------------------------------------------------------------------
# At ≥256k tokens the O(S²) attention cannot be compiled on the CPU sim
# (attribute_step's measured closures would run for hours), so this tier
# models the three long-context regions analytically, per chip, from the
# same closed forms the planner (parallel/auto_sp.py) reasons with. The
# formulas are stated inline; docs/roofline.md round 8 records a table.


def attribute_longctx_step(*, seq_len: int, hidden_size: int,
                           num_heads: int,
                           num_kv_heads: Optional[int] = None,
                           head_dim: Optional[int] = None,
                           num_layers: int = 1, batch_size: int = 1,
                           sp: int = 1, strategy: Optional[str] = None,
                           attn_chunks: int = 0,
                           fpdt_host_kv: bool = False,
                           dtype_bytes: int = 2) -> List[RegionCost]:
    """Per-chip analytic costs for the long-context regions of one
    fwd+bwd step: **attn** (compute), **sp_comm** (ICI collectives for
    the chosen sp strategy), **host_kv_stream** (FPDT host-KV D2H/H2D
    when spilling). Regions with zero cost at this plan are still
    emitted (zero rows) so the bench table shape is stable.

    - attn flops: causal QKᵀ+PV is 4·B·S²·H halved by causality, ×3 for
      fwd+bwd, ÷sp (each rank owns S/sp query rows): 6·B·S²·H/sp.
    - sp_comm bytes (×2 fwd+bwd, per layer):
      ulysses — 4 all-to-alls (q, out at num_heads width; k, v at
      kv_heads width), each moving (sp-1)/sp of its tensor;
      ring — KV blocks traverse sp-1 hops: 2·B·S·kv·D·(sp-1)/sp;
      fpdt-composed (attn_chunks>1 under sp) — KV all-gather fwd +
      reduce-scatter bwd, same (sp-1)/sp fraction of the full KV.
    - host_kv_stream bytes: full KV stacks D2H once, then H2D refetch
      averaged over the causal chunk schedule ((chunks+1)/2 of the
      stacks per pass), ×2 for the backward re-stream.
    """
    kv = num_kv_heads or num_heads
    D = head_dim or hidden_size // num_heads
    H = hidden_size
    B, S, L = batch_size, seq_len, num_layers
    p = max(int(sp), 1)
    db = dtype_bytes

    attn_flops = 6.0 * B * float(S) * S * H / p * L
    # score-free streaming traffic: q + out + per-chunk KV rereads
    kv_bytes = 2.0 * B * S * kv * D * db          # full K+V stacks
    chunks = max(int(attn_chunks), 1)
    attn_bytes = (2.0 * B * (S / p) * num_heads * D * db
                  + chunks * kv_bytes / p) * L

    if p > 1:
        frac = (p - 1) / p
        if strategy == "ulysses" and chunks <= 1:
            per_layer = (2.0 * B * S * num_heads * D
                         + 2.0 * B * S * kv * D) * db * frac
            note = "ulysses: 4 all-to-alls/layer (q,out + k,v @ GQA width)"
        elif strategy == "ring" and chunks <= 1:
            per_layer = kv_bytes * frac
            note = f"ring: {p - 1} ppermute KV hops/layer"
        else:
            per_layer = kv_bytes * frac
            note = ("fpdt+sp: KV all-gather fwd / reduce-scatter bwd "
                    "per layer")
        sp_bytes = per_layer * 2 * L              # fwd + bwd
    else:
        sp_bytes, note = 0.0, "sp=1: no sequence-parallel collectives"
    regions = [
        RegionCost("attn", attn_flops, attn_bytes,
                   note=f"causal, per chip (S/sp={S // p} query rows), "
                        "x num_layers"),
        RegionCost("sp_comm", 0.0, sp_bytes, note=note, overlapped=True),
    ]

    if fpdt_host_kv:
        hk_bytes = kv_bytes * (1.0 + (chunks + 1) / 2.0) * 2 * L
        hk_note = (f"D2H once + causal-avg H2D over {chunks} chunks, "
                   "x2 bwd, x num_layers")
    else:
        hk_bytes, hk_note = 0.0, "KV resident on device (no spill)"
    regions.append(RegionCost("host_kv_stream", 0.0, hk_bytes,
                              note=hk_note, overlapped=True))
    return regions


# ---------------------------------------------------------------------------
# Quantized-comm attribution (ZeRO++ trio: qwZ / qgZ / hpZ)
# ---------------------------------------------------------------------------
# The before/after table ROADMAP item 1 asks for: what do the quantized
# wire formats do to the two collective regions on a pod projection?
# Wire bytes come from the same closed form observability/quant_stats.py
# measures (int payload + one fp32 scale per block); links come from the
# mesh factorization hpZ controls. Analytic on purpose — it runs on CPU
# CI and extrapolates to chip counts the rig doesn't have, exactly like
# attribute_longctx_step.

def _wire_ratio(bits: int, block: int, full_bytes: float) -> float:
    """(int payload + fp32 scale per block) / full-precision bytes."""
    return (bits / 8.0 + 4.0 / block) / full_bytes


def attribute_quant_step(cfg, *, qwz: bool = False, qgz: bool = False,
                         qar: bool = False, hpz: int = 1,
                         n_chips: int = 16, slice_size: int = 8,
                         ici_gbps: Optional[float] = None,
                         dcn_gbps: Optional[float] = None
                         ) -> List[RegionCost]:
    """Per-chip analytic costs of the two quantized-collective regions
    for one fwd+bwd step of ``cfg`` on ``n_chips`` arranged in slices of
    ``slice_size`` (intra-slice ICI, inter-slice DCN):

    - **param_fetch** — the stage-3 per-layer param all-gather: each
      chip receives (g-1)/g of every layer's params, fwd + bwd, where
      g is the gather group (hpZ partition k when set, else all
      chips). qwZ turns the bf16 wire into int8 payload + one fp32
      scale per QWZ_BLOCK ((1+4/128)/2 ≈ 0.52×); hpZ keeps the group
      intra-slice so the bytes ride ICI instead of DCN.
    - **grad_reduce** — the qgZ reduction: level 1 moves every
      gradient element once over the fsdp group ((g1-1)/g1 of the fp32
      wire); when hpZ splits the mesh a second level reduces partial
      sums over the dp axis across slices. qgZ quantizes level 1 to
      int8 and the inter-slice level to int4, each + fp32 scales per
      QGZ_BLOCK. ``qar`` replaces the reduce entirely with the
      EQuARX-style quantized all-reduce: an int8 reduce-scatter plus an
      int8 all-gather over the full dp axis, each hop moving (N-1)/N of
      the gradient wire + fp32 scales per QUANT_BLOCK (qar and qgZ are
      mutually exclusive, mirroring ZeroConfig.validate).

    Each region's ``gbps``/``link`` pin the byte-weighted effective
    bandwidth of its level mix, so the roofline ms reflects the link
    flip, not just the byte shrink."""
    from deepspeed_tpu.runtime.qgz import QGZ_BLOCK
    from deepspeed_tpu.runtime.sharding import QWZ_BLOCK

    ici = (ici_gbps if ici_gbps is not None
           else float(os.environ.get("DSTPU_ICI_GBPS", _DEFAULT_ICI_GBPS)))
    dcn = (dcn_gbps if dcn_gbps is not None
           else float(os.environ.get("DSTPU_DCN_GBPS", _DEFAULT_DCN_GBPS)))
    N = max(int(n_chips), 1)
    S = max(min(int(slice_size), N), 1)
    k = max(int(hpz), 1)
    L = cfg.num_layers

    params = _abstract_params(cfg)
    lp = _per_layer_shapes(params["layers"])
    layer_elems = sum(int(jnp.prod(jnp.asarray(s.shape)))
                      for s in jax.tree.leaves(lp))
    n_params = cfg.num_params()

    # -- param_fetch: per-layer all-gather, fwd + bwd -------------------
    g = k if k > 1 else N
    frac = (g - 1) / g if g > 1 else 0.0
    fetch_full = 2.0 * layer_elems * frac * L * 2     # bf16 wire
    w_ratio = _wire_ratio(8, QWZ_BLOCK, 2.0) if qwz else 1.0
    fetch_bytes = fetch_full * w_ratio
    fetch_link = "ici" if (k > 1 and k <= S) or N <= S else "dcn"
    fetch_gbps_eff = ici if fetch_link == "ici" else dcn
    fetch_note = (
        ("int8+scales all-gather" if qwz else "bf16 all-gather")
        + f" over g={g} ({fetch_link.upper()})"
        + (f", hpZ k={k} keeps it intra-slice" if k > 1 else ""))

    if qar and qgz:
        raise ValueError("qar and qgz are mutually exclusive (both own "
                         "the gradient wire)")

    # -- grad_reduce: qgZ level structure -------------------------------
    g1 = k if k > 1 else N
    dp = N // g1 if k > 1 else 1
    l1_link = "ici" if g1 <= S else "dcn"
    l1_frac = (g1 - 1) / g1 if g1 > 1 else 0.0
    l1_ratio = _wire_ratio(8, QGZ_BLOCK, 4.0) if qgz else 1.0
    l1_bytes = 4.0 * n_params * l1_frac * l1_ratio
    l2_frac = (dp - 1) / dp if dp > 1 else 0.0
    l2_ratio = _wire_ratio(4, QGZ_BLOCK, 4.0) if qgz else 1.0
    l2_bytes = 4.0 * n_params * l2_frac * l2_ratio
    l1_ms = l1_bytes / ((ici if l1_link == "ici" else dcn) * 1e9) * 1e3
    l2_ms = l2_bytes / (dcn * 1e9) * 1e3
    red_bytes = l1_bytes + l2_bytes
    red_ms = l1_ms + l2_ms
    red_gbps = (red_bytes / (red_ms * 1e6)) if red_ms > 0 else ici
    red_link = (l1_link if dp <= 1
                else f"{l1_link}+dcn")
    red_note = (
        (f"int8 level1 over fsdp={g1} ({l1_link.upper()})" if qgz
         else f"fp32 reduce over fsdp={g1} ({l1_link.upper()})")
        + ((f" + {'int4' if qgz else 'fp32'} level2 over dp={dp} (DCN)")
           if dp > 1 else ""))

    if qar:
        # qar overrides the level structure: one flat int8 all-reduce
        # (reduce-scatter + all-gather) over the full dp axis; fp32
        # scales per QUANT_BLOCK on both hops
        from deepspeed_tpu.runtime.zeropp import QUANT_BLOCK
        ar_frac = (N - 1) / N if N > 1 else 0.0
        ar_ratio = _wire_ratio(8, QUANT_BLOCK, 4.0)
        red_link = "ici" if N <= S else "dcn"
        ar_gbps = ici if red_link == "ici" else dcn
        red_bytes = 2.0 * 4.0 * n_params * ar_frac * ar_ratio
        red_ms = red_bytes / (ar_gbps * 1e9) * 1e3
        red_gbps = ar_gbps
        red_note = (f"qar: int8 reduce-scatter + int8 all-gather over "
                    f"dp={N} ({red_link.upper()})")

    return [
        RegionCost("param_fetch", 0.0, fetch_bytes, note=fetch_note,
                   overlapped=True, gbps=fetch_gbps_eff,
                   link=fetch_link),
        RegionCost("grad_reduce", 0.0, red_bytes, note=red_note,
                   overlapped=False, gbps=red_gbps, link=red_link),
    ]


# ---------------------------------------------------------------------------
# Exposed-vs-hidden split (ISSUE 6 overlap engine)
# ---------------------------------------------------------------------------
# The overlap engine (runtime/param_stream.py pin_stage) stages each
# layer's transfers against that layer's compute: with overlap_depth=k,
# the transfer of one stage can hide behind up to k stages of compute
# before the consumer needs it. The split below is the analytic form of
# that schedule — per-stage transfer time clipped by the k-stage compute
# window — calibrated by the measured probe (tools/
# latency_hiding_probe.py): at k=0 XLA's default schedule hid none of
# the host-link traffic on v5e-1, so k=0 reports fully exposed.


def overlap_split_ms(transfer_ms: float, stage_ms: float,
                     overlap_depth: int, stages: int) -> Dict[str, float]:
    """Split a transfer's roofline time into hidden vs exposed ms under
    the staged overlap schedule.

    ``transfer_ms`` total transfer time for the step; ``stage_ms`` the
    compute time of ONE scheduling stage (a layer's fwd or bwd);
    ``stages`` how many stages the transfer is spread across (2 x layers
    for a per-layer stream); ``overlap_depth`` k = how many stages of
    compute each stage's transfer may hide behind. k=0 -> fully exposed
    (the measured no-overlap default schedule)."""
    total = max(float(transfer_ms), 0.0)
    n = max(int(stages), 1)
    k = max(int(overlap_depth), 0)
    per_stage = total / n
    hidden_per = min(per_stage, k * max(float(stage_ms), 0.0))
    hidden = hidden_per * n
    exposed = total - hidden
    return {"total_ms": total, "hidden_ms": hidden, "exposed_ms": exposed,
            "hidden_frac": 0.0 if total <= 0 else hidden / total}


def split_exposed_hidden(regions: List[RegionCost], *,
                         peak_tflops: float, hbm_gbps: float,
                         fetch_gbps: Optional[float] = None,
                         overlap_depth: int = 0,
                         num_layers: int = 1) -> List[Dict[str, Any]]:
    """Per-region exposed/hidden attribution: compute regions are fully
    exposed (they ARE the step); transfer regions (``DMA_REGIONS`` —
    param_fetch, sp_comm, host_kv_stream) split by
    :func:`overlap_split_ms` against the per-layer compute window."""
    ms: Dict[str, float] = {}
    for r in regions:
        if r.region in DMA_REGIONS:
            bw = r.gbps or _dma_gbps(r.region, fetch_gbps)
            ms[r.region] = r.bytes_accessed / (bw * 1e9) * 1e3
        else:
            compute_ms = r.flops / (peak_tflops * 1e12) * 1e3
            mem_ms = r.bytes_accessed / (hbm_gbps * 1e9) * 1e3
            ms[r.region] = max(compute_ms, mem_ms)
    stages = 2 * max(int(num_layers), 1)  # fwd + bwd stage per layer
    stage_ms = (ms.get("attn", 0.0) + ms.get("mlp", 0.0)) / stages
    out = []
    for r in regions:
        if r.region in DMA_REGIONS:
            split = overlap_split_ms(ms[r.region], stage_ms,
                                     overlap_depth, stages)
            out.append({"region": r.region, "kind": "dma",
                        "bytes": r.bytes_accessed, **split})
        else:
            total = ms[r.region]
            out.append({"region": r.region, "kind": "compute",
                        "bytes": r.bytes_accessed, "total_ms": total,
                        "hidden_ms": 0.0, "exposed_ms": total,
                        "hidden_frac": 0.0})
    return out


def attribution_markdown(regions: List[RegionCost], peak_tflops: float,
                         hbm_gbps: float,
                         fetch_gbps: Optional[float] = None,
                         title: str = "Per-region roofline attribution",
                         overlap_depth: Optional[int] = None,
                         num_layers: int = 1) -> str:
    """Render the region table docs/roofline.md embeds. Passing
    ``overlap_depth`` adds exposed/hidden ms columns from
    :func:`split_exposed_hidden` (same rows, wider table)."""
    fetch = fetch_gbps
    with_split = overlap_depth is not None
    split_by: Dict[str, Dict[str, Any]] = {}
    if with_split:
        split_by = {s["region"]: s for s in split_exposed_hidden(
            regions, peak_tflops=peak_tflops, hbm_gbps=hbm_gbps,
            fetch_gbps=fetch, overlap_depth=int(overlap_depth),
            num_layers=num_layers)}
    extra_hdr = " exposed ms | hidden ms |" if with_split else ""
    extra_sep = "---|---|" if with_split else ""
    lines = [f"### {title}", "",
             "| region | GFLOPs | GB moved | F/B | bound | "
             f"roofline ms |{extra_hdr} notes |",
             f"|---|---|---|---|---|---|{extra_sep}---|"]
    for r in regions:
        if r.region in DMA_REGIONS:
            bw = r.gbps or _dma_gbps(r.region, fetch)
            ms = r.bytes_accessed / (bw * 1e9) * 1e3
            bound = r.link or ("ici" if r.region == "sp_comm"
                               else "host-link")
        else:
            summ = roofline_summary(
                {"flops": r.flops, "bytes_accessed": r.bytes_accessed},
                peak_tflops, hbm_gbps)
            bound = summ["bound"]
            compute_ms = r.flops / (peak_tflops * 1e12) * 1e3
            mem_ms = r.bytes_accessed / (hbm_gbps * 1e9) * 1e3
            ms = max(compute_ms, mem_ms)
        inten = ("—" if r.bytes_accessed <= 0 or r.flops <= 0
                 else f"{r.flops / r.bytes_accessed:.1f}")
        note = r.note + (" (overlapped)" if r.overlapped else "")
        extra = ""
        if with_split:
            s = split_by[r.region]
            extra = (f" {s['exposed_ms']:,.2f} | "
                     f"{s['hidden_ms']:,.2f} |")
        lines.append(
            f"| {r.region} | {r.flops / 1e9:,.1f} | "
            f"{r.bytes_accessed / 1e9:,.2f} | {inten} | {bound} | "
            f"{ms:,.2f} |{extra} {note} |")
    lines.append("")
    lines.append(
        "Roofline ms = max(flops/peak, bytes/HBM-bw) per region in "
        "isolation; overlapped rows stream behind compute and bound "
        "throughput only if their bandwidth floor is missed."
        + ((" Exposed/hidden split: overlap_depth="
            f"{int(overlap_depth)} staged schedule "
            "(observability/attribution.py overlap_split_ms).")
           if with_split else ""))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CLI: python -m deepspeed_tpu.observability.attribution --layers 8 \
#          --vocab 131072 --out docs/roofline.md  (appends the table)
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="dstpu-attribution",
        description="compile per-region closures at a given shape and "
                    "print the roofline attribution table")
    ap.add_argument("--model", default="llama3-8b")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=131072)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--tiled-logits", type=int, default=None)
    ap.add_argument("--overlap-depth", type=int, default=None,
                    help="add exposed/hidden ms columns for the overlap "
                         "engine at this stage depth (0 = unstaged)")
    ap.add_argument("--peak-tflops", type=float, default=None)
    ap.add_argument("--hbm-gbps", type=float, default=None)
    ap.add_argument("--json", action="store_true",
                    help="emit the raw region dicts instead of markdown")
    args = ap.parse_args(argv)

    import dataclasses as _dc

    from deepspeed_tpu.models.zoo import get_model
    from deepspeed_tpu.observability.roofline import (detect_hbm_gbps,
                                                      detect_peak_tflops)

    model = get_model(args.model, max_seq_len=args.seq)
    updates = {"num_layers": args.layers, "vocab_size": args.vocab}
    if args.tiled_logits is not None:
        updates["tiled_logits"] = args.tiled_logits
    cfg = _dc.replace(model.config, **updates)

    dev = jax.devices()[0]
    peak = args.peak_tflops or detect_peak_tflops(dev)
    hbm = args.hbm_gbps or detect_hbm_gbps(dev)
    regions = attribute_step(cfg, args.micro, args.seq)
    if args.json:
        payload = [r.to_dict() for r in regions]
        if args.overlap_depth is not None:
            payload = {"regions": payload,
                       "overlap_depth": args.overlap_depth,
                       "split": split_exposed_hidden(
                           regions, peak_tflops=peak, hbm_gbps=hbm,
                           overlap_depth=args.overlap_depth,
                           num_layers=cfg.num_layers)}
        print(json.dumps(payload, indent=2))
    else:
        shape = (f"{args.model} {args.layers}L vocab {args.vocab:,} "
                 f"seq {args.seq} micro {args.micro}")
        print(attribution_markdown(
            regions, peak, hbm,
            title=f"Per-region roofline attribution — {shape}",
            overlap_depth=args.overlap_depth,
            num_layers=cfg.num_layers))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Decoder-only transformer LM, TPU-first.

This is the framework's model substrate — the role the user's ``nn.Module``
plays in the reference (and what its model zoo under
``inference/v2/model_implementations`` + ``module_inject/containers``
covers). One generic implementation expresses the GPT-2 / Llama / Mistral /
Qwen families via config switches (positional encoding, norm, activation,
GQA, tied embeddings); MoE variants live in models/moe_transformer.py.

TPU-first design choices:
  * functional: ``init(rng) -> params`` pytree, ``apply(params, tokens) ->
    logits``; no module objects at runtime, everything jit-traceable;
  * every param leaf has a tuple of logical axis names (see
    runtime/sharding.py) — this single annotation drives ZeRO-3 / TP / PP
    sharding instead of the reference's AutoTP layer surgery
    (module_inject/auto_tp.py:194);
  * layers are **stacked and scanned** (``lax.scan`` over a [L, ...] params
    tree): one compiled layer body regardless of depth — XLA compile time
    stays flat at 70B scale, and remat policy applies per scan step
    (reference analog: activation checkpointing
    runtime/activation_checkpointing/checkpointing.py:948);
  * bf16 compute, fp32 logits for the softmax-xent;
  * attention goes through ops/attention.py (Pallas flash kernel on TPU,
    XLA fallback elsewhere) and parallel/ulysses.py when sp > 1.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.runtime.sharding import (constrain_activation,
                                            vocab_parallel_lookup)


class LoopedStackUnsupported(NotImplementedError):
    """A path that runs each layer once a token was asked to run a looped
    stack (``TransformerConfig.ut_steps`` > 1) or one with post-branch
    norms: it refuses rather than run another model."""


class EarlyExitUnsupported(ValueError):
    """``early_exit_threshold`` below 1: a token would leave the loop before
    the last pass, which no path here does."""


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Architecture switches covering the GPT-2/Llama families."""

    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # None = MHA; < num_heads = GQA
    ffn_size: Optional[int] = None  # None = 4*hidden (gelu) or 8/3*hidden (swiglu)
    max_seq_len: int = 1024
    pos_emb: str = "learned"  # learned | rope | none
    norm: str = "layernorm"  # layernorm | rmsnorm
    activation: str = "gelu"  # gelu (exact erf) | gelu_tanh | swiglu | relu
    tie_embeddings: bool = True
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # projection biases (GPT-2/OPT-family checkpoints; Llama-family has
    # none). Zoo presets stay bias-free; the HF loader enables this when
    # the source layout carries biases.
    use_biases: bool = False
    # ZeRO-Infinity param offload: layer params live in pinned host
    # memory; the scan fetches one layer per step (and the remat replay
    # re-fetches it for backward) so HBM never holds the full stack.
    # Set by the engine from zero_optimization.offload_param.
    param_host_offload: bool = False
    # None defers to the engine's activation_checkpointing.policy config;
    # an explicit name here wins over the config
    remat_policy: Optional[str] = None
    attn_impl: str = "auto"  # auto | xla | flash
    sequence_parallel: bool = False  # SP attention over the sp mesh axis
    sp_mode: str = "ulysses"  # ulysses (all-to-all) | ring (ppermute CP)
    # ALST-style tiled compute (reference ulysses_sp.py TiledMLP /
    # TiledFusedLogitsLoss): number of sequence tiles, 0/1 = off
    tiled_logits: int = 0
    tiled_mlp: int = 0
    # FPDT-style chunked attention (reference fpdt_layer.py): number of
    # query chunks scanned sequentially, 0/1 = off
    attn_chunks: int = 0
    # FPDT host-KV streaming (reference _FPDTGPUOffloadingAttentionImpl_
    # fpdt_layer.py:545): K/V tiles live in pinned host memory and stream
    # to the chip per chunk — beyond-HBM sequence lengths on one chip.
    # Uses attn_chunks (min 2) as the chunk count.
    fpdt_host_kv: bool = False
    # FPDT residual-stream offload (VERDICT r4 #5; reference
    # SequenceChunk fpdt_layer.py:497 applied to the residual): the
    # [B, S, H] residual itself lives as a host chunk stack between
    # layers; embedding, every layer chunk, and the fused
    # final-norm+logits+loss all fetch/emit host chunks, so the device
    # never holds ANY full-S buffer. Requires fpdt_host_kv and the fused
    # sequential block; loss must go through TransformerLM.loss (the
    # full-logits apply() assembles on device only for small-S tests).
    fpdt_host_residual: bool = False
    # Falcon-style parallel residual: x + attn(ln1(x)) + mlp(ln2(x)),
    # both branches reading the pre-attention residual
    parallel_block: bool = False
    # offload_param streamed-stack A/B knobs. None resolves from
    # DSTPU_PREFETCH / DSTPU_SERIALIZE_FETCH at *config construction* so
    # the choice participates in the jit trace-cache key — flipping the
    # env after the first compile changes the next config built, never a
    # stale cached executable.
    prefetch_stream: Optional[bool] = None
    serialize_fetch: Optional[bool] = None
    # streamer tuning (same env-at-construction contract):
    # DSTPU_PREFETCH_DEPTH layers in flight ahead of compute;
    # DSTPU_GRADS_TO_HOST streams per-layer grad cotangents to host
    # inside the backward scan (see runtime/param_stream.py)
    prefetch_depth: Optional[int] = None
    grads_to_host: Optional[bool] = None
    # per-layer overlap engine depth (runtime/param_stream.py
    # pin_stage): the K newest in-flight transfers — h2d layer fetches
    # on the offload path, fsdp all-gathers on the stage-3 resident
    # path, plus the backward grad streams — are barrier-pinned into
    # the issuing layer's scheduling stage. 0 disables (today's
    # program, bit-for-bit). Same env-at-construction contract:
    # DSTPU_OVERLAP_DEPTH; the engine bridges
    # config.performance.overlap_depth onto it.
    overlap_depth: Optional[int] = None
    # fp8 MLP matmuls (ops/fp_quantizer.py fp8_matmul_ste): e4m3
    # operands into an fp32-accumulating matmul with straight-through
    # gradients. Opt-in — off keeps exact bf16/fp32 parity. Set by the
    # engine from config.performance.fp8_mlp.
    fp8_mlp: bool = False
    # a looped ("universal") stack: the SAME ``num_layers`` layers run
    # ``ut_steps`` times a token, the model's final norm after every pass
    # (its output enters the next), and pass t of layer l keeps keys and
    # values of its own (K/V slot ``t * num_layers + l``). An exit gate
    # (``exit_gate``: Linear(hidden -> 1) with a bias) reads each pass's
    # normed output; the no-cache forward returns the distribution over
    # passes it implies (:func:`apply_with_exit`). At the threshold 1 no
    # cumulative probability reaches it before the last pass, so every
    # pass runs and the last one's output is the model's; a threshold
    # below 1 is refused (:class:`EarlyExitUnsupported`).
    ut_steps: int = 1
    early_exit_threshold: float = 1.0
    # a norm after each branch too, before its residual add (``ln1_post``,
    # ``ln2_post``; ``models/hybrid.py`` has the same switch)
    post_norms: bool = False

    def __post_init__(self):
        import os as _os
        if self.prefetch_stream is None:
            object.__setattr__(self, "prefetch_stream", bool(int(
                _os.environ.get("DSTPU_PREFETCH", "1"))))
        if self.serialize_fetch is None:
            object.__setattr__(self, "serialize_fetch", bool(int(
                _os.environ.get("DSTPU_SERIALIZE_FETCH", "0"))))
        if self.prefetch_depth is None:
            object.__setattr__(self, "prefetch_depth", int(
                _os.environ.get("DSTPU_PREFETCH_DEPTH", "2")))
        if self.grads_to_host is None:
            object.__setattr__(self, "grads_to_host", bool(int(
                _os.environ.get("DSTPU_GRADS_TO_HOST", "1"))))
        if self.overlap_depth is None:
            object.__setattr__(self, "overlap_depth", int(
                _os.environ.get("DSTPU_OVERLAP_DEPTH", "0")))
        if self.sp_mode not in ("ulysses", "ring"):
            raise ValueError(
                f"sp_mode must be ulysses|ring, got {self.sp_mode!r}")
        # fpdt_host_kv + sequence_parallel compose: the layer runs
        # inside shard_map over sp, each rank streaming the
        # sp-all-gathered host KV stacks through its local q chunks
        # (parallel/fpdt.py sp_axis mode) — the former hard error here
        # is lifted (ROADMAP item 4 planner composition).
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps must be >= 1, got {self.ut_steps}")
        if self.early_exit_threshold < 1.0:
            raise EarlyExitUnsupported(
                f"early_exit_threshold {self.early_exit_threshold} < 1: "
                "leaving the loop before the last pass is not implemented "
                "(a token that exits has no keys and values in the later "
                "passes' slots); every pass runs, so only the threshold 1 "
                "is served")
        if (self.ut_steps > 1 or self.post_norms) and (
                self.fpdt_host_kv or self.parallel_block):
            raise LoopedStackUnsupported(
                "a looped stack (ut_steps > 1) or post_norms runs the plain "
                "sequential block only: not fpdt_host_kv, not parallel_block")
        if self.fpdt_host_residual:
            if not self.fpdt_host_kv:
                raise ValueError(
                    "fpdt_host_residual requires fpdt_host_kv (the "
                    "residual stack rides the same chunk grid as the "
                    "KV tiles)")
            if self.parallel_block:
                raise ValueError(
                    "fpdt_host_residual requires the fused sequential "
                    "block (attention+MLP per chunk); parallel_block "
                    "is not chunk-fusable this way")
            if self.sequence_parallel:
                raise ValueError(
                    "fpdt_host_residual does not compose with "
                    "sequence_parallel: the residual lives as a host "
                    "chunk stack, which cannot also be sharded over sp")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def ffn(self) -> int:
        if self.ffn_size:
            return self.ffn_size
        if self.activation == "swiglu":
            # Llama convention: 2/3 * 4h rounded to multiple of 256
            d = int(8 * self.hidden_size / 3)
            return 256 * ((d + 255) // 256)
        return 4 * self.hidden_size

    def flops_per_token(self) -> float:
        """Approximate training FLOPs/token (fwd+bwd ≈ 6·N params + attn)."""
        n = self.num_params()
        attn = 12 * self.num_layers * self.hidden_size * self.max_seq_len
        if self.ut_steps > 1:    # the layers' weights multiply every pass
            n += (self.ut_steps - 1) * self.num_layers * self._layer_params()
        return 6 * n + self.ut_steps * attn

    def _layer_params(self) -> int:
        h, f = self.hidden_size, self.ffn
        hd, nh, nkv = self.head_dim, self.num_heads, self.kv_heads
        attn = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
        mlp = (3 if self.activation == "swiglu" else 2) * h * f
        norm_width = 2 * h if self.norm == "layernorm" else h  # scale(+bias)
        return attn + mlp + (4 if self.post_norms else 2) * norm_width

    def num_params(self) -> int:
        h, L, v = self.hidden_size, self.num_layers, self.vocab_size
        norm_width = 2 * h if self.norm == "layernorm" else h  # scale(+bias)
        emb = v * h + (0 if self.tie_embeddings else v * h)
        pos = self.max_seq_len * h if self.pos_emb == "learned" else 0
        gate = h + 1 if self.ut_steps > 1 else 0
        return L * self._layer_params() + emb + pos + norm_width + gate


# ---------------------------------------------------------------------------
# parameter init + logical axes
# ---------------------------------------------------------------------------


def act_fn(name: str):
    """Activation by config name. "gelu" is the exact erf form (HF
    Falcon/BERT-class 'gelu'); "gelu_tanh"/"gelu_new" is the tanh
    approximation (GPT-2). The two differ by up to ~4e-4 per activation
    — enough to flip greedy tokens over a deep stack."""
    if name == "relu":
        return jax.nn.relu
    if name in ("gelu_tanh", "gelu_new"):
        return partial(jax.nn.gelu, approximate=True)
    if name == "gelu":
        return partial(jax.nn.gelu, approximate=False)
    raise ValueError(f"unknown activation {name!r}")


def _dense_init(rng, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return jax.random.normal(rng, shape, dtype) * scale


def init_params(cfg: TransformerConfig, rng: jax.Array) -> Dict[str, Any]:
    """Build the full parameter pytree (layer weights stacked on dim 0)."""
    h, L, f = cfg.hidden_size, cfg.num_layers, cfg.ffn
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    keys = jax.random.split(rng, 12)
    pd = cfg.param_dtype

    def stack(fn, key):
        return jax.vmap(fn)(jax.random.split(key, L))

    params: Dict[str, Any] = {
        "embed": {"tokens": _dense_init(keys[0], (cfg.vocab_size, h), 0.02, pd)},
        "layers": {
            "attn": {
                "wq": stack(lambda k: _dense_init(k, (h, nh, hd), dtype=pd), keys[1]),
                "wk": stack(lambda k: _dense_init(k, (h, nkv, hd), dtype=pd), keys[2]),
                "wv": stack(lambda k: _dense_init(k, (h, nkv, hd), dtype=pd), keys[3]),
                "wo": stack(
                    lambda k: _dense_init(k, (nh, hd, h), 1.0 / math.sqrt(nh * hd), pd),
                    keys[4],
                ),
                **({"bq": jnp.zeros((L, nh, hd), pd),
                    "bk": jnp.zeros((L, nkv, hd), pd),
                    "bv": jnp.zeros((L, nkv, hd), pd),
                    "bo": jnp.zeros((L, h), pd)} if cfg.use_biases else {}),
            },
            "mlp": _init_mlp(cfg, keys[5], L),
            "ln1": {"scale": jnp.ones((L, h), pd)},
            "ln2": {"scale": jnp.ones((L, h), pd)},
        },
        "final_norm": {"scale": jnp.ones((h,), pd)},
    }
    norms = ("ln1", "ln2")
    if cfg.post_norms:
        norms += ("ln1_post", "ln2_post")
        for name in norms[2:]:
            params["layers"][name] = {"scale": jnp.ones((L, h), pd)}
    if cfg.norm == "layernorm":
        for name in norms:
            params["layers"][name]["bias"] = jnp.zeros((L, h), pd)
        params["final_norm"]["bias"] = jnp.zeros((h,), pd)
    if cfg.ut_steps > 1:
        params["exit_gate"] = {"kernel": _dense_init(keys[8], (h, 1), dtype=pd),
                               "bias": jnp.zeros((1,), pd)}
    if cfg.pos_emb == "learned":
        params["embed"]["positions"] = _dense_init(
            keys[6], (cfg.max_seq_len, h), 0.01, pd
        )
    if not cfg.tie_embeddings:
        params["unembed"] = {"kernel": _dense_init(keys[7], (h, cfg.vocab_size), 0.02, pd)}
    return params


def _init_mlp(cfg, key, L):
    h, f = cfg.hidden_size, cfg.ffn
    ks = jax.random.split(key, 3)
    pd = cfg.param_dtype

    def stack(fn, k):
        return jax.vmap(fn)(jax.random.split(k, L))

    mlp = {
        "wi": stack(lambda k: _dense_init(k, (h, f), dtype=pd), ks[0]),
        "wo": stack(lambda k: _dense_init(k, (f, h), dtype=pd), ks[1]),
    }
    if cfg.activation == "swiglu":
        mlp["wg"] = stack(lambda k: _dense_init(k, (h, f), dtype=pd), ks[2])
    if cfg.use_biases:
        mlp["bi"] = jnp.zeros((L, f), pd)
        mlp["bo"] = jnp.zeros((L, h), pd)
    return mlp


def logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Logical axis names per param leaf (drives all sharding; see
    runtime/sharding.py rule tables)."""
    axes: Dict[str, Any] = {
        "embed": {"tokens": ("vocab", "embed")},
        "layers": {
            "attn": {
                "wq": ("layers", "embed", "heads", "head_dim"),
                "wk": ("layers", "embed", "kv_heads", "head_dim"),
                "wv": ("layers", "embed", "kv_heads", "head_dim"),
                "wo": ("layers", "heads", "head_dim", "embed"),
            },
            "mlp": {
                "wi": ("layers", "embed", "mlp"),
                "wo": ("layers", "mlp", "embed"),
            },
            "ln1": {"scale": ("layers", "embed")},
            "ln2": {"scale": ("layers", "embed")},
        },
        "final_norm": {"scale": ("embed",)},
    }
    norms = ("ln1", "ln2")
    if cfg.post_norms:
        norms += ("ln1_post", "ln2_post")
        for name in norms[2:]:
            axes["layers"][name] = {"scale": ("layers", "embed")}
    if cfg.norm == "layernorm":
        for name in norms:
            axes["layers"][name]["bias"] = ("layers", "embed")
        axes["final_norm"]["bias"] = ("embed",)
    if cfg.ut_steps > 1:
        axes["exit_gate"] = {"kernel": ("embed", None), "bias": (None,)}
    if cfg.use_biases:
        axes["layers"]["attn"]["bq"] = ("layers", "heads", "head_dim")
        axes["layers"]["attn"]["bk"] = ("layers", "kv_heads", "head_dim")
        axes["layers"]["attn"]["bv"] = ("layers", "kv_heads", "head_dim")
        axes["layers"]["attn"]["bo"] = ("layers", "embed")
        axes["layers"]["mlp"]["bi"] = ("layers", "mlp")
        axes["layers"]["mlp"]["bo"] = ("layers", "embed")
    if cfg.pos_emb == "learned":
        axes["embed"]["positions"] = ("seq", "embed")
    if cfg.activation == "swiglu":
        axes["layers"]["mlp"]["wg"] = ("layers", "embed", "mlp")
    if not cfg.tie_embeddings:
        axes["unembed"] = {"kernel": ("embed", "vocab")}
    return axes


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _norm(x, p, kind: str, eps: float):
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        rms = jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        out = x32 / rms * p["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        out = (x32 - mean) / jnp.sqrt(var + eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``m = 0.1 mscale ln(factor) + 1`` (1 at
    ``factor`` <= 1). A model that scales its rotary frequencies this way
    multiplies its softmax scale by ``m**2`` (``mscale`` its
    ``mscale_all_dim``)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's inverse frequencies over ``dim`` rotary dimensions [dim / 2]
    float32: pair ``i`` keeps ``theta^(-2i/dim)`` below the correction
    dimension of ``beta_fast`` rotations over ``original_max`` positions, is
    interpolated (divided by ``factor``) above that of ``beta_slow``, and
    blends linearly between the two."""
    def correction_dim(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def _rope(x, positions, theta: float, inv_freq=None):
    """Rotary embedding on [..., seq, heads, head_dim] (rotate-half pairs);
    ``inv_freq [head_dim / 2]`` replaces ``theta``'s frequencies (YaRN)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = inv_freq if inv_freq is not None else \
        1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B?, S, half]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def _attention(q, k, v, cfg: TransformerConfig, causal: bool = True):
    """Dispatch to the attention impl (Pallas flash on TPU when available)."""
    from deepspeed_tpu.ops.attention import multi_head_attention

    if cfg.sequence_parallel:
        if cfg.sp_mode == "ring":
            # ring is already blockwise: per-chip attention memory is one
            # [S/p × S/p] block, so attn_chunks adds nothing there
            from deepspeed_tpu.parallel.ring_attention import ring_attention

            return ring_attention(q, k, v, causal=causal)
        if cfg.sp_mode != "ulysses":
            raise ValueError(f"sp_mode must be ulysses|ring, got "
                             f"{cfg.sp_mode!r}")
        from deepspeed_tpu.parallel.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, causal=causal, impl=cfg.attn_impl,
                                 attn_chunks=cfg.attn_chunks)
    if cfg.attn_chunks > 1:
        from deepspeed_tpu.parallel.fpdt import chunked_attention

        return chunked_attention(q, k, v, causal=causal,
                                 q_chunks=cfg.attn_chunks)
    return multi_head_attention(q, k, v, causal=causal, impl=cfg.attn_impl)


def _qwz_fetch_tree(cfg: TransformerConfig, layer_params):
    """ZeRO++ stage-3 qwZ: route each layer weight through the int8 fsdp
    all-gather (runtime/sharding.py quantized_param_fetch; no-op unless
    the engine armed it via configure_qwz). Reference: quantized
    parameter all-gather in the stage-3 fetch path
    (partition_parameters.py:1446)."""
    from deepspeed_tpu.runtime.sharding import (quantized_param_fetch,
                                                qwz_active,
                                                qwz_sequence_barrier)

    if not qwz_active():
        return layer_params
    axes = logical_axes(cfg)["layers"]
    token = [None]  # chains fetches on the CPU sim (barrier is a TPU no-op)

    def fetch(p, a, path):
        if token[0] is not None:
            p, _ = qwz_sequence_barrier(p, token[0])
        out = quantized_param_fetch(p, a[1:], path=path)  # drop "layers"
        if out is not p:
            token[0] = out
        return out

    def walk(p, a, path):
        if isinstance(a, tuple):
            return fetch(p, a, path)
        # keystr-format paths ("['layers']['attn']['wq']") so z3-leaf
        # patterns match the same strings param_shardings sees
        return {k: (walk(p[k], a[k], f"{path}['{k}']")
                    if isinstance(p, dict) and k in a else p[k]) for k in p}

    return walk(layer_params, axes, "['layers']")


def _fpdt_post_fn(cfg: TransformerConfig, layer_params, dt):
    """Per-chunk fused block tail (residual add + ln2 + MLP) for the
    fpdt paths — built from the GIVEN param tree so the sp shard_map
    body can construct it from its own operand instead of closing over
    outer traced arrays (closure capture is not allowed across the
    shard_map boundary)."""
    ap = layer_params["attn"]
    mp = layer_params.get("mlp")

    def post_fn(x_chunk, attn_chunk):
        if cfg.use_biases:
            attn_chunk = attn_chunk + ap["bo"].astype(dt)
        xc = x_chunk + attn_chunk
        yc = _norm(xc, layer_params["ln2"], cfg.norm, cfg.norm_eps)
        if cfg.activation == "swiglu":
            gt = jnp.einsum("bch,hf->bcf", yc, mp["wg"].astype(dt))
            ut = jnp.einsum("bch,hf->bcf", yc, mp["wi"].astype(dt))
            zt = jax.nn.silu(gt) * ut
        else:
            pre = jnp.einsum("bch,hf->bcf", yc, mp["wi"].astype(dt))
            if cfg.use_biases:
                pre = pre + mp["bi"].astype(dt)
            zt = act_fn(cfg.activation)(pre)
        out = jnp.einsum("bcf,fh->bch", zt, mp["wo"].astype(dt))
        if cfg.use_biases:
            out = out + mp["bo"].astype(dt)
        return xc + out

    return post_fn


def _fpdt_sp_block(cfg: TransformerConfig, x, layer_params, positions,
                   fuse: bool):
    """fpdt_host_kv × sequence_parallel composed layer attention:
    shard_map over the sp mesh axis — each rank runs FPDT chunked
    attention on its LOCAL sequence shard against the sp-all-gathered,
    host-spilled KV stacks (parallel/fpdt.py ``sp_axis`` mode). Exact:
    the rank-major tiled gather keeps the global tile order
    position-sorted, and query positions carry the shard offset.

    Layer params enter the manual region replicated (P() specs), so tp
    does not further split the projections inside this block; the device
    transient is the gathered full-S KV at kv_heads width (~2·S·kv·D
    bytes — ~2 GB at 1M tokens / 8 KV heads / d128 / bf16), which is
    what the host spill then bounds. Works independently of sp_mode —
    this path replaces the ulysses/ring dispatch when KV streams from
    host. Returns the fused block output when ``fuse`` else the raw
    attention branch (wo applied, no bias)."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel import topology as _topo
    from deepspeed_tpu.parallel.fpdt import fpdt_attention_block
    from deepspeed_tpu.runtime.sharding import effective_dtype

    mesh = _topo._GLOBAL_MESH
    dt = effective_dtype(cfg.dtype)
    B, S, H = x.shape
    sp = int(mesh.shape["sp"])
    if S % sp:
        raise ValueError(
            f"fpdt_host_kv + sequence_parallel needs seq {S} divisible "
            f"by sp={sp}: pad-free shards keep global positions exact")
    positions = jnp.broadcast_to(positions, (B, S))
    batch_axes = tuple(a for a in _topo.BATCH_AXES if a in mesh.shape)
    x_spec = P(batch_axes, "sp", None)
    pos_spec = P(batch_axes, "sp")
    p_specs = jax.tree.map(lambda _: P(), layer_params)

    def body(x_loc, lp, pos_loc):
        post = _fpdt_post_fn(cfg, lp, dt) if fuse else None
        return fpdt_attention_block(
            x_loc, lp["attn"], pos_loc, num_heads=cfg.num_heads,
            kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta if cfg.pos_emb == "rope" else None,
            q_chunks=max(cfg.attn_chunks, 2), causal=True,
            use_biases=cfg.use_biases,
            norm_fn=lambda t: _norm(t, lp["ln1"], cfg.norm,
                                    cfg.norm_eps),
            post_fn=post, sp_axis="sp", sp_size=sp)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(x_spec, p_specs, pos_spec),
                       out_specs=x_spec, check_vma=False)
    return fn(x, layer_params, positions)


def _layer(cfg: TransformerConfig, x, layer_params, positions,
           hosted_seq_len: Optional[int] = None):
    """One transformer block. x: [B, S, H] in cfg.dtype — or, when
    ``hosted_seq_len`` is set (fpdt_host_residual), the HOST chunk stack
    [q_chunks, B*C, H]; the return matches the input form."""
    from deepspeed_tpu.runtime.sharding import effective_dtype

    layer_params = _qwz_fetch_tree(cfg, layer_params)
    ap = layer_params["attn"]
    dt = effective_dtype(cfg.dtype)
    hosted = hosted_seq_len is not None
    if not hosted:
        x = x.astype(dt)

    from jax.ad_checkpoint import checkpoint_name

    # attention
    if cfg.fpdt_host_kv:
        # host-KV streaming path: q/k/v/context never materialize at
        # full S on the chip, ln1/ln2 apply per chunk inside the scans,
        # and (for the sequential-block default) the residual add + MLP
        # fuse into the same chunk — the whole layer emits one full-S
        # buffer (parallel/fpdt.py fpdt_attention_block);
        # fpdt_host_kv + sequence_parallel composes via _fpdt_sp_block
        from deepspeed_tpu.parallel.fpdt import fpdt_attention_block

        mp = layer_params.get("mlp")
        fuse_mlp = (not cfg.parallel_block) and mp is not None
        post_fn = _fpdt_post_fn(cfg, layer_params, dt)

        if not hosted and cfg.sequence_parallel:
            from deepspeed_tpu.parallel import topology as _topo

            _mesh = _topo._GLOBAL_MESH
            if _mesh is not None and _mesh.shape.get("sp", 1) > 1:
                res = _fpdt_sp_block(cfg, x, layer_params, positions,
                                     fuse=fuse_mlp)
                if fuse_mlp:
                    return constrain_activation(
                        res, ("batch", "seq", "embed"))
                attn = res
                if cfg.use_biases:
                    attn = attn + ap["bo"].astype(dt)
                attn = constrain_activation(
                    checkpoint_name(attn, "attn_out"),
                    ("batch", "seq", "embed"))
                return _layer_mlp(cfg, x, attn, layer_params)
            # sp requested but the mesh has no sp axis > 1: degree-1
            # sequence parallelism IS the plain local path — fall through

        if hosted:
            if not fuse_mlp:
                raise ValueError(
                    "fpdt_host_residual needs the fused sequential block "
                    "(mlp present, parallel_block off)")
            # two-pass flash-style layer backward over host chunks
            # (parallel/fpdt.py fpdt_hosted_layer)
            import os as _os
            if "oldpath" in _os.environ.get("DSTPU_FPDT_BISECT", ""):
                return fpdt_attention_block(
                    x, ap, positions, num_heads=cfg.num_heads,
                    kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
                    rope_theta=(cfg.rope_theta if cfg.pos_emb == "rope"
                                else None),
                    q_chunks=max(cfg.attn_chunks, 2), causal=True,
                    use_biases=cfg.use_biases,
                    norm_fn=lambda t: _norm(t, layer_params["ln1"],
                                            cfg.norm, cfg.norm_eps),
                    post_fn=post_fn, hosted=True,
                    seq_len=hosted_seq_len)
            from deepspeed_tpu.parallel.fpdt import fpdt_hosted_layer

            B_ = positions.shape[0] if positions.ndim == 2 else 1
            T_ = x.shape[0]
            C_ = -(-hosted_seq_len // T_)
            Sp_ = T_ * C_
            pos2 = jnp.broadcast_to(positions,
                                    (x.shape[1] // C_, hosted_seq_len))
            pos_p = (jnp.pad(pos2, [(0, 0), (0, Sp_ - hosted_seq_len)])
                     if Sp_ > hosted_seq_len else pos2)
            return fpdt_hosted_layer(
                x, layer_params, pos_p, seq_len=hosted_seq_len,
                q_chunks=T_, num_heads=cfg.num_heads,
                kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
                rope_theta=(cfg.rope_theta if cfg.pos_emb == "rope"
                            else None),
                use_biases=cfg.use_biases, norm_kind=cfg.norm,
                norm_eps=cfg.norm_eps, activation=cfg.activation)
        res = fpdt_attention_block(
            x, ap, positions, num_heads=cfg.num_heads,
            kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta if cfg.pos_emb == "rope" else None,
            q_chunks=max(cfg.attn_chunks, 2), causal=True,
            use_biases=cfg.use_biases,
            norm_fn=lambda t: _norm(t, layer_params["ln1"], cfg.norm,
                                    cfg.norm_eps),
            post_fn=post_fn if fuse_mlp else None)
        if fuse_mlp:
            return constrain_activation(res, ("batch", "seq", "embed"))
        attn = res
        if cfg.use_biases:
            attn = attn + ap["bo"].astype(dt)
        attn = constrain_activation(
            checkpoint_name(attn, "attn_out"), ("batch", "seq", "embed"))
        return _layer_mlp(cfg, x, attn, layer_params)
    with jax.named_scope("attn"):
        y = _norm(x, layer_params["ln1"], cfg.norm, cfg.norm_eps)
        q = jnp.einsum("bsh,hnd->bsnd", y, ap["wq"].astype(dt))
        k = jnp.einsum("bsh,hnd->bsnd", y, ap["wk"].astype(dt))
        v = jnp.einsum("bsh,hnd->bsnd", y, ap["wv"].astype(dt))
        if cfg.use_biases:
            q = q + ap["bq"].astype(dt)
            k = k + ap["bk"].astype(dt)
            v = v + ap["bv"].astype(dt)
        q = checkpoint_name(q, "qkv_proj")
        k = checkpoint_name(k, "qkv_proj")
        v = checkpoint_name(v, "qkv_proj")
        if cfg.pos_emb == "rope":
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        q = constrain_activation(q, ("batch", "seq", "heads", None))
        k = constrain_activation(k, ("batch", "seq", "heads", None))
        v = constrain_activation(v, ("batch", "seq", "heads", None))
        if cfg.sequence_parallel or cfg.attn_chunks > 1:
            # GQA: the SP all-to-all / chunked paths split on the head axis
            # and need equal q/kv head counts; the plain path keeps KV at
            # kv_heads — the flash kernel reads grouped KV natively.
            from deepspeed_tpu.ops.attention import repeat_kv_heads
            k, v = repeat_kv_heads(q, k, v)
        attn = checkpoint_name(_attention(q, k, v, cfg), "attn_kernel_out")
        attn = jnp.einsum("bsnd,ndh->bsh", attn, ap["wo"].astype(dt))
        if cfg.use_biases:
            attn = attn + ap["bo"].astype(dt)
        if cfg.post_norms:
            attn = _norm(attn, layer_params["ln1_post"], cfg.norm,
                         cfg.norm_eps)
        attn = constrain_activation(
            checkpoint_name(attn, "attn_out"), ("batch", "seq", "embed"))
    return _layer_mlp(cfg, x, attn, layer_params)


@jax.named_scope("mlp")
def _layer_mlp(cfg: TransformerConfig, x, attn, layer_params):
    """Residual-add + MLP half of the block (shared by the standard and
    fpdt_host_kv attention paths)."""
    from jax.ad_checkpoint import checkpoint_name

    from deepspeed_tpu.runtime.sharding import effective_dtype

    mp = layer_params["mlp"]
    dt = effective_dtype(cfg.dtype)

    # mlp: sequential (x + attn first) or parallel (Falcon-style — both
    # branches read the pre-attention residual; the loader duplicates a
    # single input_layernorm into ln1/ln2 when the arch has one)
    if not cfg.parallel_block:
        x = x + attn

    if cfg.fp8_mlp:
        # fp8 MLP GEMMs (performance.fp8_mlp): e4m3 operands, fp32
        # accumulation, straight-through grads — the projections are
        # the real-shape compute bulk and tolerate fp8 forward noise
        from deepspeed_tpu.ops.fp_quantizer import fp8_matmul_ste

        def matmul(y, w):
            return fp8_matmul_ste(y, w.astype(dt), out_dtype=dt)
    else:
        def matmul(y, w):
            return jnp.einsum("...h,hf->...f", y, w.astype(dt))

    def mlp_fn(y):
        if cfg.activation == "swiglu":
            g = matmul(y, mp["wg"])
            u = matmul(y, mp["wi"])
            z = jax.nn.silu(g) * u
        else:
            act = act_fn(cfg.activation)
            pre = matmul(y, mp["wi"])
            if cfg.use_biases:
                pre = pre + mp["bi"].astype(dt)
            z = act(pre)
        z = constrain_activation(
            checkpoint_name(z, "mlp_wi"), ("batch", "seq", "mlp"))
        out = matmul(z, mp["wo"])
        if cfg.use_biases:
            out = out + mp["bo"].astype(dt)
        return checkpoint_name(out, "mlp_out")

    if cfg.tiled_mlp > 1:
        # position-wise → chunk the sequence (ALST TiledMLP analog):
        # peak MLP activation drops to one tile's worth. ln2 is
        # position-wise too — normalizing inside the tile body keeps
        # its fp32 intermediate (and the normed y) tile-sized instead
        # of full-sequence (a full-S term at 512K context)
        from deepspeed_tpu.parallel.tiled_compute import tiled_mlp

        def norm_mlp_tile(x_tile):
            return mlp_fn(_norm(x_tile, layer_params["ln2"], cfg.norm,
                                cfg.norm_eps))

        z = tiled_mlp(norm_mlp_tile, x, cfg.tiled_mlp)
    else:
        y = _norm(x, layer_params["ln2"], cfg.norm, cfg.norm_eps)
        z = mlp_fn(y)
    if cfg.post_norms:
        z = _norm(z, layer_params["ln2_post"], cfg.norm, cfg.norm_eps)
    z = constrain_activation(z, ("batch", "seq", "embed"))
    if cfg.parallel_block:
        return x + attn + z
    return x + z


# remat policy names resolve through the activation-checkpointing
# subsystem (runtime/activation_checkpointing.py), which also applies
# partition_activations / cpu_checkpointing when configured


def apply_hidden(cfg: TransformerConfig, params: Dict[str, Any],
                 tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 final_norm: bool = True,
                 pass_outputs: bool = False) -> jax.Array:
    """Forward pass up to (and including, unless ``final_norm=False``)
    the final norm: tokens [B,S] → hidden [B,S,H]. For a looped stack
    ``pass_outputs`` returns every pass's normed output instead,
    [ut_steps, B, S, H] (the last is what the head reads).

    ``final_norm=False`` lets the tiled-logits path fuse the norm into
    its per-tile pass — at long context the full-sequence norm's fp32
    intermediate ([B,S,H] fp32 = 2x the bf16 residual) is one of the
    peak-memory terms (the reference chunks final-norm+logits through
    the same tiles, fpdt_layer.py:1207)."""
    B, S = tokens.shape
    dt = cfg.dtype
    if positions is None:
        positions = jnp.arange(S)[None, :]

    if cfg.fpdt_host_residual:
        raise ValueError(
            "fpdt_host_residual: use apply_hidden_hosted / the loss "
            "path — apply_hidden would materialize the full-S buffer "
            "this mode removes")

    with jax.named_scope("embed"):
        x = vocab_parallel_lookup(params["embed"]["tokens"].astype(dt),
                                  tokens)
        if cfg.pos_emb == "learned":
            x = x + params["embed"]["positions"].astype(dt)[positions]
        x = constrain_activation(x, ("batch", "seq", "embed"))

    layer_fn = partial(_layer, cfg)

    from deepspeed_tpu.parallel import topology as _topo
    from deepspeed_tpu.parallel.pipeline import pipeline_enabled, pipelined_layers

    looped = cfg.ut_steps > 1
    if looped and (pipeline_enabled(_topo._GLOBAL_MESH)
                   or cfg.param_host_offload
                   or (cfg.overlap_depth and _topo._GLOBAL_MESH is not None
                       and _topo._GLOBAL_MESH.shape.get("fsdp", 1) > 1)):
        raise LoopedStackUnsupported(
            "a looped stack (ut_steps > 1) runs as the plain scan of layers "
            "only: not under pipeline stages, param_host_offload or the "
            "stage-3 overlap streamer")
    if pipeline_enabled(_topo._GLOBAL_MESH):
        # pp > 1: run the layer stack as a microbatched stage pipeline
        # (remat is applied per stage inside pipelined_layers)
        x = pipelined_layers(
            lambda c, lp: layer_fn(c, lp, positions), params["layers"], x)
    elif cfg.param_host_offload:
        # ZeRO-Infinity streaming: layer params live in pinned host
        # memory (engine placement); each scan step fetches ONE layer to
        # device INSIDE the rematerialized body, so neither the forward
        # nor the saved residuals ever hold the full stack in HBM — the
        # remat replay re-fetches for backward, and the cotangent of the
        # fetch is a device→host transfer, landing grads host-side
        # (reference: swap_tensor/partitioned_param_swapper.py semantics,
        # compiled by XLA instead of hand-scheduled copies).
        # default: the double-buffered prefetch streamer
        # (runtime/param_stream.py streamed_layers_prefetch — fetch of
        # layer i+1 overlaps layer i's compute; measured 2026-07-31 on
        # v5e-1 that XLA's default schedule overlaps these host fetches
        # not at all, docs/latency_hiding.md). Its custom VJP implies
        # per-layer full recompute (nothing_saveable). prefetch_stream
        # False falls back to the plain scan; serialize_fetch True
        # additionally chains each fetch on the previous layer's output
        # (the probe's no-overlap control). Both resolve from env at
        # config construction (see TransformerConfig).
        _prefetch = cfg.prefetch_stream
        _serialize_fetch = cfg.serialize_fetch

        if _prefetch and not _serialize_fetch:
            from deepspeed_tpu.runtime.param_stream import \
                streamed_layers_prefetch

            if cfg.remat and cfg.remat_policy not in (
                    None, "nothing_saveable"):
                from deepspeed_tpu.utils.logging import warning_once

                warning_once(
                    "offload_param prefetch streaming remats per layer "
                    f"(nothing_saveable); remat_policy="
                    f"{cfg.remat_policy!r} does not apply to the "
                    "streamed stack")
            x = streamed_layers_prefetch(
                layer_fn, params["layers"], x, length=cfg.num_layers,
                extra=(positions,), prefetch_depth=cfg.prefetch_depth,
                grads_to_host=cfg.grads_to_host,
                overlap_depth=cfg.overlap_depth or 0)
        else:
            def fetch_layer(i):
                from deepspeed_tpu.utils import memspace

                return jax.tree.map(
                    lambda a: memspace.put(
                        lax.dynamic_index_in_dim(a, i, keepdims=False),
                        "device"),
                    params["layers"])

            def fetched_layer_fn(carry, i):
                if _serialize_fetch:
                    carry, i = lax.optimization_barrier((carry, i))
                return layer_fn(carry, fetch_layer(i), positions)

            if cfg.remat:
                from deepspeed_tpu.runtime.activation_checkpointing import \
                    checkpoint_wrapper

                fetched_layer_fn = checkpoint_wrapper(
                    fetched_layer_fn, policy=cfg.remat_policy)

            def host_scan_body(carry, i):
                return fetched_layer_fn(carry, i), None

            x, _ = lax.scan(host_scan_body, x, jnp.arange(cfg.num_layers))
    elif (cfg.overlap_depth and _topo._GLOBAL_MESH is not None
          and _topo._GLOBAL_MESH.shape.get("fsdp", 1) > 1):
        # stage-3 resident overlap: the SAME overlap engine, with the
        # per-layer fsdp all-gather as the fetch and the per-layer grad
        # reduce-scatter as the sink — layer i+k's gather is
        # barrier-pinned into layer i's stage, and each layer's grad
        # scatter issues inside the backward scan where it overlaps the
        # previous layer's recompute (T3-style, PAPERS.md). The
        # streamer's custom VJP implies per-layer recompute, same as
        # the nothing_saveable remat the real shape runs anyway.
        from deepspeed_tpu.runtime.param_stream import \
            streamed_layers_prefetch
        from deepspeed_tpu.runtime.sharding import (fsdp_gather_slice,
                                                    fsdp_scatter_grads)

        _logical = logical_axes(cfg)["layers"]
        k = max(1, int(cfg.overlap_depth))
        x = streamed_layers_prefetch(
            layer_fn, params["layers"], x, length=cfg.num_layers,
            extra=(positions,), prefetch_depth=k,
            grads_to_host=False, overlap_depth=k,
            fetch=lambda stack, i: fsdp_gather_slice(stack, i, _logical),
            grad_sink=lambda dp: fsdp_scatter_grads(dp, _logical))
    else:
        if cfg.remat:
            from deepspeed_tpu.runtime.activation_checkpointing import \
                checkpoint_wrapper

            layer_fn = checkpoint_wrapper(layer_fn, policy=cfg.remat_policy)

        def scan_body(carry, layer_params):
            return layer_fn(carry, layer_params, positions), None

        if looped:
            # the same layers again in every pass, the model's final norm
            # behind each: what comes out is normed, and each pass's output
            # is what the exit gate reads
            if not final_norm:
                raise LoopedStackUnsupported(
                    "apply_hidden(final_norm=False): the final norm is part "
                    "of a looped stack's every pass")

            def pass_body(x, _):
                with jax.named_scope("ut_pass"):
                    x, _ = lax.scan(scan_body, x, params["layers"])
                x = _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
                return x, x

            x, passes = lax.scan(pass_body, x, None, length=cfg.ut_steps)
            return passes if pass_outputs else x   # [ut_steps, B, S, H] | x
        else:
            x, _ = lax.scan(scan_body, x, params["layers"])

    if not final_norm:
        return x
    with jax.named_scope("head_loss"):
        return _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)


def apply_hidden_hosted(cfg: TransformerConfig, params: Dict[str, Any],
                        tokens: jax.Array,
                        positions: Optional[jax.Array] = None):
    """fpdt_host_residual forward: tokens [B, S] → the residual stream as
    a HOST chunk stack [q_chunks, B*C, H] (padded on the chunk grid; no
    final norm — the hosted loss fuses it per chunk). The device holds
    one chunk (+ one KV tile) at a time; see parallel/fpdt.py.

    Returns (x_t, S, C).
    """
    from jax import lax

    from deepspeed_tpu.parallel.fpdt import _to_host
    from deepspeed_tpu.runtime.sharding import effective_dtype

    B, S = tokens.shape
    dt = effective_dtype(cfg.dtype)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    positions = jnp.broadcast_to(positions, (B, S))

    T = max(cfg.attn_chunks, 2)
    C = -(-S // T)
    Sp = T * C
    tokens_p = (jnp.pad(tokens, [(0, 0), (0, Sp - S)]) if Sp > S
                else tokens)
    pos_p = (jnp.pad(positions, [(0, 0), (0, Sp - S)]) if Sp > S
             else positions)

    # embedding, chunk by chunk, emitted straight to the host stack
    def embed_chunk(t):
        tok_c = lax.dynamic_slice_in_dim(tokens_p, t * C, C, 1)
        x_c = vocab_parallel_lookup(
            params["embed"]["tokens"].astype(dt), tok_c)
        if cfg.pos_emb == "learned":
            p_c = lax.dynamic_slice_in_dim(pos_p, t * C, C, 1)
            x_c = x_c + params["embed"]["positions"].astype(dt)[p_c]
        return x_c

    embed_chunk = jax.checkpoint(embed_chunk)

    def embed_body(_, t):
        return None, _to_host(embed_chunk(t).reshape(B * C, -1))

    _, x_t = lax.scan(embed_body, None, jnp.arange(T))

    # layers: a python loop (static depth) — memory control lives at the
    # chunk level inside each layer; a layer-level remat would have to
    # replay host emissions (mixed memory spaces). Composes with
    # param_host_offload: stream each layer's params to device first.
    for li in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[li], params["layers"])
        if cfg.param_host_offload:
            from deepspeed_tpu.utils import memspace

            lp = jax.tree.map(lambda a: memspace.put(a, "device"), lp)
        x_t = _layer(cfg, x_t, lp, positions, hosted_seq_len=S)
    return x_t, S, C


def hosted_logits_loss(cfg: TransformerConfig, params, x_t, labels, mask,
                       S: int, C: int):
    """Fused final-norm + unembed + CE over host residual chunks
    (the hosted analog of tiled_compute.tiled_logits_loss; reference
    chunks final-norm+logits the same way, fpdt_layer.py:1207).
    Returns (masked_nll_sum, mask_total)."""
    from jax import lax

    from deepspeed_tpu.parallel.fpdt import _to_device

    T, BC, H = x_t.shape
    B = BC // C
    dt = cfg.dtype
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    mask = mask.astype(jnp.float32)
    Sp = T * C
    labels_p = (jnp.pad(labels, [(0, 0), (0, Sp - S)]) if Sp > S
                else labels)
    mask_p = (jnp.pad(mask, [(0, 0), (0, Sp - S)]) if Sp > S else mask)

    if cfg.tie_embeddings:
        unembed, transpose = params["embed"]["tokens"].astype(dt), True
    else:
        unembed, transpose = params["unembed"]["kernel"].astype(dt), False

    def chunk_nll(t):
        h = _to_device(lax.dynamic_index_in_dim(
            x_t, t, keepdims=False)).reshape(B, C, H)
        h = _norm(h, params["final_norm"], cfg.norm, cfg.norm_eps)
        lbl = lax.dynamic_slice_in_dim(labels_p, t * C, C, 1)
        m = lax.dynamic_slice_in_dim(mask_p, t * C, C, 1)
        if transpose:
            logits = jnp.einsum("bch,vh->bcv", h, unembed)
        else:
            logits = jnp.einsum("bch,hv->bcv", h, unembed)
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * m
        return jnp.sum(nll), jnp.sum(m)

    chunk_nll = jax.checkpoint(chunk_nll)

    def body(carry, t):
        a, b = chunk_nll(t)
        return (carry[0] + a, carry[1] + b), None

    (nll_sum, total), _ = lax.scan(body, (jnp.zeros((), jnp.float32),
                                          jnp.zeros((), jnp.float32)),
                                   jnp.arange(T))
    return nll_sum, total


def apply(cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array,
          positions: Optional[jax.Array] = None) -> jax.Array:
    """Forward pass: tokens [B, S] int32 → logits [B, S, V] float32."""
    dt = cfg.dtype
    if cfg.fpdt_host_residual:
        # small-shape test path: assemble the hosted stack on device.
        # (Real long-context use goes through loss_fn, which never
        # materializes full-S anything.)
        x_t, S, C = apply_hidden_hosted(cfg, params, tokens, positions)
        T, BC, H = x_t.shape
        B = BC // C
        from deepspeed_tpu.utils import memspace

        x = memspace.put(x_t, "device")
        x = x.reshape(T, B, C, H).transpose(1, 0, 2, 3).reshape(B, T * C, H)
        x = x[:, :S]
        x = _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    else:
        x = apply_hidden(cfg, params, tokens, positions)
    return _unembed_logits(cfg, params, x)


def apply_with_exit(cfg: TransformerConfig, params: Dict[str, Any],
                    tokens: jax.Array, positions: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """The no-cache forward of a looped stack: ``(logits [B, S, V] float32,
    exit distribution [B, S, ut_steps] float32)``. The gate reads each
    pass's normed output, ``lam_t = sigmoid(h_t . w + b)``; pass t takes
    ``lam_t * prod_{s<t} (1 - lam_s)`` and the last pass the remainder. At
    the threshold 1 (the only one served) the logits are the last pass's
    whatever the distribution says."""
    if cfg.ut_steps < 2:
        raise LoopedStackUnsupported(
            "apply_with_exit: the stack runs once a token (ut_steps == 1) "
            "and has no exit gate")
    h = apply_hidden(cfg, params, tokens, positions, pass_outputs=True)
    with jax.named_scope("exit_gate"):
        gate = params["exit_gate"]
        z = jnp.einsum("tbsh,ho->tbs", h.astype(jnp.float32),
                       gate["kernel"].astype(jnp.float32),
                       precision=lax.Precision.HIGHEST)
        lam = jax.nn.sigmoid(z + gate["bias"].astype(jnp.float32))[:-1]
        stay = jnp.cumprod(1.0 - lam, axis=0)          # prod_{s<=t}(1 - lam_s)
        before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
        probs = jnp.concatenate([lam * before, stay[-1:]])
    return _unembed_logits(cfg, params, h[-1]), jnp.moveaxis(probs, 0, -1)


@jax.named_scope("head_loss")
def _unembed_logits(cfg: TransformerConfig, params, x) -> jax.Array:
    dt = cfg.dtype
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsh,vh->bsv", x, params["embed"]["tokens"].astype(dt))
    else:
        from deepspeed_tpu.runtime.sharding import (quantized_param_fetch,
                                                    qwz_sequence_barrier)

        unembed, x = qwz_sequence_barrier(params["unembed"]["kernel"], x)
        unembed = quantized_param_fetch(unembed, ("embed", "vocab"),
                                        path="['unembed']['kernel']")
        logits = jnp.einsum("bsh,hv->bsv", x, unembed.astype(dt))
    logits = constrain_activation(logits, ("batch", "seq", "vocab"))
    return logits.astype(jnp.float32)


def loss_fn(cfg: TransformerConfig, params, batch) -> Tuple[jax.Array, Dict]:
    """Causal-LM cross-entropy. batch: {input_ids [B,S]} or
    {input_ids, labels, loss_mask}."""
    if cfg.ut_steps > 1:
        raise LoopedStackUnsupported(
            "loss: the published objective of a looped stack (the expected "
            "loss over exit passes, entropy-regularised) is not "
            "implemented; the cross-entropy of the last pass alone would "
            "train another model")
    tokens = batch["input_ids"]
    if "labels" in batch:
        inputs, labels = tokens, batch["labels"]
    else:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask.astype(jnp.float32)
        if mask.shape[1] == tokens.shape[1] and "labels" not in batch:
            mask = mask[:, 1:]

    if cfg.fpdt_host_residual:
        # residual stream lives on host; loss fuses final-norm+unembed+CE
        # per fetched chunk — no full-S device buffer anywhere
        x_t, S_, C_ = apply_hidden_hosted(cfg, params, inputs)
        nll_sum, total = hosted_logits_loss(
            cfg, params, x_t, labels, mask, S_, C_)
        total = jnp.maximum(total, 1.0)
        loss = nll_sum / total
        return loss, {"loss": loss, "ntokens": total}

    if cfg.tiled_logits > 1:
        # fused final-norm+unembed+loss per sequence tile: neither the
        # [B,S,V] logits nor the [B,S,H] fp32 normed hidden materialize
        from deepspeed_tpu.parallel.tiled_compute import tiled_logits_loss

        hidden = apply_hidden(cfg, params, inputs, final_norm=False)

        with jax.named_scope("head_loss"):
            def fnorm_tile(h):
                return _norm(h, params["final_norm"], cfg.norm, cfg.norm_eps)
            if cfg.tie_embeddings:
                # the table also feeds the token lookup; its gather stays
                # exact (quantizing it would noise embeddings, not just wire)
                unembed = params["embed"]["tokens"].astype(cfg.dtype)
                transpose = True
            else:
                from deepspeed_tpu.runtime.sharding import (
                    quantized_param_fetch, qwz_sequence_barrier)

                unembed, hidden = qwz_sequence_barrier(
                    params["unembed"]["kernel"], hidden)
                unembed = quantized_param_fetch(
                    unembed, ("embed", "vocab"), path="['unembed']['kernel']")
                unembed = unembed.astype(cfg.dtype)
                transpose = False
            nll_sum, total = tiled_logits_loss(
                hidden, unembed, labels, mask, cfg.tiled_logits,
                transpose_unembed=transpose, tile_transform=fnorm_tile)
            total = jnp.maximum(total, 1.0)
            loss = nll_sum / total
            return loss, {"loss": loss, "ntokens": total}

    logits = apply(cfg, params, inputs)
    with jax.named_scope("head_loss"):
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None],
                                   axis=-1)[..., 0]
        nll = logz - gold
        if mask is None:
            mask = jnp.ones_like(nll)
        total = jnp.maximum(mask.sum(), 1.0)
        loss = (nll * mask).sum() / total
    return loss, {"loss": loss, "ntokens": total}


class TransformerLM:
    """Thin object bundling (config, init, apply, loss, logical_axes) — the
    'model' handed to deepspeed_tpu.initialize()."""

    def __init__(self, config: TransformerConfig):
        self.config = config

    def init(self, rng) -> Dict[str, Any]:
        from deepspeed_tpu.utils.init_on_device import OnDevice

        # under `with OnDevice(device="meta")` this returns the abstract
        # tree (reference OnDevice/zero.Init construction-time behavior)
        return OnDevice.apply(init_params, self.config, rng)

    def abstract_params(self, rng=None):
        """Shapes/dtypes without materializing (the zero.Init analog's
        first half; see runtime/zero_init.py)."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda r: init_params(self.config, r), rng)

    def logical_axes(self) -> Dict[str, Any]:
        return logical_axes(self.config)

    def apply(self, params, tokens, positions=None):
        return apply(self.config, params, tokens, positions)

    def apply_with_exit(self, params, tokens, positions=None):
        """``(logits, exit distribution over the passes)`` of a looped stack
        (:func:`apply_with_exit`)."""
        return apply_with_exit(self.config, params, tokens, positions)

    def loss(self, params, batch):
        return loss_fn(self.config, params, batch)

    def flops_per_token(self) -> float:
        return self.config.flops_per_token()

    def num_params(self) -> int:
        return self.config.num_params()

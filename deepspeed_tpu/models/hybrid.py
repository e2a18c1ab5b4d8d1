"""Hybrid recurrent / attention LM with sparse experts (the ``qwen3_next``
family: Qwen3-Next-80B-A3B).

The stack is not one scanned block: in each *period* of
``full_attention_interval`` layers all but the last are **recurrent** (gated
DeltaNet, ``ops/pallas/gated_delta.py``) and the last is **full** (gated
softmax attention with QK-norm and rotary on a part of each head); every
layer ends in an expert block (``parallel/moe.py::moe_ffn_share``: softmax
routing over all the experts, the top-k renormalised, the experts held here,
and a shared expert behind a sigmoid gate). Norms, rotary and the embedding
are ``models/transformer.py``'s.

The parameter tree stacks every per-layer leaf over *all* layers under
``"layers"`` (both mixers' leaves for every layer: the layout a checkpoint
loader or the benchmark's weight table hands over); :func:`serving_params`
keeps, of each mixer, the layers that use it, and that is what the forward
passes and the serving runner (``inference/hybrid_runner.py``) take:

    layers  ln1, ln2, moe.{router, shared, shared_gate}   [L, ...]
    experts wg, wi, wo                                     [L, E_held, ...]
    gdn     the recurrent layers' mixer                    [L - L/period, ...]
    attn    the full layers' mixer                         [L / period, ...]

``experts_held`` / ``expert_offset`` give the chip's share of the routed
experts (None: all of them); the router always has ``num_experts`` outputs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models.transformer import (TransformerConfig, _norm, _rope)
from deepspeed_tpu.ops.pallas.gated_delta import gdn_chunk
from deepspeed_tpu.parallel.moe import GateConfig, moe_ffn_share
from deepspeed_tpu.runtime.sharding import (effective_dtype,
                                            vocab_parallel_lookup)


@dataclasses.dataclass(frozen=True)
class HybridConfig(TransformerConfig):
    """``TransformerConfig`` (hidden, heads, KV heads, vocabulary, rope,
    norm) plus the recurrent mixer, the attention's extras and the experts.
    ``num_layers`` is a whole number of periods."""

    attn_head_dim: int = 256
    partial_rotary_factor: float = 0.25
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512              # the router's outputs
    top_k: int = 10
    moe_ffn_size: int = 512
    shared_ffn_size: int = 512
    experts_held: Optional[int] = None  # None: all of them
    expert_offset: int = 0

    def __post_init__(self):
        super().__post_init__()
        if self.num_layers % self.full_attention_interval:
            raise ValueError(
                f"num_layers={self.num_layers} is not a whole number of "
                f"periods of {self.full_attention_interval} layers")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear value heads must be a multiple of the "
                             "key heads")
        if self.held + self.expert_offset > self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + self.held}"
                f" lie outside the router's {self.num_experts} outputs")

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def kv_layers(self) -> int:
        """Layers that hold keys and values: the full ones."""
        return self.periods

    @property
    def recurrent_layers(self) -> int:
        return self.num_layers - self.periods

    @property
    def conv_channels(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def gate(self) -> GateConfig:
        return GateConfig(num_experts=self.num_experts, top_k=self.top_k,
                          drop_tokens=False)

    def is_full(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    def num_params(self) -> int:
        return sum(math.prod(s) for s in jax.tree.leaves(
            _shapes(self), is_leaf=lambda x: isinstance(x, tuple)))

    def flops_per_token(self) -> float:
        """Forward and backward, 6 a weight a token touches (the experts:
        ``top_k`` and the shared one)."""
        h = self.hidden_size
        active = 3 * h * (self.top_k * self.moe_ffn_size + self.shared_ffn_size)
        held_all = 3 * h * self.moe_ffn_size * self.held
        return 6.0 * (self.num_params() - self.num_layers * (held_all - active))


def _shapes(cfg: HybridConfig) -> Dict[str, Any]:
    """Every leaf's shape, in the tree's own nesting."""
    h, v, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    nq, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    e, f, fs = cfg.held, cfg.moe_ffn_size, cfg.shared_ffn_size
    return {
        "embed": {"tokens": (v, h)},
        "final_norm": {"scale": (h,)},
        "unembed": {"kernel": (h, v)},
        "layers": {
            "ln1": {"scale": (L, h)}, "ln2": {"scale": (L, h)},
            "attn": {"wq": (L, h, nq, 2 * d), "wk": (L, h, nkv, d),
                     "wv": (L, h, nkv, d), "wo": (L, nq, d, h),
                     "q_norm": (L, d), "k_norm": (L, d)},
            "gdn": {"wq": (L, h, nk, dk), "wk": (L, h, nk, dk),
                    "wv": (L, h, nv, dv), "wz": (L, h, nv, dv),
                    "wb": (L, h, nv), "wa": (L, h, nv),
                    "conv": (L, cfg.linear_conv_kernel_dim, cfg.conv_channels),
                    "A_log": (L, nv), "dt_bias": (L, nv), "norm": (L, dv),
                    "wo": (L, nv, dv, h)},
            "moe": {"router": (L, h, cfg.num_experts),
                    "experts": {"wg": (L, e, h, f), "wi": (L, e, h, f),
                                "wo": (L, e, f, h)},
                    "shared": {"wg": (L, h, fs), "wi": (L, h, fs),
                               "wo": (L, fs, h)},
                    "shared_gate": (L, h)},
        },
    }


_GAINS = ("scale", "q_norm", "k_norm", "norm")


def init_params(cfg: HybridConfig, rng: jax.Array) -> Dict[str, Any]:
    """Fan-in normal draws, gains one, ``A_log`` zero, ``dt_bias`` spread."""
    shapes = _shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, (path, shape) in zip(keys, leaves):
        name = path[-1].key
        group = path[-2].key if len(path) > 1 else ""
        if name in _GAINS:
            x = jnp.ones(shape, cfg.param_dtype)
        elif name == "A_log":
            x = jnp.zeros(shape, cfg.param_dtype)
        elif name == "dt_bias":
            x = jax.random.normal(key, shape, cfg.param_dtype) * 2.0
        elif name in ("tokens", "kernel"):
            x = jax.random.normal(key, shape, cfg.param_dtype) * 0.02
        elif name == "conv":
            x = jax.random.normal(key, shape, cfg.param_dtype) * 0.5
        else:
            if name == "wo":   # contracts everything but the last axis
                fan = math.prod(shape[2:-1]) if group in ("attn", "gdn") \
                    else shape[-2]
            else:              # [L, (E,) h, ...]: contracts h
                fan = cfg.hidden_size
            x = jax.random.normal(key, shape, cfg.param_dtype) / math.sqrt(fan)
        out.append(x)
    return jax.tree.unflatten(treedef, out)


def logical_axes(cfg: HybridConfig) -> Dict[str, Any]:
    L = "layers"
    return {
        "embed": {"tokens": ("vocab", "embed")},
        "final_norm": {"scale": ("embed",)},
        "unembed": {"kernel": ("embed", "vocab")},
        "layers": {
            "ln1": {"scale": (L, "embed")}, "ln2": {"scale": (L, "embed")},
            "attn": {"wq": (L, "embed", "heads", "head_dim"),
                     "wk": (L, "embed", "kv_heads", "head_dim"),
                     "wv": (L, "embed", "kv_heads", "head_dim"),
                     "wo": (L, "heads", "head_dim", "embed"),
                     "q_norm": (L, "head_dim"), "k_norm": (L, "head_dim")},
            # the recurrent mixer is replicated: its state pool is per
            # sequence, not per head shard
            "gdn": {"wq": (L, "embed", None, None),
                    "wk": (L, "embed", None, None),
                    "wv": (L, "embed", None, None),
                    "wz": (L, "embed", None, None),
                    "wb": (L, "embed", None), "wa": (L, "embed", None),
                    "conv": (L, None, None), "A_log": (L, None),
                    "dt_bias": (L, None), "norm": (L, None),
                    "wo": (L, None, None, "embed")},
            "moe": {"router": (L, "embed", None),
                    "experts": {"wg": (L, "expert", "embed", None),
                                "wi": (L, "expert", "embed", None),
                                "wo": (L, "expert", None, "embed")},
                    "shared": {"wg": (L, "embed", None),
                               "wi": (L, "embed", None),
                               "wo": (L, None, "embed")},
                    "shared_gate": (L, "embed")},
        },
    }


def serving_params(cfg: HybridConfig, params: Dict[str, Any]) -> Dict[str, Any]:
    """The tree the forward passes take: of each mixer only the layers that
    use it (the other slices are dropped with the tree handed in), the
    routed experts apart from the other per-layer leaves (the grouped
    product reads them by layer, inside the kernel). Idempotent."""
    if "experts" in params:
        return params
    layers = dict(params["layers"])
    full = jnp.asarray([l for l in range(cfg.num_layers) if cfg.is_full(l)])
    rec = jnp.asarray([l for l in range(cfg.num_layers) if not cfg.is_full(l)])
    attn = jax.tree.map(lambda x: x[full], layers.pop("attn"))
    gdn = jax.tree.map(lambda x: x[rec], layers.pop("gdn"))
    moe = dict(layers["moe"])
    experts = moe.pop("experts")
    layers["moe"] = moe
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "unembed": params["unembed"], "layers": layers,
            "experts": experts, "gdn": gdn, "attn": attn}


# ---------------------------------------------------------------------------
# the layer's pieces, shared by the full forward and the serving runner
# ---------------------------------------------------------------------------


def _rms(x, gain, eps):
    return _norm(x, {"scale": gain}, "rmsnorm", eps)


def attn_project(cfg: HybridConfig, ap, y, positions):
    """Queries and keys normed and rotated, values, and the output gate.
    y [..., H]; positions [...]. Returns q [..., nq, d], k, v [..., nkv, d],
    gate [..., nq, d]."""
    dt, d = y.dtype, cfg.head_dim
    qg = jnp.einsum("...h,hnd->...nd", y, ap["wq"].astype(dt))
    q, gate = qg[..., :d], qg[..., d:]
    k = jnp.einsum("...h,hnd->...nd", y, ap["wk"].astype(dt))
    v = jnp.einsum("...h,hnd->...nd", y, ap["wv"].astype(dt))
    q = _rms(q, ap["q_norm"], cfg.norm_eps)
    k = _rms(k, ap["k_norm"], cfg.norm_eps)
    rot = int(d * cfg.partial_rotary_factor)

    def rope(x):
        return jnp.concatenate(
            [_rope(x[..., :rot], positions, cfg.rope_theta), x[..., rot:]], -1)

    return rope(q), rope(k), v, gate


@jax.named_scope("attn_gate")
def attn_output(ap, attn, gate):
    """``o_proj(attn * sigmoid(gate))``; attn, gate [..., nq, d]."""
    dt = attn.dtype
    o = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
    return jnp.einsum("...nd,ndh->...h", o, ap["wo"].astype(dt))


def gdn_project(cfg: HybridConfig, gp, y):
    """The recurrent mixer's projections of y [..., H]: the convolution's
    input ``q|k|v`` [..., C], ``z`` [..., nv, dv], and float32 ``beta``,
    ``g`` [..., nv]."""
    dt = y.dtype
    lead = y.shape[:-1]
    q = jnp.einsum("...h,hnd->...nd", y, gp["wq"].astype(dt))
    k = jnp.einsum("...h,hnd->...nd", y, gp["wk"].astype(dt))
    v = jnp.einsum("...h,hnd->...nd", y, gp["wv"].astype(dt))
    z = jnp.einsum("...h,hnd->...nd", y, gp["wz"].astype(dt))
    b = jnp.einsum("...h,hn->...n", y, gp["wb"].astype(dt)).astype(jnp.float32)
    a = jnp.einsum("...h,hn->...n", y, gp["wa"].astype(dt)).astype(jnp.float32)
    mixed = jnp.concatenate([x.reshape(lead + (-1,)) for x in (q, k, v)], -1)
    g = -jnp.exp(gp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + gp["dt_bias"].astype(jnp.float32))
    return mixed, z, jax.nn.sigmoid(b), g


def gdn_heads(cfg: HybridConfig, conv_out):
    """After the convolution and SiLU: float32 q, k [..., nv, dk] (key heads
    repeated, L2-normalised, q scaled) and v [..., nv, dv]."""
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    lead = conv_out.shape[:-1]
    x = jax.nn.silu(conv_out.astype(jnp.float32))
    q = x[..., :nk * dk].reshape(lead + (nk, dk))
    k = x[..., nk * dk:2 * nk * dk].reshape(lead + (nk, dk))
    v = x[..., 2 * nk * dk:].reshape(lead + (nv, dv))

    def unit(t):
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q), nv // nk, axis=-2) / math.sqrt(dk)
    k = jnp.repeat(unit(k), nv // nk, axis=-2)
    return q, k, v


def gdn_output(cfg: HybridConfig, gp, o, z):
    """``out_proj(rmsnorm(o) * silu(z))``; o float32 [..., nv, dv]."""
    dt = z.dtype
    o = _rms(o, gp["norm"], cfg.norm_eps).astype(dt) * jax.nn.silu(z)
    return jnp.einsum("...nd,ndh->...h", o, gp["wo"].astype(dt))


@jax.named_scope("gdn_conv")
def causal_conv(taps, tail, x):
    """Depthwise causal convolution over the token axis. taps [K, C]; x
    [B, T, C]; tail [B, K - 1, C], the inputs before x (zeros at a
    sequence's start). Returns (out [B, T, C], the window [B, K - 1 + T, C]
    of which the last K - 1 *real* rows are the next tail)."""
    K = taps.shape[0]
    window = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    T = x.shape[1]
    out = sum(taps[i].astype(x.dtype) * window[:, i:i + T] for i in range(K))
    return out, window


def expert_block(cfg: HybridConfig, lp, experts, x, layer, valid=None):
    """``x + experts(norm(x))`` on flat tokens x [T, H]; returns the
    routing counts beside it."""
    y = _rms(x, lp["ln2"]["scale"], cfg.norm_eps)
    moe = lp["moe"]
    out, counts = moe_ffn_share(
        y, moe["router"], experts, cfg.gate, offset=cfg.expert_offset,
        shared=dict(moe["shared"], gate=moe["shared_gate"]), valid=valid,
        layer=layer)
    return x + out, counts


# ---------------------------------------------------------------------------
# full forward (no cache): the v1 engine's ``forward``, and the tests
# ---------------------------------------------------------------------------


def apply(cfg: HybridConfig, params: Dict[str, Any], tokens: jax.Array,
          positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] -> float32 logits [B, S, V]: every sequence from an
    empty state, the recurrence in its chunked form."""
    p = serving_params(cfg, params)
    B, S = tokens.shape
    dt = effective_dtype(cfg.dtype)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = vocab_parallel_lookup(p["embed"]["tokens"].astype(dt), tokens)
    per = cfg.full_attention_interval
    nv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    g_ = cfg.num_heads // cfg.kv_heads

    def layer_of(l):
        return jax.tree.map(lambda a: a[l], p["layers"])

    def ffn(x, l):
        out, _ = expert_block(cfg, layer_of(l), p["experts"],
                              x.reshape(B * S, -1), l)
        return out.reshape(B, S, -1)

    for l in range(cfg.num_layers):
        lp = layer_of(l)
        y = _rms(x, lp["ln1"]["scale"], cfg.norm_eps)
        if cfg.is_full(l):
            ap = jax.tree.map(lambda a: a[l // per], p["attn"])
            q, k, v, gate = attn_project(cfg, ap, y, positions)
            qh = q.reshape(B, S, cfg.kv_heads, g_, cfg.head_dim)
            s = jnp.einsum("bskgd,btkd->bkgst", qh, k).astype(jnp.float32)
            s = jnp.where(causal, s / math.sqrt(cfg.head_dim), -1e30)
            pr = jax.nn.softmax(s, axis=-1).astype(dt)
            a = jnp.einsum("bkgst,btkd->bskgd", pr, v).reshape(q.shape)
            x = x + attn_output(ap, a, gate)
        else:
            gp = jax.tree.map(lambda a: a[l - l // per], p["gdn"])
            mixed, z, beta, g = gdn_project(cfg, gp, y)
            tail = jnp.zeros((B, cfg.linear_conv_kernel_dim - 1,
                              cfg.conv_channels), dt)
            conv, _ = causal_conv(gp["conv"], tail, mixed)
            qf, kf, vf = gdn_heads(cfg, conv)
            o, _ = gdn_chunk(qf, kf, vf, g, beta,
                             jnp.zeros((B, nv, dk, dv), jnp.float32))
            x = x + gdn_output(cfg, gp, o, z)
        x = ffn(x, l)
    x = _rms(x, p["final_norm"]["scale"], cfg.norm_eps)
    return jnp.einsum("bsh,hv->bsv", x,
                      p["unembed"]["kernel"].astype(dt)).astype(jnp.float32)


def loss_fn(cfg: HybridConfig, params, batch) -> Tuple[jax.Array, Dict]:
    """Mean next-token cross-entropy over ``batch["input_ids"]`` [B, S+1]."""
    ids = batch["input_ids"]
    logits = apply(cfg, params, ids[:, :-1])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll), {}


class HybridLM:
    """(config, init, apply, loss, logical_axes): the model object the
    engines take, as ``TransformerLM`` is for the dense block."""

    def __init__(self, config: HybridConfig):
        self.config = config

    def init(self, rng) -> Dict[str, Any]:
        return init_params(self.config, rng)

    def logical_axes(self) -> Dict[str, Any]:
        return logical_axes(self.config)

    def apply(self, params, tokens, positions=None):
        return apply(self.config, params, tokens, positions)

    def loss(self, params, batch):
        return loss_fn(self.config, params, batch)

    def flops_per_token(self) -> float:
        return self.config.flops_per_token()

    def num_params(self) -> int:
        return self.config.num_params()

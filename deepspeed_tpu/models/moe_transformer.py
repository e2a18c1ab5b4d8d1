"""Mixture-of-Experts transformer LM (Mixtral/Qwen-MoE family).

Reference: deepspeed/moe/layer.py:17 ``MoE`` wrapping an expert FFN into a
dense model, experts deepspeed/moe/experts.py:13, EP groups
utils/groups.py:304; model family: inference/v2/model_implementations/
mixtral + qwen_v2_moe. Reuses the dense transformer's attention/norm and
swaps the FFN for parallel/moe.py's gated expert dispatch; expert weights
carry the "expert" logical axis → ep mesh axis.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.parallel.moe import GateConfig, moe_ffn
from deepspeed_tpu.runtime.sharding import (constrain_activation,
                                            vocab_parallel_lookup)


@dataclasses.dataclass(frozen=True)
class MoETransformerConfig(tfm.TransformerConfig):
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    drop_tokens: bool = True
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.0
    # "auto" | "grouped" (dropless grouped-GEMM) | "einsum" (capacity pad)
    moe_impl: str = "auto"

    def __post_init__(self):
        super().__post_init__()
        if self.ut_steps > 1 or self.post_norms:
            raise tfm.LoopedStackUnsupported(
                "the expert stack runs each layer once a token and has no "
                "post-branch norms (ut_steps, post_norms: models/"
                "transformer.py's dense block alone)")

    @property
    def gate(self) -> GateConfig:
        return GateConfig(
            num_experts=self.num_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            eval_capacity_factor=self.eval_capacity_factor,
            min_capacity=self.min_capacity, drop_tokens=self.drop_tokens,
            aux_loss_weight=self.aux_loss_weight,
            z_loss_weight=self.z_loss_weight)

    def num_params(self) -> int:
        h, L, f, v = self.hidden_size, self.num_layers, self.ffn, self.vocab_size
        nh, nkv, hd = self.num_heads, self.kv_heads, self.head_dim
        attn = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
        expert = (3 if self.activation == "swiglu" else 2) * h * f
        router = h * self.num_experts
        norm_width = 2 * h if self.norm == "layernorm" else h
        per_layer = attn + self.num_experts * expert + router + 2 * norm_width
        emb = v * h + (0 if self.tie_embeddings else v * h)
        pos = self.max_seq_len * h if self.pos_emb == "learned" else 0
        return L * per_layer + emb + pos + norm_width

    def active_params(self) -> int:
        """Params touched per token (top_k of num_experts)."""
        dense = self.num_params()
        h, L, f = self.hidden_size, self.num_layers, self.ffn
        expert = (3 if self.activation == "swiglu" else 2) * h * f
        return dense - L * (self.num_experts - self.top_k) * expert

    def flops_per_token(self) -> float:
        return 6 * self.active_params() + \
            12 * self.num_layers * self.hidden_size * self.max_seq_len


def init_params(cfg: MoETransformerConfig, rng: jax.Array) -> Dict[str, Any]:
    base = tfm.init_params(cfg, rng)
    # replace the dense mlp with router + stacked experts
    h, L, f, E = cfg.hidden_size, cfg.num_layers, cfg.ffn, cfg.num_experts
    keys = jax.random.split(jax.random.fold_in(rng, 17), 4)
    pd = cfg.param_dtype

    def stack(key, shape, scale):
        return jax.random.normal(key, (L, E) + shape, pd) * scale

    moe = {
        "router": jax.random.normal(keys[0], (L, h, E), pd) * (1.0 / math.sqrt(h)),
        "experts": {
            "wi": stack(keys[1], (h, f), 1.0 / math.sqrt(h)),
            "wo": stack(keys[2], (f, h), 1.0 / math.sqrt(f)),
        },
    }
    if cfg.activation == "swiglu":
        moe["experts"]["wg"] = stack(keys[3], (h, f), 1.0 / math.sqrt(h))
    base["layers"]["moe"] = moe
    del base["layers"]["mlp"]
    return base


def logical_axes(cfg: MoETransformerConfig) -> Dict[str, Any]:
    axes = tfm.logical_axes(cfg)
    moe = {
        "router": ("layers", "embed", None),
        "experts": {
            "wi": ("layers", "expert", "embed", "mlp"),
            "wo": ("layers", "expert", "mlp", "embed"),
        },
    }
    if cfg.activation == "swiglu":
        moe["experts"]["wg"] = ("layers", "expert", "embed", "mlp")
    axes["layers"]["moe"] = moe
    del axes["layers"]["mlp"]
    return axes


def _moe_layer(cfg: MoETransformerConfig, x, layer_params, positions,
               train: bool):
    """Transformer block with MoE FFN. Returns (x, l_aux_sum)."""
    from deepspeed_tpu.runtime.sharding import effective_dtype

    ap = layer_params["attn"]
    dt = effective_dtype(cfg.dtype)
    x = x.astype(dt)

    y = tfm._norm(x, layer_params["ln1"], cfg.norm, cfg.norm_eps)
    q = jnp.einsum("bsh,hnd->bsnd", y, ap["wq"].astype(dt))
    k = jnp.einsum("bsh,hnd->bsnd", y, ap["wk"].astype(dt))
    v = jnp.einsum("bsh,hnd->bsnd", y, ap["wv"].astype(dt))
    if cfg.pos_emb == "rope":
        q = tfm._rope(q, positions, cfg.rope_theta)
        k = tfm._rope(k, positions, cfg.rope_theta)
    if cfg.sequence_parallel or cfg.attn_chunks > 1:
        # head-split SP paths need equal q/kv head counts; the plain
        # path keeps KV grouped for the GQA-native flash kernel
        from deepspeed_tpu.ops.attention import repeat_kv_heads
        k, v = repeat_kv_heads(q, k, v)
    attn = tfm._attention(q, k, v, cfg)
    attn = jnp.einsum("bsnd,ndh->bsh", attn, ap["wo"].astype(dt))
    x = x + constrain_activation(attn, ("batch", "seq", "embed"))

    y = tfm._norm(x, layer_params["ln2"], cfg.norm, cfg.norm_eps)
    out, aux = moe_ffn(y, layer_params["moe"]["router"],
                       layer_params["moe"]["experts"], cfg.gate,
                       activation=cfg.activation, train=train,
                       impl=cfg.moe_impl)
    l_aux = aux["l_aux"] * cfg.aux_loss_weight
    if cfg.z_loss_weight:
        l_aux = l_aux + aux["l_zloss"] * cfg.z_loss_weight
    return x + out, l_aux


def apply(cfg: MoETransformerConfig, params, tokens, positions=None,
          train: bool = True) -> Tuple[jax.Array, jax.Array]:
    """tokens [B,S] → (logits [B,S,V] fp32, total aux loss)."""
    B, S = tokens.shape
    dt = cfg.dtype
    if positions is None:
        positions = jnp.arange(S)[None, :]
    x = vocab_parallel_lookup(params["embed"]["tokens"].astype(dt), tokens)
    if cfg.pos_emb == "learned":
        x = x + params["embed"]["positions"].astype(dt)[positions]
    x = constrain_activation(x, ("batch", "seq", "embed"))

    layer_fn = partial(_moe_layer, cfg)

    from deepspeed_tpu.parallel import topology as _topo
    from deepspeed_tpu.parallel.pipeline import (
        pipeline_enabled, pipelined_layers)

    if pipeline_enabled(_topo._GLOBAL_MESH):
        # pp > 1: microbatched stage pipeline threading the aux-loss
        # accumulator through the ring (remat applied per stage inside)
        x, aux_total = pipelined_layers(
            lambda c, lp: layer_fn(c, lp, positions, train),
            params["layers"], x, with_aux=True)
    elif cfg.param_host_offload:
        # ZeRO-Infinity streaming for the expert stack (mirrors
        # models/transformer.py): each scan step fetches one layer's
        # params — including its experts, the bulk of an MoE model —
        # inside the rematerialized body, so HBM holds one layer's
        # experts at a time
        def fetch_layer(i):
            from deepspeed_tpu.utils import memspace

            return jax.tree.map(
                lambda a: memspace.put(
                    lax.dynamic_index_in_dim(a, i, keepdims=False),
                    "device"),
                params["layers"])

        def fetched_fn(x, i):
            return layer_fn(x, fetch_layer(i), positions, train)

        if cfg.remat:
            fetched_fn = jax.checkpoint(fetched_fn)

        def host_body(carry, i):
            x, aux = carry
            x, l_aux = fetched_fn(x, i)
            return (x, aux + l_aux), None

        (x, aux_total), _ = lax.scan(
            host_body, (x, jnp.asarray(0.0, jnp.float32)),
            jnp.arange(cfg.num_layers))
    else:
        if cfg.remat:
            layer_fn = jax.checkpoint(layer_fn)

        def body(carry, layer_params):
            x, aux = carry
            x, l_aux = layer_fn(x, layer_params, positions, train)
            return (x, aux + l_aux), None

        (x, aux_total), _ = lax.scan(
            body, (x, jnp.asarray(0.0, jnp.float32)), params["layers"])

    x = tfm._norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsh,vh->bsv", x, params["embed"]["tokens"].astype(dt))
    else:
        logits = jnp.einsum("bsh,hv->bsv", x, params["unembed"]["kernel"].astype(dt))
    return logits.astype(jnp.float32), aux_total


class MoETransformerLM:
    """Model-protocol wrapper (same contract as TransformerLM)."""

    def __init__(self, config: MoETransformerConfig):
        self.config = config

    def init(self, rng):
        return init_params(self.config, rng)

    def logical_axes(self):
        return logical_axes(self.config)

    def apply(self, params, tokens, positions=None):
        logits, _ = apply(self.config, params, tokens, positions, train=False)
        return logits

    def loss(self, params, batch):
        tokens = batch["input_ids"]
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        logits, aux_loss = apply(self.config, params, inputs, train=True)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = (logz - gold).mean()
        total = nll + aux_loss
        return total, {"loss": total, "lm_loss": nll, "aux_loss": aux_loss,
                       "ntokens": jnp.asarray(labels.size, jnp.float32)}

    def flops_per_token(self):
        return self.config.flops_per_token()

    def num_params(self):
        return self.config.num_params()

"""A second, tiny architecture for the CPU tests: proof that the harness
takes an architecture it has never seen through files alone. Its block
differs from ``references/mistral.py``'s in shape and in leaves:

    n1, n2 = ln(h_l; g1, b1), ln(h_l; g2, b2)        LayerNorm with bias
    h_l+1  = h_l + Wo . attn(rope(Wq n1), rope(Wk n1), Wv n1)
                 + Wout . gelu(Win n2)                parallel residual
    logits = ln(h_L; gf, bf) . U

One key/value head (multi-query), an ungated GELU MLP (no gate matrix) and
a bias beside every gain. It follows no published model; the program runs
it as the zoo's ``tiny`` preset with ``parallel_block``, ``layernorm`` and
``gelu`` (the configuration's ``preset_overrides``). At this size the
whole model is differentiated at once; the interface is the one
``harness/manifest.py`` states.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import Leaf
from benchmarks.references.mistral import _mm, attention, rope


@dataclasses.dataclass(frozen=True)
class Arch:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    ffn_hidden_size: int
    vocab_size: int
    num_hidden_layers: int
    rope_theta: float
    layer_norm_epsilon: float

    @classmethod
    def from_model(cls, model: Dict) -> "Arch":
        return cls(**{f.name: model[f.name] for f in dataclasses.fields(cls)})

    def leaf_table(self) -> Tuple[Leaf, ...]:
        h, nq, nkv, d, f, v = (self.hidden_size, self.num_attention_heads,
                               self.num_key_value_heads, self.head_dim,
                               self.ffn_hidden_size, self.vocab_size)
        fan = 1.0 / math.sqrt(h)
        return (
            Leaf("attn.wq", "wq", (h, nq, d), fan, True),
            Leaf("attn.wk", "wk", (h, nkv, d), fan, True),
            Leaf("attn.wv", "wv", (h, nkv, d), fan, True),
            Leaf("attn.wo", "wo", (nq, d, h), 1.0 / math.sqrt(nq * d), True),
            Leaf("mlp.wi", "fc_in", (h, f), fan, True),
            Leaf("mlp.wo", "fc_out", (f, h), 1.0 / math.sqrt(f), True),
            Leaf("ln1.scale", "ln_attn_g", (h,), None, True),
            Leaf("ln1.bias", "ln_attn_b", (h,), 0.1, True),
            Leaf("ln2.scale", "ln_mlp_g", (h,), None, True),
            Leaf("ln2.bias", "ln_mlp_b", (h,), 0.1, True),
            Leaf("embed.tokens", "wte", (v, h), 0.02, False),
            Leaf("final_norm.scale", "ln_f_g", (h,), None, False),
            Leaf("final_norm.bias", "ln_f_b", (h,), 0.1, False),
            Leaf("unembed.kernel", "head", (h, v), 0.02, False),
        )


CHECK_LAYER_LEAVES = ("wq", "wk", "wv", "wo", "fc_in", "fc_out", "ln_mlp_b")
CHECK_TOP_LEAVES = ("ln_f_g", "ln_f_b", "head")


def train_flops_per_token(a: Arch, seq: int) -> float:
    h, d = a.hidden_size, a.head_dim
    per_layer = (h * d * (2 * a.num_attention_heads + 2 * a.num_key_value_heads)
                 + 2 * h * a.ffn_hidden_size)
    fwd = 2.0 * (a.num_hidden_layers * per_layer + h * a.vocab_size) + (
        a.num_hidden_layers * 2.0 * seq * d * a.num_attention_heads)
    return 3.0 * fwd


def layer_norm(x, g, b, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def layer(arch: Arch, numerics: str, x, w: Dict, positions):
    n1 = layer_norm(x, w["ln_attn_g"], w["ln_attn_b"], arch.layer_norm_epsilon)
    n2 = layer_norm(x, w["ln_mlp_g"], w["ln_mlp_b"], arch.layer_norm_epsilon)
    q = rope(_mm("th,hnd->tnd", n1, w["wq"], numerics), positions,
             arch.rope_theta)
    k = rope(_mm("th,hnd->tnd", n1, w["wk"], numerics), positions,
             arch.rope_theta)
    v = _mm("th,hnd->tnd", n1, w["wv"], numerics)
    a = _mm("tnd,ndh->th", attention(q, k, v, numerics), w["wo"], numerics)
    m = _mm("tf,fh->th", jax.nn.gelu(_mm("th,hf->tf", n2, w["fc_in"], numerics),
                                     approximate=False), w["fc_out"], numerics)
    return x + a + m


def _logits(arch: Arch, numerics: str, tokens, layers: Sequence[Dict],
            top: Dict):
    x = top["wte"][tokens]
    pos = jnp.arange(tokens.shape[0])
    for w in layers:
        x = layer(arch, numerics, x, w, pos)
    x = layer_norm(x, top["ln_f_g"], top["ln_f_b"], arch.layer_norm_epsilon)
    return _mm("th,hv->tv", x, top["head"], numerics)


def forward_logits(arch: Arch, tokens: Sequence, rows: Sequence[Sequence[int]],
                   layer_weights: Callable[[int], Dict], top: Dict,
                   numerics: str = "float32"):
    layers = [layer_weights(l) for l in range(arch.num_hidden_layers)]
    fwd = jax.jit(functools.partial(_logits, arch, numerics))
    return [fwd(jnp.asarray(t), layers, top)[jnp.asarray(r)]
            for t, r in zip(tokens, rows)]


def loss_and_grads(arch: Arch, batch, layer_weights: Callable[[int], Dict],
                   top: Dict, keep: Callable[[str, object], object],
                   numerics: str = "float32") -> Dict:
    """Mean causal-LM loss over ``batch`` ``[B, S + 1]`` and its gradient;
    ``keep(name, grad)`` once for every leaf (``"layers.1.wq"``, ``"head"``)."""
    layers = [layer_weights(l) for l in range(arch.num_hidden_layers)]
    inputs, labels = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])

    def loss(layers_, top_):
        def one(tokens, gold):
            lg = _logits(arch, numerics, tokens, layers_, top_)
            picked = jnp.take_along_axis(lg, gold[:, None], axis=-1)[:, 0]
            return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - picked)
        return jnp.mean(jax.vmap(one)(inputs, labels))

    value, (g_layers, g_top) = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1)))(layers, top)
    flat = {f"layers.{l}.{k}": g for l, gl in enumerate(g_layers)
            for k, g in gl.items()}
    flat.update(g_top)
    kept = {name: keep(name, g) for name, g in flat.items()}
    return {"loss": float(value),
            "grad_norm": float(jnp.sqrt(sum(jnp.sum(g * g)
                                            for g in flat.values()))),
            "kept": {k: v for k, v in kept.items() if v is not None}}

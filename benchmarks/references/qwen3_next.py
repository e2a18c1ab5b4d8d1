"""Plain Qwen3-Next: the forward pass in straightforward ``jax.numpy``,
float32, every matrix product at ``highest`` precision. No kernel, no
cache, no pool, no chunking, and no import from the program: this file
decides ``correct``, so it follows the published description (the
``qwen3_next`` layer equations of ``Qwen3NextForCausalLM``; gated DeltaNet,
Yang et al. 2024, arXiv:2412.06464) and nothing else.

Every RMSNorm has eps 1e-6 and a gain ``g`` applied as ``xhat * g``. Layer
``l`` is a *full* layer when ``(l + 1) % full_attention_interval == 0`` and a
*recurrent* layer otherwise; every layer ends in the expert block.

Full layer.  ``y = norm(x)``; ``q_proj(y)`` gives 16 heads of 512, split per
head into query and gate (256 each); ``k_proj``, ``v_proj`` give 2 heads of
256; ``q = rmsnorm_256(q)``, ``k = rmsnorm_256(k)`` (one gain vector each);
rotary (theta 1e7, rotate-half pairs) on the first ``partial_rotary_factor``
of each head (64 of 256), the rest untouched; causal softmax attention at
scale 256^-1/2, each KV head serving 8 query heads; ``o = attn *
sigmoid(gate)``; ``x += o_proj(o)``.

Recurrent layer (gated DeltaNet).  ``y = norm(x)``; projections ``q, k`` (16
heads of 128), ``v, z`` (32 heads of 128), ``b, a`` (32 each); ``q|k|v``
concatenated (8,192 channels) through a causal depthwise convolution of 4
taps without bias, then SiLU; ``beta = sigmoid(b)``; ``g = -exp(A_log) *
softplus(a + dt_bias)``; each ``q``, ``k`` head repeated twice; ``q`` and ``k``
L2-normalised over their 128, ``q`` scaled by 128^-1/2; a head's state ``S``
(128 x 128, key x value) starts at zero and for every token, one after
another (``lax.scan`` over tokens, no chunked form here):

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t

``o <- rmsnorm_128(o) * silu(z)`` per head (one gain vector); ``x +=
out_proj(o)``.

Expert block.  ``y = norm(x)``; ``p = softmax(y W_r)`` over all the router's
outputs (512), the 10 largest, renormalised to sum to one
(``norm_topk_prob``); ``x += sum_i w_i E_i(y) + sigmoid(y . w_sg)
E_shared(y)`` with ``E = down(silu(gate(y)) * up(y))``.

Final norm, untied head.

Departures from the source, each also in the configuration's ``assumed``:

* **The chip's share.** ``num_experts`` in the configuration is the number
  of routed experts *held here* (``Arch.num_experts``), starting at
  ``expert_offset``; the router keeps the published ``router_outputs`` (the
  published file's ``num_experts``). Every held expert is computed for every
  token and masked by its weight; what the absent experts would have added
  is left out, as in the program, and that partial sum goes on to the next
  layer. ``vocab_size`` is the slice of rows held here.
* **Gains are stored as the gain**, not as ``gain - 1`` (the checkpoint's
  zero-centred layout): with drawn weights that is a layout.
* **Separate projection leaves** for ``q, k, v, z, b, a``: the checkpoint
  interleaves them by key head in ``in_proj_qkvz`` / ``in_proj_ba``; with
  drawn weights that is a layout too. The convolution's channels are
  ordered ``q | k | v``, as in the source.
* **No multi-token-prediction module**: it is not in the ``config`` and is
  not served.
* ``loss_and_grads`` is not given: no training configuration names this
  reference (``train_flops_per_token`` and the ``CHECK_*_LEAVES`` likewise).

Every per-layer leaf is declared for *every* layer (``harness/weights.py``
stacks per-layer leaves over all layers): a full layer's DeltaNet leaves and
a recurrent layer's attention leaves are drawn and never read.

``numerics``: ``float32`` is the reference; ``fp8`` and ``bf16`` are the
*controls* (operands of every weight product and of the attention products
rounded to that type, accumulated in float32; the recurrence's own
arithmetic stays float32).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import Leaf
# the operand rounding of the controls, the product at ``highest`` and the
# RMSNorm are the first reference's: one definition of what a control is
from benchmarks.references.mistral import _mm, rms_norm

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the equations need, under the published names;
    ``num_experts`` counts the experts held here."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    rms_norm_eps: float
    full_attention_interval: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts_per_tok: int
    num_experts: int
    vocab_size: int
    num_hidden_layers: int
    router_outputs: int
    expert_offset: int

    @classmethod
    def from_model(cls, model: Dict) -> "Arch":
        names = [f.name for f in dataclasses.fields(cls)]
        return cls(**{k: model[k] for k in names})

    def is_full(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    def leaf_table(self) -> Tuple[Leaf, ...]:
        """Every weight, as data for ``harness/weights.py``: its path in
        the program's tree (``deepspeed_tpu.models.hybrid``), the name the
        equations below use, its shape and the scale of its normal draw
        (fan-in, so that activations stay of order one; None: a gain drawn
        around one). ``A_log`` and ``dt_bias`` are drawn so that the decay
        ``exp(g)`` spreads from heads that forget in a token to heads that
        remember tens of tokens; ``in_proj_a`` small, so that a head's
        decay is mostly its own; the router at four times fan-in, so that
        a token's ten weights spread over an order of magnitude, as a
        trained router's do, and are not all near a tenth (which ten
        experts a token takes, and so the load on each, is as random)."""
        h, v = self.hidden_size, self.vocab_size
        nq, nkv, d = (self.num_attention_heads, self.num_key_value_heads,
                      self.head_dim)
        nk, nv, dk, dv = (self.linear_num_key_heads,
                          self.linear_num_value_heads,
                          self.linear_key_head_dim,
                          self.linear_value_head_dim)
        e, f, fs = (self.num_experts, self.moe_intermediate_size,
                    self.shared_expert_intermediate_size)
        conv_ch = 2 * nk * dk + nv * dv
        fan = 1.0 / math.sqrt(h)
        return (
            Leaf("ln1.scale", "input_layernorm", (h,), None, True),
            Leaf("ln2.scale", "post_attention_layernorm", (h,), None, True),
            # full layers
            Leaf("attn.wq", "q_proj", (h, nq, 2 * d), fan, True),
            Leaf("attn.wk", "k_proj", (h, nkv, d), fan, True),
            Leaf("attn.wv", "v_proj", (h, nkv, d), fan, True),
            Leaf("attn.wo", "o_proj", (nq, d, h), 1.0 / math.sqrt(nq * d),
                 True),
            Leaf("attn.q_norm", "q_norm", (d,), None, True),
            Leaf("attn.k_norm", "k_norm", (d,), None, True),
            # recurrent layers
            Leaf("gdn.wq", "in_proj_q", (h, nk, dk), fan, True),
            Leaf("gdn.wk", "in_proj_k", (h, nk, dk), fan, True),
            Leaf("gdn.wv", "in_proj_v", (h, nv, dv), fan, True),
            Leaf("gdn.wz", "in_proj_z", (h, nv, dv), fan, True),
            Leaf("gdn.wb", "in_proj_b", (h, nv), fan, True),
            Leaf("gdn.wa", "in_proj_a", (h, nv), 0.5 * fan, True),
            Leaf("gdn.conv", "conv1d", (self.linear_conv_kernel_dim, conv_ch),
                 0.5, True),
            Leaf("gdn.A_log", "A_log", (nv,), 0.5, True),
            Leaf("gdn.dt_bias", "dt_bias", (nv,), 2.0, True),
            Leaf("gdn.norm", "linear_norm", (dv,), None, True),
            Leaf("gdn.wo", "linear_out_proj", (nv, dv, h),
                 1.0 / math.sqrt(nv * dv), True),
            # expert block
            Leaf("moe.router", "gate", (h, self.router_outputs), 4.0 * fan, True),
            Leaf("moe.experts.wg", "experts_gate_proj", (e, h, f), fan, True),
            Leaf("moe.experts.wi", "experts_up_proj", (e, h, f), fan, True),
            Leaf("moe.experts.wo", "experts_down_proj", (e, f, h),
                 1.0 / math.sqrt(f), True),
            Leaf("moe.shared.wg", "shared_gate_proj", (h, fs), fan, True),
            Leaf("moe.shared.wi", "shared_up_proj", (h, fs), fan, True),
            Leaf("moe.shared.wo", "shared_down_proj", (fs, h),
                 1.0 / math.sqrt(fs), True),
            Leaf("moe.shared_gate", "shared_expert_gate", (h,), fan, True),
            Leaf("embed.tokens", "embed_tokens", (v, h), 0.02, False),
            Leaf("final_norm.scale", "norm", (h,), None, False),
            Leaf("unembed.kernel", "lm_head", (h, v), 0.02, False),
        )


def partial_rope(x, positions, theta: float, rotary: int):
    """x [T, heads, D]: rotate the halves of the first ``rotary`` dimensions
    by position * theta^(-2i/rotary); the rest pass through."""
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def full_mixer(a: Arch, numerics: str, y, w: Dict, positions):
    """Gated softmax attention on one sequence. y [T, H] (normed)."""
    d = a.head_dim
    qg = _mm("th,hnd->tnd", y, w["q_proj"], numerics)      # [T, nq, 2d]
    q, gate = qg[..., :d], qg[..., d:]
    k = _mm("th,hnd->tnd", y, w["k_proj"], numerics)
    v = _mm("th,hnd->tnd", y, w["v_proj"], numerics)
    q = rms_norm(q, w["q_norm"], a.rms_norm_eps)
    k = rms_norm(k, w["k_norm"], a.rms_norm_eps)
    rotary = int(d * a.partial_rotary_factor)
    q = partial_rope(q, positions, a.rope_theta, rotary)
    k = partial_rope(k, positions, a.rope_theta, rotary)
    T, nq = q.shape[0], q.shape[1]
    group = nq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = _mm("tnd,snd->nts", q, k, numerics) / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm("nts,snd->tnd", p, v, numerics) * jax.nn.sigmoid(gate)
    return _mm("tnd,ndh->th", o, w["o_proj"], numerics)


def causal_conv(x, taps):
    """Depthwise causal convolution, no bias. x [T, C]; taps [K, C]:
    ``out[t] = sum_i taps[i] * x[t - (K - 1) + i]`` with zeros before the
    sequence's start (the source's ``conv1d`` with left padding K - 1)."""
    K = taps.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    return sum(taps[i][None, :] * xp[i:i + x.shape[0]] for i in range(K))


def l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, token by token. q, k [T, nv, dk]; v [T, nv,
    dv]; g, beta [T, nv]. Returns o [T, nv, dv]; the state starts at zero."""
    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[:, None, None]
        kv = jnp.einsum("nkv,nk->nv", S, k_t, precision=HIGHEST)
        d = b_t[:, None] * (v_t - kv)
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.einsum("nkv,nk->nv", S, q_t, precision=HIGHEST)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    _, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o


def recurrent_mixer(a: Arch, numerics: str, y, w: Dict):
    """Gated DeltaNet on one sequence. y [T, H] (normed)."""
    nk, nv, dk, dv = (a.linear_num_key_heads, a.linear_num_value_heads,
                      a.linear_key_head_dim, a.linear_value_head_dim)
    T = y.shape[0]
    q = _mm("th,hnd->tnd", y, w["in_proj_q"], numerics).reshape(T, nk * dk)
    k = _mm("th,hnd->tnd", y, w["in_proj_k"], numerics).reshape(T, nk * dk)
    v = _mm("th,hnd->tnd", y, w["in_proj_v"], numerics).reshape(T, nv * dv)
    z = _mm("th,hnd->tnd", y, w["in_proj_z"], numerics)
    b = _mm("th,hn->tn", y, w["in_proj_b"], numerics)
    aa = _mm("th,hn->tn", y, w["in_proj_a"], numerics)
    mixed = jax.nn.silu(causal_conv(jnp.concatenate([q, k, v], -1),
                                    w["conv1d"]))
    q = mixed[:, :nk * dk].reshape(T, nk, dk)
    k = mixed[:, nk * dk:2 * nk * dk].reshape(T, nk, dk)
    v = mixed[:, 2 * nk * dk:].reshape(T, nv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["A_log"])[None, :] * jax.nn.softplus(aa + w["dt_bias"][None, :])
    q = jnp.repeat(q, nv // nk, axis=1)
    k = jnp.repeat(k, nv // nk, axis=1)
    q = l2_normalise(q) / jnp.sqrt(jnp.float32(dk))
    k = l2_normalise(k)
    o = delta_rule(q, k, v, g, beta)
    o = rms_norm(o, w["linear_norm"], a.rms_norm_eps) * jax.nn.silu(z)
    return _mm("tnd,ndh->th", o, w["linear_out_proj"], numerics)


def expert_block(a: Arch, numerics: str, y, w: Dict):
    """The held experts' part of the routed sum, plus the shared expert.
    y [T, H] (normed). Routing is over all ``router_outputs`` experts, in
    float32 whatever the numerics: which experts a token takes is not a
    matrix product's precision."""
    logits = jnp.einsum("th,he->te", y, w["gate"], precision=HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(p, a.num_experts_per_tok)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    held = a.expert_offset + jnp.arange(a.num_experts)
    # weight of held expert e for token t: its renormalised share if chosen
    wte = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                            top[:, :, None], 0.0), axis=1)      # [T, E]
    gate = _mm("th,ehf->tef", y, w["experts_gate_proj"], numerics)
    up = _mm("th,ehf->tef", y, w["experts_up_proj"], numerics)
    # the weight goes in before the down projection (it is linear), so
    # that no [T, E, H] array is made
    routed = _mm("tef,efh->th", jax.nn.silu(gate) * up * wte[:, :, None],
                 w["experts_down_proj"], numerics)
    sg = _mm("th,hf->tf", y, w["shared_gate_proj"], numerics)
    su = _mm("th,hf->tf", y, w["shared_up_proj"], numerics)
    shared = _mm("tf,fh->th", jax.nn.silu(sg) * su, w["shared_down_proj"],
                 numerics)
    sgate = jax.nn.sigmoid(jnp.einsum("th,h->t", y, w["shared_expert_gate"],
                                      precision=HIGHEST))
    return routed + sgate[:, None] * shared


def layer(a: Arch, numerics: str, full: bool, x, w: Dict, positions):
    """One layer on one sequence; ``full`` is static."""
    y = rms_norm(x, w["input_layernorm"], a.rms_norm_eps)
    x = x + (full_mixer(a, numerics, y, w, positions) if full
             else recurrent_mixer(a, numerics, y, w))
    y = rms_norm(x, w["post_attention_layernorm"], a.rms_norm_eps)
    return x + expert_block(a, numerics, y, w)


def head_logits(a: Arch, numerics: str, x, norm, lm_head):
    return _mm("th,hv->tv", rms_norm(x, norm, a.rms_norm_eps), lm_head,
               numerics)


@functools.lru_cache(maxsize=None)
def _programs(a: Arch, numerics: str):
    """The jitted pieces, one set per (sizes, numerics)."""
    def one_layer(full, x, w, positions):
        return layer(a, numerics, full, x, w, positions)

    return {"rec": jax.jit(functools.partial(one_layer, False)),
            "full": jax.jit(functools.partial(one_layer, True)),
            "logits": jax.jit(functools.partial(head_logits, a, numerics))}


def forward_logits(arch: Arch, tokens: Sequence, rows: Sequence[Sequence[int]],
                   layer_weights: Callable[[int], Dict], top: Dict,
                   numerics: str = "float32"):
    """Full forward of each sequence in ``tokens`` (1-D int arrays) and the
    logits at the positions ``rows[i]`` of sequence i. Layers outermost, so
    one layer's weights live at a time. Returns a list of float32 arrays
    ``[len(rows[i]), vocab]``."""
    p = _programs(arch, numerics)
    xs = [top["embed_tokens"][jnp.asarray(t)] for t in tokens]
    pos = [jnp.arange(len(t)) for t in tokens]
    for l in range(arch.num_hidden_layers):
        w = layer_weights(l)
        fn = p["full"] if arch.is_full(l) else p["rec"]
        xs = [fn(x, w, ps) for x, ps in zip(xs, pos)]
        del w
    return [p["logits"](x[jnp.asarray(r)], top["norm"], top["lm_head"])
            for x, r in zip(xs, rows)]

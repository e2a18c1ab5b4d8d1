"""Plain Kimi-K2 (``model_type`` ``kimi_k2``: DeepSeek-V3's layers): the
forward pass in straightforward ``jax.numpy``, float32, every matrix product
at ``highest`` precision. No kernel, no cache, no pool, and no import from
the program: this file decides ``correct``, so it follows the published
description (DeepSeek-V2, arXiv:2405.04434, section 2.1 for multi-head
latent attention; DeepSeek-V3, arXiv:2412.19437, section 2.1.2 for the
router; the ``DeepseekV3ForCausalLM`` equations the ``kimi_k2`` config
reuses) and nothing else. Latent attention is computed in its **expanded**
form only (every head's keys and values up-projected from the compressed
vector): the program's absorbed decode is then checked against different
arithmetic.

Every RMSNorm has eps ``rms_norm_eps`` (1e-5) and a gain applied as ``xhat *
g``. ``x`` is a token's hidden state, ``y = norm(x)``.

Mixer (every layer).  ``c_q = norm(y W_qa)`` (1536); ``q = c_q W_qb``: 64
heads of 128 ``nope`` + 64 ``rope`` dims, rotary on the ``rope`` dims.
``[c_kv ; k_r] = y W_kva`` (512 + 64); ``c_kv <- norm(c_kv)``; rotary on
``k_r``, one ``k_r`` for all heads. ``[k_n ; v] = c_kv W_kvb``: 64 heads of
128 + 128. ``score = (q_n . k_n + q_r . k_r) s``, causal softmax, ``o = sum p
v``, ``x += concat(o) W_o``. ``s = 192^-1/2 m^2``, ``m = 0.1 mscale_all_dim
ln(factor) + 1`` (1.4159 at factor 64). Rotary is YaRN's: inverse
frequencies over the 64 rotary dims at base ``rope_theta``, interpolated
(divided by ``factor``) above the correction dimension of ``beta_slow``
rotations over ``original_max_position_embeddings``, untouched below that of
``beta_fast``, a linear ramp between; the cos/sin factor ``mscale /
mscale_all_dim`` scaling is 1 here and is applied as such.

Feed-forward.  The first ``first_k_dense_replace`` layers: SwiGLU of
``intermediate_size``. The others: ``sigma = sigmoid(y W_r)`` over all the
router's outputs (384); the ``num_experts_per_tok`` largest of ``sigma + b``
(``b`` = ``e_score_correction_bias``; ``n_group`` 1: no group limit); weights
``w = sigma[chosen] / (sum sigma[chosen] + 1e-20) * routed_scaling_factor``
(the bias chooses, never weighs); ``x += sum_i w_i E_i(y) + E_shared(y)``,
``E = down(silu(gate(y)) * up(y))``, the shared expert without a gate.

Final norm, untied head.

Departures from the source, each also in the configuration's ``assumed``:

* **The chip's share.** ``n_routed_experts`` in the configuration is the
  number of routed experts *held here* (``Arch.n_routed_experts``), starting
  at ``expert_offset``; the router keeps the published ``router_outputs``
  (384). Every held expert is computed for every token and masked by its
  weight; what the absent experts would have added is left out, as in the
  program, and that partial sum goes on to the next layer. ``vocab_size`` is
  the slice of rows held here.
* **Rotary pairs are the halves** of the 64 rotary dims (``(x1, x2)`` =
  first and second 32), not interleaved neighbours: the published code
  permutes interleaved pairs into halves before rotating; with drawn weights
  that is a layout.
* **Gains are stored as the gain**; ``num_key_value_heads`` (64) is in the
  file and read by nothing (latent attention has no KV head).
* ``loss_and_grads`` is not given: no training configuration names this
  reference (``train_flops_per_token`` and the ``CHECK_*_LEAVES`` are).

Every per-layer leaf is declared for *every* layer (``harness/weights.py``
stacks per-layer leaves over all layers): a dense layer's router, bias,
routed and shared experts are drawn and never read. The dense feed-forward
is a top leaf ``[first_k_dense_replace, ...]``, so that it is not stacked
over the expert layers.

**Memory.** The check runs this file beside an engine that fills 12 of the
chip's 16 GiB, and the runner pads every sequence to the context ceiling
(32,768). So ``forward_logits`` keeps every sequence's stream on the *host*
and brings one block of ``QUERY_BLOCK`` tokens to the device at a time; a
sequence is cut behind the last row read; a layer is two passes over the
blocks (the latents of every token, then attention and feed-forward of each
block over the latents before it, keys ``KEY_BLOCK`` at a time under a
running softmax in float32); the top leaves go to the host first and come
back when used; each layer waits for its results before the next layer's
weights are drawn. On the device at once: one layer's float32 weights (2.7
GB), one sequence's latents (75 MB) and a block's temporaries (0.5 GB).
``forward_logits`` *consumes* ``top`` (it empties the dict it is given).

``numerics``: ``float32`` is the reference; ``fp8`` and ``bf16`` are the
*controls* (operands of every weight product and of the attention products
rounded to that type, accumulated in float32; the router stays float32).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.weights import Leaf
from benchmarks.references.mistral import _mm, rms_norm

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512       # tokens brought to the device at a time
KEY_BLOCK = 2048        # context tokens expanded to keys and values at once
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the equations need, under the published names;
    ``n_routed_experts`` counts the experts held here."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_norm_eps: float
    rope_theta: float
    first_k_dense_replace: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    vocab_size: int
    num_hidden_layers: int
    router_outputs: int
    expert_offset: int
    yarn: Tuple[float, ...]             # in the order of YARN_KEYS

    @classmethod
    def from_model(cls, model: Dict) -> "Arch":
        plain = [f.name for f in dataclasses.fields(cls) if f.name != "yarn"]
        for key, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                          ("topk_group", 1), ("norm_topk_prob", True),
                          ("topk_method", "noaux_tc")):
            if model.get(key) != want:
                raise ValueError(f"references/kimi_k2.py writes the router "
                                 f"down for {key}={want!r}, not "
                                 f"{model.get(key)!r}")
        rs = model["rope_scaling"]
        if rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {rs.get('type')!r}")
        return cls(**{k: model[k] for k in plain},
                   yarn=tuple(float(rs[k]) for k in YARN_KEYS))

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def leaf_table(self) -> Tuple[Leaf, ...]:
        """Every weight, as data for ``harness/weights.py``: its path in the
        program's tree (``deepspeed_tpu.models.hybrid``), the name the
        equations below use, its shape and the scale of its normal draw
        (fan-in, so that activations stay of order one; None: a gain drawn
        around one). The router at fan-in: a token's logits then have a
        standard deviation near one, its sigmoid scores spread over (0.05,
        0.95) with a standard deviation of 0.21, and its eight chosen scores
        lie at 0.89-0.95 (a draw at four times fan-in saturates them: sixty
        experts a token within 0.02 of one, which a bias of any size then
        chooses among). ``e_score_correction_bias`` at a quarter of the
        distance between neighbouring scores near the top (``3 / outputs``:
        0.0075 at 384), and at most 0.01: 0.00195 at 384 outputs, which moves
        one choice in forty-two and leaves every expert's load within 5% of
        the mean (at 0.01 it moved one in nine and spread the loads by 24%,
        twice the mean on the most loaded; at 0.1 six in ten and twelve times
        the mean: a correction that unbalances is no stand-in for one trained
        to balance, and which experts a chip holds would then decide its
        step's time; ``assumed`` in the configuration)."""
        h, v = self.hidden_size, self.vocab_size
        n, ql, c = self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank
        dn, r, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                     self.v_head_dim)
        e, f, F = (self.n_routed_experts, self.moe_intermediate_size,
                   self.intermediate_size)
        fs, K = f * self.n_shared_experts, self.first_k_dense_replace
        fan = 1.0 / math.sqrt(h)
        return (
            Leaf("ln1.scale", "input_layernorm", (h,), None, True),
            Leaf("ln2.scale", "post_attention_layernorm", (h,), None, True),
            Leaf("mla.wqa", "q_a_proj", (h, ql), fan, True),
            Leaf("mla.q_norm", "q_a_layernorm", (ql,), None, True),
            Leaf("mla.wqb", "q_b_proj", (ql, n, dn + r), 1.0 / math.sqrt(ql),
                 True),
            Leaf("mla.wkva", "kv_a_proj_with_mqa", (h, c + r), fan, True),
            Leaf("mla.kv_norm", "kv_a_layernorm", (c,), None, True),
            Leaf("mla.wkvb", "kv_b_proj", (c, n, dn + dv), 1.0 / math.sqrt(c),
                 True),
            Leaf("mla.wo", "o_proj", (n, dv, h), 1.0 / math.sqrt(n * dv),
                 True),
            Leaf("moe.router", "gate", (h, self.router_outputs), fan, True),
            Leaf("moe.router_bias", "e_score_correction_bias",
                 (self.router_outputs,), min(0.01, 0.75 / self.router_outputs),
                 True),
            Leaf("moe.experts.wg", "experts_gate_proj", (e, h, f), fan, True),
            Leaf("moe.experts.wi", "experts_up_proj", (e, h, f), fan, True),
            Leaf("moe.experts.wo", "experts_down_proj", (e, f, h),
                 1.0 / math.sqrt(f), True),
            Leaf("moe.shared.wg", "shared_gate_proj", (h, fs), fan, True),
            Leaf("moe.shared.wi", "shared_up_proj", (h, fs), fan, True),
            Leaf("moe.shared.wo", "shared_down_proj", (fs, h),
                 1.0 / math.sqrt(fs), True),
            Leaf("dense.wg", "dense_gate_proj", (K, h, F), fan, False),
            Leaf("dense.wi", "dense_up_proj", (K, h, F), fan, False),
            Leaf("dense.wo", "dense_down_proj", (K, F, h), 1.0 / math.sqrt(F),
                 False),
            Leaf("embed.tokens", "embed_tokens", (v, h), 0.02, False),
            Leaf("final_norm.scale", "norm", (h,), None, False),
            Leaf("unembed.kernel", "lm_head", (h, v), 0.02, False),
        )


CHECK_LAYER_LEAVES = ("q_a_proj", "q_b_proj", "kv_a_proj_with_mqa",
                      "kv_b_proj", "o_proj", "shared_up_proj")
CHECK_TOP_LEAVES = ("norm", "lm_head")
EXPERT_LEAVES = ("gate", "e_score_correction_bias", "experts_gate_proj",
                 "experts_up_proj", "experts_down_proj", "shared_gate_proj",
                 "shared_up_proj", "shared_down_proj")
DENSE_LEAVES = ("dense_gate_proj", "dense_up_proj", "dense_down_proj")


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Operations the forward and backward passes require per trained token
    on this share: 2 a weight a token touches (the mixer, the dense layers'
    feed-forward, of an expert layer the shared expert, the router and the
    ``top_k * held / router_outputs`` routed experts a token finds here on
    average, the head), causal latent attention in its expanded form
    averaged over a full sequence (``seq / 2`` keys of ``nope + rope`` and
    values of ``v`` a head), backward twice the forward."""
    h, n = a.hidden_size, a.num_attention_heads
    dn, r, dv = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim
    mixer = (h * a.q_lora_rank + a.q_lora_rank * n * (dn + r)
             + h * (a.kv_lora_rank + r) + a.kv_lora_rank * n * (dn + dv)
             + n * dv * h)
    K, L = a.first_k_dense_replace, a.num_hidden_layers
    here = a.num_experts_per_tok * a.n_routed_experts / a.router_outputs
    expert = 3 * h * a.moe_intermediate_size
    ffn = K * 3 * h * a.intermediate_size + (L - K) * (
        (here + a.n_shared_experts) * expert + h * a.router_outputs)
    attn = L * 2.0 * (seq / 2) * n * (dn + r + dv)
    return 3.0 * (2.0 * (L * mixer + ffn + h * a.vocab_size) + attn)


def yarn_scale(a: Arch) -> float:
    """``m = 0.1 mscale_all_dim ln(factor) + 1``: the softmax scale's
    ``m**2``."""
    factor, _, _, _, _, all_dim = a.yarn
    return 1.0 if factor <= 1 else 0.1 * all_dim * math.log(factor) + 1.0


def yarn_cos_sin_factor(a: Arch) -> float:
    """What cos and sin are multiplied by: ``m(mscale) / m(mscale_all_dim)``
    (1 when the two are equal, as published)."""
    factor, _, _, _, mscale, all_dim = a.yarn
    if factor <= 1:
        return 1.0
    return (0.1 * mscale * math.log(factor) + 1.0) / (
        0.1 * all_dim * math.log(factor) + 1.0)


def yarn_inverse_frequencies(a: Arch):
    """[rope / 2] float32."""
    factor, original, beta_fast, beta_slow, _, _ = a.yarn
    dim, base = a.qk_rope_head_dim, a.rope_theta

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    original_freq = base ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return original_freq / factor * ramp + original_freq * (1.0 - ramp)


def rotary(a: Arch, x, positions):
    """x [T, ..., rope]: rotate the halves by position * YaRN's inverse
    frequencies."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * yarn_inverse_frequencies(a)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    c = yarn_cos_sin_factor(a)
    cos, sin = jnp.cos(ang) * c, jnp.sin(ang) * c
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latents(a: Arch, numerics: str, x, w: Dict, positions):
    """What a token leaves for later tokens: ``[c_kv ; k_r]`` [T, c + rope],
    the normed compressed vector and the rotated shared key."""
    y = rms_norm(x, w["input_layernorm"], a.rms_norm_eps)
    kva = _mm("th,hc->tc", y, w["kv_a_proj_with_mqa"], numerics)
    c = a.kv_lora_rank
    c_kv = rms_norm(kva[:, :c], w["kv_a_layernorm"], a.rms_norm_eps)
    return jnp.concatenate([c_kv, rotary(a, kva[:, c:], positions)], -1)


def queries(a: Arch, numerics: str, y, w: Dict, positions):
    """``q_n`` [T, n, nope] and the rotated ``q_r`` [T, n, rope]."""
    c_q = rms_norm(_mm("th,hq->tq", y, w["q_a_proj"], numerics),
                   w["q_a_layernorm"], a.rms_norm_eps)
    q = _mm("tq,qnd->tnd", c_q, w["q_b_proj"], numerics)
    dn = a.qk_nope_head_dim
    return q[..., :dn], rotary(a, q[..., dn:], positions)


def expanded_scores(a: Arch, numerics: str, q_n, q_r, lat, w: Dict):
    """Scores of queries against context latents ``lat`` [S, c + rope] and
    the context's values, both in the expanded form: every head's keys and
    values up-projected from the compressed vector. Returns (scores [n, T,
    S], values [S, n, v])."""
    c, dn = a.kv_lora_rank, a.qk_nope_head_dim
    kv = _mm("sc,cnd->snd", lat[:, :c], w["kv_b_proj"], numerics)
    k_n, v, k_r = kv[..., :dn], kv[..., dn:], lat[:, c:]
    s = (_mm("tnd,snd->nts", q_n, k_n, numerics)
         + _mm("tnd,sd->nts", q_r, k_r, numerics))
    m = yarn_scale(a)
    return s * (m * m / math.sqrt(dn + a.qk_rope_head_dim)), v


def attention(a: Arch, numerics: str, q_n, q_r, lat, w: Dict, positions):
    """Causal softmax attention of queries at ``positions`` [T] over the
    latents ``lat`` [N, c + rope] of positions 0..N-1, the context
    ``KEY_BLOCK`` tokens at a time under a running softmax (the same sum as
    one softmax over the whole context, never held at once). Returns o [T,
    n, v]."""
    n, T = q_n.shape[1], q_n.shape[0]
    kb = min(KEY_BLOCK, lat.shape[0])
    blocks = (jnp.max(positions) + kb) // kb         # as far as a query sees

    def block(b, carry):
        m, den, acc = carry
        part = jax.lax.dynamic_slice_in_dim(lat, b * kb, kb)
        s, v = expanded_scores(a, numerics, q_n, q_r, part, w)
        seen = (b * kb + jnp.arange(kb))[None, :] <= positions[:, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        den = alpha * den + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + _mm("nts,snd->ntd", p, v, numerics)
        return m_new, den, acc

    # (every query sees position 0, in block 0: the running maximum is
    # finite from the first block on)
    init = (jnp.full((n, T), -jnp.inf), jnp.zeros((n, T)),
            jnp.zeros((n, T, a.v_head_dim)))
    _, den, acc = jax.lax.fori_loop(0, blocks, block, init)
    return jnp.swapaxes(acc / den[..., None], 0, 1)


def swiglu(numerics: str, y, gate, up, down):
    return _mm("tf,fh->th", jax.nn.silu(_mm("th,hf->tf", y, gate, numerics))
               * _mm("th,hf->tf", y, up, numerics), down, numerics)


def route(a: Arch, y, w: Dict):
    """Sigmoid scores over all the router's outputs, in float32 whatever the
    numerics (which experts a token takes is not a matrix product's
    precision): the ``top_k`` of ``score + bias`` chosen, the chosen
    *scores* renormalised and scaled. Returns (weights [T, k], experts [T,
    k])."""
    score = jax.nn.sigmoid(jnp.einsum("th,he->te", y, w["gate"],
                                      precision=HIGHEST))
    _, idx = jax.lax.top_k(score + w["e_score_correction_bias"][None, :],
                           a.num_experts_per_tok)
    top = jnp.take_along_axis(score, idx, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return top * a.routed_scaling_factor, idx


def expert_block(a: Arch, numerics: str, y, w: Dict):
    """The held experts' part of the routed sum, plus the shared expert
    (no gate). y [T, H] (normed)."""
    top, idx = route(a, y, w)
    held = a.expert_offset + jnp.arange(a.n_routed_experts)
    wte = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                            top[:, :, None], 0.0), axis=1)      # [T, E]
    gate = _mm("th,ehf->tef", y, w["experts_gate_proj"], numerics)
    up = _mm("th,ehf->tef", y, w["experts_up_proj"], numerics)
    routed = _mm("tef,efh->th", jax.nn.silu(gate) * up * wte[:, :, None],
                 w["experts_down_proj"], numerics)
    return routed + swiglu(numerics, y, w["shared_gate_proj"],
                           w["shared_up_proj"], w["shared_down_proj"])


def block_layer(a: Arch, numerics: str, dense: bool, x, lat, w: Dict,
                positions):
    """One layer on a block of one sequence's tokens: x [T, H] at
    ``positions`` [T]; ``lat`` [N, c + rope] the sequence's latents of this
    layer (positions 0..N-1; what lies after a query is not read). ``dense``
    is static: ``w`` then holds the dense feed-forward's three matrices."""
    y = rms_norm(x, w["input_layernorm"], a.rms_norm_eps)
    q_n, q_r = queries(a, numerics, y, w, positions)
    o = attention(a, numerics, q_n, q_r, lat, w, positions)
    x = x + _mm("tnd,ndh->th", o, w["o_proj"], numerics)
    y = rms_norm(x, w["post_attention_layernorm"], a.rms_norm_eps)
    if dense:
        return x + swiglu(numerics, y, w["dense_gate_proj"],
                          w["dense_up_proj"], w["dense_down_proj"])
    return x + expert_block(a, numerics, y, w)


def layer(a: Arch, numerics: str, l: int, x, w: Dict, top: Dict):
    """One layer on one whole sequence x [T, H] (the tests' sizes): the
    latents of every token, then :func:`block_layer` on all of it."""
    positions = jnp.arange(x.shape[0])
    if a.is_dense(l):
        w = dict(w, **{k: top[k][l] for k in DENSE_LEAVES})
    return block_layer(a, numerics, a.is_dense(l), x,
                       latents(a, numerics, x, w, positions), w, positions)


def head_logits(a: Arch, numerics: str, x, norm, lm_head):
    return _mm("th,hv->tv", rms_norm(x, norm, a.rms_norm_eps), lm_head,
               numerics)


@functools.lru_cache(maxsize=None)
def _programs(a: Arch, numerics: str):
    """The jitted pieces, one set per (sizes, numerics)."""
    return {"latents": jax.jit(functools.partial(latents, a, numerics)),
            True: jax.jit(functools.partial(block_layer, a, numerics, True)),
            False: jax.jit(functools.partial(block_layer, a, numerics, False)),
            "logits": jax.jit(functools.partial(head_logits, a, numerics))}


def _to_host(tree: Dict, keys) -> Dict:
    """Move ``tree[k]`` for k in keys to the host and out of ``tree`` (the
    device copies go with the dict's references)."""
    return {k: np.asarray(tree.pop(k)) for k in keys if k in tree}


def forward_logits(arch: Arch, tokens: Sequence, rows: Sequence[Sequence[int]],
                   layer_weights: Callable[[int], Dict], top: Dict,
                   numerics: str = "float32"):
    """Full forward of each sequence in ``tokens`` (1-D int arrays) and the
    logits at the positions ``rows[i]`` of sequence i. Layers outermost, so
    one layer's weights live at a time; each sequence's stream on the host,
    a block of ``QUERY_BLOCK`` tokens on the device at a time (module
    docstring, "Memory"). A sequence is cut behind the last row read
    (attention is causal: what follows changes no row that is read); the
    last layer computes the blocks that hold a row alone. ``top`` is
    emptied. Returns a list of float32 arrays ``[len(rows[i]), vocab]``."""
    p = _programs(arch, numerics)
    host = _to_host(top, [leaf.published for leaf in arch.leaf_table()
                          if not leaf.per_layer])
    qb = QUERY_BLOCK
    xs, cut = [], []
    for t, r in zip(tokens, rows):
        n = min(-(-(int(max(r)) + 1) // qb) * qb, -(-len(t) // qb) * qb)
        ids = np.zeros(n, np.int64)
        ids[:min(n, len(t))] = np.asarray(t)[:n]
        xs.append(host["embed_tokens"][ids])
        cut.append(n)
    ceiling = max(cut)                      # one latent shape for all
    last = arch.num_hidden_layers - 1
    for l in range(arch.num_hidden_layers):
        w = dict(layer_weights(l))
        dense = arch.is_dense(l)
        if dense:                           # drawn for every layer, unread
            for k in EXPERT_LEAVES:
                w.pop(k)
            w.update({k: jnp.asarray(host[k][l]) for k in DENSE_LEAVES})
        for i, (x, r) in enumerate(zip(xs, rows)):
            starts = range(0, cut[i], qb)
            lat = [p["latents"](jnp.asarray(x[s:s + qb]), w,
                                jnp.arange(s, s + qb)) for s in starts]
            lat = jnp.concatenate(lat + [jnp.zeros(
                (ceiling - cut[i], lat[0].shape[1]), jnp.float32)])
            need = {int(t) // qb for t in r} if l == last else None
            out = np.array(x)
            for s in starts:
                if need is None or s // qb in need:
                    out[s:s + qb] = np.asarray(p[dense](
                        jnp.asarray(x[s:s + qb]), lat, w,
                        jnp.arange(s, s + qb)))
            xs[i] = out
            del lat
        del w                               # before the next layer's come
    norm, head = jnp.asarray(host["norm"]), jnp.asarray(host["lm_head"])
    return [p["logits"](jnp.asarray(x[np.asarray(r)]), norm, head)
            for x, r in zip(xs, rows)]


def loss_and_grads(*args, **kwargs):
    raise NotImplementedError(
        "references/kimi_k2.py gives no loss_and_grads: no training "
        "configuration names this reference (its share of one chip is a "
        "serving cut: training at 16 bytes a parameter does not fit)")

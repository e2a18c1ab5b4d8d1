"""Plain dots3-note (``model_type`` ``dots3_note``): the forward pass in
straightforward ``jax.numpy``, float32, every matrix product at ``highest``
precision. No kernel, no cache, no pool, no ring, and no import from the
program: this file decides ``correct``. Latent attention is computed in its
**expanded** form only, the selector as a ``top_k`` and a mask over the whole
context, the window as a band: the program's absorbed decode over gathered
rows and over a ring of pages is then checked against different arithmetic.

Every RMSNorm has eps ``rms_norm_eps`` (1e-5) and a gain applied as ``xhat *
g``. ``x`` is a token's hidden state, ``y = norm(x)``; ``rope_b`` rotates
the *last* ``rope`` dims of a head (or the lone rotary key) as halves, base
``b``.

Full layer (``layer_types[l] == "full_attention"``).  ``c_q0 = norm(y
W_qa)`` (1024), ``c_q = s_q c_q0``; ``q = c_q W_qb``: 128 heads of 128
``nope`` + 64 ``rope`` dims (``rope_8e7``). ``[c_kv ; k_r] = y W_kva`` (512 +
64); ``c_kv <- s_kv norm(c_kv)``; ``k_r <- rope_8e7(k_r)``, one for all
heads. ``[k_n ; v] = c_kv W_kvb``: 128 heads of 128 + 128. Selector: ``qI =
c_q0 W_Iq``: 64 heads of 128, the last 64 dims rotated; ``kI = LayerNorm(y
W_Ik)`` (128, gain and bias, eps 1e-6, the last 64 dims rotated); ``w = y
W_Iw`` (64); ``I(t, s) = sum_h w[t, h] (64 * 128)^-1/2 relu(qI[t, h] .
kI[s])`` for ``s <= t``; ``S_t`` = the ``index_topk`` (2,048) largest (all
of them while ``t < 2048``; ``lax.top_k``: a tie goes to the earlier token).
``score = (q_n . k_n + q_r . k_r) / sqrt(192)``, softmax over ``s in S_t``,
``o = sum p v``; ``g = sigmoid(y W_g)`` (one a head), ``x += concat(g_h o_h)
W_o``.

Sliding layer.  The same latent form with the ``swa_*`` sizes (``c_q``
1024, ``c_kv`` 1024 + a rotary key of 64, 64 heads of 192 + 64 (``rope_5e4``),
values 128, scale ``1 / sqrt(256)``), key ``s`` visible to query ``t`` iff ``0
<= t - s < sliding_window_size`` (513), no selector, the gate of 64.

Feed-forward.  The first ``first_k_dense_replace`` layers: SwiGLU of
``intermediate_size``. The others: ``sigma = sigmoid(y W_r)`` over all the
router's outputs (256); the ``num_experts_per_tok`` largest of ``sigma + b``
(``b`` = ``e_score_correction_bias``; no group limit); weights ``w =
sigma[chosen] / (sum sigma[chosen] + 1e-20) * routed_scaling_factor``; ``x +=
sum_i w_i E_i(y) + E_shared(y)``, the shared expert without a gate.

Final norm, untied head.

What the config does not say, each also in the configuration's ``assumed``:

1. ``apply_mla_qkv_lora_rescale``: ``s_q = sqrt(hidden / q_lora_rank)``,
   ``s_kv = sqrt(hidden / kv_lora_rank)`` on the normed latents (LongCat-
   Flash's scale correction for latent attention), the sliding layers with
   their own ranks.
2. The gate reads the layer's normed input, one logit a head.
3. The selector is DeepSeek-V3.2-Exp's (the same three config keys): a
   LayerNorm with bias on ``kI``, the heads' weights from ``y``, ``qI`` from
   the query latent *before* the rescale.
4. 513 counts the query's own position.
5. Rotary pairs are the halves; gains are stored as the gain.

**The chip's share**, as ``references/kimi_k2.py``: ``n_routed_experts`` in
the configuration counts the routed experts *held here* from
``expert_offset``; the router keeps ``router_outputs`` (256); what the absent
experts would add is left out. ``vocab_size`` is the slice of rows held here.

Both mixers' leaves are **per-layer leaves**, declared for every layer
(``harness/weights.py`` stacks every per-layer leaf over all layers): the
tree handed to the engine holds a full mixer for the six sliding layers and a
sliding one for the three full layers that nothing reads, and the engine
drops them as it cuts each mixer to its own layers (``donate_params``).
Declared as top leaves over their own layers they would hold no dead slot,
but ``reference_top`` makes every top leaf in float32 on the device at once
(4.0 GB of mixers beside an engine that fills two thirds of the chip: it does
not fit; my chip run, PR 45). The router, bias and experts are per-layer
leaves too (the dense layer's are drawn and never read); the dense
feed-forward is a top leaf.

**Memory**, as ``references/kimi_k2.py``: every sequence's stream on the
host (two buffers that change places each layer), a block of ``QUERY_BLOCK``
tokens on the device at a time; a layer is two passes over the blocks (what
every token leaves for later ones, then each block's attention and
feed-forward); keys ``KEY_BLOCK`` at a time under a running softmax. **Work
left out because nothing reads it**: a sliding layer's block reads the
``window - 1`` tokens before it and no more, a full layer's the key blocks up
to its own last token and no later one, and a layer computes the blocks
something reads (the rows asked for, through the windows above it:
``blocks_read``) and no other. A block's result is fetched while the next
``IN_FLIGHT`` blocks run: fetched before the next was sent, the device stood
idle for two fifths of a sequence's 28 s (my chip run, PR 45).
``forward_logits`` *consumes* ``top``.

``numerics``: ``float32`` is the reference; ``fp8`` and ``bf16`` are the
*controls* (operands of every weight product, of the selector's products and
of the attention products rounded to that type, accumulated in float32; the
router stays float32).
"""

from __future__ import annotations

import collections
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
IN_FLIGHT = 2           # blocks whose results the host has not fetched yet
KEY_BLOCK = 2048        # context tokens expanded to keys and values at once
INDEX_NORM_EPS = 1e-6   # the selector's LayerNorm (assumed 3)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One latent mixer's sizes."""

    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    s_q: float
    s_kv: float


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the equations need, under the published names;
    ``n_routed_experts`` counts the experts held here, ``layer_types`` is the
    published list and the stack holds ``num_hidden_layers`` of it from
    ``first_layer``."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    swa_num_attention_heads: int
    swa_q_lora_rank: int
    swa_kv_lora_rank: int
    swa_qk_nope_head_dim: int
    swa_qk_rope_head_dim: int
    swa_v_head_dim: int
    swa_rope_theta: float
    sliding_window_size: int
    apply_mla_qkv_lora_rescale: bool
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
    first_layer: int
    layer_types: Tuple[str, ...]

    @classmethod
    def from_model(cls, model: Dict) -> "Arch":
        for key, want in (("scoring_func", "sigmoid"), ("norm_topk_prob", True),
                          ("topk_method", "noaux_tc"), ("rope_scaling", None),
                          ("attention_gate_type", "headwise"),
                          ("swa_attention_gate_type", "headwise")):
            if model.get(key) != want:
                raise ValueError(f"references/dots3_note.py writes the layer "
                                 f"down for {key}={want!r}, not "
                                 f"{model.get(key)!r}")
        if "n_group" in model:
            raise ValueError("references/dots3_note.py has no group limit")
        names = [f.name for f in dataclasses.fields(cls)]
        values = {k: model[k] for k in names if k not in ("first_layer",
                                                          "layer_types")}
        a = cls(**values, first_layer=int(model.get("first_layer", 0)),
                layer_types=tuple(model["layer_types"]))
        if a.first_layer + a.num_hidden_layers > len(a.layer_types):
            raise ValueError("the stack's layers lie outside layer_types")
        return a

    def is_dense(self, layer: int) -> bool:
        return self.first_layer + layer < self.first_k_dense_replace

    def is_full(self, layer: int) -> bool:
        return self.layer_types[self.first_layer + layer] == "full_attention"

    @property
    def full_layers(self) -> int:
        return sum(self.is_full(l) for l in range(self.num_hidden_layers))

    @property
    def dense_layers(self) -> int:
        return sum(self.is_dense(l) for l in range(self.num_hidden_layers))

    def sizes(self, full: bool) -> Sizes:
        h = self.hidden_size

        def s(rank):
            return math.sqrt(h / rank) if self.apply_mla_qkv_lora_rescale \
                else 1.0

        if full:
            return Sizes(self.num_attention_heads, self.q_lora_rank,
                         self.kv_lora_rank, self.qk_nope_head_dim,
                         self.qk_rope_head_dim, self.v_head_dim,
                         self.rope_theta, s(self.q_lora_rank),
                         s(self.kv_lora_rank))
        return Sizes(self.swa_num_attention_heads, self.swa_q_lora_rank,
                     self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                     self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                     self.swa_rope_theta, s(self.swa_q_lora_rank),
                     s(self.swa_kv_lora_rank))

    def _mixer_leaves(self, full: bool):
        """A mixer's leaves, one set a layer; published names ``self_attn.*``
        / ``swa_attn.*`` flattened (``assumed``: the checkpoint's leaf names
        are not in the catalog). The two up-projections are drawn at the
        fan-in *of their rescaled input* (``1 / (s sqrt(rank))``, which is
        ``1 / sqrt(hidden)``): queries, keys and values then have entries of
        order one and a query's scores over its context a standard deviation
        near one, as a full-rank projection of the hidden state would give,
        which is what the scale correction is for. Drawn at ``1 /
        sqrt(rank)`` beside the rescale every attention logit is 7 times
        (full) or 5 times (sliding) larger, each head attends to one or two
        tokens, and the stack turns a rounding in one layer into another
        choice in the next: a 10% difference after layer 0 was 86% after
        layer 8 between the program's own float32-accumulated bfloat16
        forward and this file (my chip run, PR 45)."""
        h, z = self.hidden_size, self.sizes(full)
        p, pub = ("mla", "attn") if full else ("wmla", "swa")
        fan = 1.0 / math.sqrt(h)
        out = [
            ("wqa", "q_a_proj", (h, z.q_rank), fan),
            ("q_norm", "q_a_layernorm", (z.q_rank,), None),
            ("wqb", "q_b_proj", (z.q_rank, z.heads, z.nope + z.rope),
             1.0 / (z.s_q * math.sqrt(z.q_rank))),
            ("wkva", "kv_a_proj_with_mqa", (h, z.kv_rank + z.rope), fan),
            ("kv_norm", "kv_a_layernorm", (z.kv_rank,), None),
            ("wkvb", "kv_b_proj", (z.kv_rank, z.heads, z.nope + z.v),
             1.0 / (z.s_kv * math.sqrt(z.kv_rank))),
            ("wo", "o_proj", (z.heads, z.v, h), 1.0 / math.sqrt(z.heads * z.v)),
            ("wgate", "gate_proj", (h, z.heads), fan)]
        if full:
            ni, di = self.index_n_heads, self.index_head_dim
            out += [("wiq", "indexer_wq_b", (z.q_rank, ni, di),
                     1.0 / math.sqrt(z.q_rank)),
                    ("wik", "indexer_wk", (h, di), fan),
                    ("ik_norm", "indexer_k_norm", (di,), None),
                    ("ik_bias", "indexer_k_norm_bias", (di,), 0.1),
                    ("wiw", "indexer_weights_proj", (h, ni), fan)]
        return tuple(Leaf(f"{p}.{a}", f"{pub}_{b}", shape, scale, True)
                     for a, b, shape, scale in out)

    def leaf_table(self) -> Tuple[Leaf, ...]:
        """Every weight, as data for ``harness/weights.py``. Draws as
        ``references/kimi_k2.py``'s: fan-in, the router at fan-in,
        ``e_score_correction_bias`` at ``0.75 / outputs`` (a quarter of the
        distance between neighbouring scores near the top: it moves one
        choice in some forty and leaves the loads balanced). The selector's
        LayerNorm bias at 0.1, so that a dropped bias shows."""
        h, v = self.hidden_size, self.vocab_size
        e, f, F = (self.n_routed_experts, self.moe_intermediate_size,
                   self.intermediate_size)
        fs, K = f * self.n_shared_experts, self.dense_layers
        fan = 1.0 / math.sqrt(h)
        return (
            Leaf("ln1.scale", "input_layernorm", (h,), None, True),
            Leaf("ln2.scale", "post_attention_layernorm", (h,), None, True),
            *self._mixer_leaves(True),
            *self._mixer_leaves(False),
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
            Leaf("embed.tokens", "embed_tokens", (v, h), 1.0, False),
            Leaf("final_norm.scale", "norm", (h,), None, False),
            Leaf("unembed.kernel", "lm_head", (h, v), 0.02, False),
        )


MIXER_LEAVES = ("q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
                "kv_a_layernorm", "kv_b_proj", "o_proj", "gate_proj")
INDEX_LEAVES = ("indexer_wq_b", "indexer_wk", "indexer_k_norm",
                "indexer_k_norm_bias", "indexer_weights_proj")
CHECK_LAYER_LEAVES = ("shared_up_proj",)
CHECK_TOP_LEAVES = ("norm", "lm_head")
EXPERT_LEAVES = ("gate", "e_score_correction_bias", "experts_gate_proj",
                 "experts_up_proj", "experts_down_proj", "shared_gate_proj",
                 "shared_up_proj", "shared_down_proj")
DENSE_LEAVES = ("dense_gate_proj", "dense_up_proj", "dense_down_proj")


def mixer_weights(a: Arch, l: int, w: Dict) -> Dict:
    """Layer ``l``'s own mixer under the names the equations use
    (``q_a_proj`` ...), out of the layer's leaves (which hold both)."""
    full = a.is_full(l)
    pub = ("attn", "swa")[not full]
    names = MIXER_LEAVES + (INDEX_LEAVES if full else ())
    return {k: w[f"{pub}_{k}"] for k in names}


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Operations the forward and backward passes require per trained token
    on this share: 2 a weight a token touches, causal attention over the
    keys a query sees on average (a full layer ``min(seq / 2, index_topk)``
    and the selector's scores over ``seq / 2``; a sliding layer its
    window), backward twice the forward."""
    h = a.hidden_size
    total = h * a.vocab_size
    for l in range(a.num_hidden_layers):
        full = a.is_full(l)
        z = a.sizes(full)
        total += (h * z.q_rank + z.q_rank * z.heads * (z.nope + z.rope)
                  + h * (z.kv_rank + z.rope)
                  + z.kv_rank * z.heads * (z.nope + z.v)
                  + z.heads * z.v * h + h * z.heads)
        keys = min(seq / 2, a.index_topk if full else a.sliding_window_size)
        total += keys * z.heads * (z.nope + z.rope + z.v)
        if full:
            total += (z.q_rank * a.index_n_heads * a.index_head_dim
                      + h * (a.index_head_dim + a.index_n_heads)
                      + (seq / 2) * a.index_n_heads * a.index_head_dim)
        if a.is_dense(l):
            total += 3 * h * a.intermediate_size
        else:
            here = a.num_experts_per_tok * a.n_routed_experts / a.router_outputs
            total += ((here + a.n_shared_experts) * 3 * h
                      * a.moe_intermediate_size + h * a.router_outputs)
    return 3.0 * 2.0 * total


def rotary(x, positions, theta: float):
    """x [T, ..., rope]: rotate the halves by position * theta^(-2i / rope)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rotate_last(x, positions, theta: float, rope: int):
    """``rope_b`` on the last ``rope`` dims of x [T, ..., d]."""
    return jnp.concatenate([x[..., :-rope],
                            rotary(x[..., -rope:], positions, theta)], -1)


def layer_norm(x, g, b, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def leaves_behind(a: Arch, numerics: str, full: bool, x, w: Dict, positions):
    """What a token leaves for later tokens: its latent ``[c_kv ; k_r]`` [T,
    c + rope] and, of a full layer, its selector key ``kI`` [T, di] (an
    empty array of a sliding layer)."""
    z = a.sizes(full)
    y = rms_norm(x, w["input_layernorm"], a.rms_norm_eps)
    kva = _mm("th,hc->tc", y, w["kv_a_proj_with_mqa"], numerics)
    c = z.kv_rank
    c_kv = z.s_kv * rms_norm(kva[:, :c], w["kv_a_layernorm"], a.rms_norm_eps)
    lat = jnp.concatenate([c_kv, rotary(kva[:, c:], positions, z.theta)], -1)
    if not full:
        return lat, jnp.zeros((x.shape[0], 0), jnp.float32)
    k = layer_norm(_mm("th,hd->td", y, w["indexer_wk"], numerics),
                   w["indexer_k_norm"], w["indexer_k_norm_bias"],
                   INDEX_NORM_EPS)
    return lat, rotate_last(k, positions, z.theta, z.rope)


def queries(a: Arch, numerics: str, full: bool, y, w: Dict, positions):
    """``q_n`` [T, n, nope], the rotated ``q_r`` [T, n, rope] and the normed
    query latent before its rescale [T, q_rank]."""
    z = a.sizes(full)
    c_q0 = rms_norm(_mm("th,hq->tq", y, w["q_a_proj"], numerics),
                    w["q_a_layernorm"], a.rms_norm_eps)
    q = _mm("tq,qnd->tnd", z.s_q * c_q0, w["q_b_proj"], numerics)
    return (q[..., :z.nope], rotary(q[..., z.nope:], positions, z.theta),
            c_q0)


def _key_block(n: int) -> int:
    """Keys a block holds: all ``n``, or the largest size that divides them
    into whole blocks of at most ``KEY_BLOCK``."""
    return n if n <= KEY_BLOCK else math.gcd(n, KEY_BLOCK)


def selector_scores(a: Arch, numerics: str, y, c_q0, keys, w: Dict, positions,
                    blocks=None):
    """``I(t, s)`` [T, N] of the block's queries against the sequence's
    selector keys [N, di], ``KEY_BLOCK`` keys at a time; of the key blocks
    the first ``blocks`` alone (the others hold no visible key and read
    0)."""
    z = a.sizes(True)
    q = rotate_last(_mm("tq,qnd->tnd", c_q0, w["indexer_wq_b"], numerics),
                    positions, z.theta, z.rope)
    wt = _mm("th,hn->tn", y, w["indexer_weights_proj"], numerics) / math.sqrt(
        a.index_n_heads * a.index_head_dim)
    kb = _key_block(keys.shape[0])

    def block(b, out):
        part = jax.lax.dynamic_slice_in_dim(keys, b * kb, kb)
        s = jax.nn.relu(_mm("tnd,sd->tns", q, part, numerics))
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.einsum("tns,tn->ts", s, wt, precision=HIGHEST), b * kb, 1)

    return jax.lax.fori_loop(
        0, keys.shape[0] // kb if blocks is None else blocks, block,
        jnp.zeros((q.shape[0], keys.shape[0]), jnp.float32))


def select(a: Arch, scores, positions):
    """bool [T, N]: each query's ``index_topk`` largest scores among ``s <=
    t`` (``lax.top_k``: the earlier of two equal scores first)."""
    T, N = scores.shape
    seen = jnp.arange(N)[None, :] <= positions[:, None]
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf),
                           min(a.index_topk, N))
    chosen = jnp.zeros((T, N), bool).at[jnp.arange(T)[:, None], idx].set(True)
    return chosen & seen


def visible_blocks(n: int, positions, first):
    """How many of the ``_key_block``-sized blocks of ``n`` keys, the first
    at position ``first``, hold a key that a query at ``positions`` may see:
    those up to the last query's own."""
    kb = _key_block(n)
    return jnp.clip((jnp.max(positions) - first) // kb + 1, 1, n // kb)


def attention(a: Arch, numerics: str, full: bool, q_n, q_r, lat, w: Dict,
              visible, blocks=None):
    """Softmax attention in the expanded form of queries over the latents
    ``lat`` [N, c + rope] under ``visible`` bool [T, N] (every query sees a
    key), the context ``KEY_BLOCK`` tokens at a time under a running
    softmax, the first ``blocks`` key blocks alone (no later one holds a
    visible key). A head's key is ``[k_n ; k_r]``, the rotary key the same
    for every head. Returns o [T, n, v]."""
    z = a.sizes(full)
    n, T = q_n.shape[1], q_n.shape[0]
    kb = _key_block(lat.shape[0])
    scale = 1.0 / math.sqrt(z.nope + z.rope)
    q = jnp.concatenate([q_n, q_r], -1)

    def block(b, carry):
        m, den, acc = carry
        part = jax.lax.dynamic_slice_in_dim(lat, b * kb, kb)
        kv = _mm("sc,cnd->snd", part[:, :z.kv_rank], w["kv_b_proj"], numerics)
        k = jnp.concatenate([kv[..., :z.nope], jnp.broadcast_to(
            part[:, None, z.kv_rank:], (kb, n, z.rope))], -1)
        s = _mm("tnd,snd->nts", q, k, numerics) * scale
        ok = jax.lax.dynamic_slice_in_dim(visible, b * kb, kb, 1)
        s = jnp.where(ok[None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)   # no key yet
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
        p = jnp.exp(s - safe[..., None])
        den = alpha * den + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + _mm("nts,snd->ntd", p,
                                           kv[..., z.nope:], numerics)
        return m_new, den, acc

    init = (jnp.full((n, T), -jnp.inf), jnp.zeros((n, T)),
            jnp.zeros((n, T, z.v)))
    _, den, acc = jax.lax.fori_loop(
        0, lat.shape[0] // kb if blocks is None else blocks, block, init)
    return jnp.swapaxes(acc / den[..., None], 0, 1)


def swiglu(numerics: str, y, gate, up, down):
    return _mm("tf,fh->th", jax.nn.silu(_mm("th,hf->tf", y, gate, numerics))
               * _mm("th,hf->tf", y, up, numerics), down, numerics)


def route(a: Arch, y, w: Dict):
    """Sigmoid scores over all the router's outputs, in float32 whatever the
    numerics: the ``top_k`` of ``score + bias`` chosen, the chosen *scores*
    renormalised and scaled. Returns (weights [T, k], experts [T, k])."""
    score = jax.nn.sigmoid(jnp.einsum("th,he->te", y, w["gate"],
                                      precision=HIGHEST))
    _, idx = jax.lax.top_k(score + w["e_score_correction_bias"][None, :],
                           a.num_experts_per_tok)
    top = jnp.take_along_axis(score, idx, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return top * a.routed_scaling_factor, idx


def expert_block(a: Arch, numerics: str, y, w: Dict, shared: bool = True):
    """The held experts' part of the routed sum, plus (``shared``) the
    shared expert, no gate. y [T, H] (normed)."""
    top, idx = route(a, y, w)
    held = a.expert_offset + jnp.arange(a.n_routed_experts)
    wte = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                            top[:, :, None], 0.0), axis=1)      # [T, E]
    gate = _mm("th,ehf->tef", y, w["experts_gate_proj"], numerics)
    up = _mm("th,ehf->tef", y, w["experts_up_proj"], numerics)
    routed = _mm("tef,efh->th", jax.nn.silu(gate) * up * wte[:, :, None],
                 w["experts_down_proj"], numerics)
    if not shared:
        return routed
    return routed + swiglu(numerics, y, w["shared_gate_proj"],
                           w["shared_up_proj"], w["shared_down_proj"])


def block_layer(a: Arch, numerics: str, full: bool, dense: bool, x, lat, keys,
                w: Dict, positions, first):
    """One layer on a block of one sequence's tokens: x [T, H] at
    ``positions`` [T]. ``lat`` [N, c + rope] are this layer's latents of
    positions ``first .. first + N - 1`` (a full layer: the whole sequence,
    ``first`` 0; a sliding layer: from ``window - 1`` tokens before the
    block on, positions below 0 never visible) and ``keys`` the selector's
    keys of the same positions. ``full`` and ``dense`` are static."""
    y = rms_norm(x, w["input_layernorm"], a.rms_norm_eps)
    q_n, q_r, c_q0 = queries(a, numerics, full, y, w, positions)
    at = first + jnp.arange(lat.shape[0])
    back = positions[:, None] - at[None, :]
    blocks = visible_blocks(lat.shape[0], positions, first)
    if full:
        visible = select(a, selector_scores(a, numerics, y, c_q0, keys, w,
                                            positions, blocks), positions)
    else:
        visible = (back >= 0) & (back < a.sliding_window_size) & (at >= 0)[None]
    o = attention(a, numerics, full, q_n, q_r, lat, w, visible, blocks)
    g = jax.nn.sigmoid(_mm("th,hn->tn", y, w["gate_proj"], numerics))
    x = x + _mm("tnd,ndh->th", o * g[..., None], w["o_proj"], numerics)
    y = rms_norm(x, w["post_attention_layernorm"], a.rms_norm_eps)
    if dense:
        return x + swiglu(numerics, y, w["dense_gate_proj"],
                          w["dense_up_proj"], w["dense_down_proj"])
    return x + expert_block(a, numerics, y, w)


def layer(a: Arch, numerics: str, l: int, x, w: Dict, top: Dict):
    """One layer on one whole sequence x [T, H] (the tests' sizes)."""
    positions = jnp.arange(x.shape[0])
    w = dict(w, **mixer_weights(a, l, w))
    if a.is_dense(l):
        w.update({k: top[k][l] for k in DENSE_LEAVES})
    full = a.is_full(l)
    lat, keys = leaves_behind(a, numerics, full, x, w, positions)
    return block_layer(a, numerics, full, a.is_dense(l), x, lat, keys, w,
                       positions, 0)


def head_logits(a: Arch, numerics: str, x, norm, lm_head):
    return _mm("th,hv->tv", rms_norm(x, norm, a.rms_norm_eps), lm_head,
               numerics)


@functools.lru_cache(maxsize=None)
def _programs(a: Arch, numerics: str):
    """The jitted pieces, one set per (sizes, numerics)."""
    out = {"logits": jax.jit(functools.partial(head_logits, a, numerics))}
    for full in (True, False):
        out["behind", full] = jax.jit(functools.partial(
            leaves_behind, a, numerics, full))
        for dense in (True, False):
            out["layer", full, dense] = jax.jit(functools.partial(
                block_layer, a, numerics, full, dense))
    return out


def _to_host(tree: Dict, keys) -> Dict:
    return {k: np.asarray(tree.pop(k)) for k in keys if k in tree}


def blocks_read(arch: Arch, rows: Sequence[int], qb: int):
    """For each layer, the ``qb``-token blocks of its output that anything
    reads: of the last layer the blocks that hold a row; of the layer below a
    sliding layer the blocks that layer's window reaches from the blocks it
    computes; of the layer below a full layer every block up to the last."""
    P = arch.sliding_window_size - 1
    need, cur = [], {int(t) // qb for t in rows}
    for l in reversed(range(arch.num_hidden_layers)):
        need.insert(0, cur)
        cur = set(range(max(cur) + 1)) if arch.is_full(l) else {
            c for b in cur for c in range(max(0, (b * qb - P) // qb), b + 1)}
    return need


def forward_logits(arch: Arch, tokens: Sequence, rows: Sequence[Sequence[int]],
                   layer_weights: Callable[[int], Dict], top: Dict,
                   numerics: str = "float32"):
    """Full forward of each sequence in ``tokens`` (1-D int arrays) and the
    logits at the positions ``rows[i]`` of sequence i. Layers outermost, one
    layer's weights on the device at a time; each sequence's stream on the
    host, a block of ``QUERY_BLOCK`` tokens on the device at a time (module
    docstring, "Memory"). A sequence is cut behind the last row read, and a
    layer computes the blocks that a later layer or a row reads alone
    (``blocks_read``; the others keep the layer's input). ``top`` is
    emptied. Returns a list of float32 arrays ``[len(rows[i]), vocab]``."""
    p = _programs(arch, numerics)
    host = _to_host(top, [leaf.published for leaf in arch.leaf_table()
                          if not leaf.per_layer])
    qb, P = QUERY_BLOCK, arch.sliding_window_size - 1
    back = -(-P // qb) * qb                 # whole blocks behind a block
    xs, cut = [], []
    for t, r in zip(tokens, rows):
        n = min(-(-(int(max(r)) + 1) // qb) * qb, -(-len(t) // qb) * qb)
        ids = np.zeros(n, np.int64)
        ids[:min(n, len(t))] = np.asarray(t)[:n]
        xs.append(host["embed_tokens"][ids])
        cut.append(n)
    # one shape of the full layers' context for all: whole key blocks
    ceiling = -(-max(cut) // KEY_BLOCK) * KEY_BLOCK if max(cut) > KEY_BLOCK \
        else max(cut)
    need = [blocks_read(arch, r, qb) for r in rows]
    spare = [np.empty_like(x) for x in xs]  # a layer's output; then its input
    for l in range(arch.num_hidden_layers):
        w = dict(layer_weights(l))
        full, dense = arch.is_full(l), arch.is_dense(l)
        if dense:                           # drawn for every layer, unread
            for k in EXPERT_LEAVES:
                w.pop(k)
            w.update({k: jnp.asarray(host[k][l]) for k in DENSE_LEAVES})
        mine = mixer_weights(arch, l, w)
        w = {k: v for k, v in w.items()
             if not k.startswith(("attn_", "swa_"))}    # the other's: unread
        w.update(mine)
        for i, x in enumerate(xs):
            starts = range(0, cut[i], qb)
            at = {s: np.arange(s, s + qb, dtype=np.int32) for s in starts}
            behind = [p["behind", full](x[s:s + qb], w, at[s]) for s in starts]
            lat = jnp.concatenate([b[0] for b in behind])
            keys = jnp.concatenate([b[1] for b in behind])
            if full:
                pad = ceiling - cut[i]
                lat, keys = (jnp.pad(a, ((0, pad), (0, 0))) for a in (lat, keys))
            else:       # ``back`` rows of no token before position 0
                lat = jnp.pad(lat, ((back, 0), (0, 0)))
            del behind
            out, flying = spare[i], collections.deque()

            def land():
                s, y = flying.popleft()
                out[s:s + qb] = np.asarray(y)

            for s in starts:
                if s // qb not in need[i][l]:
                    out[s:s + qb] = x[s:s + qb]
                    continue
                if full:
                    ctx, ks, first = lat, keys, 0
                else:   # the block's own tokens and ``back`` before them
                    ctx = jax.lax.dynamic_slice_in_dim(lat, s, back + qb)
                    ks, first = keys[:back + qb], s - back
                y = p["layer", full, dense](x[s:s + qb], ctx, ks, w, at[s],
                                            np.int32(first))
                y.copy_to_host_async()
                flying.append((s, y))
                if len(flying) > IN_FLIGHT:     # the device works meanwhile
                    land()
            while flying:
                land()
            xs[i], spare[i] = out, x
            del lat, keys
        del w                               # before the next layer's come
    norm, head = jnp.asarray(host["norm"]), jnp.asarray(host["lm_head"])
    return [p["logits"](jnp.asarray(x[np.asarray(r)]), norm, head)
            for x, r in zip(xs, rows)]


def loss_and_grads(*args, **kwargs):
    raise NotImplementedError(
        "references/dots3_note.py gives no loss_and_grads: no training "
        "configuration names this reference (its share of one chip is a "
        "serving cut: training at 16 bytes a parameter does not fit)")

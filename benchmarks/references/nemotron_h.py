"""Plain Nemotron-H (``model_type`` ``nemotron_h``: NVIDIA Nemotron 3 Nano):
the forward pass, the loss and its gradients in straightforward ``jax.numpy``,
float32, every matrix product at ``highest`` precision. No kernel, no chunked
scan, no sorted rows, no row buffer, no checkpointing policy and no import
from the program: this file decides ``correct``, so it follows the published
``config.json`` and the public ``modeling_nemotron_h.py`` and nothing else.
What the configuration's keys alone do not say is marked (+) here and listed
under ``assumed`` in the configuration file.

    x_0 = E[tokens]
    block l (published index), one mixer each (+ the block's form):
      x = x + mixer_l(norm_l(x))          RMSNorm, eps layer_norm_epsilon,
                                          gain applied as xhat * g
    logits = lm_head(norm_f(x))           (untied)

``hybrid_override_pattern[l]`` names the mixer.

**M, Mamba-2** (``mamba_num_heads`` n heads of ``mamba_head_dim`` P: inner
width n P (+ not ``expand`` x hidden); ``n_groups`` G, ``ssm_state_size`` N,
``conv_kernel`` K; no projection bias, ``use_conv_bias``):
``[z | xBC | dt] = u W_in`` (widths n P | n P + 2 G N | n); ``xBC <-
silu(conv1d(xBC) + b)``, causal, depthwise, K taps, from a zero tail; ``[x |
B | C]`` (n P | G N | G N), head h reads group ``h // (n / G)``; ``dt <-
softplus(dt + dt_bias)`` (+ ``time_step_limit`` (0, inf): no clamp), ``A =
-exp(A_log)``; a head's state ``S [P, N]`` from zero, **the recurrence as it
is written, a token at a time**: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
B_t``, ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm_grouped(y * silu(z)) *
norm.weight`` (+ the gate first, then mean squares over each of the G groups
of n P / G channels); ``mixer = y W_out``.

**E, experts**: ``s = sigmoid(u W_r)`` in float32 over all the router's
outputs; the ``num_experts_per_tok`` largest of ``s +
e_score_correction_bias`` are chosen (``n_group`` = ``topk_group`` = 1: no
group limit); weights ``routed_scaling_factor * s_i / (sum of the chosen s +
1e-20)`` (``norm_topk_prob``; the bias chooses, never weighs); ``mixer = sum_i
w_i down_i(relu(up_i u)^2) + down_s(relu(up_s u)^2)``: two matrices an expert
(``mlp_hidden_act`` ``relu2``), no gate on the shared expert; the experts as
a loop over experts. No auxiliary loss: the published model balances by the
bias.

**\\*, attention**: ``num_attention_heads`` query and ``num_key_value_heads``
KV heads of ``head_dim``, no bias, causal softmax at scale ``head_dim^-1/2``,
``mixer = attn W_o``. (+) **No position encoding**, no QK-norm, no gate: the
family's modeling file applies none in its attention mixer; ``rope_theta`` and
``partial_rotary_factor`` stay in the configuration and are read by nothing.

Departures from the source, each also in the configuration's ``assumed``:

* **The chip's share.** ``n_routed_experts`` in the configuration is the
  number of routed experts *held here* (``Arch.n_routed_experts``), starting
  at ``expert_offset``; the router keeps the published ``router_outputs``.
  The sum runs over the chosen experts among the held ones; what the absent
  experts would have added is left out, as in the program, and that partial
  sum goes on to the next block. ``vocab_size`` is the slice of rows held
  here. ``num_hidden_layers`` blocks are held from ``first_layer`` on;
  ``hybrid_override_pattern`` stays as published and is read at the published
  index.
* The trainer's update of ``e_score_correction_bias`` between steps is no
  part of the forward or the backward: the reference takes the bias as drawn
  from the seed, which is what the check's step (the run's first) sees; the
  program moves it after every step. It gets no gradient.

**The leaves** (``harness/weights.py`` stacks a per-layer leaf over all the
blocks; ``runners/train.py`` draws one block of all for every name in
``CHECK_LAYER_LEAVES``): only the block's norm has a slot a block. The leaves
of each kind of block are *top* leaves with a leading axis over that kind's
own blocks (``[Mamba blocks, ...]``, ``[expert blocks, ...]``, ``[attention
blocks, ...]``), so that the training state holds no leaf for a part a block
lacks; every one of them is in ``CHECK_TOP_LEAVES``, and a top leaf is
compared whole.

**Memory.** One sequence and one block at a time; the recurrence in
segments of ``SEGMENT`` tokens, each recomputed in the backward (the states
kept are the segments' first: 2 MiB each at 64 x 64 x 128); attention in
blocks of ``QUERY_BLOCK`` queries, each recomputed likewise.

``numerics``: ``float32`` is the reference; ``fp8`` and ``bf16`` are the
*controls* (operands of every weight product, of the attention products and
of the recurrence's two products rounded to that type, accumulated in
float32; the router, the state, the decays and the step sizes stay float32).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import Leaf
from benchmarks.references.mistral import _mm, _round, rms_norm, rope
from benchmarks.references.trinity import _blocked

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
SEGMENT = 128
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# The scales of the normal draws that are no fan-in (``leaf_table``):
# ``A_log`` around 0 (A = -e^n: 0.14 to 7) and ``dt_bias`` at 2 n (with the
# projection's own unit draw the step sizes softplus(.) run from 0.02 to 4),
# so that a head's memory 1 / (dt |A|) runs from under one token to some
# hundreds, heads that forget at once and heads that carry a chunk's state
# over many chunks side by side; the published initialiser's ranges
# (``time_step_min`` .. ``time_step_max``, A in 1 .. 16) are one-sided and a
# zero-mean draw cannot give them. The taps at 0.5 (four of them keep the
# convolution's output at its input's size), their bias at 0.5 (large enough
# that a dropped bias shows); ``D`` and every gain 1 + 0.1 n.
A_LOG_SCALE, DT_BIAS_SCALE, TAP_SCALE, CONV_BIAS_SCALE = 1.0, 2.0, 0.5, 0.5
# The embedding is drawn at 1 and every mixer's output projection at fan-in
# over sqrt(published blocks) (``Arch.out_scale``: ``rescale_prenorm_residual``
# is true in the published configuration, and the family's initialiser divides
# each block's ``out_proj`` so), so that the stream carries the token and a
# block adds a seventh of it. With the embedding at 0.02 and the output
# projections at fan-in the stream *was* the blocks' outputs, and those share
# a vector over all tokens (a squared ReLU's hidden row has mean 0.5 beside a
# deviation of 1.1, the convolution's bias shifts every token alike): the
# router then saw a constant an expert, the fullest held expert got 2.75 times
# the mean at the first step, the pairs a token and block fell from 0.419 to
# 0.327 over a window where a share of 8 of 128 expects 0.375, and one seed
# routed 760 pairs beyond the row buffer in its first step (my chip runs, PR
# 57, seeds 2147484001 and -003). A model balanced by its bias loads its
# experts alike, and which experts a chip holds must not decide its step.
EMBED_SCALE = 1.0


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the equations need, under the published names;
    ``n_routed_experts`` counts the experts held here, ``num_hidden_layers``
    the blocks held from ``first_layer`` on."""

    hidden_size: int
    head_dim: int
    num_attention_heads: int
    num_key_value_heads: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    layer_norm_epsilon: float
    rope_theta: float
    vocab_size: int
    num_hidden_layers: int
    router_outputs: int
    expert_offset: int
    first_layer: int
    hybrid_override_pattern: str

    @classmethod
    def from_model(cls, model: Dict) -> "Arch":
        for key, want in (("mlp_hidden_act", "relu2"), ("norm_topk_prob", True),
                          ("n_group", 1), ("topk_group", 1),
                          ("n_shared_experts", 1), ("use_conv_bias", True),
                          ("mamba_proj_bias", False), ("mlp_bias", False),
                          ("attention_bias", False),
                          ("mamba_hidden_act", "silu"),
                          ("tie_word_embeddings", False)):
            if model.get(key) != want:
                raise ValueError(f"references/nemotron_h.py writes the block "
                                 f"down for {key}={want!r}, not "
                                 f"{model.get(key)!r}")
        a = cls(**{f.name: model[f.name] for f in dataclasses.fields(cls)})
        if set(a.hybrid_override_pattern) - {MAMBA, EXPERTS, ATTENTION} \
                or a.first_layer + a.num_hidden_layers > len(
                    a.hybrid_override_pattern):
            raise ValueError(f"blocks {a.first_layer}.."
                             f"{a.first_layer + a.num_hidden_layers} of the "
                             f"pattern {a.hybrid_override_pattern!r}")
        return a

    @property
    def kinds(self) -> str:
        """The held blocks' mixers, a letter each."""
        return self.hybrid_override_pattern[
            self.first_layer:self.first_layer + self.num_hidden_layers]

    def blocks_of(self, kind: str) -> int:
        return self.kinds.count(kind)

    def index_in_kind(self, l: int) -> int:
        """Block ``l``'s index among the held blocks of its own kind."""
        return self.kinds[:l].count(self.kinds[l])

    @property
    def out_scale(self) -> float:
        """What a mixer's output projection is drawn at, over its fan-in:
        ``1 / sqrt(blocks of the published model)``."""
        return 1.0 / math.sqrt(len(self.hybrid_override_pattern))

    @property
    def expert_layers(self) -> int:
        return self.blocks_of(EXPERTS)

    @property
    def num_experts(self) -> int:
        """The routed experts held here, under the name the readers of the
        step's routing counters use."""
        return self.n_routed_experts

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    # the places a fixture that breaks one mechanism overrides
    def gate_before_norm(self) -> bool:
        """Whether the Mamba-2 gate multiplies before the grouped norm."""
        return True

    def norm_groups(self) -> int:
        """Groups of channels the gated norm takes its mean squares over."""
        return self.n_groups

    def activation(self, u):
        """The experts' activation of the up-projection's output."""
        return jnp.square(jax.nn.relu(u))

    def rotates(self) -> bool:
        """Whether the attention mixer's queries and keys are rotated."""
        return False

    def leaf_table(self) -> Tuple[Leaf, ...]:
        """Every weight, as data for ``harness/weights.py``: its path in the
        program's tree (``deepspeed_tpu.models.hybrid``, a stack of one-mixer
        blocks), the name the equations below use, its shape and the scale of
        its normal draw (fan-in, so that activations stay of order one; None:
        a gain drawn around one). The router at fan-in and
        ``e_score_correction_bias`` at a quarter of the distance between
        neighbouring scores near the top, at most 0.01
        (``references/kimi_k2.py`` gives the readings behind both). The
        Mamba-2 mixer's own draws, the embedding's and the output
        projections' (``out_scale``) are the constants above, with the
        readings that led to them."""
        h, v, d = self.hidden_size, self.vocab_size, self.head_dim
        nq, nkv = self.num_attention_heads, self.num_key_value_heads
        n, di, cc = self.mamba_num_heads, self.mamba_inner, self.conv_channels
        e, f = self.n_routed_experts, self.moe_intermediate_size
        fs, R = self.moe_shared_expert_intermediate_size, self.router_outputs
        M, E, A = (self.blocks_of(k) for k in (MAMBA, EXPERTS, ATTENTION))
        fan, out = 1.0 / math.sqrt(h), self.out_scale
        return (
            Leaf("ln1.scale", "block_norm", (h,), None, True),
            Leaf("mamba2.w_in", "mamba_in_proj", (M, h, di + cc + n), fan,
                 False),
            Leaf("mamba2.conv", "mamba_conv1d", (M, self.conv_kernel, cc),
                 TAP_SCALE, False),
            Leaf("mamba2.conv_bias", "mamba_conv1d_bias", (M, cc),
                 CONV_BIAS_SCALE, False),
            Leaf("mamba2.A_log", "mamba_A_log", (M, n), A_LOG_SCALE, False),
            Leaf("mamba2.dt_bias", "mamba_dt_bias", (M, n), DT_BIAS_SCALE,
                 False),
            Leaf("mamba2.D", "mamba_D", (M, n), None, False),
            Leaf("mamba2.norm", "mamba_norm", (M, di), None, False),
            Leaf("mamba2.w_out", "mamba_out_proj", (M, di, h),
                 out / math.sqrt(di), False),
            Leaf("attn.wq", "q_proj", (A, h, nq, d), fan, False),
            Leaf("attn.wk", "k_proj", (A, h, nkv, d), fan, False),
            Leaf("attn.wv", "v_proj", (A, h, nkv, d), fan, False),
            Leaf("attn.wo", "o_proj", (A, nq, d, h),
                 out / math.sqrt(nq * d), False),
            Leaf("moe.router", "router", (E, h, R), fan, False),
            Leaf("moe.router_bias", "e_score_correction_bias", (E, R),
                 min(0.01, 0.75 / R), False),
            Leaf("moe.shared.wi", "shared_up_proj", (E, h, fs), fan, False),
            Leaf("moe.shared.wo", "shared_down_proj", (E, fs, h),
                 out / math.sqrt(fs), False),
            Leaf("experts.wi", "experts_up_proj", (E, e, h, f), fan, False),
            Leaf("experts.wo", "experts_down_proj", (E, e, f, h),
                 out / math.sqrt(f), False),
            Leaf("embed.tokens", "embed_tokens", (v, h), EMBED_SCALE, False),
            Leaf("final_norm.scale", "norm_f", (h,), None, False),
            Leaf("unembed.kernel", "lm_head", (h, v), 0.02, False),
        )


# which top leaves a kind of block reads (published names)
LEAVES_OF = {
    MAMBA: ("mamba_in_proj", "mamba_conv1d", "mamba_conv1d_bias",
            "mamba_A_log", "mamba_dt_bias", "mamba_D", "mamba_norm",
            "mamba_out_proj"),
    EXPERTS: ("router", "e_score_correction_bias", "shared_up_proj",
              "shared_down_proj", "experts_up_proj", "experts_down_proj"),
    ATTENTION: ("q_proj", "k_proj", "v_proj", "o_proj"),
}
# the gradient leaves the training check samples (published names): of one
# seeded block its norm; of the top every leaf of every kind of block (axis 0
# is the kind's own blocks, so each is compared whole), the final norm, the
# head and the embedding's sampled rows
CHECK_LAYER_LEAVES = ("block_norm",)
CHECK_TOP_LEAVES = tuple(
    n for n in LEAVES_OF[MAMBA] + LEAVES_OF[EXPERTS] + LEAVES_OF[ATTENTION]
    + ("norm_f", "lm_head", "embed_tokens")
    if n != "e_score_correction_bias")      # (it gets no gradient)


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Operations the forward and backward passes require per trained token
    on this share; recomputation is not counted. Forward 2 a weight a token
    touches: a Mamba-2 block's two projections and its taps, an expert
    block's router, shared expert and the ``top_k * held / router_outputs``
    routed experts a token finds here *on average* (two matrices each), an
    attention block's four projections, the head's rows held here (the
    embedding is a lookup); the scan in its chunked form at the causal half
    (``C . B`` a group and the masked product a head over ``(chunk + 1) / 2``
    tokens, a chunk's state and its read-out ``head x state`` a head each:
    what carries the states over the chunks is not counted, a loop would do
    it without a product); attention 2 products of ``(seq + 1) / 2`` keys by
    head_dim a query head. Backward twice the forward."""
    h, d, nq = a.hidden_size, a.head_dim, a.num_attention_heads
    n, P, G, N = (a.mamba_num_heads, a.mamba_head_dim, a.n_groups,
                  a.ssm_state_size)
    half = (a.chunk_size + 1) / 2.0
    mamba = (h * (a.mamba_inner + a.conv_channels + n) + a.mamba_inner * h
             + a.conv_kernel * a.conv_channels
             + half * (G * N + n * P) + 2 * n * P * N)
    here = a.num_experts_per_tok * a.n_routed_experts / a.router_outputs
    experts = (h * a.router_outputs
               + 2 * h * a.moe_shared_expert_intermediate_size
               + here * 2 * h * a.moe_intermediate_size)
    attn = (h * d * (2 * nq + 2 * a.num_key_value_heads)
            + 2 * (seq + 1) / 2.0 * d * nq)
    return 3.0 * 2.0 * (a.blocks_of(MAMBA) * mamba
                        + a.blocks_of(EXPERTS) * experts
                        + a.blocks_of(ATTENTION) * attn + h * a.vocab_size)


def recurrence(numerics: str, x, dt, A, B, C, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D
    x_t`` from ``S = 0``, a token at a time. x [T, n, P]; dt [T, n]; A, D
    [n]; B, C [T, G, N]. The outer product's and the read-out's operands are
    rounded under a control; the state and the decay never."""
    T, n, P = x.shape
    rep = n // B.shape[1]
    B, C = jnp.repeat(B, rep, axis=1), jnp.repeat(C, rep, axis=1)   # [T,n,N]

    def token(S, t):
        xt, dtt, Bt, Ct = t
        S = jnp.exp(dtt * A)[:, None, None] * S + _mm(
            "np,ns->nps", xt * dtt[:, None], Bt, numerics)
        return S, jnp.einsum("nps,ns->np", S, _round(Ct, numerics),
                             precision=HIGHEST)

    def segment(S, seg):
        return jax.lax.scan(token, S, seg)

    seg = SEGMENT if T % SEGMENT == 0 else T
    cut = tuple(a.reshape((T // seg, seg) + a.shape[1:])
                for a in (x, dt, B, C))
    _, y = jax.lax.scan(jax.checkpoint(segment),
                        jnp.zeros((n, P, B.shape[-1]), jnp.float32), cut)
    return y.reshape(T, n, P) + x * D[:, None]


def mamba_mixer(a: Arch, numerics: str, u, w: Dict):
    """The Mamba-2 mixer of one sequence u [T, H] (normed)."""
    n, P, G, N = (a.mamba_num_heads, a.mamba_head_dim, a.n_groups,
                  a.ssm_state_size)
    di, cc, K, T = a.mamba_inner, a.conv_channels, a.conv_kernel, u.shape[0]
    zxbcdt = _mm("th,hc->tc", u, w["mamba_in_proj"], numerics)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + cc], zxbcdt[:, di + cc:])
    past = jnp.concatenate([jnp.zeros((K - 1, cc), xbc.dtype), xbc])
    conv = sum(w["mamba_conv1d"][i] * past[i:i + T] for i in range(K))
    xbc = jax.nn.silu(conv + w["mamba_conv1d_bias"])
    x = xbc[:, :di].reshape(T, n, P)
    B = xbc[:, di:di + G * N].reshape(T, G, N)
    C = xbc[:, di + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + w["mamba_dt_bias"])
    y = recurrence(numerics, x, dt, -jnp.exp(w["mamba_A_log"]), B, C,
                   w["mamba_D"]).reshape(T, di)
    groups = a.norm_groups()

    def grouped_norm(t):
        t = t.reshape(T, groups, di // groups)
        return (t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True)
                                  + a.layer_norm_epsilon)).reshape(T, di)

    if a.gate_before_norm():
        y = grouped_norm(y * jax.nn.silu(z)) * w["mamba_norm"]
    else:
        y = grouped_norm(y) * w["mamba_norm"] * jax.nn.silu(z)
    return _mm("tc,ch->th", y, w["mamba_out_proj"], numerics)


def route(a: Arch, u, router, bias):
    """(weights [T, k] float32, experts [T, k]) by the published rule, in
    float32 whatever the numerics: which experts a token takes is not a
    matrix product's precision."""
    s = jax.nn.sigmoid(jnp.einsum("th,he->te", u, router, precision=HIGHEST))
    _, idx = jax.lax.top_k(s + bias, a.num_experts_per_tok)
    top = jnp.take_along_axis(s, idx, axis=-1)
    return (a.routed_scaling_factor * top
            / (jnp.sum(top, -1, keepdims=True) + 1e-20)), idx


def ffn(a: Arch, numerics: str, u, up, down):
    """``down(act(up u))``: an expert, routed or shared."""
    return _mm("tf,fh->th", a.activation(_mm("th,hf->tf", u, up, numerics)),
               down, numerics)


def expert_mixer(a: Arch, numerics: str, u, w: Dict):
    """The held experts' part of the routed sum, a loop over the experts
    held (each computed for every token and weighted by what the token's
    choice gives it, zero where it did not choose it), plus the shared
    expert. u [T, H] (normed)."""
    wt, idx = route(a, u, w["router"], w["e_score_correction_bias"])
    out = ffn(a, numerics, u, w["shared_up_proj"], w["shared_down_proj"])
    for e in range(a.n_routed_experts):
        weight = jnp.sum(jnp.where(idx == a.expert_offset + e, wt, 0.0), -1)
        out = out + weight[:, None] * ffn(
            a, numerics, u, w["experts_up_proj"][e],
            w["experts_down_proj"][e])
    return out


def attention_mixer(a: Arch, numerics: str, u, w: Dict):
    """Causal grouped-query attention of one sequence u [T, H] (normed), in
    blocks of queries; position is the index (and enters nowhere unless a
    fixture rotates)."""
    T, d = u.shape[0], a.head_dim
    q = _mm("th,hnd->tnd", u, w["q_proj"], numerics)
    k = _mm("th,hnd->tnd", u, w["k_proj"], numerics)
    v = _mm("th,hnd->tnd", u, w["v_proj"], numerics)
    if a.rotates():
        q, k = (rope(t, jnp.arange(T), a.rope_theta) for t in (q, k))
    group = a.num_attention_heads // a.num_key_value_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    j = jnp.arange(T)[None, :]

    def block(qb, ib):
        s = _mm("tnd,snd->nts", qb, k, numerics) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where((ib[:, None] >= j)[None], s, -jnp.inf),
                           axis=-1)
        return _mm("nts,snd->tnd", p, v, numerics)

    o = _blocked(block, QUERY_BLOCK, q, jnp.arange(T))
    return _mm("tnd,ndh->th", o, w["o_proj"], numerics)


MIXERS = {MAMBA: mamba_mixer, EXPERTS: expert_mixer, ATTENTION: attention_mixer}


def block(a: Arch, numerics: str, kind: str, x, norm, w: Dict):
    """One block on one sequence: ``x + mixer(norm(x))``. x [T, H]; ``w``
    the block's own leaves (``LEAVES_OF[kind]``, without the leading axis);
    ``kind`` is static."""
    return x + MIXERS[kind](a, numerics, rms_norm(x, norm, a.layer_norm_epsilon),
                            w)


def head_logits(a: Arch, numerics: str, x, norm, lm_head):
    return _mm("th,hv->tv", rms_norm(x, norm, a.layer_norm_epsilon), lm_head,
               numerics)


def _nll_sum(a, numerics, x, norm, lm_head, labels):
    logits = head_logits(a, numerics, x, norm, lm_head)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


def leaves_of(a: Arch, top: Dict, l: int) -> Dict:
    """Block ``l``'s own leaves out of the top stacks."""
    at = a.index_in_kind(l)
    return {n: top[n][at] for n in LEAVES_OF[a.kinds[l]]}


@functools.lru_cache(maxsize=None)
def _block_programs(a: Arch, numerics: str, kind: str):
    """One kind of block's jitted forward and backward."""
    def bwd(x, norm, w, dy):
        _, vjp = jax.vjp(lambda x_, n_, w_: block(a, numerics, kind, x_, n_,
                                                  w_), x, norm, w)
        return vjp(dy)

    return {"fwd": jax.jit(functools.partial(block, a, numerics, kind)),
            "bwd": jax.jit(bwd)}


@functools.lru_cache(maxsize=None)
def _programs(a: Arch, numerics: str):
    """The jitted pieces that no block kind enters."""
    def head(x, norm, lm_head, labels):
        return jax.value_and_grad(
            functools.partial(_nll_sum, a, numerics), argnums=(0, 1, 2))(
                x, norm, lm_head, labels)

    return {"head": jax.jit(head),
            "logits": jax.jit(functools.partial(head_logits, a, numerics))}


def forward_logits(arch: Arch, tokens: Sequence, rows: Sequence[Sequence[int]],
                   layer_weights: Callable[[int], Dict], top: Dict,
                   numerics: str = "float32"):
    """Full forward of each sequence in ``tokens`` (1-D int arrays) and the
    logits at the positions ``rows[i]`` of sequence i. Returns a list of
    float32 arrays ``[len(rows[i]), vocab]``."""
    p = _programs(arch, numerics)
    xs = [top["embed_tokens"][jnp.asarray(t)] for t in tokens]
    for l in range(arch.num_hidden_layers):
        fwd = _block_programs(arch, numerics, arch.kinds[l])["fwd"]
        norm, w = layer_weights(l)["block_norm"], leaves_of(arch, top, l)
        xs = [fwd(x, norm, w) for x in xs]
    return [p["logits"](x[jnp.asarray(r)], top["norm_f"], top["lm_head"])
            for x, r in zip(xs, rows)]


def loss_and_grads(arch: Arch, batch, layer_weights: Callable[[int], Dict],
                   top: Dict, keep: Callable[[str, object], object],
                   numerics: str = "float32") -> Dict:
    """Causal-LM loss (mean over every predicted token of the batch: no
    auxiliary term) and its gradient, one sequence and one block at a time.

    ``batch`` is ``[B, S + 1]`` token ids: inputs ``[:, :-1]``, labels
    ``[:, 1:]``. ``keep(name, grad)`` is called once for every gradient leaf
    (``"layers.3.block_norm"``, ``"mamba_in_proj"`` ``[Mamba blocks, ...]``,
    ``"norm_f"``, ...) and returns what the caller wants kept of it.
    Returns ``{"loss", "grad_norm", "kept": {name: value}}``."""
    p = _programs(arch, numerics)
    B, S = batch.shape[0], batch.shape[1] - 1
    denom = jnp.float32(B * S)
    inputs, labels = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])
    L = arch.num_hidden_layers
    acts = [[top["embed_tokens"][inputs[b]] for b in range(B)]]
    for l in range(L):
        fwd = _block_programs(arch, numerics, arch.kinds[l])["fwd"]
        norm, w = layer_weights(l)["block_norm"], leaves_of(arch, top, l)
        acts.append(jax.block_until_ready([fwd(x, norm, w)
                                           for x in acts[-1]]))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    sq = jax.jit(lambda t: sum(jnp.sum(g * g) for g in jax.tree.leaves(t)))
    nll, dxs, gtop = jnp.float32(0), [], None
    for b in range(B):
        val, (dx, dnorm, dhead) = p["head"](acts[-1][b], top["norm_f"],
                                            top["lm_head"], labels[b])
        nll += val
        dxs.append(dx / denom)
        g = {"norm_f": dnorm / denom, "lm_head": dhead / denom}
        gtop = jax.block_until_ready(g if gtop is None else add(gtop, g))
    acts.pop()
    kept, sq_sum = {}, sq(gtop)
    for name, g in gtop.items():
        kept[name] = keep(name, g)
    del gtop
    stacks = {n: [None] * arch.blocks_of(kind)
              for kind, names in LEAVES_OF.items() for n in names}
    for l in reversed(range(L)):
        bwd = _block_programs(arch, numerics, arch.kinds[l])["bwd"]
        norm, w, xs = (layer_weights(l)["block_norm"],
                       leaves_of(arch, top, l), acts.pop())
        gn = gw = None
        for b in range(B):
            dxs[b], gnorm, gleaves = bwd(xs[b], norm, w, dxs[b])
            # one sequence's gradients in flight at a time
            gn, gw = jax.block_until_ready(
                (gnorm, gleaves) if gn is None
                else (add(gn, gnorm), add(gw, gleaves)))
        sq_sum += sq(gn) + sq(gw)
        kept[f"layers.{l}.block_norm"] = keep(f"layers.{l}.block_norm", gn)
        for name, g in gw.items():
            stacks[name][arch.index_in_kind(l)] = g
        del norm, w, gn, gw, xs
    for name, parts in stacks.items():
        if parts:
            kept[name] = keep(name, jnp.stack(parts))
        parts.clear()
    gemb = jnp.zeros_like(top["embed_tokens"])
    for b in range(B):
        gemb = gemb.at[inputs[b]].add(dxs[b])
    sq_sum += jnp.sum(gemb * gemb)
    kept["embed_tokens"] = keep("embed_tokens", gemb)
    return {"loss": float(nll / denom), "grad_norm": float(jnp.sqrt(sq_sum)),
            "kept": {k: v for k, v in kept.items() if v is not None}}

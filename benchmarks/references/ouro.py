"""Plain Ouro (a looped language model): the forward pass in straightforward
``jax.numpy``, float32, every matrix product at ``highest`` precision. No
kernel, no cache, no batching trick, and no import from the program: this
file decides ``correct``, so it follows the published description (Zhu et
al. 2025, "Scaling Latent Reasoning via Looped Language Models"; the
family's ``modeling_ouro.py``: ``OuroModel.forward``,
``OuroDecoderLayer.forward``, ``OuroForCausalLM.forward``) and nothing else.
``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``:

    x = E[tokens]
    for t in 0 .. total_ut_steps - 1:        # the SAME layers in every pass
      for l in 0 .. num_hidden_layers - 1:
        n = rms(x; g1_l)                                    input_layernorm
        a = x + rms(Wo_l . attn_{t,l}(rope(Wq_l n), rope(Wk_l n), Wv_l n); g1'_l)
                                                            input_layernorm_2
        m = rms(a; g2_l)                                    post_attention_layernorm
        x = a + rms(Wd_l . (silu(Wg_l m) * Wu_l m); g2'_l)  post_attention_layernorm_2
      x = rms(x; g_f)       # the model's ONE final norm, after every pass;
                            # its output enters pass t + 1
      h_t = x ;  lam_t = sigmoid(w_e . h_t + b_e)           early_exit_gate
    p_t = lam_t * prod_{s<t} (1 - lam_s) for t < T - 1, p_{T-1} the remainder
    logits = h_{T-1} . U    # early_exit_threshold 1: no cumulative
                            # probability reaches 1 before the last pass

``attn_{t,l}`` is causal attention over the keys and values that **pass t of
layer l** made for every earlier position (the published cache,
``UniversalTransformerCache``, keeps them under index ``t * L + l``: a token
keeps ``cache_layers = total_ut_steps * num_hidden_layers`` layer slots of
K/V while the weights stay ``num_hidden_layers`` layers). Here there is no
cache: each pass is a full causal forward over the whole sequence, which is
the same thing. Heads: ``num_attention_heads`` query heads on
``num_key_value_heads`` key/value heads (16 on 16 as published: no
grouping), rotary on the whole head (``rotate_half``).

Assumed, because the catalog row (``config.json``) cannot confirm them; each
configuration file lists them too:

* no bias on the four attention projections;
* no QK-norm;
* the exit gate is ``Linear(hidden_size -> 1)`` *with* a bias;
* below a threshold of 1 the published rule picks the first pass whose
  cumulative exit probability reaches the threshold, every pass still being
  run: this file is written for the threshold 1 alone and refuses another.

Weights arrive as data, one layer at a time, from a function of the layer
index, and the same ``num_hidden_layers`` layers are drawn again in each
pass; the reference never holds more than one layer in float32. The gate is
drawn so that no ``lam_t`` saturates in float32 or in bfloat16 (a saturated
gate would make the exit distribution a constant that any program matches):
``h_t`` is a normed vector (each component of order one), ``w_e`` is normal
at half of fan-in (``0.5 / sqrt(hidden_size)``) and ``b_e`` normal at 0.5,
so ``w_e . h_t + b_e`` has a standard deviation near 0.7 and ``lam_t`` stays
within (0.02, 0.98) to six deviations; bfloat16 rounds a sigmoid to 1 only
past 6.2. ``Numerics`` is the one switch: ``float32`` is the reference,
``fp8`` and ``bf16`` are the *controls* that the comparison has to refuse
(operands of every matrix product rounded to that type first, accumulated in
float32).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import Leaf

# The draw of the two norm gains behind a branch (``input_layernorm_2``,
# ``post_attention_layernorm_2``): normal around zero at a quarter. A gain
# decides how large a branch's update is beside the stream. Drawn around one
# (as the gains before a branch are) every one of the 384 updates of a token
# has the norm of the stream's first, and a stack of random layers that deep
# is chaotic: on the chip the program's bfloat16 then read 0.25-0.30 from this
# file's float32 and the fp8 control 1.10-1.14, which is what two unrelated
# outputs read (my chip run, PR 52: PERF.md section 2), so the comparison
# told little. At a quarter an update is a part of the stream, as in a
# trained model, and rounding stays rounding. A dropped gain reads as one and
# shows all the more.
POST_GAIN = 0.25

WANTED = (("hidden_act", "silu"), ("tie_word_embeddings", False),
          ("rope_scaling", None), ("use_sliding_window", False))


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the equations need, under the published names."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    vocab_size: int
    num_hidden_layers: int
    rope_theta: float
    rms_norm_eps: float
    total_ut_steps: int
    early_exit_threshold: float

    @classmethod
    def from_model(cls, model: Dict) -> "Arch":
        for key, want in WANTED:
            if model.get(key, want) != want:
                raise ValueError(f"references/ouro.py writes the layer down "
                                 f"for {key}={want!r}, not {model[key]!r}")
        if model["early_exit_threshold"] < 1:
            raise ValueError(
                "references/ouro.py is written for early_exit_threshold 1 "
                "(the logits are the last pass's); the selection rule below "
                f"it is assumed, not confirmed: got "
                f"{model['early_exit_threshold']!r}")
        names = [f.name for f in dataclasses.fields(cls)]
        return cls(**{k: model[k] for k in names})

    @property
    def cache_layers(self) -> int:
        """Layer slots of keys and values a token keeps: one a pass and
        layer."""
        return self.total_ut_steps * self.num_hidden_layers

    def leaf_table(self) -> Tuple[Leaf, ...]:
        """Every weight, as data for ``harness/weights.py``: its path in the
        program's tree (the layout ``deepspeed_tpu.models.transformer``
        takes, layer leaves stacked on axis 0), the published name the
        equations above use, its shape and the scale of its normal draw:
        fan-in for the matrices, so that a branch's output is of order one
        before its norm; None for a norm gain before a branch and for the
        final one, drawn around one so that a dropped gain would show; the
        two gains *behind* a branch normal at ``POST_GAIN``; the embedding at
        1.0, so that the stream a layer reads is of order one from the first
        layer on and a branch's normed output a part of it, as in a trained
        model; the gate as the module's docstring says."""
        h, nq, nkv, d, f, v = (self.hidden_size, self.num_attention_heads,
                               self.num_key_value_heads, self.head_dim,
                               self.intermediate_size, self.vocab_size)
        fan = 1.0 / math.sqrt(h)
        return (
            Leaf("attn.wq", "q_proj", (h, nq, d), fan, True),
            Leaf("attn.wk", "k_proj", (h, nkv, d), fan, True),
            Leaf("attn.wv", "v_proj", (h, nkv, d), fan, True),
            Leaf("attn.wo", "o_proj", (nq, d, h), 1.0 / math.sqrt(nq * d),
                 True),
            Leaf("mlp.wg", "gate_proj", (h, f), fan, True),
            Leaf("mlp.wi", "up_proj", (h, f), fan, True),
            Leaf("mlp.wo", "down_proj", (f, h), 1.0 / math.sqrt(f), True),
            Leaf("ln1.scale", "input_layernorm", (h,), None, True),
            Leaf("ln1_post.scale", "input_layernorm_2", (h,), POST_GAIN, True),
            Leaf("ln2.scale", "post_attention_layernorm", (h,), None, True),
            Leaf("ln2_post.scale", "post_attention_layernorm_2", (h,),
                 POST_GAIN, True),
            Leaf("embed.tokens", "embed_tokens", (v, h), 1.0, False),
            Leaf("final_norm.scale", "norm", (h,), None, False),
            Leaf("exit_gate.kernel", "early_exit_gate", (h, 1), 0.5 * fan,
                 False),
            Leaf("exit_gate.bias", "early_exit_gate_bias", (1,), 0.5, False),
            Leaf("unembed.kernel", "lm_head", (h, v), 0.02, False),
        )


CHECK_LAYER_LEAVES = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                      "up_proj", "down_proj")
CHECK_TOP_LEAVES = ("norm", "lm_head")


def layer_matmul_params(a: Arch) -> int:
    """One layer's weights that multiply every token in every pass."""
    h, d = a.hidden_size, a.head_dim
    attn = h * d * (2 * a.num_attention_heads + 2 * a.num_key_value_heads)
    return attn + 3 * h * a.intermediate_size


def serve_flops_per_token(a: Arch, context: float) -> float:
    """Operations one token row requires of a forward pass, whatever program
    carries it (a prompt's chunk or a decode step): 2 a weight of the layers
    in **every pass** and of the head once, and per pass and layer two
    products of ``context`` keys by ``head_dim`` per query head. The exit
    gate (4,096 operations a pass) is not counted: the step programs at the
    threshold 1 do not compute it."""
    per_pass = a.num_hidden_layers * (
        2.0 * layer_matmul_params(a)
        + 2 * 2.0 * context * a.head_dim * a.num_attention_heads)
    return a.total_ut_steps * per_pass + 2.0 * a.hidden_size * a.vocab_size


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Forward and backward of the whole loop per trained token (no
    configuration trains this model: the published objective is not in the
    catalog row)."""
    return 3.0 * serve_flops_per_token(a, seq / 2.0)


def _round(x, numerics: str):
    """Round a matrix-product operand to the control's type. fp8 is e4m3
    with one scale for the tensor (amax -> 448)."""
    if numerics == "float32":
        return x
    if numerics == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if numerics == "fp8":
        scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    raise ValueError(f"unknown numerics {numerics!r}")


def _mm(spec: str, a, b, numerics: str):
    return jnp.einsum(spec, _round(a, numerics), _round(b, numerics),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, positions, theta: float):
    """x [T, heads, D]; rotate the halves (x1, x2) by position * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def keys_and_values(arch: Arch, numerics: str, n, w: Dict, positions):
    """What one pass of one layer keeps a token: its rotated key and its
    value, from the normed stream ``n`` [T, H]."""
    k = _mm("th,hnd->tnd", n, w["k_proj"], numerics)
    v = _mm("th,hnd->tnd", n, w["v_proj"], numerics)
    return rope(k, positions, arch.rope_theta), v


def attention(q, k, v, numerics: str):
    """Causal attention. q [T, nq, D]; k, v [T, nkv, D] (nq a multiple of
    nkv; equal as published)."""
    T, nq, D = q.shape
    group = nq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = _mm("tnd,snd->nts", q, k, numerics) / jnp.sqrt(jnp.float32(D))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return _mm("nts,snd->tnd", p, v, numerics)


def layer(arch: Arch, numerics: str, x, w: Dict, positions, kv=None):
    """One block in one pass on one sequence. x [T, H]; ``w`` holds this
    layer's matrices under the published names (float32). Returns the
    block's output and the keys and values this pass of it made (``kv``: to
    attend over others instead, which no published model does; the planted
    faults of the tests do)."""
    eps = arch.rms_norm_eps
    n = rms_norm(x, w["input_layernorm"], eps)
    q = rope(_mm("th,hnd->tnd", n, w["q_proj"], numerics), positions,
             arch.rope_theta)
    made = keys_and_values(arch, numerics, n, w, positions)
    k, v = made if kv is None else kv
    o = _mm("tnd,ndh->th", attention(q, k, v, numerics), w["o_proj"], numerics)
    a = x + rms_norm(o, w["input_layernorm_2"], eps)
    m = rms_norm(a, w["post_attention_layernorm"], eps)
    gate = _mm("th,hf->tf", m, w["gate_proj"], numerics)
    up = _mm("th,hf->tf", m, w["up_proj"], numerics)
    d = _mm("tf,fh->th", jax.nn.silu(gate) * up, w["down_proj"], numerics)
    return a + rms_norm(d, w["post_attention_layernorm_2"], eps), made


def pass_norm(arch: Arch, x, norm):
    """The model's one final norm, applied after every pass."""
    return rms_norm(x, norm, arch.rms_norm_eps)


def next_pass_input(x, normed):
    """What enters pass t + 1 of what pass t left (``x``) and its norm: the
    norm's output, as published (``hidden_states = self.norm(hidden_states)``
    inside the loop)."""
    return normed


def exit_distribution(arch: Arch, hs: Sequence, gate, bias):
    """``hs``: each pass's normed output [T, H]. Returns [T, passes]: pass t
    takes ``lam_t`` of what the passes before it left, the last pass the
    remainder."""
    left, out = 1.0, []
    for h in hs[:-1]:
        lam = jax.nn.sigmoid(jnp.einsum(
            "th,ho->t", h, gate, precision=jax.lax.Precision.HIGHEST) + bias[0])
        out.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(out + [left * jnp.ones_like(out[0])], axis=-1)


def head_logits(arch: Arch, numerics: str, h, lm_head):
    """``h`` is the last pass's normed output: the head has no norm of its
    own beyond the loop's."""
    return _mm("th,hv->tv", h, lm_head, numerics)


@functools.lru_cache(maxsize=None)
def _programs(arch: Arch, numerics: str):
    """The jitted pieces, one set per (sizes, numerics)."""
    return {"fwd": jax.jit(lambda x, w, pos: layer(arch, numerics, x, w,
                                                   pos)[0]),
            "norm": jax.jit(functools.partial(pass_norm, arch)),
            "logits": jax.jit(functools.partial(head_logits, arch, numerics)),
            "exit": jax.jit(functools.partial(exit_distribution, arch))}


def passes(arch: Arch) -> range:
    return range(arch.total_ut_steps)


def forward_logits(arch: Arch, tokens: Sequence, rows: Sequence[Sequence[int]],
                   layer_weights: Callable[[int], Dict], top: Dict,
                   numerics: str = "float32", exit_probs: bool = False):
    """Full forward of each sequence in ``tokens`` (1-D int arrays of any
    lengths) and the logits at the positions ``rows[i]`` of sequence i.
    Passes outermost, then layers, so one layer's weights live at a time
    (drawn again in each pass). Returns a list of float32 arrays
    ``[len(rows[i]), vocab]``; with ``exit_probs`` also the exit
    distribution at those rows, ``[len(rows[i]), passes]`` each."""
    p = _programs(arch, numerics)
    xs = [top["embed_tokens"][jnp.asarray(t)] for t in tokens]
    pos = [jnp.arange(len(t)) for t in tokens]
    hs = []
    for _ in passes(arch):
        for l in range(arch.num_hidden_layers):
            w = layer_weights(l)
            xs = [p["fwd"](x, w, ps) for x, ps in zip(xs, pos)]
            del w
        normed = [p["norm"](x, top["norm"]) for x in xs]
        hs.append([h[jnp.asarray(r)] for h, r in zip(normed, rows)])
        xs = [next_pass_input(x, h) for x, h in zip(xs, normed)]
    logits = [p["logits"](h, top["lm_head"]) for h in hs[-1]]
    if not exit_probs:
        return logits
    return logits, [p["exit"]([h[i] for h in hs], top["early_exit_gate"],
                              top["early_exit_gate_bias"])
                    for i in range(len(tokens))]


def loss_and_grads(*args, **kwargs):
    raise NotImplementedError(
        "references/ouro.py gives no loss_and_grads: no training "
        "configuration names this reference (the published objective, the "
        "expected loss over exit passes with an entropy term, is not in the "
        "catalog row)")

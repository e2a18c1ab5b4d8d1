"""Plain Mistral: the forward pass, the loss and its gradients in
straightforward ``jax.numpy``, float32, every matrix product at
``highest`` precision. No kernel, no cache, no batching trick, and no
import from the program: this file decides ``correct``, so it follows the
published description (Jiang et al. 2023, arXiv:2310.06825; the
``MistralForCausalLM`` layer equations) and nothing else.

    h_0   = E[tokens]
    a_l   = h_l + Wo . attn(rope(Wq n1), rope(Wk n1), Wv n1),  n1 = rms(h_l) g1
    h_l+1 = a_l + Wd . (silu(Wg n2) * Wu n2),                  n2 = rms(a_l) g2
    logits = rms(h_L) g_f . U          (untied head)

Attention is causal and grouped: query head ``i`` reads key/value head
``i // (n_q / n_kv)``. Rotary embedding rotates the two halves of a head
(the ``rotate_half`` convention of the published implementation).
Departure from the source, noted in every configuration file: the
published 4096-token sliding window is not applied, because no context
here reaches it.

Weights arrive as data, one layer at a time, from a function of the layer
index; the reference never holds more than one layer in float32. ``Numerics``
is the one switch: ``float32`` is the reference, ``fp8`` and ``bf16`` are the
*controls* that the comparison has to refuse (operands of every matrix
product rounded to that type first, accumulated in float32).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import Leaf


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

    @classmethod
    def from_model(cls, model: Dict) -> "Arch":
        names = [f.name for f in dataclasses.fields(cls)]
        return cls(**{k: model[k] for k in names})

    def leaf_table(self) -> Tuple[Leaf, ...]:
        """Every weight, as data for ``harness/weights.py``: its path in
        the program's tree (the layout ``deepspeed_tpu.models.transformer``
        takes, layer leaves stacked on axis 0), the published name the
        equations below use, its shape and the scale of its normal draw
        (the usual fan-in rule, so that activations stay of order one
        through the depth; None: a norm gain, drawn around one so that a
        dropped gain would show)."""
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
            Leaf("ln2.scale", "post_attention_layernorm", (h,), None, True),
            Leaf("embed.tokens", "embed_tokens", (v, h), 0.02, False),
            Leaf("final_norm.scale", "norm", (h,), None, False),
            Leaf("unembed.kernel", "lm_head", (h, v), 0.02, False),
        )


# the gradient leaves the training check samples (published names): one
# seeded layer's seven matrices, and of the top the final norm and the head
CHECK_LAYER_LEAVES = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                      "up_proj", "down_proj")
CHECK_TOP_LEAVES = ("norm", "lm_head")


def matmul_params(a: Arch) -> int:
    """Weights that multiply every token: the blocks and the head (the
    embedding is a lookup)."""
    h, d = a.hidden_size, a.head_dim
    attn = h * d * (2 * a.num_attention_heads + 2 * a.num_key_value_heads)
    mlp = 3 * h * a.intermediate_size
    return a.num_hidden_layers * (attn + mlp) + h * a.vocab_size


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Operations the forward and backward passes require per trained
    token; recomputation is not counted (the numerator of model-FLOP/s
    utilization, not of hardware utilization). Forward 2 FLOP per weight;
    causal attention, averaged over the positions of a full sequence, 2
    products of seq/2 keys by head_dim per query head: 2 * 2 * (seq / 2) *
    head_dim * heads per layer. Backward twice the forward."""
    fwd = 2.0 * matmul_params(a) + (a.num_hidden_layers * 2.0 * seq
                                    * a.head_dim * a.num_attention_heads)
    return 3.0 * fwd


def _round(x, numerics: str):
    """Round a matrix-product operand to the control's type. fp8 is e4m3
    with one scale for the tensor (amax -> 448); gradients pass straight
    through the rounding — the usual recipe, and the one the program's own
    ``fp8_matmul_ste`` uses, so the control is the step that would tempt."""
    if numerics == "float32":
        return x
    if numerics == "bf16":
        low = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif numerics == "fp8":
        scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        low = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    else:
        raise ValueError(f"unknown numerics {numerics!r}")
    return x + jax.lax.stop_gradient(low - x)


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


def attention(q, k, v, numerics: str):
    """Causal grouped-query attention. q [T, nq, D]; k, v [T, nkv, D]."""
    T, nq, D = q.shape
    group = nq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = _mm("tnd,snd->nts", q, k, numerics) / jnp.sqrt(jnp.float32(D))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return _mm("nts,snd->tnd", p, v, numerics)


def layer(arch: Arch, numerics: str, x, w: Dict, positions):
    """One block on one sequence. x [T, H]; ``w`` holds this layer's
    matrices under the published names (float32)."""
    n1 = rms_norm(x, w["input_layernorm"], arch.rms_norm_eps)
    q = _mm("th,hnd->tnd", n1, w["q_proj"], numerics)
    k = _mm("th,hnd->tnd", n1, w["k_proj"], numerics)
    v = _mm("th,hnd->tnd", n1, w["v_proj"], numerics)
    q = rope(q, positions, arch.rope_theta)
    k = rope(k, positions, arch.rope_theta)
    a = x + _mm("tnd,ndh->th", attention(q, k, v, numerics), w["o_proj"],
                numerics)
    n2 = rms_norm(a, w["post_attention_layernorm"], arch.rms_norm_eps)
    gate = _mm("th,hf->tf", n2, w["gate_proj"], numerics)
    up = _mm("th,hf->tf", n2, w["up_proj"], numerics)
    return a + _mm("tf,fh->th", jax.nn.silu(gate) * up, w["down_proj"],
                   numerics)


def head_logits(arch: Arch, numerics: str, x, norm, lm_head):
    return _mm("th,hv->tv", rms_norm(x, norm, arch.rms_norm_eps), lm_head,
               numerics)


def _nll_sum(arch, numerics, x, norm, lm_head, labels):
    logits = head_logits(arch, numerics, x, norm, lm_head)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


@functools.lru_cache(maxsize=None)
def _programs(arch: Arch, numerics: str):
    """The jitted pieces, one set per (sizes, numerics)."""
    fwd = jax.jit(functools.partial(layer, arch, numerics))

    def bwd(x, w, positions, dy):
        _, vjp = jax.vjp(lambda x_, w_: layer(arch, numerics, x_, w_,
                                              positions), x, w)
        return vjp(dy)

    def head(x, norm, lm_head, labels):
        return jax.value_and_grad(
            functools.partial(_nll_sum, arch, numerics), argnums=(0, 1, 2))(
                x, norm, lm_head, labels)

    return {"fwd": fwd, "bwd": jax.jit(bwd), "head": jax.jit(head),
            "logits": jax.jit(functools.partial(head_logits, arch,
                                                numerics))}


def forward_logits(arch: Arch, tokens: Sequence, rows: Sequence[Sequence[int]],
                   layer_weights: Callable[[int], Dict], top: Dict,
                   numerics: str = "float32"):
    """Full forward of each sequence in ``tokens`` (1-D int arrays of any
    lengths) and the logits at the positions ``rows[i]`` of sequence i.
    Layers outermost, so one layer's weights live at a time. Returns a
    list of float32 arrays ``[len(rows[i]), vocab]``."""
    p = _programs(arch, numerics)
    xs = [top["embed_tokens"][jnp.asarray(t)] for t in tokens]
    pos = [jnp.arange(len(t)) for t in tokens]
    for l in range(arch.num_hidden_layers):
        w = layer_weights(l)
        xs = [p["fwd"](x, w, ps) for x, ps in zip(xs, pos)]
        del w
    return [p["logits"](x[jnp.asarray(r)], top["norm"], top["lm_head"])
            for x, r in zip(xs, rows)]


def loss_and_grads(arch: Arch, batch, layer_weights: Callable[[int], Dict],
                   top: Dict, keep: Callable[[str, object], object],
                   numerics: str = "float32") -> Dict:
    """Causal-LM loss (mean over every predicted token of the batch) and
    its gradient, one sequence and one layer at a time.

    ``batch`` is ``[B, S + 1]`` token ids: inputs ``[:, :-1]``, labels
    ``[:, 1:]``. ``keep(name, grad)`` is called once for every gradient
    leaf (``"layers.3.q_proj"``, ``"norm"``, ``"lm_head"``,
    ``"embed_tokens"``) and returns what the caller wants kept of it (a
    slice, or None). Returns ``{"loss", "grad_norm", "kept": {name:
    value}}``; nothing of size stays on the device."""
    p = _programs(arch, numerics)
    B, S = batch.shape[0], batch.shape[1] - 1
    denom = jnp.float32(B * S)
    inputs, labels = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])
    pos = jnp.arange(S)
    acts = [[top["embed_tokens"][inputs[b]] for b in range(B)]]
    for l in range(arch.num_hidden_layers):
        w = layer_weights(l)
        # wait for each layer: run ahead, the host would have every
        # layer's float32 weights made before the first is used
        acts.append(jax.block_until_ready([p["fwd"](x, w, pos)
                                           for x in acts[-1]]))
        del w
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    sq = jax.jit(lambda t: sum(jnp.sum(g * g) for g in jax.tree.leaves(t)))
    nll, dxs, gtop = jnp.float32(0), [], None
    for b in range(B):
        val, (dx, dnorm, dhead) = p["head"](acts[-1][b], top["norm"],
                                            top["lm_head"], labels[b])
        nll += val
        dxs.append(dx / denom)
        g = {"norm": dnorm / denom, "lm_head": dhead / denom}
        gtop = jax.block_until_ready(g if gtop is None else add(gtop, g))
    acts.pop()
    kept, sq_sum = {}, sq(gtop)
    for name, g in gtop.items():
        kept[name] = keep(name, g)
    del gtop
    for l in reversed(range(arch.num_hidden_layers)):
        w, gl, xs = layer_weights(l), None, acts.pop()
        for b in range(B):
            dxs[b], gw = p["bwd"](xs[b], w, pos, dxs[b])
            # one sequence's gradients in flight at a time: dispatched
            # ahead, every sequence's would be allocated at once
            gl = jax.block_until_ready(gw if gl is None else add(gl, gw))
        sq_sum += sq(gl)
        for name, g in gl.items():
            kept[f"layers.{l}.{name}"] = keep(f"layers.{l}.{name}", g)
        del w, gl, xs
    gemb = jnp.zeros_like(top["embed_tokens"])
    for b in range(B):
        gemb = gemb.at[inputs[b]].add(dxs[b])
    sq_sum += jnp.sum(gemb * gemb)
    kept["embed_tokens"] = keep("embed_tokens", gemb)
    return {"loss": float(nll / denom), "grad_norm": float(jnp.sqrt(sq_sum)),
            "kept": {k: v for k, v in kept.items() if v is not None}}

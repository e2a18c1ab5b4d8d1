"""Plain Trinity (``model_type`` ``afmoe``: Arcee Trinity Mini / Nano): the
forward pass, the loss and its gradients in straightforward ``jax.numpy``,
float32, every matrix product at ``highest`` precision. No kernel, no sorted
rows, no row buffer, no checkpointing policy and no import from the program:
this file decides ``correct``, so it follows the published ``config.json``
and the public ``modeling_afmoe.py`` of ``transformers`` and nothing else.
What the configuration's keys alone do not say is marked (+) here and listed
under ``assumed`` in the configuration file.

Every RMSNorm has eps ``rms_norm_eps`` and a gain applied as ``xhat * g``.

    x_0 = E[tokens] * sqrt(hidden_size)                 (+ ``mup_enabled``)
    layer l (published index), four norms (+ sandwich):
      x = x + post_attention_layernorm(attn_l(input_layernorm(x)))
      x = x + post_mlp_layernorm(ffn_l(pre_mlp_layernorm(x)))
    logits = lm_head(norm(x))                           (untied)

``attn_l(y)``: ``q = q_norm(W_q y)``, ``k = k_norm(W_k y)`` (RMSNorm over the
head, a gain each), ``v = W_v y``, ``g = W_g y`` (+ ``gate_proj``: an output
gate as wide as the queries); query head ``i`` reads key/value head ``i //
(n_q / n_kv)``. **Rotary** (theta ``rope_theta``, the whole head, the two
halves rotated) **on q and k only where** ``layer_types[l]`` **is**
``sliding_attention``; (+) a ``full_attention`` layer has no position
encoding. Scores ``q k^T / sqrt(head_dim)``; key ``j`` is visible to query
``i`` iff ``0 <= i - j`` and, in a sliding layer, ``i - j < sliding_window``.
``out = W_o (softmax(scores) v * sigmoid(g))``. No bias anywhere.

``ffn_l``, ``l < num_dense_layers``: SwiGLU of ``intermediate_size``.
Otherwise ``s = sigmoid(W_r y)`` in float32 over all the router's outputs;
the ``num_experts_per_tok`` largest of ``s + expert_bias`` are chosen
(``n_group`` = ``topk_group`` = 1: no group limit); weights ``w_e =
route_scale * s_e / (sum of the chosen s + 1e-20)`` (``route_norm``; the bias
chooses, never weighs); ``ffn(y) = shared(y) + sum_e w_e expert_e(y)``,
every expert a SwiGLU of ``moe_intermediate_size``, the shared one ungated.
No auxiliary loss: the published model balances by the bias.

Departures from the source, each also in the configuration's ``assumed``:

* **The chip's share.** ``num_experts`` in the configuration is the number of
  routed experts *held here* (``Arch.num_experts``), starting at
  ``expert_offset``; the router keeps the published ``router_outputs``.
  Every held expert is computed for every token and masked by its weight;
  what the absent experts would have added is left out, as in the program,
  and that partial sum goes on to the next layer. ``vocab_size`` is the
  slice of rows held here. ``num_hidden_layers`` layers are held from
  ``first_layer`` on; ``num_dense_layers`` and ``layer_types`` stay as
  published and are read at the published index.
* ``q_proj`` and ``gate_proj`` are one leaf ``[hidden, heads, 2 *
  head_dim]`` (queries first): with drawn weights a layout.
* The trainer's update of ``expert_bias`` between steps
  (``load_balance_coeff``) is no part of the forward or the backward: the
  reference takes the bias as drawn from the seed, which is what the check's
  step (the run's first) sees; the program moves it after every step. It
  gets no gradient.

**The leaves** (``harness/weights.py`` stacks every per-layer leaf over all
the layers, and ``runners/train.py`` draws one layer of all for every name
in ``CHECK_LAYER_LEAVES``): the routed experts are a *top* leaf over the
expert layers alone ``[expert layers, held, ...]`` and the dense
feed-forward one over the dense layers ``[dense layers, ...]``, so that the
training state holds no dead slot of either; the router, its bias and the
shared expert keep a slot a layer (a dense layer's is drawn, never read,
and its gradient is zero on both sides).

**Memory.** One sequence and one layer at a time; attention in blocks of
``QUERY_BLOCK`` queries and the experts in blocks of ``TOKEN_BLOCK`` tokens,
each block recomputed in the backward, so that 8,192 tokens fit: a block's
scores are ``[heads, 512, 8192]`` float32 (0.5 GB), a block's expert
hiddens ``[1024, held, width]`` (67 MB at 16 of 1024).

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

from benchmarks.harness.weights import Leaf
from benchmarks.references.mistral import _mm, rms_norm, rope

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
TOKEN_BLOCK = 1024
SLIDING, FULL = "sliding_attention", "full_attention"
# The scale of the normal draw of ``post_attention_layernorm``'s gain (every
# other gain is 1 + 0.1 n). With drawn weights QK-norm pins a score's standard
# deviation at one, so attention averages two thousand keys' values: noise
# around a vector nearly common to all tokens. A post-attention gain around
# one scales exactly that to the stream's own size in every layer, a fifth of
# the variance of the router's input is then the same for every token, and
# the router sees a constant an expert: 22 of the 64 held experts got a row,
# the fullest 5.7 times the mean, 0.14 pairs a token and layer for the 1.0 a
# share of an eighth expects (my chip run, PR 43, seed 2147484001). Sharper
# scores (``q_norm`` / ``k_norm`` gains at 3 n) balance the load (0.99 pairs,
# fullest 1.19; my chip run, PR 43) but make the loss's gradient a sum over
# near-ties of a peaked softmax that bf16 cannot reproduce: the program read
# 1.19-1.24 from the reference and the fp8 control 1.39-1.40. At 0.1 n the
# attention branch is a tenth of the embedding, the common share of the
# router's input is under 1%, the experts load alike for every seed (as a
# model balanced by its bias does), and the gradients stay as conditioned as
# at gains of one (section 2 of PERF.md has the readings).
POST_ATTENTION_GAIN = 0.1


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the equations need, under the published names;
    ``num_experts`` counts the experts held here, ``num_hidden_layers`` the
    layers held from ``first_layer`` on."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float
    rope_theta: float
    sliding_window: int
    num_dense_layers: int
    num_experts: int
    num_shared_experts: int
    num_experts_per_tok: int
    route_scale: float
    vocab_size: int
    num_hidden_layers: int
    router_outputs: int
    expert_offset: int
    first_layer: int
    layer_types: Tuple[str, ...]

    @classmethod
    def from_model(cls, model: Dict) -> "Arch":
        plain = [f.name for f in dataclasses.fields(cls)
                 if f.name != "layer_types"]
        for key, want in (("score_func", "sigmoid"), ("route_norm", True),
                          ("n_group", 1), ("topk_group", 1),
                          ("mup_enabled", True)):
            if model.get(key) != want:
                raise ValueError(f"references/trinity.py writes the layer "
                                 f"down for {key}={want!r}, not "
                                 f"{model.get(key)!r}")
        a = cls(**{k: model[k] for k in plain},
                layer_types=tuple(model["layer_types"]))
        if set(a.layer_types) - {SLIDING, FULL} or a.first_layer \
                + a.num_hidden_layers > len(a.layer_types):
            raise ValueError(f"layers {a.first_layer}.."
                             f"{a.first_layer + a.num_hidden_layers} of "
                             f"{len(a.layer_types)} layer_types")
        return a

    def is_dense(self, layer: int) -> bool:
        return self.first_layer + layer < self.num_dense_layers

    def is_sliding(self, layer: int) -> bool:
        return self.layer_types[self.first_layer + layer] == SLIDING

    # the three places a layer's kind enters the equations (a fixture that
    # breaks one of them overrides one of these)
    def window_of(self, layer: int):
        """The layer's sliding window (None: every earlier key)."""
        return self.sliding_window if self.is_sliding(layer) else None

    def rotates(self, layer: int) -> bool:
        """Whether the layer's queries and keys are rotated."""
        return self.is_sliding(layer)

    def choice_scores(self, scores, bias):
        """What the router's choice ranks."""
        return scores + bias

    @property
    def dense_layers(self) -> int:
        return sum(self.is_dense(l) for l in range(self.num_hidden_layers))

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.dense_layers

    def leaf_table(self) -> Tuple[Leaf, ...]:
        """Every weight, as data for ``harness/weights.py``: its path in the
        program's tree (``deepspeed_tpu.models.hybrid`` with
        ``experts_apart``), the name the equations below use, its shape and
        the scale of its normal draw (fan-in, so that activations stay of
        order one; None: a gain drawn around one). The router at fan-in and
        ``expert_bias`` at a quarter of the distance between neighbouring
        scores near the top, at most 0.01 (``references/kimi_k2.py`` gives
        the readings behind both: a larger draw saturates the scores or
        unbalances the loads, and which experts a chip holds would then
        decide its step's time). ``post_attention_layernorm``'s gain at
        ``POST_ATTENTION_GAIN``."""
        h, v, d = self.hidden_size, self.vocab_size, self.head_dim
        nq, nkv = self.num_attention_heads, self.num_key_value_heads
        e, f, F = (self.num_experts, self.moe_intermediate_size,
                   self.intermediate_size)
        fs, R = f * self.num_shared_experts, self.router_outputs
        K, Le = self.dense_layers, self.expert_layers
        fan = 1.0 / math.sqrt(h)
        return (
            Leaf("ln1.scale", "input_layernorm", (h,), None, True),
            Leaf("ln1_post.scale", "post_attention_layernorm", (h,),
                 POST_ATTENTION_GAIN, True),
            Leaf("ln2.scale", "pre_mlp_layernorm", (h,), None, True),
            Leaf("ln2_post.scale", "post_mlp_layernorm", (h,), None, True),
            Leaf("attn.wq", "q_gate_proj", (h, nq, 2 * d), fan, True),
            Leaf("attn.wk", "k_proj", (h, nkv, d), fan, True),
            Leaf("attn.wv", "v_proj", (h, nkv, d), fan, True),
            Leaf("attn.wo", "o_proj", (nq, d, h), 1.0 / math.sqrt(nq * d),
                 True),
            Leaf("attn.q_norm", "q_norm", (d,), None, True),
            Leaf("attn.k_norm", "k_norm", (d,), None, True),
            Leaf("moe.router", "router", (h, R), fan, True),
            Leaf("moe.router_bias", "expert_bias", (R,),
                 min(0.01, 0.75 / R), True),
            Leaf("moe.shared.wg", "shared_gate_proj", (h, fs), fan, True),
            Leaf("moe.shared.wi", "shared_up_proj", (h, fs), fan, True),
            Leaf("moe.shared.wo", "shared_down_proj", (fs, h),
                 1.0 / math.sqrt(fs), True),
            Leaf("experts.wg", "experts_gate_proj", (Le, e, h, f), fan, False),
            Leaf("experts.wi", "experts_up_proj", (Le, e, h, f), fan, False),
            Leaf("experts.wo", "experts_down_proj", (Le, e, f, h),
                 1.0 / math.sqrt(f), False),
            Leaf("dense.wg", "dense_gate_proj", (K, h, F), fan, False),
            Leaf("dense.wi", "dense_up_proj", (K, h, F), fan, False),
            Leaf("dense.wo", "dense_down_proj", (K, F, h), 1.0 / math.sqrt(F),
                 False),
            Leaf("embed.tokens", "embed_tokens", (v, h), 0.02, False),
            Leaf("final_norm.scale", "norm", (h,), None, False),
            Leaf("unembed.kernel", "lm_head", (h, v), 0.02, False),
        )


# the gradient leaves the training check samples (published names). Of one
# seeded layer: leaves every layer has (attention, the four norms) and
# leaves with a slot a layer (the router and the shared expert: zero on both
# sides when the seeded layer is the dense one). Of the top: the final norm,
# the head, the dense feed-forward and the routed experts, whose axis 0 is
# the expert layers, so every held expert of every expert layer is compared.
CHECK_LAYER_LEAVES = ("q_gate_proj", "k_proj", "v_proj", "o_proj",
                      "input_layernorm", "post_attention_layernorm",
                      "pre_mlp_layernorm", "post_mlp_layernorm", "router",
                      "shared_gate_proj", "shared_up_proj",
                      "shared_down_proj")
CHECK_TOP_LEAVES = ("norm", "lm_head", "dense_gate_proj", "dense_up_proj",
                    "dense_down_proj", "experts_gate_proj",
                    "experts_up_proj", "experts_down_proj")
EXPERT_LEAVES = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")
DENSE_LEAVES = ("dense_gate_proj", "dense_up_proj", "dense_down_proj")


def keys_per_query(seq: int, window=None) -> float:
    """Keys a query of a full sequence attends over, averaged over its
    positions: ``sum_i min(i + 1, window) / seq``."""
    if window is None or window >= seq:
        return (seq + 1) / 2.0
    return (window * (window + 1) / 2.0 + (seq - window) * window) / seq


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Operations the forward and backward passes require per trained token
    on this share; recomputation is not counted. Forward 2 a weight a token
    touches: attention's five projections, the dense layers' feed-forward,
    of an expert layer the shared expert, the router and the ``top_k * held
    / router_outputs`` routed experts a token finds here *on average* (one,
    at 8 of 128 with 16 held), the head's rows held here (the embedding is
    a lookup); attention 2 products of ``keys_per_query`` keys by head_dim
    a query head, the window's count in a sliding layer. Backward twice the
    forward."""
    h, d, nq = a.hidden_size, a.head_dim, a.num_attention_heads
    L, K = a.num_hidden_layers, a.dense_layers
    attn = h * d * (3 * nq + 2 * a.num_key_value_heads)
    here = a.num_experts_per_tok * a.num_experts / a.router_outputs
    expert = 3 * h * a.moe_intermediate_size
    ffn = K * 3 * h * a.intermediate_size + (L - K) * (
        (here + a.num_shared_experts) * expert + h * a.router_outputs)
    pairs = sum(keys_per_query(seq, a.window_of(l)) for l in range(L))
    return 3.0 * (2.0 * (L * attn + ffn + h * a.vocab_size)
                  + 2 * 2.0 * pairs * d * nq)


def _blocked(fn, block: int, *xs):
    """``fn`` over blocks of ``block`` rows of every array in ``xs`` (axis
    0), each block recomputed in the backward; whole where the rows do not
    divide."""
    T = xs[0].shape[0]
    if T <= block or T % block:
        return fn(*xs)
    cut = tuple(x.reshape((T // block, block) + x.shape[1:]) for x in xs)
    out = jax.lax.map(lambda b: jax.checkpoint(fn)(*b), cut)
    return out.reshape((T,) + out.shape[2:])


def attention(a: Arch, numerics: str, q, k, v, window):
    """Causal grouped-query attention, under ``window`` where given: key j
    is visible to query i iff 0 <= i - j < window. q [T, nq, D]; k, v
    [T, nkv, D]. Position is the index."""
    T, nq, D = q.shape
    group = nq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    j = jnp.arange(T)[None, :]

    def block(qb, ib):
        s = _mm("tnd,snd->nts", qb, k, numerics) / jnp.sqrt(jnp.float32(D))
        gap = ib[:, None] - j
        ok = gap >= 0 if window is None else (gap >= 0) & (gap < window)
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return _mm("nts,snd->tnd", p, v, numerics)

    return _blocked(block, QUERY_BLOCK, q, jnp.arange(T))


def route(a: Arch, y, router, bias):
    """(weights [T, k] float32, experts [T, k]) by the published rule, in
    float32 whatever the numerics: which experts a token takes is not a
    matrix product's precision."""
    s = jax.nn.sigmoid(jnp.einsum("th,he->te", y, router, precision=HIGHEST))
    _, idx = jax.lax.top_k(a.choice_scores(s, bias), a.num_experts_per_tok)
    top = jnp.take_along_axis(s, idx, axis=-1)
    return a.route_scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20), idx


def swiglu(numerics: str, y, gate, up, down):
    return _mm("tf,fh->th", jax.nn.silu(_mm("th,hf->tf", y, gate, numerics))
               * _mm("th,hf->tf", y, up, numerics), down, numerics)


def expert_block(a: Arch, numerics: str, y, w: Dict, ew: Dict):
    """The held experts' part of the routed sum, plus the shared expert.
    y [T, H] (normed); ``w`` the layer's router, bias and shared expert,
    ``ew`` its held experts ``[held, ...]``."""
    wt, idx = route(a, y, w["router"], w["expert_bias"])
    held = a.expert_offset + jnp.arange(a.num_experts)
    # weight of held expert e for token t: its scaled share if chosen
    wte = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                            wt[:, :, None], 0.0), axis=1)       # [T, E]

    def block(yb, wb):
        g = _mm("th,ehf->tef", yb, ew["experts_gate_proj"], numerics)
        u = _mm("th,ehf->tef", yb, ew["experts_up_proj"], numerics)
        # the weight goes in before the down projection (it is linear),
        # so that no [T, E, H] array is made
        return _mm("tef,efh->th", jax.nn.silu(g) * u * wb[:, :, None],
                   ew["experts_down_proj"], numerics)

    routed = _blocked(block, TOKEN_BLOCK, y, wte)
    return routed + swiglu(numerics, y, w["shared_gate_proj"],
                           w["shared_up_proj"], w["shared_down_proj"])


def attention_branch(a: Arch, numerics: str, rotary: bool, window, x, w: Dict,
                     positions):
    """``x + post_attention_layernorm(attn(input_layernorm(x)))``; ``rotary``
    and ``window`` are static."""
    eps, d = a.rms_norm_eps, a.head_dim
    y = rms_norm(x, w["input_layernorm"], eps)
    qg = _mm("th,hnd->tnd", y, w["q_gate_proj"], numerics)
    q = rms_norm(qg[..., :d], w["q_norm"], eps)
    k = rms_norm(_mm("th,hnd->tnd", y, w["k_proj"], numerics), w["k_norm"],
                 eps)
    v = _mm("th,hnd->tnd", y, w["v_proj"], numerics)
    if rotary:
        q = rope(q, positions, a.rope_theta)
        k = rope(k, positions, a.rope_theta)
    o = attention(a, numerics, q, k, v, window)
    o = _mm("tnd,ndh->th", o * jax.nn.sigmoid(qg[..., d:]), w["o_proj"],
            numerics)
    return x + rms_norm(o, w["post_attention_layernorm"], eps)


def layer(a: Arch, numerics: str, kind: Tuple, x, w: Dict, fw: Dict,
          positions):
    """One layer on one sequence. x [T, H]; ``w`` this layer's per-layer
    leaves, ``fw`` its feed-forward's own (the dense SwiGLU's three or the
    held experts' three); ``kind`` = (rotary, window, dense) is static."""
    eps = a.rms_norm_eps
    rotary, window, dense = kind
    x = attention_branch(a, numerics, rotary, window, x, w, positions)
    y = rms_norm(x, w["pre_mlp_layernorm"], eps)
    if dense:
        f = swiglu(numerics, y, fw["dense_gate_proj"], fw["dense_up_proj"],
                   fw["dense_down_proj"])
    else:
        f = expert_block(a, numerics, y, w, fw)
    return x + rms_norm(f, w["post_mlp_layernorm"], eps)


def head_logits(a: Arch, numerics: str, x, norm, lm_head):
    return _mm("th,hv->tv", rms_norm(x, norm, a.rms_norm_eps), lm_head,
               numerics)


def _nll_sum(a, numerics, x, norm, lm_head, labels):
    logits = head_logits(a, numerics, x, norm, lm_head)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


def feed_forward_of(a: Arch, top: Dict, l: int) -> Dict:
    """Layer ``l``'s own feed-forward leaves out of the top stacks."""
    if a.is_dense(l):
        return {n: top[n][l] for n in DENSE_LEAVES}
    return {n: top[n][l - a.dense_layers] for n in EXPERT_LEAVES}


@functools.lru_cache(maxsize=None)
def _layer_programs(a: Arch, numerics: str, kind: Tuple):
    """One kind of layer's jitted forward, backward and attention branch."""
    def bwd(x, w, fw, positions, dy):
        _, vjp = jax.vjp(lambda x_, w_, fw_: layer(
            a, numerics, kind, x_, w_, fw_, positions), x, w, fw)
        return vjp(dy)

    return {"fwd": jax.jit(functools.partial(layer, a, numerics, kind)),
            "bwd": jax.jit(bwd),
            "attn": jax.jit(functools.partial(attention_branch, a, numerics,
                                              *kind[:2]))}


@functools.lru_cache(maxsize=None)
def _programs(a: Arch, numerics: str):
    """The jitted pieces that no layer kind enters."""
    def head(x, norm, lm_head, labels):
        return jax.value_and_grad(
            functools.partial(_nll_sum, a, numerics), argnums=(0, 1, 2))(
                x, norm, lm_head, labels)

    return {"head": jax.jit(head),
            "logits": jax.jit(functools.partial(head_logits, a, numerics)),
            "route": jax.jit(functools.partial(route, a))}


def _of_layer(a: Arch, numerics: str, l: int):
    return _layer_programs(a, numerics, (a.rotates(l), a.window_of(l),
                                         a.is_dense(l)))


def _embed(a: Arch, top: Dict, ids):
    return top["embed_tokens"][jnp.asarray(ids)] * jnp.sqrt(
        jnp.float32(a.hidden_size))


def forward_logits(arch: Arch, tokens: Sequence, rows: Sequence[Sequence[int]],
                   layer_weights: Callable[[int], Dict], top: Dict,
                   numerics: str = "float32"):
    """Full forward of each sequence in ``tokens`` (1-D int arrays) and the
    logits at the positions ``rows[i]`` of sequence i. Layers outermost, so
    one layer's per-layer weights live at a time. Returns a list of float32
    arrays ``[len(rows[i]), vocab]``."""
    p = _programs(arch, numerics)
    xs = [_embed(arch, top, t) for t in tokens]
    pos = [jnp.arange(len(t)) for t in tokens]
    for l in range(arch.num_hidden_layers):
        w, fw = layer_weights(l), feed_forward_of(arch, top, l)
        xs = [_of_layer(arch, numerics, l)["fwd"](x, w, fw, ps)
              for x, ps in zip(xs, pos)]
        del w, fw
    return [p["logits"](x[jnp.asarray(r)], top["norm"], top["lm_head"])
            for x, r in zip(xs, rows)]


def loss_and_grads(arch: Arch, batch, layer_weights: Callable[[int], Dict],
                   top: Dict, keep: Callable[[str, object], object],
                   numerics: str = "float32") -> Dict:
    """Causal-LM loss (mean over every predicted token of the batch) and its
    gradient, one sequence and one layer at a time.

    ``batch`` is ``[B, S + 1]`` token ids: inputs ``[:, :-1]``, labels
    ``[:, 1:]``. ``keep(name, grad)`` is called once for every gradient leaf
    (``"layers.3.k_proj"``, ``"experts_up_proj"`` ``[expert layers, held,
    ...]``, ``"norm"``, ...) and returns what the caller wants kept of it.
    Returns ``{"loss", "grad_norm", "kept": {name: value}}``."""
    p = _programs(arch, numerics)
    B, S = batch.shape[0], batch.shape[1] - 1
    denom = jnp.float32(B * S)
    inputs, labels = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])
    pos = jnp.arange(S)
    L = arch.num_hidden_layers
    acts = [[_embed(arch, top, inputs[b]) for b in range(B)]]
    for l in range(L):
        w, fw = layer_weights(l), feed_forward_of(arch, top, l)
        # wait for each layer: run ahead, the host would have every layer's
        # float32 weights made before the first is used
        acts.append(jax.block_until_ready(
            [_of_layer(arch, numerics, l)["fwd"](x, w, fw, pos)
             for x in acts[-1]]))
        del w, fw
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
    stacks = {n: [None] * (arch.dense_layers if n in DENSE_LEAVES
                           else arch.expert_layers)
              for n in DENSE_LEAVES + EXPERT_LEAVES}
    for l in reversed(range(L)):
        w, fw, xs = layer_weights(l), feed_forward_of(arch, top, l), acts.pop()
        gl = gf = None
        for b in range(B):
            dxs[b], gw, gfw = _of_layer(arch, numerics, l)["bwd"](
                xs[b], w, fw, pos, dxs[b])
            # one sequence's gradients in flight at a time
            gl, gf = jax.block_until_ready(
                (gw, gfw) if gl is None else (add(gl, gw), add(gf, gfw)))
        sq_sum += sq(gl) + sq(gf)
        for name, g in gl.items():
            kept[f"layers.{l}.{name}"] = keep(f"layers.{l}.{name}", g)
        at = l if arch.is_dense(l) else l - arch.dense_layers
        for name, g in gf.items():
            stacks[name][at] = g
        del w, fw, gl, gf, xs
    for name, parts in stacks.items():
        if parts:
            kept[name] = keep(name, jnp.stack(parts))
        parts.clear()
    scale = jnp.sqrt(jnp.float32(arch.hidden_size))
    gemb = jnp.zeros_like(top["embed_tokens"])
    for b in range(B):
        gemb = gemb.at[inputs[b]].add(dxs[b] * scale)
    sq_sum += jnp.sum(gemb * gemb)
    kept["embed_tokens"] = keep("embed_tokens", gemb)
    return {"loss": float(nll / denom), "grad_norm": float(jnp.sqrt(sq_sum)),
            "kept": {k: v for k, v in kept.items() if v is not None}}


def routed_pairs(arch: Arch, tokens, layer_weights: Callable[[int], Dict],
                 top: Dict, numerics: str = "float32"):
    """For one sequence, the experts each token takes in every expert layer
    ``[expert layers, T, k]`` (sorted a token), with the stream computed in
    ``numerics``: what a tool compares between two precisions to count the
    (token, expert) pairs a rounded hidden state flips."""
    p = _programs(arch, numerics)
    x, pos, out = _embed(arch, top, tokens), jnp.arange(len(tokens)), []
    for l in range(arch.num_hidden_layers):
        w, fw = layer_weights(l), feed_forward_of(arch, top, l)
        if not arch.is_dense(l):
            # the router reads the stream after this layer's attention
            mid = _of_layer(arch, numerics, l)["attn"](x, w, pos)
            y = rms_norm(mid, w["pre_mlp_layernorm"], arch.rms_norm_eps)
            out.append(jnp.sort(p["route"](y, w["router"],
                                           w["expert_bias"])[1], axis=-1))
        x = _of_layer(arch, numerics, l)["fwd"](x, w, fw, pos)
    return jnp.stack(out)


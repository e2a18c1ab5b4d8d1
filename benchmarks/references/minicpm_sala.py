"""Plain MiniCPM-SALA: the forward pass in straightforward ``jax.numpy``,
float32, every matrix product at ``highest`` precision. No kernel, no cache,
no pool, no chunked recurrence, and no import from the program: this file
decides ``correct``.

Source: ``https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json``
(``model_type`` ``minicpm_sala``): 32 layers whose mixers ``mixer_types``
lists, 8 ``minicpm4`` (block-sparse softmax attention) among 24
``lightning-attn`` (linear attention), a dense SwiGLU under each, muP
scalings. A configuration holds ``num_hidden_layers`` of them from the
published layer ``first_layer`` on. Below ``h`` is the hidden size, ``d`` =
128 the head size, ``c = scale_depth / sqrt(published layers)`` = 0.2475.

**Stack.** ``x0 = scale_emb * E[id]``. For each layer ``x += c *
Mixer(rmsnorm(x))``; ``x += c * MLP(rmsnorm(x))``; ``MLP(y) = Wd(silu(Wg y) *
(Wu y))``, width 16,384. Logits ``= Wh (rmsnorm(x) / (h / dim_model_base))``.
Every RMSNorm has eps 1e-6 and a gain applied as ``xhat * g``.

**``lightning-attn``.** ``q = rope(norm(Wq y))``, ``k = rope(norm(Wk y))``,
``v = Wv y``: 32 heads of 128 each (32 KV heads), ``norm`` an RMSNorm over the
head with one gain vector, rotary (theta 10,000, rotate-half pairs) over the
whole head. For each head, in float32, one token after another (``lax.scan``):

    S_t = a S_(t-1) + k_t v_t^T  (128 x 128);   o_t = S_t^T q_t / sqrt(d)

``out = Wo(sigmoid(Wz y) * rmsnorm(o))``. The decay is a constant of head ``j``
and of the layer's *published* index ``l``: ``a = exp(-s_j (1 - l / (L - 1) +
1e-5))``, ``s_j = 2^(-8 (j + 1) / 32)``, ``L`` = 32.

**``minicpm4``.** ``q = norm(Wq y)`` (32 x 128), ``k = norm(Wk y)``, ``v = Wv
y`` (2 x 128); **no rotary** (``attn_use_rope`` false); scale ``1 / sqrt(d)``;
``out = Wo(sigmoid(gate) * o)``. The query at position ``t`` sees ``n = t + 1``
tokens:

* ``t < dense_len``: ``o`` is plain causal softmax attention;
* else the InfLLM-V2 rule (no parameter). Compressed keys ``ck_j = mean(k[16 j
  : 16 j + 32])`` for every whole window (``16 j + 32 <= n``), for each KV
  head. For each of the 16 query heads of a group ``p = softmax_j(q . ck_j /
  sqrt(d))``; ``r`` = the sum of ``p`` over the group's heads; the score of
  block ``i`` (tokens ``64 i .. 64 i + 63``) is the largest ``r_j`` over the
  windows that overlap it. Always read: block 0 (``init_blocks``) and every
  block that holds one of the last 2,048 visible tokens (``window_size``).
  The rest by score, highest first, up to 64 blocks in all (``topk``; ties
  to the lower index). ``o`` is causal softmax attention of each head over
  the visible tokens of its group's chosen blocks.

The reference computes the rule as a mask over a dense score matrix, a block
of queries at a time.

Not in the published ``config.json`` and set here by the family's convention
(each also under the configuration's ``assumed``):

* the seven sizes of ``sparse_config`` (``kernel_size`` 32, ``kernel_stride``
  16, ``block_size`` 64, ``init_blocks`` 1, ``window_size`` 2048, ``topk`` 64,
  ``dense_len`` 8192: MiniCPM4's);
* the decay's formula (the lightning-attention convention);
* the output norm of ``lightning-attn`` taken over the head with one gain
  vector.

Departures from the source, each also in ``assumed``:

1. The rule is a function of the query's *position* (``t >= dense_len``);
   the published code switches on the length of the call, which a chunked
   prefill cannot reproduce.
2. The published kernels approximate the softmax's normaliser over windows
   with a coarser pooling; here it is exact.
3. Both output gates are elementwise (4096 x 4096); the config gives only
   ``use_output_gate`` / ``attn_use_output_gate``. The ``minicpm4`` gate is
   stored with the query projection (``q_gate_proj`` [h, heads, 2 d]: query
   then gate), a layout with drawn weights.
4. ``mup_denominator`` is kept in the file and read by nothing.

Every per-layer leaf is declared for *every* layer (``harness/weights.py``
stacks per-layer leaves over all layers): a ``minicpm4`` layer's lightning
leaves and a lightning layer's attention leaves are drawn and never read.
``loss_and_grads`` raises: no training configuration names this reference.

``numerics``: ``float32`` is the reference; ``fp8`` and ``bf16`` are the
*controls* (operands of every weight product and of the attention products
rounded to that type, accumulated in float32; the recurrence's own arithmetic
and the block selection stay float32: which blocks a query reads is not a
matrix product's precision).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import Leaf
# the operand rounding of the controls, the product at ``highest``, the
# RMSNorm and the rotary are the first reference's: one definition each
from benchmarks.references.mistral import _mm, rms_norm, rope

HIGHEST = jax.lax.Precision.HIGHEST
SPARSE_KEYS = ("kernel_size", "kernel_stride", "block_size", "init_blocks",
               "window_size", "topk", "dense_len")
# queries of one block of the dense score matrix, and the multiple a
# sequence's length is rounded up to (one compiled program a length)
QUERY_BLOCK = 128
LENGTH_STEP = 2048
# tokens of a lightning layer whose projections are held at once
TOKEN_CHUNK = 2048


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the equations need, under the published names; ``mixers``
    are the layers held here and ``published_layers`` the model's count."""

    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    lightning_nh: int
    lightning_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    scale_emb: float
    scale_depth: float
    dim_model_base: int
    vocab_size: int
    num_hidden_layers: int
    first_layer: int
    published_layers: int
    mixers: Tuple[str, ...]
    sparse: Tuple[int, ...]             # in the order of SPARSE_KEYS

    @classmethod
    def from_model(cls, model: Dict) -> "Arch":
        plain = [f.name for f in dataclasses.fields(cls)
                 if f.name not in ("published_layers", "mixers", "sparse")]
        first, held = model["first_layer"], model["num_hidden_layers"]
        return cls(**{k: model[k] for k in plain},
                   published_layers=len(model["mixer_types"]),
                   mixers=tuple(model["mixer_types"][first:first + held]),
                   sparse=tuple(model["sparse_config"][k] for k in SPARSE_KEYS))

    def is_sparse(self, layer: int) -> bool:
        return self.mixers[layer] == "minicpm4"

    def leaf_table(self) -> Tuple[Leaf, ...]:
        """Every weight, as data for ``harness/weights.py``: its path in the
        program's tree (``deepspeed_tpu.models.hybrid``), the name the
        equations below use, its shape and the scale of its normal draw
        (fan-in, so that activations stay of order one; None: a gain drawn
        around one). The ``minicpm4`` QK-norm gains are drawn around *zero*
        at 1.5: a query-key score then has a standard deviation of 2.25 and
        a query-window score of 0.4, so that attention is peaked over tens
        of keys and the blocks' scores differ (gains near one leave every
        window within a few percent of the next)."""
        h, v, f = self.hidden_size, self.vocab_size, self.intermediate_size
        nq, nkv, d = (self.num_attention_heads, self.num_key_value_heads,
                      self.head_dim)
        n, dl = self.lightning_nh, self.lightning_head_dim
        fan = 1.0 / math.sqrt(h)
        return (
            Leaf("ln1.scale", "input_layernorm", (h,), None, True),
            Leaf("ln2.scale", "post_attention_layernorm", (h,), None, True),
            # minicpm4 layers
            Leaf("attn.wq", "q_gate_proj", (h, nq, 2 * d), fan, True),
            Leaf("attn.wk", "k_proj", (h, nkv, d), fan, True),
            Leaf("attn.wv", "v_proj", (h, nkv, d), fan, True),
            Leaf("attn.wo", "o_proj", (nq, d, h), 1.0 / math.sqrt(nq * d),
                 True),
            Leaf("attn.q_norm", "q_norm", (d,), 1.5, True),
            Leaf("attn.k_norm", "k_norm", (d,), 1.5, True),
            # lightning layers
            Leaf("lightning.wq", "lightning_q_proj", (h, n, dl), fan, True),
            Leaf("lightning.wk", "lightning_k_proj", (h, n, dl), fan, True),
            Leaf("lightning.wv", "lightning_v_proj", (h, n, dl), fan, True),
            Leaf("lightning.wz", "lightning_z_proj", (h, n, dl), fan, True),
            Leaf("lightning.q_norm", "lightning_q_norm", (dl,), None, True),
            Leaf("lightning.k_norm", "lightning_k_norm", (dl,), None, True),
            Leaf("lightning.norm", "lightning_o_norm", (dl,), None, True),
            Leaf("lightning.wo", "lightning_o_proj", (n, dl, h),
                 1.0 / math.sqrt(n * dl), True),
            # feed-forward
            Leaf("mlp.wg", "gate_proj", (h, f), fan, True),
            Leaf("mlp.wi", "up_proj", (h, f), fan, True),
            Leaf("mlp.wo", "down_proj", (f, h), 1.0 / math.sqrt(f), True),
            Leaf("embed.tokens", "embed_tokens", (v, h), 0.02, False),
            Leaf("final_norm.scale", "norm", (h,), None, False),
            Leaf("unembed.kernel", "lm_head", (h, v), 0.02, False),
        )


def lightning_decay(a: Arch, layer: int):
    """``a`` [heads] of the held layer ``layer``."""
    j = jnp.arange(a.lightning_nh, dtype=jnp.float32)
    slope = 2.0 ** (-8.0 * (j + 1.0) / a.lightning_nh)
    published = a.first_layer + layer
    return jnp.exp(-slope * (1.0 - published / (a.published_layers - 1) + 1e-5))


def lightning_layer(a: Arch, numerics: str, x, w: Dict, decay):
    """A ``lightning-attn`` layer (mixer and feed-forward) on one sequence,
    x [T, H]: TOKEN_CHUNK tokens at a time with the state carried, so that
    no projection of the whole sequence is held (the recurrence itself runs
    one token after another)."""
    d = a.lightning_head_dim
    c = a.scale_depth / math.sqrt(a.published_layers)
    T, H = x.shape
    C = min(TOKEN_CHUNK, T)
    pad = (-T) % C
    xp = jnp.pad(x, ((0, pad), (0, 0)))                # causal: the end is free

    def step(S, t):
        q_t, k_t, v_t = t
        S = decay[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("nkv,nk->nv", S, q_t, precision=HIGHEST)

    def chunk(S, xs):
        xc, pos = xs
        y = rms_norm(xc, w["input_layernorm"], a.rms_norm_eps)
        q = _mm("th,hnd->tnd", y, w["lightning_q_proj"], numerics)
        k = _mm("th,hnd->tnd", y, w["lightning_k_proj"], numerics)
        v = _mm("th,hnd->tnd", y, w["lightning_v_proj"], numerics)
        z = _mm("th,hnd->tnd", y, w["lightning_z_proj"], numerics)
        q = rope(rms_norm(q, w["lightning_q_norm"], a.rms_norm_eps), pos,
                 a.rope_theta)
        k = rope(rms_norm(k, w["lightning_k_norm"], a.rms_norm_eps), pos,
                 a.rope_theta)
        S, o = jax.lax.scan(step, S, (q, k, v))
        o = rms_norm(o / math.sqrt(d), w["lightning_o_norm"], a.rms_norm_eps)
        xc = xc + c * _mm("tnd,ndh->th", jax.nn.sigmoid(z) * o,
                          w["lightning_o_proj"], numerics)
        y = rms_norm(xc, w["post_attention_layernorm"], a.rms_norm_eps)
        return S, xc + c * mlp(a, numerics, y, w)

    S0 = jnp.zeros((a.lightning_nh, d, d), jnp.float32)
    _, out = jax.lax.scan(chunk, S0, (
        xp.reshape(-1, C, H), jnp.arange(T + pad).reshape(-1, C)))
    return out.reshape(-1, H)[:T]


def chosen_blocks(a: Arch, q, ck, t, nblocks: int):
    """The InfLLM-V2 rule for a block of queries. q [Q, nkv, g, d]; ck [W,
    nkv, d], window ``j`` at row ``j``; t [Q] positions. Returns bool [Q, nkv,
    nblocks]: the blocks each (query, KV head) reads (every visible block
    below ``dense_len``)."""
    ks, stride, B, init, window, topk, dense_len = a.sparse
    W = ck.shape[0]
    n = t + 1
    s = jnp.einsum("qkgd,wkd->qkgw", q, ck, precision=HIGHEST) / math.sqrt(
        q.shape[-1])
    whole = (stride * jnp.arange(W) + ks)[None, :] <= n[:, None]      # [Q, W]
    p = jax.nn.softmax(jnp.where(whole[:, None, None, :], s, -jnp.inf), -1)
    r = jnp.sum(jnp.where(whole[:, None, None, :], p, 0.0), axis=2)  # [Q,k,W]
    r = jnp.where(whole[:, None, :], r, -jnp.inf)
    # block i = tokens [B i, B i + B); window j = tokens [stride j, stride j
    # + ks): they overlap for j from ceil((B i - ks + 1) / stride) to
    # (B i + B - 1) // stride
    i = jnp.arange(nblocks)
    lo = -((ks - 1 - B * i) // stride)
    reach = (B - 1) // stride + (ks - 1) // stride + 1
    j = lo[:, None] + jnp.arange(reach)[None, :]                 # [blocks, R]
    ok = (j >= 0) & (j < W) & (stride * j < (B * (i + 1))[:, None])
    over = jnp.where(ok, r[:, :, jnp.clip(j, 0, W - 1)], -jnp.inf)
    score = jnp.max(over, axis=-1)                               # [Q, k, blocks]
    seen = i[None, :] <= ((n - 1) // B)[:, None]
    forced = (i[None, :] < init) | (
        i[None, :] >= (jnp.maximum(n - window, 0) // B)[:, None])
    key = jnp.where((forced & seen)[:, None, :], jnp.inf,
                    jnp.where(seen[:, None, :], jnp.maximum(score, 0.0),
                              -jnp.inf))
    order = jnp.argsort(-key, axis=-1, stable=True)[..., :topk]
    taken = jnp.take_along_axis(key, order, axis=-1) > -jnp.inf
    rows = jnp.arange(q.shape[0])[:, None, None]
    heads = jnp.arange(q.shape[1])[None, :, None]
    chosen = jnp.zeros(key.shape, bool).at[rows, heads, order].set(taken)
    return jnp.where((t >= dense_len)[:, None, None], chosen,
                     seen[:, None, :])


def mlp(a: Arch, numerics: str, y, w: Dict):
    gate = _mm("th,hf->tf", y, w["gate_proj"], numerics)
    up = _mm("th,hf->tf", y, w["up_proj"], numerics)
    return _mm("tf,fh->th", jax.nn.silu(gate) * up, w["down_proj"], numerics)


def sparse_layer(a: Arch, numerics: str, x, w: Dict, out_rows):
    """A ``minicpm4`` layer (mixer and feed-forward) on one sequence: keys
    and values of every position, then QUERY_BLOCK of the positions
    ``out_rows`` [Q] at a time (their queries against the dense score
    matrix under the rule's mask, the output gate and projection, the
    feed-forward). x [T, H]. Returns the stream at ``out_rows``, [Q, H]."""
    d = a.head_dim
    ks, stride, B = a.sparse[:3]
    c = a.scale_depth / math.sqrt(a.published_layers)
    T = x.shape[0]
    nkv = a.num_key_value_heads
    g = a.num_attention_heads // nkv
    y = rms_norm(x, w["input_layernorm"], a.rms_norm_eps)
    k = rms_norm(_mm("th,hnd->tnd", y, w["k_proj"], numerics), w["k_norm"],
                 a.rms_norm_eps)
    v = _mm("th,hnd->tnd", y, w["v_proj"], numerics)
    W = (T - ks) // stride + 1
    ck = jnp.mean(k[(stride * jnp.arange(W))[:, None] + jnp.arange(ks)], axis=1)
    key_pos = jnp.arange(T)

    def block(tb):                         # [QB] positions
        xb = x[tb]
        yb = rms_norm(xb, w["input_layernorm"], a.rms_norm_eps)
        qg = _mm("th,hnd->tnd", yb, w["q_gate_proj"], numerics)
        q, gate = qg[..., :d], qg[..., d:]
        qh = rms_norm(q, w["q_norm"], a.rms_norm_eps).reshape(-1, nkv, g, d)
        mask = chosen_blocks(a, qh, ck, tb, -(-T // B))          # [QB,k,blocks]
        ok = (jnp.repeat(mask, B, axis=-1)[..., :T]
              & (key_pos[None, :] <= tb[:, None])[:, None, :])
        s = _mm("qkgd,tkd->qkgt", qh, k, numerics) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(ok[:, :, None, :], s, -jnp.inf), axis=-1)
        o = _mm("qkgt,tkd->qkgd", p, v, numerics).reshape(q.shape)
        xb = xb + c * _mm("tnd,ndh->th", o * jax.nn.sigmoid(gate),
                          w["o_proj"], numerics)
        yb = rms_norm(xb, w["post_attention_layernorm"], a.rms_norm_eps)
        return xb + c * mlp(a, numerics, yb, w)

    out = jax.lax.map(block, out_rows.reshape(-1, QUERY_BLOCK))
    return out.reshape(-1, x.shape[1])


def layer(a: Arch, numerics: str, sparse: bool, x, w: Dict, out_rows, decay):
    """One layer on one sequence; returns the stream at ``out_rows`` [Q]
    alone (every position, in order, for all but the last layer: what no
    later layer reads is not computed)."""
    if sparse:
        return sparse_layer(a, numerics, x, w, out_rows)
    return lightning_layer(a, numerics, x, w, decay)[out_rows]


def head_logits(a: Arch, numerics: str, x, norm, lm_head):
    x = rms_norm(x, norm, a.rms_norm_eps) / (a.hidden_size / a.dim_model_base)
    return _mm("th,hv->tv", x, lm_head, numerics)


@functools.lru_cache(maxsize=None)
def _programs(a: Arch, numerics: str):
    """The jitted pieces, one set per (sizes, numerics)."""
    def one_layer(sparse, x, w, out_rows, decay):
        return layer(a, numerics, sparse, x, w, out_rows, decay)

    return {True: jax.jit(functools.partial(one_layer, True)),
            False: jax.jit(functools.partial(one_layer, False)),
            "logits": jax.jit(functools.partial(head_logits, a, numerics))}


def _padded(rows):
    """``rows`` padded with its last entry to whole query blocks."""
    rows = jnp.asarray(rows, jnp.int32)
    pad = (-len(rows)) % QUERY_BLOCK
    return jnp.concatenate([rows, jnp.full((pad,), rows[-1], jnp.int32)])


def forward_logits(arch: Arch, tokens: Sequence, rows: Sequence[Sequence[int]],
                   layer_weights: Callable[[int], Dict], top: Dict,
                   numerics: str = "float32"):
    """Full forward of each sequence in ``tokens`` (1-D int arrays) and the
    logits at the positions ``rows[i]`` of sequence i. Layers outermost, so
    one layer's weights live at a time. A sequence is cut behind the last
    row read, rounded up to LENGTH_STEP (attention and the recurrence are
    causal: what follows changes no row that is read), and the last layer
    computes those rows alone. Returns a list of float32 arrays
    ``[len(rows[i]), vocab]``."""
    p = _programs(arch, numerics)
    xs, alls, lasts = [], [], []
    for t, r in zip(tokens, rows):
        n = min(len(t), -(-(int(max(r)) + 1) // LENGTH_STEP) * LENGTH_STEP)
        n = max(n, arch.sparse[0])
        xs.append(arch.scale_emb * top["embed_tokens"][jnp.asarray(t[:n])])
        alls.append(_padded(jnp.arange(n)))
        lasts.append(_padded(r))
    last = arch.num_hidden_layers - 1
    for l in range(arch.num_hidden_layers):
        w = layer_weights(l)
        decay = lightning_decay(arch, l)
        fn = p[arch.is_sparse(l)]
        xs = [fn(x, w, (ls if l == last else al), decay)[
            :len(ls) if l == last else x.shape[0]]
              for x, al, ls in zip(xs, alls, lasts)]
        del w
    return [p["logits"](x[:len(r)], top["norm"], top["lm_head"])
            for x, r in zip(xs, rows)]


def loss_and_grads(*args, **kwargs):
    raise NotImplementedError(
        "references/minicpm_sala.py gives no loss_and_grads: no training "
        "configuration names this reference (the program cannot "
        "differentiate a chunked recurrence yet)")

"""Block-sparse attention by compressed keys (the InfLLM-V2 rule of the
MiniCPM4 family, arXiv:2506.07900): which blocks of its context a query
reads, and attention over them.

A context is cut into **blocks** of ``block`` tokens. Beside the keys lives a
coarse summary of them, the **compressed keys**: window ``j`` is the mean of
the ``kernel`` keys from token ``stride * j`` on, one for every KV head. A
query at position ``t`` (it sees ``n = t + 1`` tokens) with ``t >= dense_len``
scores the windows that are whole (``stride * j + kernel <= n``): a softmax
over them for each query head, summed over the heads of a KV group; a block's
score is the largest of the windows that overlap it. It reads block 0 (the
first ``init_blocks``), every block that holds one of its last ``window``
tokens, and the best of the rest up to ``topk`` blocks in all (ties to the
lower index). Below ``dense_len`` it reads everything. The rule adds no
parameter.

Everything here is plain ``jax.numpy`` on one sequence's worth of windows:
:func:`select_blocks` is the rule; :func:`compress_windows` makes the
summaries from keys; :func:`blocked_attention` is the chunked-prefill form
(an online softmax over one sequence's own keys under the block mask). The
decode form hands :func:`select_blocks`' list to the paged decode kernel
(``inference/hybrid_runner.py``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SparseSizes:
    kernel: int = 32
    stride: int = 16
    block: int = 64
    init_blocks: int = 1
    window: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if self.block % self.stride or self.kernel % self.stride:
            raise ValueError("block and kernel must be multiples of stride")
        forced = self.init_blocks + self.window // self.block + 1
        if self.topk < forced:
            raise ValueError(
                f"topk={self.topk} cannot hold the {forced} blocks every "
                "query reads (init_blocks + the window's)")

    @property
    def per_block(self) -> int:
        """Windows that start in one block."""
        return self.block // self.stride

    def windows(self, n):
        """Whole windows among ``n`` tokens."""
        return (n - self.kernel) // self.stride + 1       # <= 0: none


def compress_windows(keys: jax.Array) -> jax.Array:
    """keys [..., kernel, heads, d] -> the window's summary [..., heads, d],
    the mean in float32."""
    return jnp.mean(keys.astype(jnp.float32), axis=-3)


@jax.named_scope("sparse_select")
def select_blocks(sz: SparseSizes, q: jax.Array, ck: jax.Array, t: jax.Array,
                  scale: float):
    """The blocks each (query, KV head) reads.

    q   [Q, nkv, g, d]: the queries, grouped by KV head
    ck  [W, nkv, d]: the compressed keys of the query's sequence, window
        ``j`` at row ``j`` (rows of windows that are not whole yet hold
        anything: masked here), ``W`` a multiple of ``per_block``
    t   [Q] int32: each query's position

    Returns ``(idx [Q, nkv, K] int32, count [Q, nkv] int32, visible [Q]
    int32)`` with ``K = min(topk, W / per_block)``: the chosen blocks in
    ascending order, the first ``count`` of them real (the rest ``nblocks``,
    past every block), and the number of blocks the query sees. Queries below
    ``dense_len`` get the rule's answer too; the caller decides.
    """
    W = ck.shape[0]
    pb = sz.per_block
    nblocks = W // pb
    K = min(sz.topk, nblocks)
    n = t + 1
    s = jnp.einsum("qkgd,wkd->qkgw", q, ck.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    whole = jnp.arange(W)[None, :] < sz.windows(n)[:, None]         # [Q, W]
    s = jnp.where(whole[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    r = jnp.sum(jnp.where(whole[:, None, None, :], p, 0.0), axis=2)  # [Q,k,W]
    r = jnp.where(whole[:, None, :], r, -1.0).reshape(r.shape[:2] + (nblocks, pb))
    score = jnp.max(r, axis=-1)                                  # own windows
    for m in range(1, (sz.kernel - 1) // sz.stride + 1):         # reach in
        prev = jnp.pad(r[:, :, :-1, pb - m], ((0, 0), (0, 0), (1, 0)),
                       constant_values=-1.0)
        score = jnp.maximum(score, prev)
    blk = jnp.arange(nblocks)[None, :]
    last = (n - 1)[:, None] // sz.block
    seen = blk <= last                                               # [Q, B]
    forced = (blk < sz.init_blocks) | (
        blk >= jnp.maximum(n - sz.window, 0)[:, None] // sz.block)
    # a visible block with no whole window (the newest) scores 0; forced
    # blocks above every score (a sum of g probabilities)
    score = jnp.where((forced & seen)[:, None, :], 1e6,
                      jnp.where(seen[:, None, :], jnp.maximum(score, 0.0),
                                -1.0))
    val, idx = lax.top_k(score, K)
    idx = jnp.sort(jnp.where(val >= 0.0, idx, nblocks), axis=-1)
    count = jnp.sum(val >= 0.0, axis=-1).astype(jnp.int32)
    return idx.astype(jnp.int32), count, (last[:, 0] + 1).astype(jnp.int32)


def block_mask(sz: SparseSizes, idx: jax.Array, t: jax.Array, nblocks: int):
    """[Q, nkv, nblocks] bool: the blocks a query reads, the rule's above
    ``dense_len`` and every visible one below."""
    chosen = jnp.any(idx[..., None] == jnp.arange(nblocks), axis=-2)
    seen = jnp.arange(nblocks)[None, :] <= (t // sz.block)[:, None]
    return jnp.where((t >= sz.dense_len)[:, None, None], chosen,
                     seen[:, None, :])


@jax.named_scope("sparse_attn")
def blocked_attention(q: jax.Array, keys: jax.Array, values: jax.Array,
                      mask: jax.Array, t: jax.Array, n_keys, scale: float,
                      block: int, step: int = 1024) -> jax.Array:
    """Causal attention of one sequence's queries over its own keys under a
    block mask, ``step`` keys at a time with a running softmax.

    q [Q, nkv, g, d]; keys, values [Tk, nkv, d] (``Tk`` whole blocks); mask [Q, nkv, Tk / block]; t [Q] positions; ``n_keys``
    (traced) the keys that exist: later steps are not visited. Returns
    [Q, nkv, g, d] in q's dtype.
    """
    Q, nkv, g, d = q.shape
    Tk = keys.shape[0]
    step = min(step, Tk)
    while Tk % step:
        step -= block
    bps = step // block
    dt = q.dtype

    def fold(i, carry):
        m_prev, l_prev, acc = carry
        k = lax.dynamic_slice_in_dim(keys, i * step, step)
        v = lax.dynamic_slice_in_dim(values, i * step, step)
        mk = lax.dynamic_slice_in_dim(mask, i * bps, bps, axis=2)
        pos = i * step + jnp.arange(step)
        ok = jnp.repeat(mk, block, axis=2) & (pos[None, :] <= t[:, None])[:, None]
        s = jnp.einsum("qkgd,tkd->qkgt", q, k.astype(dt),
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok[:, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok[:, :, None, :], jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "qkgt,tkd->qkgd", p.astype(dt), v.astype(dt),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((Q, nkv, g, 1), NEG_INF, jnp.float32),
            jnp.zeros((Q, nkv, g, 1), jnp.float32),
            jnp.zeros((Q, nkv, g, d), jnp.float32))
    _, l, acc = lax.fori_loop(0, (n_keys + step - 1) // step, fold, init)
    return (acc / jnp.where(l == 0.0, 1.0, l)).astype(dt)

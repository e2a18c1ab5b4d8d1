"""The four serving step programs for a hybrid model (``models/hybrid.py``):
periods of recurrent layers and one full-attention layer, every layer with
its expert block.

Same programs, same names and the same leading arguments as
``model_runner``'s (``ragged_forward`` = ``jit_dstpu_serve_gather``,
``ragged_prefill_forward``, ``ragged_decode_forward``,
``ragged_multi_decode``), so the engine, the scheduler and the trace readers
do not tell the models apart. What differs:

* argument 1, donated, is the dict of **both** pools — ``kv`` (the paged
  pool, one layer a *period*), ``state`` and ``conv`` (the recurrent-state
  pool, ``inference/ragged/state_pool.py``) — plus ``counters``; all are
  carried through the layer loops and updated in place (the kernels read the
  pools whole, by layer and page or slot);
* one more trailing argument, ``state_slots [S]``: the state-pool slot of the
  sequence in each batch slot (the scratch slot for an empty one);
* the layer loop is a scan over periods with a scan over the period's
  recurrent layers inside it: one recurrent and one full layer body a
  program, whatever the depth;
* a token step of a recurrent layer is the ``gdn_decode`` kernel; many tokens
  a sequence (gather, prefill) go through the chunked form on a
  sequence-by-token layout.

``counters`` comes back as this call's ``[moe_token_layers, moe_local_pairs,
moe_experts_hit]`` (summed over the steps of a burst).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.inference.model_runner import (_kv_dense, _kv_write,
                                                  _paged_decode,
                                                  _paged_prefill, _unembed)
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.hybrid import HybridConfig
from deepspeed_tpu.ops.pallas.gated_delta import gdn_chunk, gdn_decode
from deepspeed_tpu.runtime.sharding import (effective_dtype,
                                            vocab_parallel_lookup)


def _run_stack(cfg: HybridConfig, params, x, pools, rec_fn, full_fn, valid):
    """The layer loop. ``x`` has any leading shape; ``rec_fn(y, gp, l_rec,
    state, conv) -> (out, state, conv)`` and ``full_fn(y, ap, l_kv, kv) ->
    (out, kv)`` are the two mixers on normed input; ``valid`` marks the real
    tokens (flat, for the experts' counters). Returns (x, pools')."""
    per = cfg.full_attention_interval
    P = cfg.periods
    lead, H = x.shape[:-1], x.shape[-1]
    experts = params["experts"]

    def by_period(tree, n):
        return jax.tree.map(lambda a: a.reshape((P, n) + a.shape[1:]), tree)

    def ffn(x, lp, l, counts):
        out, c = hybrid.expert_block(cfg, lp, experts, x.reshape(-1, H), l,
                                     valid)
        return out.reshape(lead + (H,)), counts + jnp.stack(
            [jnp.sum(valid).astype(jnp.int32), c["pairs"], c["experts_hit"]])

    def rec_layer(carry, inputs):
        x, state, conv, counts = carry
        lp, gp, l, l_rec = inputs
        with jax.named_scope("gdn"):
            y = hybrid._rms(x, lp["ln1"]["scale"], cfg.norm_eps)
            out, state, conv = rec_fn(y, gp, l_rec, state, conv)
        x, counts = ffn(x + out, lp, l, counts)
        return (x, state, conv, counts), None

    def period(carry, inputs):
        x, kv, state, conv, counts = carry
        lps, gps, ap, p = inputs
        ls = p * per + jnp.arange(per - 1, dtype=jnp.int32)
        l_recs = p * (per - 1) + jnp.arange(per - 1, dtype=jnp.int32)
        (x, state, conv, counts), _ = lax.scan(
            rec_layer, (x, state, conv, counts),
            (jax.tree.map(lambda a: a[:per - 1], lps), gps, ls, l_recs))
        lp = jax.tree.map(lambda a: a[per - 1], lps)
        with jax.named_scope("attn"):
            y = hybrid._rms(x, lp["ln1"]["scale"], cfg.norm_eps)
            out, kv = full_fn(y, ap, p, kv)
        x, counts = ffn(x + out, lp, p * per + per - 1, counts)
        return (x, kv, state, conv, counts), None

    carry = (x, pools["kv"], pools["state"], pools["conv"],
             jnp.zeros((3,), jnp.int32))
    (x, kv, state, conv, counts), _ = lax.scan(
        period, carry,
        (by_period(params["layers"], per), by_period(params["gdn"], per - 1),
         params["attn"], jnp.arange(P, dtype=jnp.int32)))
    return x, {"kv": kv, "state": state, "conv": conv, "counters": counts}


def _segment_recurrence(cfg, gp, l_rec, state, conv, slots, mixed, beta, g,
                        real, nreal):
    """Convolution and chunked recurrence on a sequence-by-token layout:
    mixed [S, Tq, C]; beta, g [S, Tq, nv]; real [S, Tq]; nreal [S]. Reads and
    writes each row's slot of both pools. Returns (o [S, Tq, nv, dv], state,
    conv)."""
    K1 = cfg.linear_conv_kernel_dim - 1
    out, window = hybrid.causal_conv(gp["conv"], conv[l_rec, slots], mixed)
    # the next tail: the last K - 1 real inputs (the old tail where a row
    # brought fewer)
    at = nreal[:, None] + jnp.arange(K1)[None, :]                  # [S, K1]
    tail = jnp.take_along_axis(window, at[:, :, None], axis=1)
    conv = conv.at[l_rec, slots].set(tail.astype(conv.dtype))
    q, k, v = hybrid.gdn_heads(cfg, out)
    m = real[..., None]
    o, new = gdn_chunk(q, k, v, jnp.where(m, g, 0.0), jnp.where(m, beta, 0.0),
                       state[l_rec, slots])
    return o, state.at[l_rec, slots].set(new), conv


def _embed(cfg, params, ids):
    return vocab_parallel_lookup(
        params["embed"]["tokens"].astype(effective_dtype(cfg.dtype)), ids)


def _scratch(pools, alive, state_slots):
    """Rows without a sequence read and write the pool's scratch slot."""
    return jnp.where(alive, state_slots, pools["state"].shape[1] - 1)


def ragged_forward(cfg: HybridConfig, params, pools: Dict, token_ids, token_seq,
                   token_pos, block_table, num_tokens, state_slots
                   ) -> Tuple[jax.Array, Dict]:
    """One ragged step over flat tokens (``model_runner.ragged_forward``'s
    contract; sequences lie one after another in the flat order). Returns
    (logits [T, V] float32, pools')."""
    T = token_ids.shape[0]
    S, Bm = block_table.shape
    bs = pools["kv"].shape[2]
    dt = effective_dtype(cfg.dtype)
    real = jnp.arange(T) < num_tokens
    x = _embed(cfg, params, token_ids)

    scratch = pools["kv"].shape[1] - 1
    page = jnp.where(real, block_table[token_seq, token_pos // bs], scratch)
    offset = jnp.where(real, token_pos % bs, bs - 1)
    key_pos = jnp.arange(Bm * bs)
    g_ = cfg.num_heads // cfg.kv_heads

    # flat <-> sequence-by-token: row s holds its sequence's tokens of this
    # step from column 0
    nreal = jnp.zeros((S,), jnp.int32).at[token_seq].add(real.astype(jnp.int32))
    start = jnp.cumsum(nreal) - nreal
    cols = jnp.arange(T)[None, :]
    seg_real = cols < nreal[:, None]                               # [S, T]
    to_seg = jnp.minimum(start[:, None] + cols, T - 1)
    col_of = jnp.arange(T) - start[token_seq]
    slots = _scratch(pools, nreal > 0, state_slots)

    def rec_fn(y, gp, l_rec, state, conv):
        mixed, z, beta, g = hybrid.gdn_project(cfg, gp, y)
        o, state, conv = _segment_recurrence(
            cfg, gp, l_rec, state, conv, slots, mixed[to_seg], beta[to_seg],
            g[to_seg], seg_real, nreal)
        return hybrid.gdn_output(cfg, gp, o[token_seq, col_of], z), state, conv

    def full_fn(y, ap, l_kv, kv):
        q, k, v, gate = hybrid.attn_project(cfg, ap, y, token_pos)
        kv, _ = _kv_write(kv, None, l_kv, page, offset, k, v)
        with jax.named_scope("kv_gather"):
            ctx = kv[l_kv, block_table].reshape(
                S, Bm * bs, 2, cfg.kv_heads, cfg.head_dim)[token_seq]
        qh = q.reshape(T, cfg.kv_heads, g_, cfg.head_dim)
        s = jnp.einsum("tkgd,tmkd->tkgm", qh, ctx[:, :, 0].astype(dt))
        s = s.astype(jnp.float32) / jnp.sqrt(jnp.float32(cfg.head_dim))
        seen = key_pos[None, None, None, :] <= token_pos[:, None, None, None]
        pr = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1).astype(dt)
        a = jnp.einsum("tkgm,tmkd->tkgd", pr, ctx[:, :, 1].astype(dt))
        return hybrid.attn_output(ap, a.reshape(q.shape), gate), kv

    x, pools = _run_stack(cfg, params, x, pools, rec_fn, full_fn, real)
    return _unembed(cfg, params, x), pools


def ragged_prefill_forward(cfg: HybridConfig, params, pools: Dict, seg_tokens,
                           seg_pos0, seg_nreal, block_table, state_slots, *,
                           mesh=None) -> Tuple[jax.Array, Dict]:
    """Prefill chunks, one segment a sequence slot, attention through the
    paged prefill kernel. Returns (logits [S, Tq, V] float32, pools')."""
    S, Tq = seg_tokens.shape
    bs = pools["kv"].shape[2]
    dt = effective_dtype(cfg.dtype)
    qi = jnp.arange(Tq)[None, :]
    pos = seg_pos0[:, None] + qi
    real = qi < seg_nreal[:, None]
    ctx_lens = seg_pos0 + seg_nreal
    x = _embed(cfg, params, seg_tokens)

    scratch = pools["kv"].shape[1] - 1
    page = jnp.where(real, jnp.take_along_axis(block_table, pos // bs, axis=1),
                     scratch)
    offset = jnp.where(real, pos % bs, bs - 1)
    slots = _scratch(pools, seg_nreal > 0, state_slots[:S])

    def rec_fn(y, gp, l_rec, state, conv):
        mixed, z, beta, g = hybrid.gdn_project(cfg, gp, y)
        o, state, conv = _segment_recurrence(
            cfg, gp, l_rec, state, conv, slots, mixed, beta, g, real,
            seg_nreal)
        return hybrid.gdn_output(cfg, gp, o, z), state, conv

    def full_fn(y, ap, l_kv, kv):
        q, k, v, gate = hybrid.attn_project(cfg, ap, y, pos)
        kv, _ = _kv_write(kv, None, l_kv, page, offset, k, v)
        a = _paged_prefill(mesh, q.astype(dt), *_kv_dense(kv, None, l_kv, dt),
                           block_table, seg_pos0, ctx_lens)
        return hybrid.attn_output(ap, a.astype(dt), gate), kv

    x, pools = _run_stack(cfg, params, x, pools, rec_fn, full_fn,
                          real.reshape(-1))
    return _unembed(cfg, params, x), pools


def ragged_decode_forward(cfg: HybridConfig, params, pools: Dict, token_ids,
                          token_pos, block_table, context_lens, state_slots, *,
                          mesh=None) -> Tuple[jax.Array, Dict]:
    """One decode step: one new token for each live slot (``context_lens``
    0 marks a dead one). The recurrent layers run the ``gdn_decode`` kernel on
    each sequence's slot, the full layers the paged decode kernel. Returns
    (logits [S, V] float32, pools')."""
    S = token_ids.shape[0]
    bs = pools["kv"].shape[2]
    dt = effective_dtype(cfg.dtype)
    alive = context_lens > 0
    x = _embed(cfg, params, token_ids)

    scratch = pools["kv"].shape[1] - 1
    page = jnp.where(alive, block_table[jnp.arange(S), token_pos // bs],
                     scratch)
    offset = jnp.where(alive, token_pos % bs, bs - 1)
    slots = _scratch(pools, alive, state_slots)

    def rec_fn(y, gp, l_rec, state, conv):
        mixed, z, beta, g = hybrid.gdn_project(cfg, gp, y)
        out, window = hybrid.causal_conv(gp["conv"], conv[l_rec, slots],
                                         mixed[:, None, :])
        conv = conv.at[l_rec, slots].set(window[:, 1:].astype(conv.dtype))
        q, k, v = hybrid.gdn_heads(cfg, out[:, 0])
        o, state = gdn_decode(state, l_rec, slots, q, k, v, g, beta)
        return hybrid.gdn_output(cfg, gp, o, z), state, conv

    def full_fn(y, ap, l_kv, kv):
        q, k, v, gate = hybrid.attn_project(cfg, ap, y, token_pos)
        kv, _ = _kv_write(kv, None, l_kv, page, offset, k, v)
        a = _paged_decode(mesh, q.astype(dt), *_kv_dense(kv, None, l_kv, dt),
                          block_table, context_lens)
        return hybrid.attn_output(ap, a.astype(dt), gate), kv

    x, pools = _run_stack(cfg, params, x, pools, rec_fn, full_fn, alive)
    return _unembed(cfg, params, x), pools


def ragged_multi_decode(cfg: HybridConfig, params, pools: Dict, token_ids,
                        token_pos, block_table, context_lens, state_slots, *,
                        steps: int, mesh=None) -> Tuple[jax.Array, Dict]:
    """``steps`` greedy decode steps in one program, the argmax fed back on
    the device (``model_runner.ragged_multi_decode``'s contract); the
    counters sum over the steps. Returns (tokens [steps, S] int32, pools')."""
    def body(carry, _):
        pools, tok, pos, ctx, counts = carry
        logits, pools = ragged_decode_forward(
            cfg, params, pools, tok, pos, block_table, ctx, state_slots,
            mesh=mesh)
        alive = ctx > 0
        nxt = jnp.where(alive, jnp.argmax(logits, axis=-1).astype(jnp.int32), 0)
        counts = counts + pools["counters"]
        return (pools, nxt, pos + 1, jnp.where(alive, ctx + 1, 0), counts), nxt

    (pools, _, _, _, counts), toks = lax.scan(
        body, (pools, token_ids, token_pos, context_lens,
               jnp.zeros((3,), jnp.int32)), length=steps)
    return toks, dict(pools, counters=counts)

"""The four serving step programs for a hybrid model (``models/hybrid.py``):
recurrent layers and full-attention layers in the order the configuration
lists, every layer with its expert block or dense feed-forward.

Same programs, same names and the same leading arguments as
``model_runner``'s (``ragged_forward`` = ``jit_dstpu_serve_gather``,
``ragged_prefill_forward``, ``ragged_decode_forward``,
``ragged_multi_decode``), so the engine, the scheduler and the trace readers
do not tell the models apart. What differs:

* argument 1, donated, is the dict of the pools — ``kv`` (the paged pool,
  one layer a full layer), ``state`` and ``conv`` (the recurrent-state
  pool, ``inference/ragged/state_pool.py``) and, for a model with the
  block-sparse rule, ``ck`` (the compressed keys, page-addressed beside
  ``kv``: ``inference/ragged/kv_cache.py``); all are carried through the
  layer loops and updated in place (the kernels read the pools whole, by
  layer and page or slot). The dict that comes back holds one key more,
  ``counters``: the call's own vector, a new array each call and no
  argument of the next, so the engine can keep it while the pools go on;
* one more trailing argument, ``state_slots [S]``: the state-pool slot of the
  sequence in each batch slot (the scratch slot for an empty one);
* the layer loop is a scan over the repeats of the layer pattern with a scan
  over each run of one kind inside it (``HybridConfig.stack_plan``): one
  layer body a run, whatever the depth;
* a token step of a recurrent layer is the ``gdn_decode`` or
  ``lightning_decode`` kernel; many tokens a sequence (gather, prefill) go
  through the chunked form on a sequence-by-token layout;
* with the block-sparse rule a full layer's token step selects pages
  (``sparse_select``) and runs the paged decode kernel over the chosen ones
  (``sparse_attn``); a chunk attends over its own sequence's pages under the
  block mask; the gather program is not built. Without the rule a chunk
  attends over its own sequence's pages as plain products, the dense
  runner's ``model_runner._segment_attention``;
* with the learned block selector (``msa_topk``) the pools hold ``pk``, the
  pooled keys (a running maximum a page and KV head of the tokens' indexer
  keys, ``ragged/kv_cache.py``): a full layer's step projects the indexer's
  queries and keys, folds the new keys into their pages' rows
  (``msa_pool_write``), scores the sequence's pooled keys and chooses
  (``msa_index``), then attends (``msa_attn``): a token step through the paged
  decode kernel over the pages each (sequence, KV head) chose, a chunk through
  the block-masked chunk kernel (``paged_block_prefill``: a tile of queries
  over the pool's pages that one of its queries chose); the gather program
  is not built;
* with latent attention (``attention_kind`` "mla") the paged pool is a latent
  pool (one vector a token) and there is no recurrent layer, state pool or
  ``state_slots``: a token step writes the token's latent and runs the
  absorbed form through the ``mla_decode`` kernel over the sequence's own
  pages (``mla_absorb`` / ``mla_attn``), a chunk the expanded form over its
  sequence's cached latents, up-projected a block of context at a time
  (``mla_chunk``); the prologue's dense layers run before the scan
  (``dense_ffn``); the gather program is not built;
* with the learned selector (``index_topk``) the pools hold ``ik`` too, the
  selector's key a token, page-addressed beside ``kv``: a full layer's token
  step scores every cached key (``dsa_index``: the kernel of that name),
  keeps the ``index_topk`` highest exactly (``dsa_select``) and runs the
  absorbed form over the sequence's pages with the choice as one more term
  of the ``mla_decode`` kernel's mask (``dsa_attn``); a chunk gets the choice
  as one more term of the expanded form's mask;
* with windowed latent layers (``window_attention_kind`` "mla": the third
  mixer kind of ``_run_stack``, scope ``wmla``) the pools hold ``wkv``, the
  windowed pool (``ragged/kv_cache.py``), and one more trailing argument,
  ``window_table [S, ring_pages]``: each sequence's ring of pages. A token
  step writes the token's latent to ring entry ``(pos // page) % ring_pages``
  and runs the absorbed form over the ring from the window's first page on
  under a lower bound (``wmla_attn``); a chunk attends over the ``window -
  1`` tokens before it (read from the ring first) and its own latents under
  the band (``wmla_chunk``), then writes its last rows.

``counters`` comes back as this call's vector, in the order of ``COUNTERS``
below (summed over the steps of a burst).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from deepspeed_tpu.inference.model_runner import (_kv_write, _paged_decode,
                                                  _segment_attention)
from deepspeed_tpu.inference.ragged.kv_cache import (KVCacheConfig,
                                                     WindowPoolConfig)
from deepspeed_tpu.inference.ragged.state_pool import StatePoolConfig
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.hybrid import HybridConfig
from deepspeed_tpu.ops import block_sparse
from deepspeed_tpu.ops.pallas.gated_delta import (CHUNK, gdn_chunk,
                                                  gdn_decode,
                                                  lightning_chunk,
                                                  lightning_decode)
from deepspeed_tpu.runtime.sharding import effective_dtype


# what a step program counts, in the order of its ``counters`` vector: the
# expert blocks' part, then the block-selecting attention's, then latent
# attention's decode kernel's (the context tokens it was asked to read, and
# the page copies it started, which the kernel counts itself; summed over
# sequences, layers and steps), then the selector's and the windowed latent
# layers' (the cached rows a full layer's queries attended over and could
# see; the rows a windowed layer's queries read, and the ring pages a token
# began to write over: summed likewise)
MOE_COUNTERS = ("moe_token_layers", "moe_local_pairs", "moe_experts_hit",
                "moe_work_items")
SPARSE_COUNTERS = ("sparse_blocks_selected", "sparse_blocks_visible",
                   "sparse_dense_tokens")
MLA_COUNTERS = ("mla_context_tokens", "mla_pages_read")
DSA_COUNTERS = ("dsa_rows_selected", "dsa_rows_visible")
WINDOW_COUNTERS = ("window_rows_read", "window_pages_recycled")
# the learned block selector's: over the real (query, KV head) pairs the
# blocks chosen and the blocks visible, and the queries whose context is no
# more than the rule reads anyway; then, over a chunk's (tile of queries, KV
# head) pairs, the blocks the chunk kernel visited (one of the tile's queries
# chose them) and the blocks the tile's last query sees
MSA_COUNTERS = ("msa_blocks_chosen", "msa_blocks_visible",
                "msa_dense_queries", "msa_tile_blocks_visited",
                "msa_tile_blocks_visible")
COUNTERS = (MOE_COUNTERS + SPARSE_COUNTERS + MLA_COUNTERS + DSA_COUNTERS
            + WINDOW_COUNTERS + MSA_COUNTERS)
# those ``stats`` also keeps for the two decode programs alone (``_decode``),
# and the occupancy keys it has for every model (0 for a store it has not)
DECODE_COUNTERS = ("moe_local_pairs", "moe_experts_hit", "moe_work_items")
OCCUPANCY = ("state_slots", "state_slots_in_use", "compressed_keys_in_use",
             "window_pages_in_use", "pooled_keys_in_use")

serving_params = hybrid.serving_params  # the tree these programs take
_MOE = len(MOE_COUNTERS)    # where the expert blocks' counters end
_SPARSE = _MOE + len(SPARSE_COUNTERS)   # and the sparse rule's; then latent
_MLA = _SPARSE + len(MLA_COUNTERS)      # attention's; then the selector's
_WINDOW = _MLA + len(DSA_COUNTERS)      # the windowed latent layers'; and
_MSA = _WINDOW + len(WINDOW_COUNTERS)   # the learned block selector's


def _no_counts():
    """A call's ``counters`` before it has counted: every program starts
    from this and hands its own vector out, so none is taken in."""
    return jnp.zeros((len(COUNTERS),), jnp.int32)


def _run_stack(cfg: HybridConfig, params, x, pools, rec_fn, full_fn, valid,
               win_fn=None):
    """The layer loop. ``x`` has any leading shape; ``rec_fn(y, mp, l_rec,
    pools) -> (out, pools)``, ``full_fn(y, ap, l_kv, pools) -> (out,
    pools)`` and ``win_fn(y, wp, l_win, pools) -> (out, pools)`` are the
    mixers on normed input (recurrent, full, windowed latent: each indexed
    among its own layers); ``valid`` marks the real
    tokens (flat, for the experts' counters). The layers run as
    ``cfg.stack_plan`` lays them out: a scan over the repeats of the pattern
    and, inside, each run of one kind scanned (a lone layer called), so a
    program holds one layer body a run whatever the depth. ``pools`` is the
    carry, ``pools["counters"]`` what this call counted. Returns (x, pools')."""
    if any(cfg.layer_windows) and not cfg.window_layers:
        # (latent windowed layers keep a ring of pages in a pool of their
        # own; a window over the paged K/V pool has no such layer kind yet)
        raise NotImplementedError(
            "windowed gated softmax attention is not served yet: only "
            "latent windowed layers have a pool whose pages are reused "
            "behind the window (ragged/kv_cache.py, WindowedLatentPool)")
    if cfg.post_norms:
        raise NotImplementedError(
            "post-branch norms are not served yet")
    reps, runs = cfg.stack_plan
    K = cfg.dense_layers
    per = (cfg.num_layers - K) // reps
    kinds = (True, False, "w")
    in_period = {k: sum(n for kind, n in runs if kind == k) for k in kinds}
    lead, H = x.shape[:-1], x.shape[-1]
    experts = params["experts"]
    full_kind = "mla" if cfg.attention_kind == "mla" else "attn"
    mixers = {True: params[full_kind],
              False: params.get(cfg.recurrent_kind), "w": params.get("wmla")}
    fns = {True: (full_fn, full_kind), False: (rec_fn, cfg.recurrent_kind),
           "w": (win_fn, "wmla")}

    def at(tree, i):
        # a layer's leaves read where they lie in the stack: a run's slice
        # handed to the scan as its xs would be copied first (0.75 GiB a
        # feed-forward matrix for six layers of 4096 x 16384)
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), tree)

    def body(full, prologue=False):
        mixer, scope = fns[full]

        def layer(carry, where):
            x, pools = carry
            l, l_mix = where
            lp, mp = at(params["layers"], l), at(mixers[full], l_mix)
            with jax.named_scope(scope):
                y = hybrid._rms(x, lp["ln1"]["scale"], cfg.norm_eps)
                out, pools = mixer(y, mp, l_mix, pools)
            x, c = hybrid.expert_block(
                cfg, lp, experts, hybrid.residual(cfg, x, out).reshape(-1, H),
                l - K, valid,
                dense=at(params["dense"], l) if prologue else None)
            if c is not None:
                pools = dict(pools, counters=pools["counters"].at[:_MOE].add(
                    jnp.stack([jnp.sum(valid).astype(jnp.int32), c["pairs"],
                               c["experts_hit"], c["work_items"]])))
            return (x.reshape(lead + (H,)), pools), None

        return layer

    # mixer-layer index of the first layer after the prologue, by kind
    base = {k: sum(kind == k for kind in cfg.mixer_kinds[:K]) for k in kinds}

    def period(carry, r):
        first = {True: 0, False: 0, "w": 0, None: 0}
        for full, n in runs:
            steps = jnp.arange(n, dtype=jnp.int32)
            where = (K + r * per + first[None] + steps,
                     base[full] + r * in_period[full] + first[full] + steps)
            if n == 1:
                carry, _ = body(full)(carry, (where[0][0], where[1][0]))
            else:
                carry, _ = lax.scan(body(full), carry, where)
            first[None] += n
            first[full] += n
        return carry, None

    carry = (x, dict(pools, counters=_no_counts()))
    seen = dict.fromkeys(kinds, 0)
    for l in range(K):      # the prologue: dense layers, outside the scan
        full = cfg.mixer_kinds[l]
        carry, _ = body(full, prologue=True)(
            carry, (jnp.int32(l), jnp.int32(seen[full])))
        seen[full] += 1
    carry, _ = lax.scan(period, carry, jnp.arange(reps, dtype=jnp.int32))
    return carry


def _rec_project(cfg, mp, y, pos):
    """The recurrent mixer's projections of y [..., H] at positions pos
    [...]: ``(what the recurrence reads, z)``."""
    if cfg.recurrent_kind == "lightning":
        q, k, v, z = hybrid.lightning_project(cfg, mp, y, pos)
        return (q, k, v), z
    mixed, z, beta, g = hybrid.gdn_project(cfg, mp, y)
    return (mixed, beta, g), z


def _rec_output(cfg, mp, o, z):
    if cfg.recurrent_kind == "lightning":
        return hybrid.lightning_output(cfg, mp, o, z)
    return hybrid.gdn_output(cfg, mp, o, z)


def _segment_recurrence(cfg, mp, l_rec, pools, slots, proj, real, nreal):
    """The chunked recurrence (and the delta rule's convolution) on a
    sequence-by-token layout: ``proj`` is :func:`_rec_project`'s first part
    on [S, Tq, ...]; real [S, Tq]; nreal [S]. Reads and writes each row's
    slot of the state pool (and of the convolution's). Returns (o [S, Tq, n,
    dv] float32, pools')."""
    state = pools["state"]
    m = real[..., None]
    if cfg.recurrent_kind == "lightning":
        q, k, v = proj
        g = jnp.where(m, cfg.lightning_decay()[l_rec], 0.0)
        o, new = lightning_chunk(q, jnp.where(m[..., None], k, 0.0), v, g,
                                 state[l_rec, slots])
        return o, dict(pools, state=state.at[l_rec, slots].set(new))
    mixed, beta, g = proj
    conv = pools["conv"]
    K1 = cfg.linear_conv_kernel_dim - 1
    out, window = hybrid.causal_conv(mp["conv"], conv[l_rec, slots], mixed)
    # the next tail: the last K - 1 real inputs (the old tail where a row
    # brought fewer)
    at = nreal[:, None] + jnp.arange(K1)[None, :]                  # [S, K1]
    tail = jnp.take_along_axis(window, at[:, :, None], axis=1)
    conv = conv.at[l_rec, slots].set(tail.astype(conv.dtype))
    q, k, v = hybrid.gdn_heads(cfg, out)
    o, new = gdn_chunk(q, k, v, jnp.where(m, g, 0.0), jnp.where(m, beta, 0.0),
                       state[l_rec, slots])
    return o, dict(pools, state=state.at[l_rec, slots].set(new), conv=conv)


def _compress_new(sz, kv, ck, l_kv, block_table, pos0, n_new, windows: int):
    """Write the compressed keys of every window whose *last* token is among
    a row's ``n_new`` tokens from ``pos0`` on (at most ``windows`` of them),
    from the keys in the pool (this step's are written already): window ``j``
    goes to the page where it starts, ``ck[l_kv, page, slot]``. pos0, n_new
    [S]; block_table [S, Bm]. Returns ck'."""
    S, Bm = block_table.shape
    bs = kv.shape[2]
    j0 = jnp.maximum(0, -((sz.kernel - 1 - pos0) // sz.stride))     # ceil
    j = j0[:, None] + jnp.arange(windows)[None, :]                   # [S, J]
    start = sz.stride * j
    whole = (start + sz.kernel <= (pos0 + n_new)[:, None]) & (n_new > 0)[:, None]
    tok = jnp.minimum(start[..., None] + jnp.arange(sz.kernel), Bm * bs - 1)
    page = jnp.take_along_axis(block_table, (tok // bs).reshape(S, -1),
                               axis=1).reshape(tok.shape)
    new = block_sparse.compress_windows(kv[l_kv, page, tok % bs, 0])
    home = jnp.take_along_axis(block_table, jnp.minimum(start // bs, Bm - 1),
                               axis=1)
    return ck.at[l_kv, jnp.where(whole, home, ck.shape[1] - 1),
                 jnp.where(whole, (start % bs) // sz.stride, 0)].set(
                     new.astype(ck.dtype))


def _sparse_counts(sz, t, real, count, visible):
    """This call's ``[blocks selected, blocks visible, dense tokens]``: over
    the real queries past ``dense_len`` the (query, KV head) pairs' chosen
    and visible blocks, and the real queries below it."""
    sparse = real & (t >= sz.dense_len)
    return jnp.stack([
        jnp.sum(jnp.where(sparse[..., None], count, 0)),
        jnp.sum(jnp.where(sparse, visible, 0)) * count.shape[-1],
        jnp.sum(real & ~sparse)]).astype(jnp.int32)


def _decode_over_chosen(cfg, mesh, q, kv, l_kv, table, ctx):
    """The paged decode kernel over the pages each (sequence, KV head) chose:
    ``table`` [S, nkv, width], ``ctx`` [S, nkv] the tokens they stand for.
    Each pair is a row of the kernel's batch with all the query heads; a
    group's own row is kept. Returns [S, nq, d]."""
    S, nkv, width = table.shape
    g, d = cfg.num_heads // nkv, cfg.head_dim
    out = _paged_decode(mesh, jnp.repeat(q, nkv, axis=0), kv, l_kv,
                        table.reshape(S * nkv, width), ctx.reshape(-1))
    own = jnp.arange(nkv)
    return out.reshape(S, nkv, nkv, g, d)[:, own, own].reshape(S, nkv * g, d)


def _sequence_pages(kv, l_kv, table, nkv: int, d: int):
    """One sequence's keys and values of one layer, [Bm * bs, nkv, d] each,
    held to the pool's own layout: the products of a chunk want the token
    second-minor, and without the constraint XLA lays the whole pool out
    anew for this gather, 2 GiB, and not its 32 MiB result."""
    pages = with_layout_constraint(
        kv[l_kv, table], Layout(major_to_minor=(0, 1, 2, 3, 4)))
    return (pages[:, :, 0].reshape(-1, nkv, d),
            pages[:, :, 1].reshape(-1, nkv, d))


def _sparse_decode(cfg, mesh, q, kv, ck, l_kv, block_table, context_lens):
    """Decode attention over the pages each (sequence, KV head) *chose*: the
    rule's list in place of the sequence's whole table, the paged decode
    kernel as it is. A chosen list is ascending and ends in the sequence's
    newest page, so all its pages but the last are full and ``count`` pages
    stand for a context of ``(count - 1) * page + the newest page's tokens``.
    Each (sequence, KV head) pair is a row of the kernel's batch with all the
    query heads; a group's own row is kept. Positions below ``dense_len``
    walk their own table. Returns (attention [S, nq, d], counts [3])."""
    sz = cfg.sparse
    S, Bm = block_table.shape
    bs, nkv, d = kv.shape[2], cfg.kv_heads, cfg.head_dim
    g = cfg.num_heads // nkv
    alive = context_lens > 0
    t = jnp.maximum(context_lens - 1, 0)
    with jax.named_scope("sparse_select"):
        cks = ck[l_kv, block_table].reshape(S, Bm * sz.per_block, nkv, d)
        idx, count, visible = jax.vmap(
            lambda qs, c, ts: block_sparse.select_blocks(
                sz, qs[None], c, ts[None], 1.0 / math.sqrt(d)))(
                    q.reshape(S, nkv, g, d), cks, t)
        idx, count, visible = idx[:, 0], count[:, 0], visible[:, 0]
        width = min(Bm, max(idx.shape[-1], -(-sz.dense_len // bs)))
        chosen = jnp.take_along_axis(
            block_table[:, None, :], jnp.minimum(idx, Bm - 1), axis=2)
        chosen = jnp.pad(chosen, ((0, 0), (0, 0), (0, width - idx.shape[-1])))
        sparse = (t >= sz.dense_len)[:, None]
        table = jnp.where(sparse[..., None], chosen,
                          block_table[:, None, :width])
        ctx = jnp.where(sparse, (count - 1) * bs + (t % bs + 1)[:, None],
                        context_lens[:, None])
        ctx = jnp.where(alive[:, None], ctx, 0)
    with jax.named_scope("sparse_attn"):
        out = _decode_over_chosen(cfg, mesh, q, kv, l_kv, table, ctx)
    return out, _sparse_counts(sz, t, alive, count, visible)


def _sparse_prefill(cfg, q, kv, ck, l_kv, block_table, pos, real, ctx_lens):
    """Chunk attention under the block mask, one segment after another, each
    reading its own sequence's pages and compressed keys (a sequence's keys
    and values of one layer: 32 MiB at 32k tokens; nothing holds a context a
    token). q [S, Tq, nq, d]; pos, real [S, Tq]. Returns (attention, counts
    [3])."""
    sz = cfg.sparse
    S, Tq = pos.shape
    Bm = block_table.shape[1]
    nkv, d = cfg.kv_heads, cfg.head_dim
    scale = 1.0 / math.sqrt(d)

    def segment(args):
        qs, table, ts, rs, n = args
        keys, values = _sequence_pages(kv, l_kv, table, nkv, d)
        cks = ck[l_kv, table].reshape(-1, nkv, d)
        idx, count, visible = block_sparse.select_blocks(sz, qs, cks, ts, scale)
        a = block_sparse.blocked_attention(
            qs, keys, values, block_sparse.block_mask(sz, idx, ts, Bm), ts, n,
            scale, sz.block)
        return a, _sparse_counts(sz, ts, rs, count, visible)

    a, counts = lax.map(segment, (
        q.reshape(S, Tq, nkv, -1, d), block_table, pos, real, ctx_lens))
    return a.reshape(q.shape), jnp.sum(counts, axis=0)


def _msa_counts(pools, sz, mask, visible, real, tiles=None):
    """``pools`` with the learned selector's counters added: mask [..., nkv,
    B] the blocks each real query's KV heads read, visible [...] the blocks
    it sees; ``tiles`` (a chunk's) the lists the kernel walked: the blocks
    each (KV head, tile) visited and the blocks each tile's last query sees."""
    nkv = mask.shape[-2]
    walked, in_sight = (0, 0) if tiles is None else tiles
    counts = jnp.stack([
        jnp.sum(jnp.where(real[..., None, None], mask, False)),
        jnp.sum(jnp.where(real, visible, 0)) * nkv,
        jnp.sum(real & (visible <= sz.width)),
        jnp.sum(walked), jnp.sum(in_sight) * nkv])
    return dict(pools, counters=pools["counters"].at[_MSA:].add(
        counts.astype(jnp.int32)))


def _msa_decode(cfg, mesh, ap, y, q, pools, l, block_table, token_pos,
                context_lens, page):
    """A token step of a full layer under the learned block selector: the
    token's indexer key folded into its page's pooled key (the key alone for
    a page's first token), the sequence's pooled keys scored and the blocks
    chosen, then the paged decode kernel over the pages each (sequence, KV
    head) chose, as :func:`_sparse_decode` hands them over: a chosen list
    is ascending and ends in the newest page. Returns (attention [S, nq, d],
    pools')."""
    sz = cfg.msa
    Bm, bs = block_table.shape[1], sz.block
    alive = context_lens > 0
    with jax.named_scope("msa_index"):
        qi, ki = hybrid.msa_project(ap, y)
        with jax.named_scope("msa_pool_write"):
            pk = pools["pk"]
            ki = ki.astype(pk.dtype)
            first = (token_pos % bs == 0)[:, None, None]
            pk = pk.at[l, page].set(
                jnp.where(first, ki, jnp.maximum(pk[l, page], ki)))
        mask, visible = jax.vmap(
            lambda qs, p, t: block_sparse.msa_select(sz, qs[None], p, t[None])
        )(qi, pk[l, block_table], token_pos)
        mask, visible = mask[:, 0], visible[:, 0]               # [S, nkv, Bm]
        width = min(Bm, sz.width)
        idx = jnp.sort(jnp.where(mask, jnp.arange(Bm), Bm), axis=-1)[
            ..., :width]
        count = jnp.sum(mask, axis=-1).astype(jnp.int32)
        table = jnp.take_along_axis(block_table[:, None, :],
                                    jnp.minimum(idx, Bm - 1), axis=2)
        ctx = jnp.where(alive[:, None],
                        (count - 1) * bs + (token_pos % bs + 1)[:, None], 0)
    with jax.named_scope("msa_attn"):
        out = _decode_over_chosen(cfg, mesh, q, pools["kv"], l, table, ctx)
    return out, _msa_counts(dict(pools, pk=pk), sz, mask, visible, alive)


def _msa_pool_chunk(sz, pk, l, ki, block_table, pos, real, seg_pos0):
    """A chunk's indexer keys ki [S, Tq, nkv, di] folded into the pooled keys
    of the pages it touches (at most ``(Tq - 1) / block + 2``): the maximum
    over the chunk's tokens of each block, with the page's row where the
    block began before the chunk. Returns pk'."""
    S, Tq = pos.shape
    bs, Bm = sz.block, block_table.shape[1]
    nb = -(-(Tq - 1) // bs) + 1
    j = jnp.arange(nb)
    b0 = seg_pos0 // bs
    inside = ((pos // bs - b0[:, None])[:, None, :] == j[None, :, None]) \
        & real[:, None, :]                                      # [S, nb, Tq]
    ki = ki.astype(pk.dtype)
    new = jnp.max(jnp.where(inside[..., None, None], ki[:, None], -jnp.inf),
                  axis=2)                                   # [S, nb, nkv, di]
    page = jnp.take_along_axis(
        block_table, jnp.minimum(b0[:, None] + j[None, :], Bm - 1), axis=1)
    began = (j[None, :] == 0) & (seg_pos0 % bs != 0)[:, None]
    new = jnp.where(began[..., None, None],
                    jnp.maximum(pk[l, page], new), new)
    page = jnp.where(jnp.any(inside, axis=2), page, pk.shape[1] - 1)
    return pk.at[l, page].set(new)


@jax.jit
def _msa_chunk_attention(q, kv, block_table, mask, seg_pos0, ctx_lens, l):
    """The block-masked chunk kernel over the pool, as one function of the
    step program: the dense layer's call and the scanned layers' share one
    trace and one lowering of the kernel (1.6 s each on a serving host, at
    every start of every prefill program). Its operations carry ``msa_attn``
    in their own name stack, which a called function does not inherit."""
    from deepspeed_tpu.ops.pallas.paged_attention import \
        block_prefill_attention

    with jax.named_scope("msa_attn"):
        return block_prefill_attention(q, kv, block_table, mask, seg_pos0,
                                       ctx_lens, layer=l)


def _msa_prefill(cfg, ap, y, q, pools, l, block_table, pos, real, seg_pos0,
                 ctx_lens):
    """Chunk attention under the learned selector's block mask: the pooled
    keys of each segment's own sequence scored and the blocks chosen, then
    the chunk kernel over the pool's pages, a tile of queries over the
    blocks one of them chose (this chunk's keys, values and pooled keys are
    written already). q [S, Tq, nq, d]; pos, real [S, Tq]. Returns
    (attention, pools')."""
    sz = cfg.msa
    with jax.named_scope("msa_index"):
        qi, ki = hybrid.msa_project(ap, y)
        with jax.named_scope("msa_pool_write"):
            pk = _msa_pool_chunk(sz, pools["pk"], l, ki, block_table, pos,
                                 real, seg_pos0)
        mask, visible = jax.vmap(
            lambda qs, p, t: block_sparse.msa_select(sz, qs, p, t))(
                qi, pk[l, block_table], pos)            # [S, Tq, nkv, Bm]
    with jax.named_scope("msa_attn"):
        a, *tiles = _msa_chunk_attention(q, pools["kv"], block_table, mask,
                                         seg_pos0, ctx_lens, l)
    return a, _msa_counts(dict(pools, pk=pk), sz, mask, visible, real, tiles)


def _scratch(pools, alive, state_slots):
    """Rows without a sequence read and write the pool's scratch slot (a
    stack without recurrent layers has no state pool: None)."""
    if "state" not in pools:
        return None
    return jnp.where(alive, state_slots, pools["state"].shape[1] - 1)


def _padded(x, width: int):
    """x [..., d] with zeros up to a pool's lane-padded row width."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _mla_write(cfg, mp, y, pos, pools, l, page, offset):
    """The mixer's projections and the tokens' latents written to their rows
    of the latent pool (zeros up to the pool's lane-padded width). Returns
    (q_n, q_r, pools') and, of a mixer with the selector, the normed query
    latent its queries read as a fourth value."""
    with jax.named_scope("mla_project"):
        q_n, q_r, latent, *cq0 = hybrid.mla_project(
            cfg, mp, y, pos, query_latent=bool(cfg.index_topk))
        kv = pools["kv"]
        kv = kv.at[l, page, offset].set(
            _padded(latent, kv.shape[-1]).astype(kv.dtype))
    return (q_n, q_r, dict(pools, kv=kv), *cq0)


def _absorbed_decode(cfg, mp, q_n, q_r, kv, l, table, ctx, windowed=False,
                     lower=None, chosen=None, scope="mla_attn"):
    """A token step of latent attention in its absorbed form: each head's
    query carried into the latent's coordinates (``mla_absorb``), the
    ``mla_decode`` kernel over the pages ``table`` lists of ``kv[l]`` (keys
    and values the same rows; under ``scope``; ``lower`` / ``chosen``: the
    kernel's window bound and the selector's choice), the result carried
    back to the head's values. Returns (o [S, n, v], pages fetched [S])."""
    from deepspeed_tpu.ops.pallas.paged_attention import mla_decode_attention

    z = cfg.mla_sizes(windowed)
    W = kv.shape[3]
    with jax.named_scope("mla_absorb"):
        q = _padded(jnp.concatenate(
            [hybrid.mla_absorb_q(cfg, mp, q_n), q_r], -1), W)
    with jax.named_scope(scope):
        o, fetched = mla_decode_attention(
            q.astype(kv.dtype), kv, table, ctx, value_dim=z.kv_rank,
            scale=z.scale, layer=l, lower=lower, chosen=chosen)
    with jax.named_scope("mla_absorb"):
        o = hybrid.mla_absorb_o(cfg, mp, o.astype(q_n.dtype), windowed)
    return o, fetched


def _mla_decode(cfg, mp, q_n, q_r, pools, l, block_table, context_lens):
    """A token step of dense latent attention over the sequence's own pages.
    Returns (o [S, n, v], pools')."""
    o, fetched = _absorbed_decode(cfg, mp, q_n, q_r, pools["kv"], l,
                                  block_table, context_lens)
    # asked of the kernel, and what the kernel counted as it fetched
    counts = jnp.stack([jnp.sum(context_lens), jnp.sum(fetched)])
    return o, dict(pools, counters=pools["counters"].at[_SPARSE:_MLA].add(
        counts.astype(jnp.int32)))


def _index_write(cfg, mp, y, cq0, pos, pools, l, page, offset):
    """The selector's projections, and the tokens' keys written beside their
    latents (``ik``, page-addressed as ``kv``). Returns (qI, w, pools')."""
    qi, ki, w = hybrid.index_project(cfg, mp, y, cq0, pos)
    ik = pools["ik"]
    return qi, w, dict(pools, ik=ik.at[l, page, offset].set(
        ki.astype(ik.dtype)))


def _dsa_decode(cfg, mp, y, cq0, q_n, q_r, pools, l, block_table, token_pos,
                context_lens, page, offset):
    """A token step of a full latent layer with the selector: score every
    cached token's indexer key (``dsa_index``: the kernel of that name over
    the sequence's own pages of keys), keep the ``index_topk`` highest,
    exactly (``dsa_select``: the k-th score found by its bits, no sort;
    every token of a shorter context), and run the absorbed form over the
    sequence's own pages with the choice as one more term of the kernel's
    mask (``dsa_attn``). The pages are walked whole: on this chip a gather
    of 2,048 chosen rows a sequence costs more than reading the context
    (1.5 ms a layer against 1.2 GB at the memory's rate; PERF.md section
    6, PR 45). Returns (o [S, n, v], pools')."""
    from deepspeed_tpu.ops.pallas.paged_attention import index_scores_decode

    Bm, bs = block_table.shape[1], pools["kv"].shape[2]
    with jax.named_scope("dsa_index"):
        qi, w, pools = _index_write(cfg, mp, y, cq0, token_pos, pools, l,
                                    page, offset)
        scores = index_scores_decode(qi.astype(pools["ik"].dtype), w,
                                     pools["ik"], block_table, context_lens,
                                     layer=l)
    with jax.named_scope("dsa_select"):
        seen = jnp.arange(Bm * bs)[None, :] < context_lens[:, None]
        chosen = block_sparse.topk_mask(jnp.where(seen, scores, 0.0), seen,
                                        cfg.index_topk)
    o, _ = _absorbed_decode(cfg, mp, q_n, q_r, pools["kv"], l, block_table,
                            context_lens, chosen=chosen, scope="dsa_attn")
    counts = jnp.stack([jnp.sum(jnp.minimum(context_lens, cfg.index_topk)),
                        jnp.sum(context_lens)])
    return o, dict(pools, counters=pools["counters"].at[_MLA:_WINDOW].add(
        counts.astype(jnp.int32)))


def _ring(window_table, pos, bs: int):
    """The ring page of each position: entry ``(pos // bs) % ring_pages`` of
    the sequence's row of ``window_table`` [S, R]; pos [S] or [S, T]."""
    entry = (pos // bs) % window_table.shape[1]
    if pos.ndim == 1:
        return jnp.take_along_axis(window_table, entry[:, None], axis=1)[:, 0]
    return jnp.take_along_axis(window_table, entry, axis=1)


def _window_counts(pools, rows_read, pos, real, bs: int, ring: int):
    """``pools`` with a windowed layer's counters added: the rows its
    queries read, and the ring pages a real token at ``pos`` began to write
    over (the first row of a page whose entry held an older page)."""
    recycled = real & (pos % bs == 0) & (pos // bs >= ring)
    counts = jnp.stack([jnp.sum(rows_read), jnp.sum(recycled)])
    return dict(pools, counters=pools["counters"].at[_WINDOW:_MSA].add(
        counts.astype(jnp.int32)))


def _wmla_decode(cfg, wp, y, pools, l, window_table, token_pos, context_lens):
    """A token step of a windowed latent layer: the token's latent written
    to its ring page, then the absorbed form over the ring's pages from the
    window's first on (the table turned so that entry 0 is that page), with
    the window's far edge as the kernel's lower bound. Returns (o [S, n, v],
    pools')."""
    wkv = pools["wkv"]
    bs, R, W = wkv.shape[2], window_table.shape[1], cfg.sliding_window
    alive = context_lens > 0
    with jax.named_scope("mla_project"):
        q_n, q_r, latent = hybrid.mla_project(cfg, wp, y, token_pos,
                                              windowed=True)
        page = jnp.where(alive, _ring(window_table, token_pos, bs),
                         wkv.shape[1] - 1)
        offset = jnp.where(alive, token_pos % bs, bs - 1)
        wkv = wkv.at[l, page, offset].set(
            _padded(latent, wkv.shape[-1]).astype(wkv.dtype))
    lo = jnp.maximum(context_lens - W, 0)       # the first position seen
    first = lo // bs                            # its page, along the context
    turned = jnp.take_along_axis(
        window_table, (first[:, None] + jnp.arange(R)[None, :]) % R, axis=1)
    ctx = jnp.where(alive, context_lens - first * bs, 0)
    o, _ = _absorbed_decode(cfg, wp, q_n, q_r, wkv, l, turned, ctx,
                            windowed=True, lower=lo - first * bs,
                            scope="wmla_attn")
    pools = _window_counts(dict(pools, wkv=wkv),
                           jnp.minimum(context_lens, W), token_pos, alive, bs,
                           R)
    return o, pools


# context tokens whose keys and values the chunk path holds expanded at once
_MLA_CHUNK_KEYS = 512


@jax.named_scope("mla_chunk")
def _mla_chunk(cfg, mp, q_n, q_r, kv, l, block_table, pos, ctx_lens,
               selected=None):
    """Chunk attention in the expanded form, one segment after another:
    each attends over its own sequence's cached latents (this chunk's are
    written already), up-projected to keys and values ``_MLA_CHUNK_KEYS``
    context tokens at a time under a running softmax, and no further than
    the segment's context: nothing holds a sequence's expanded keys (0.75
    GiB a layer at 24k tokens). q_n, q_r [S, Tq, n, .]; pos [S, Tq];
    ``selected`` bool [S, Tq, Bm * bs] or None: the context tokens the
    selector kept for each query, one more term of the mask.
    Returns o [S, Tq, n, v] in q's type."""
    S, Tq = pos.shape
    bs, Bm = kv.shape[2], block_table.shape[1]
    n, dv, dt = cfg.num_heads, cfg.v_head_dim, q_n.dtype
    Tk = min(_MLA_CHUNK_KEYS, Bm * bs)
    scale = cfg.mla_scale

    if selected is None:        # (a mask of nothing: one shape of the map)
        selected = jnp.ones((S, 1, 1), bool)

    def segment(args):
        qn, qr, table, ts, ctx, sel = args
        rows = with_layout_constraint(
            kv[l, table], Layout(major_to_minor=(0, 1, 2))).reshape(
                Bm * bs, -1)

        def block(b, carry):
            m, den, acc = carry
            lat = lax.dynamic_slice_in_dim(rows, b * Tk, Tk).astype(dt)
            k_n, v, k_r = hybrid.mla_expand(cfg, mp, lat)
            sc = (jnp.einsum("qnd,knd->nqk", qn, k_n)
                  + jnp.einsum("qnd,kd->nqk", qr, k_r)).astype(
                      jnp.float32) * scale
            seen = (b * Tk + jnp.arange(Tk))[None, :] <= ts[:, None]
            if sel.shape[-1] > 1:
                seen = seen & lax.dynamic_slice_in_dim(sel, b * Tk, Tk, 1)
            sc = jnp.where(seen[None], sc, -1e30)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen[None], jnp.exp(sc - m_new[..., None]), 0.0)
            den = alpha * den + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "nqk,knd->nqd", p.astype(dt), v).astype(jnp.float32)
            return m_new, den, acc

        init = (jnp.full((n, Tq), -1e30, jnp.float32),
                jnp.zeros((n, Tq), jnp.float32),
                jnp.zeros((n, Tq, dv), jnp.float32))
        _, den, acc = lax.fori_loop(0, (ctx + Tk - 1) // Tk, block, init)
        o = acc / jnp.where(den == 0.0, 1.0, den)[..., None]
        return jnp.swapaxes(o, 0, 1).astype(dt)

    return lax.map(segment, (q_n, q_r, block_table, pos, ctx_lens, selected))


def _dsa_chunk_select(cfg, qi, w, ik, l, block_table, pos, ctx_lens):
    """The selector on a chunk, one segment after another: the scores of
    the segment's queries against its sequence's cached indexer keys (this
    chunk's are written already), ``_MLA_CHUNK_KEYS`` context tokens at a
    time and no further than the context, then for each query the
    ``index_topk`` causally visible tokens that score highest, exactly, as a
    mask. qi [S, Tq, ni, di]; w [S, Tq, ni]; pos [S, Tq]. Returns bool [S,
    Tq, Bm * bs]."""
    S, Tq = pos.shape
    bs, Bm = ik.shape[2], block_table.shape[1]
    N = Bm * bs
    Tk = min(_MLA_CHUNK_KEYS, N)

    def segment(args):
        q, ws, table, ts, ctx = args
        keys = ik[l, table].reshape(N, -1)

        def block(b, sc):
            part = hybrid.index_scores(
                q, ws, lax.dynamic_slice_in_dim(keys, b * Tk, Tk))
            return lax.dynamic_update_slice_in_dim(sc, part, b * Tk, 1)

        sc = lax.fori_loop(0, (ctx + Tk - 1) // Tk, block,
                           jnp.zeros((Tq, N), jnp.float32))
        visible = jnp.arange(N)[None, :] <= ts[:, None]
        return block_sparse.topk_mask(sc, visible, cfg.index_topk)

    return lax.map(segment, (qi, w, block_table, pos, ctx_lens))


# queries of a windowed chunk whose scores are held at once
_WINDOW_CHUNK_QUERIES = 512


def _wmla_chunk(cfg, wp, y, pools, l, window_table, pos, real, seg_pos0,
                seg_nreal):
    """A chunk through a windowed latent layer, in the expanded form: its
    keys are the ``window - 1`` tokens before it, read from the ring
    *before* this chunk's rows are written (a chunk longer than the ring
    writes over itself), and its own latents, which never leave the
    program; a block of ``_WINDOW_CHUNK_QUERIES`` queries attends over the
    keys it can see, under the band. Then the chunk's last rows, as many as
    the ring holds without meeting itself, are written. y [S, Tq, H]; pos,
    real [S, Tq]. Returns (o [S, Tq, n, v], pools')."""
    S, Tq = pos.shape
    wkv = pools["wkv"]
    bs, R, W = wkv.shape[2], window_table.shape[1], cfg.sliding_window
    z = cfg.mla_sizes(True)
    dt = y.dtype
    P = W - 1                       # earlier tokens a query can see
    Bq = min(Tq, _WINDOW_CHUNK_QUERIES)
    with jax.named_scope("mla_project"):
        q_n, q_r, latent = hybrid.mla_project(cfg, wp, y, pos, windowed=True)
    with jax.named_scope("wmla_chunk"):
        tpos = seg_pos0[:, None] - P + jnp.arange(P)[None, :]       # [S, P]
        at = jnp.maximum(tpos, 0)
        tail = wkv[l, _ring(window_table, at, bs), at % bs]
        keys = jnp.concatenate(
            [tail[..., :z.latent_dim].astype(dt), latent], axis=1)
        kpos = jnp.concatenate([tpos, pos], axis=1)
        kreal = jnp.concatenate([tpos >= 0, real], axis=1)

        def segment(args):
            qn, qr, ks, kp, kr, ts = args

            def block(i):
                sl = lambda a, n: lax.dynamic_slice_in_dim(a, i * Bq, n)
                k_n, v, k_r = hybrid.mla_expand(cfg, wp, sl(ks, P + Bq),
                                                windowed=True)
                sc = (jnp.einsum("qnd,knd->nqk", sl(qn, Bq), k_n)
                      + jnp.einsum("qnd,kd->nqk", sl(qr, Bq), k_r)).astype(
                          jnp.float32) * z.scale
                back = sl(ts, Bq)[:, None] - sl(kp, P + Bq)[None, :]
                ok = (back >= 0) & (back < W) & sl(kr, P + Bq)[None, :]
                pr = jax.nn.softmax(jnp.where(ok[None], sc, -1e30), axis=-1)
                return jnp.einsum("nqk,knd->qnd", pr.astype(dt), v)

            return lax.map(block, jnp.arange(Tq // Bq)).reshape(
                Tq, z.heads, z.v)

        o = lax.map(segment, (q_n, q_r, keys, kpos, kreal, pos))
        # of a chunk longer than the ring, the rows the next step can see
        keep = real & (pos >= (seg_pos0 + seg_nreal)[:, None] - (R - 1) * bs)
        page = jnp.where(keep, _ring(window_table, pos, bs), wkv.shape[1] - 1)
        offset = jnp.where(keep, pos % bs, bs - 1)
        wkv = wkv.at[l, page, offset].set(
            _padded(latent, wkv.shape[-1]).astype(wkv.dtype))
    rows = jnp.where(real, jnp.minimum(pos + 1, W), 0)
    return o, _window_counts(dict(pools, wkv=wkv), rows, pos, keep, bs, R)


def refuse_unserved(cfg: HybridConfig) -> None:
    """The stacks the training path runs and no step program here does, each
    by its own exception (raised where an engine is built: ``store_specs``)."""
    if cfg.one_mixer:
        raise hybrid.OneMixerStackUnsupported(
            "a stack of one-mixer blocks is not served yet: the step "
            "programs run a mixer and a feed-forward a layer, and "
            "stack_plan and the KV / state pools count a slot a layer")
    if cfg.recurrent_kind == "mamba2" and cfg.recurrent_layers:
        raise hybrid.StateSpaceUnsupported(
            "the Mamba-2 mixer is not served yet: no decode rule, no state "
            "slot (heads x head x state float32 and a convolution tail) in "
            "the pool, no prefill that carries the scan's state across "
            "chunks")


def store_specs(cfg: HybridConfig, *, kv_blocks: int, kv_block_size: int,
                max_seqs: int, state_slots: Optional[int], dtype, quant_bits):
    """``model_runner.store_specs``'s contract, and the one place a
    configuration is read for what it keeps per sequence: pages of keys and
    values a full layer (with the sparse rule's compressed keys: a page is
    a block of the rule) or of latents (with the selector's keys); a slot
    of recurrent state; a ring of windowed latent pages, every sequence of
    a step its whole ring."""
    refuse_unserved(cfg)
    sparse = cfg.sparse
    rule = sparse or cfg.msa
    if rule is not None and kv_block_size != rule.block:
        raise ValueError(
            f"kv_block_size={kv_block_size}: a model with block-sparse "
            f"attention needs pages of its block size, {rule.block}")
    paged = KVCacheConfig(
        num_layers=cfg.kv_layers, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, block_size=kv_block_size,
        num_blocks=kv_blocks, dtype=dtype, quant_bits=quant_bits,
        compressed_per_block=sparse.per_block if sparse else 0,
        kind="latent" if cfg.latent_dim else "kv",
        latent_dim=cfg.latent_dim, index_key_dim=cfg.index_key_dim,
        pooled_key_dim=cfg.msa.index_dim if cfg.msa else 0)
    if quant_bits is not None:      # (a latent pool's own refusal came first)
        raise ValueError(
            "a quantized KV pool is not wired into the hybrid step "
            "programs (inference/hybrid_runner.py): serve with "
            "kv_quant_bits=None")
    beside = []
    if cfg.recurrent_layers:
        beside.append(StatePoolConfig(
            layers=cfg.recurrent_layers, slots=int(state_slots or max_seqs),
            heads=cfg.linear_num_value_heads, key_dim=cfg.linear_key_head_dim,
            value_dim=cfg.linear_value_head_dim, conv_taps=cfg.conv_taps,
            conv_channels=cfg.conv_channels, dtype=dtype))
    if cfg.window_latent_dim:
        beside.append(WindowPoolConfig.for_sequences(
            max_seqs, layers=cfg.window_layers, window=cfg.sliding_window,
            row_dim=cfg.window_latent_dim, block_size=kv_block_size,
            dtype=dtype))
    return paged, beside


def passes_per_token(cfg: HybridConfig) -> int:
    """``model_runner.passes_per_token``'s contract: a hybrid stack runs
    each layer once a token."""
    return 1


def min_segment(cfg: HybridConfig) -> int:
    """The prefill program's smallest chunk bucket: the chunked recurrence
    pads every row of a segment batch to a whole chunk (which also holds
    whole blocks of the sparse rule), so a smaller bucket would compile one
    more prefill program for the same work (each costs 12 s of compiling at
    the seventh architecture's sizes)."""
    if not cfg.recurrent_layers:
        # (under the learned selector a block of the rule: a chunk's keys
        # are pooled a block at a time, and its prompts are long)
        return cfg.msa.block if cfg.msa else 8
    return max(CHUNK, cfg.sparse.block if cfg.sparse else 0)


def has_gather(cfg: HybridConfig) -> bool:
    """No step of a model with block-sparse or latent attention runs the
    gather program (:func:`ragged_forward` refuses): its chunks go one
    sequence a call through the prefill program."""
    return (cfg.sparse is None and cfg.msa is None
            and cfg.attention_kind != "mla")


def gather_rows_computed(max_seqs: int, max_tokens: int) -> int:
    """The token rows one call of :func:`ragged_forward` computes, whatever
    it carries (a ``dstpu/dispatch`` span's ``padded_rows``): it lays the
    flat tokens out anew, sequence by token, for the chunked recurrence
    (``seg_real`` [S, T]), so every recurrent layer runs ``max_seqs`` rows
    of ``max_tokens`` each."""
    return max_seqs * max_tokens


def ragged_forward(cfg: HybridConfig, params, pools: Dict, token_ids, token_seq,
                   token_pos, block_table, num_tokens, state_slots=None
                   ) -> Tuple[jax.Array, Dict]:
    """One ragged step over flat tokens (``model_runner.ragged_forward``'s
    contract; sequences lie one after another in the flat order). Returns
    (logits [T, V] float32, pools'). Not built for a model with the sparse
    rule: it lays out one whole context per *token*, and such a model's
    contexts are long; the engine runs its chunks through the prefill
    program and its single tokens through the decode program."""
    if not has_gather(cfg):
        raise NotImplementedError(
            "the gather program holds a context per token and is not built "
            "for a model with block-sparse or latent attention: its steps "
            "run the prefill and decode programs")
    T = token_ids.shape[0]
    S, Bm = block_table.shape
    bs = pools["kv"].shape[2]
    dt = effective_dtype(cfg.dtype)
    real = jnp.arange(T) < num_tokens
    x = hybrid.embed_tokens(cfg, params, token_ids)

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

    def rec_fn(y, mp, l_rec, pools):
        proj, z = _rec_project(cfg, mp, y, token_pos)
        o, pools = _segment_recurrence(
            cfg, mp, l_rec, pools, slots, tuple(a[to_seg] for a in proj),
            seg_real, nreal)
        return _rec_output(cfg, mp, o[token_seq, col_of], z), pools

    def full_fn(y, ap, l_kv, pools):
        q, k, v, gate = hybrid.attn_project(cfg, ap, y, token_pos)
        kv, _ = _kv_write(pools["kv"], None, l_kv, page, offset, k, v)
        with jax.named_scope("kv_gather"):
            ctx = kv[l_kv, block_table].reshape(
                S, Bm * bs, 2, cfg.kv_heads, cfg.head_dim)[token_seq]
        qh = q.reshape(T, cfg.kv_heads, g_, cfg.head_dim)
        s = jnp.einsum("tkgd,tmkd->tkgm", qh, ctx[:, :, 0].astype(dt))
        s = s.astype(jnp.float32) / jnp.sqrt(jnp.float32(cfg.head_dim))
        seen = key_pos[None, None, None, :] <= token_pos[:, None, None, None]
        pr = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1).astype(dt)
        a = jnp.einsum("tkgm,tmkd->tkgd", pr, ctx[:, :, 1].astype(dt))
        return (hybrid.attn_output(ap, a.reshape(q.shape), gate),
                dict(pools, kv=kv))

    x, pools = _run_stack(cfg, params, x, pools, rec_fn, full_fn, real)
    return hybrid.head_logits(cfg, params, x), pools


def ragged_prefill_forward(cfg: HybridConfig, params, pools: Dict, seg_tokens,
                           seg_pos0, seg_nreal, block_table, state_slots=None,
                           window_table=None, *, mesh=None
                           ) -> Tuple[jax.Array, Dict]:
    """Prefill chunks, one segment a sequence slot; attention over each
    segment's own pages: plain products a KV head
    (``model_runner._segment_attention``) or, with the sparse rule, under
    its block mask. (``mesh`` is the step programs' common keyword.)
    Returns (logits [S, Tq, V] float32, pools')."""
    S, Tq = seg_tokens.shape
    bs = pools["kv"].shape[2]
    dt = effective_dtype(cfg.dtype)
    qi = jnp.arange(Tq)[None, :]
    pos = seg_pos0[:, None] + qi
    real = qi < seg_nreal[:, None]
    ctx_lens = seg_pos0 + seg_nreal
    x = hybrid.embed_tokens(cfg, params, seg_tokens)

    scratch = pools["kv"].shape[1] - 1
    page = jnp.where(real, jnp.take_along_axis(block_table, pos // bs, axis=1),
                     scratch)
    offset = jnp.where(real, pos % bs, bs - 1)
    slots = _scratch(pools, seg_nreal > 0,
                     None if state_slots is None else state_slots[:S])
    sz = cfg.sparse

    def rec_fn(y, mp, l_rec, pools):
        proj, z = _rec_project(cfg, mp, y, pos)
        o, pools = _segment_recurrence(cfg, mp, l_rec, pools, slots, proj,
                                       real, seg_nreal)
        return _rec_output(cfg, mp, o, z), pools

    def full_fn(y, ap, l_kv, pools):
        if cfg.attention_kind == "mla":
            q_n, q_r, pools, *cq0 = _mla_write(cfg, ap, y, pos, pools, l_kv,
                                               page, offset)
            sel = None
            if cq0:
                with jax.named_scope("dsa_index"):
                    qi, w, pools = _index_write(cfg, ap, y, cq0[0], pos,
                                                pools, l_kv, page, offset)
                    sel = _dsa_chunk_select(cfg, qi, w, pools["ik"], l_kv,
                                            block_table, pos, ctx_lens)
                seen = jnp.where(real, pos + 1, 0)
                pools = dict(pools, counters=pools["counters"].at[
                    _MLA:_WINDOW].add(jnp.stack([
                        jnp.sum(jnp.minimum(seen, cfg.index_topk)),
                        jnp.sum(seen)]).astype(jnp.int32)))
            o = _mla_chunk(cfg, ap, q_n, q_r, pools["kv"], l_kv, block_table,
                           pos, ctx_lens, sel)
            return hybrid.mla_output(ap, o, y), pools
        q, k, v, gate = hybrid.attn_project(cfg, ap, y, pos)
        kv, _ = _kv_write(pools["kv"], None, l_kv, page, offset, k, v)
        pools = dict(pools, kv=kv)
        if cfg.msa is not None:
            a, pools = _msa_prefill(cfg, ap, y, q.astype(dt), pools, l_kv,
                                    block_table, pos, real, seg_pos0,
                                    ctx_lens)
        elif sz is None:
            a = _segment_attention(cfg, q.astype(dt), kv, None, l_kv,
                                   block_table, pos)
        else:
            ck = _compress_new(sz, kv, pools["ck"], l_kv, block_table,
                               seg_pos0, seg_nreal, Tq // sz.stride + 1)
            a, counts = _sparse_prefill(cfg, q.astype(dt), kv, ck, l_kv,
                                        block_table, pos, real, ctx_lens)
            pools = dict(pools, ck=ck,
                         counters=pools["counters"].at[_MOE:_SPARSE].add(counts))
        return hybrid.attn_output(ap, a.astype(dt), gate), pools

    def win_fn(y, wp, l_win, pools):
        o, pools = _wmla_chunk(cfg, wp, y, pools, l_win, window_table[:S],
                               pos, real, seg_pos0, seg_nreal)
        return hybrid.mla_output(wp, o, y), pools

    x, pools = _run_stack(cfg, params, x, pools, rec_fn, full_fn,
                          real.reshape(-1), win_fn)
    return hybrid.head_logits(cfg, params, x), pools


def ragged_decode_forward(cfg: HybridConfig, params, pools: Dict, token_ids,
                          token_pos, block_table, context_lens,
                          state_slots=None, window_table=None, *, mesh=None
                          ) -> Tuple[jax.Array, Dict]:
    """One decode step: one new token for each live slot (``context_lens``
    0 marks a dead one). The recurrent layers run their decode kernel
    (``gdn_decode`` or ``lightning_decode``) on each sequence's slot, the
    full layers the paged decode kernel: over the sequence's pages or, with
    the sparse rule, over the pages it chose. Returns (logits [S, V] float32,
    pools')."""
    S = token_ids.shape[0]
    bs = pools["kv"].shape[2]
    dt = effective_dtype(cfg.dtype)
    alive = context_lens > 0
    x = hybrid.embed_tokens(cfg, params, token_ids)

    scratch = pools["kv"].shape[1] - 1
    page = jnp.where(alive, block_table[jnp.arange(S), token_pos // bs],
                     scratch)
    offset = jnp.where(alive, token_pos % bs, bs - 1)
    slots = _scratch(pools, alive, state_slots)
    sz = cfg.sparse

    def rec_fn(y, mp, l_rec, pools):
        if cfg.recurrent_kind == "lightning":
            q, k, v, z = hybrid.lightning_project(cfg, mp, y, token_pos)
            decay = jnp.broadcast_to(jnp.exp(cfg.lightning_decay()[l_rec]),
                                     q.shape[:2])
            o, state = lightning_decode(pools["state"], l_rec, slots, q, k, v,
                                        decay)
            return (hybrid.lightning_output(cfg, mp, o, z),
                    dict(pools, state=state))
        conv = pools["conv"]
        mixed, z, beta, g = hybrid.gdn_project(cfg, mp, y)
        out, window = hybrid.causal_conv(mp["conv"], conv[l_rec, slots],
                                         mixed[:, None, :])
        conv = conv.at[l_rec, slots].set(window[:, 1:].astype(conv.dtype))
        q, k, v = hybrid.gdn_heads(cfg, out[:, 0])
        o, state = gdn_decode(pools["state"], l_rec, slots, q, k, v, g, beta)
        return (hybrid.gdn_output(cfg, mp, o, z),
                dict(pools, state=state, conv=conv))

    def full_fn(y, ap, l_kv, pools):
        if cfg.attention_kind == "mla":
            q_n, q_r, pools, *cq0 = _mla_write(cfg, ap, y, token_pos, pools,
                                               l_kv, page, offset)
            if cq0:
                o, pools = _dsa_decode(cfg, ap, y, cq0[0], q_n, q_r, pools,
                                       l_kv, block_table, token_pos,
                                       context_lens, page, offset)
            else:
                o, pools = _mla_decode(cfg, ap, q_n, q_r, pools, l_kv,
                                       block_table, context_lens)
            return hybrid.mla_output(ap, o, y), pools
        q, k, v, gate = hybrid.attn_project(cfg, ap, y, token_pos)
        kv, _ = _kv_write(pools["kv"], None, l_kv, page, offset, k, v)
        pools = dict(pools, kv=kv)
        if cfg.msa is not None:
            a, pools = _msa_decode(cfg, mesh, ap, y, q.astype(dt), pools,
                                   l_kv, block_table, token_pos,
                                   context_lens, page)
        elif sz is None:
            a = _paged_decode(mesh, q.astype(dt), kv, l_kv, block_table,
                              context_lens)
        else:
            ck = _compress_new(sz, kv, pools["ck"], l_kv, block_table,
                               token_pos, alive.astype(jnp.int32), 1)
            a, counts = _sparse_decode(cfg, mesh, q.astype(dt), kv, ck, l_kv,
                                       block_table, context_lens)
            pools = dict(pools, ck=ck,
                         counters=pools["counters"].at[_MOE:_SPARSE].add(counts))
        return hybrid.attn_output(ap, a.astype(dt), gate), pools

    def win_fn(y, wp, l_win, pools):
        o, pools = _wmla_decode(cfg, wp, y, pools, l_win, window_table,
                                token_pos, context_lens)
        return hybrid.mla_output(wp, o, y), pools

    x, pools = _run_stack(cfg, params, x, pools, rec_fn, full_fn, alive,
                          win_fn)
    return hybrid.head_logits(cfg, params, x), pools


def ragged_multi_decode(cfg: HybridConfig, params, pools: Dict, token_ids,
                        token_pos, block_table, context_lens,
                        state_slots=None, window_table=None, *, steps: int,
                        mesh=None) -> Tuple[jax.Array, Dict, jax.Array]:
    """``steps`` greedy decode steps in one program, the argmax fed back on
    the device (``model_runner.ragged_multi_decode``'s contract, the last
    row handed out once more for the burst that follows included: a caller
    with a full batch issues call n+1 from it before it reads call n); the
    counters sum over the steps and are this call's own, so a caller holds
    them until it reads the call's tokens. Returns (tokens [steps, S] int32,
    pools', tokens[steps - 1])."""
    def body(carry, _):
        pools, tok, pos, ctx, counts = carry
        logits, pools = ragged_decode_forward(
            cfg, params, pools, tok, pos, block_table, ctx, state_slots,
            window_table, mesh=mesh)
        alive = ctx > 0
        nxt = jnp.where(alive, jnp.argmax(logits, axis=-1).astype(jnp.int32), 0)
        counts = counts + pools["counters"]
        return (pools, nxt, pos + 1, jnp.where(alive, ctx + 1, 0), counts), nxt

    (pools, last, _, _, counts), toks = lax.scan(
        body, (dict(pools, counters=_no_counts()), token_ids, token_pos,
               context_lens, _no_counts()), length=steps)
    return toks, dict(pools, counters=counts), last

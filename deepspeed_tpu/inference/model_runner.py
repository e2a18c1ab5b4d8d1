"""Compiled inference forwards over the TransformerLM param tree.

Two paths, both reusing models/transformer.py weights unchanged:

  * ``forward_with_cache`` — dense per-batch KV cache, for the v1-style
    engine (reference: fused inference kernels consuming a contiguous
    cache, csrc/transformer/inference).
  * ``ragged_forward`` — paged/blocked KV with flat-token ragged batches,
    for the FastGen-style engine (reference: inference/v2 ragged kernels:
    blocked flash attention + fused rotary/KV-append,
    inference/v2/kernels/ragged_ops/). On TPU the KV append is an XLA
    scatter fused into the step, and attention runs over gathered pages;
    a Pallas paged-attention kernel can swap in behind the same signature.

Both are pure functions: (params, cache, metadata) -> (logits, cache'),
jitted once per shape bucket (the CUDA-graph analog, engine.py:497).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.inference.ragged.kv_cache import KVCacheConfig
from deepspeed_tpu.models.transformer import (
    LoopedStackUnsupported, TransformerConfig, _norm, _rope, act_fn)
from deepspeed_tpu.ops.pallas.quantization import (kv_dequantize,
                                                   kv_pack, kv_quantize,
                                                   kv_unpack)
from deepspeed_tpu.runtime.sharding import (effective_dtype,
                                            vocab_parallel_lookup)


def _kv_parts(kv_state):
    """Split the ragged KV pool pytree (``BlockedKVCache.kv_state``): ``kv``
    alone (bf16 pool) yields (data, None); with the fp32 ``scales`` of a
    quantized pool, both. The quantized branch is chosen at trace time, so
    the unquantized lowering carries no quant ops at all (and the dict
    itself never reaches the lowered program)."""
    return kv_state["kv"], kv_state.get("scales")


def _kv_bits(kv_layer):
    """Storage width of a quantized pool, inferred at trace time from
    the payload dtype: int8 holds one value per byte; uint8 is the
    packed-nibble int4 pool (two values per byte, last dim head_dim//2
    — the codec PR 12 ships for the handoff wire, applied to storage);
    float8_e4m3fn is the fp8 quality-midpoint pool (ISSUE 17), which
    the codec passes through unpacked."""
    if kv_layer.dtype == jnp.float8_e4m3fn:
        return "fp8"
    return 4 if kv_layer.dtype == jnp.uint8 else 8


def _kernel_pages() -> int:
    """``kernels.pages_per_compute_block`` where a kernel config is
    installed (ops.attention.set_kernel_config: a training engine does,
    a serving engine does not), resolved at trace time; 0, and nothing
    installed, leave the block to the kernel."""
    from deepspeed_tpu.ops import attention as attn_ops

    return int(getattr(attn_ops._KERNEL_CONFIG, "pages_per_compute_block",
                       0) or 0)


@jax.named_scope("kv_write")
def _kv_write(kv, kv_sc, layer, page, offset, k, v):
    """Scatter this step's keys and values into their rows
    ``[layer, page, offset]`` of the carried pool (K and V as one scatter
    of the stacked pair); a quantized pool quantizes on append (one scale
    per head vector). The pool is a loop carry that nothing else reads,
    so XLA updates it in place. Returns (kv', kv_sc')."""
    new = jnp.stack([k, v], axis=-3)              # [..., 2, nkv, hd]
    if kv_sc is None:
        return kv.at[layer, page, offset].set(new.astype(kv.dtype)), None
    bits = _kv_bits(kv)
    q, sc = kv_quantize(new, bits=bits)
    kv = kv.at[layer, page, offset].set(kv_pack(q, bits))
    return kv, kv_sc.at[layer, page, offset].set(sc)


def _kv_dense(kv, kv_sc, layer, dt):
    """The (pool, layer) pair the Pallas kernels take. A bf16 pool goes
    in whole (the kernel's index map picks the layer); a quantized pool
    dequantizes its per-layer slice (transient, 1/L of the bf16 pool; the
    persistent pool stays int8 / packed int4 / fp8), which the kernel
    reads as a one-layer pool at layer 0."""
    if kv_sc is None:
        return kv, layer
    dense = kv_dequantize(kv_unpack(kv[layer], _kv_bits(kv)), kv_sc[layer],
                          dtype=dt)
    return dense[None], 0


def _gather_sequences(cfg, kv, kv_sc, layer, block_table, dt):
    """The pages of each row of ``block_table`` [S, Bm] (and no others)
    taken from layer ``layer`` of the pool, a quantized pool dequantized on
    read (only the gathered pages, never the pool), split into keys and
    values and laid out head-major: two of [S, nkv, Lmax, hd]."""
    gathered = kv[layer, block_table]    # [S, Bm, bs, 2, nkv, hd(/2)]
    if kv_sc is not None:
        gathered = kv_dequantize(
            kv_unpack(gathered, _kv_bits(kv)),
            kv_sc[layer, block_table], dtype=dt)
    gathered = gathered.reshape(block_table.shape[0], -1, 2, cfg.kv_heads,
                                cfg.head_dim)
    return (gathered[:, :, 0].transpose(0, 2, 1, 3),
            gathered[:, :, 1].transpose(0, 2, 1, 3))


def _scan_layers(cfg, layer_body, x, params, kv_data, kv_scales):
    """Run ``layer_body((x, kv, kv_sc), (layer_params, l))`` over the
    layers with the pool as the scan's *carry*: one buffer, scattered
    into at ``[l, ...]`` and read at ``l``, never sliced per layer into
    ``xs`` nor restacked from ``ys``. Returns (x, kv_state').

    A looped stack (``cfg.ut_steps`` passes over ``L`` layers) is ONE scan
    over the pool's ``ut_steps * L`` slots: step ``s`` is pass ``s // L``
    of layer ``s % L``, takes that layer's weights from the stack, writes
    and reads the pool's slot ``s`` (:func:`store_specs`), and where it ends
    a pass but the last the model's final norm follows it (the last pass's
    is the head's, :func:`_unembed`, as for a stack run once). One loop and not a loop of passes around a
    loop of layers: XLA hoists a relayout of the whole ``wq``/``wk``/``wv``
    stacks out of an inner loop, 1.1 GiB of temporaries a call at the
    published widths."""
    if cfg.ut_steps > 1:
        slots = kv_data.shape[0]
        L = slots // cfg.ut_steps

        def slot_body(carry, slot):
            l = slot % L
            layer_params = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, l, keepdims=False),
                params["layers"])
            with jax.named_scope("ut_pass"):
                (x, kv, kv_sc), _ = layer_body(carry, (layer_params, slot))
            with jax.named_scope("pass_norm"):
                normed = _norm(x, params["final_norm"], cfg.norm,
                               cfg.norm_eps)
            between = (l == L - 1) & (slot < slots - 1)
            return (jnp.where(between, normed, x), kv, kv_sc), None

        (x, kv_data, kv_scales), _ = lax.scan(
            slot_body, (x, kv_data, kv_scales),
            jnp.arange(slots, dtype=jnp.int32))
    else:
        (x, kv_data, kv_scales), _ = lax.scan(
            layer_body, (x, kv_data, kv_scales),
            (params["layers"], jnp.arange(kv_data.shape[0], dtype=jnp.int32)))
    return x, ({"kv": kv_data} if kv_scales is None
               else {"kv": kv_data, "scales": kv_scales})


def _attn_out(cfg: TransformerConfig, layer_params, attn, spec: str):
    """The attention branch's output: heads back to hidden (``spec``), its
    bias, and with ``post_norms`` the branch's own norm."""
    attn = jnp.einsum(spec, attn, layer_params["attn"]["wo"].astype(attn.dtype))
    if cfg.use_biases:
        attn = attn + layer_params["attn"]["bo"].astype(attn.dtype)
    if cfg.post_norms:
        attn = _norm(attn, layer_params["ln1_post"], cfg.norm, cfg.norm_eps)
    return attn


def _qkv(cfg: TransformerConfig, layer_params, y, positions):
    """Project y [..., H] to q/k/v with rope applied. Returns q [.., nh, hd],
    k/v [.., nkv, hd] (GQA heads NOT repeated — cache stays small)."""
    ap = layer_params["attn"]
    dt = y.dtype
    q = jnp.einsum("...h,hnd->...nd", y, ap["wq"].astype(dt))
    k = jnp.einsum("...h,hnd->...nd", y, ap["wk"].astype(dt))
    v = jnp.einsum("...h,hnd->...nd", y, ap["wv"].astype(dt))
    if cfg.use_biases:
        q = q + ap["bq"].astype(dt)
        k = k + ap["bk"].astype(dt)
        v = v + ap["bv"].astype(dt)
    if cfg.pos_emb == "rope":
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attention_probs(cfg: TransformerConfig, scores, mask):
    """Scaled, masked softmax over the last (context) axis: the scale in
    the scores' own dtype, mask and softmax in float32, the result back in
    that dtype. Whatever the leading axes are; ``mask`` broadcasts."""
    dt = scores.dtype
    scores = scores / jnp.sqrt(jnp.float32(cfg.head_dim)).astype(dt)
    scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
    return jax.nn.softmax(scores, axis=-1).astype(dt)


@jax.named_scope("mlp")
def _mlp(cfg: TransformerConfig, layer_params, x):
    if "moe" in layer_params:
        return _moe_mlp(cfg, layer_params, x)
    mp = layer_params["mlp"]
    dt = x.dtype
    y = _norm(x, layer_params["ln2"], cfg.norm, cfg.norm_eps)
    if cfg.activation == "swiglu":
        g = jnp.einsum("...h,hf->...f", y, mp["wg"].astype(dt))
        u = jnp.einsum("...h,hf->...f", y, mp["wi"].astype(dt))
        z = jax.nn.silu(g) * u
    else:
        act = act_fn(cfg.activation)
        pre = jnp.einsum("...h,hf->...f", y, mp["wi"].astype(dt))
        if cfg.use_biases:
            pre = pre + mp["bi"].astype(dt)
        z = act(pre)
    out = jnp.einsum("...f,fh->...h", z, mp["wo"].astype(dt))
    if cfg.use_biases:
        out = out + mp["bo"].astype(dt)
    if cfg.post_norms:
        out = _norm(out, layer_params["ln2_post"], cfg.norm, cfg.norm_eps)
    return x + out


def _moe_mlp(cfg, layer_params, x):
    """MoE FFN for the inference runners (reference: inference/v2
    model_implementations mixtral/qwen_v2_moe — moe_gather/moe_scatter +
    top_k_gating ragged kernels). Token dropping is disabled: serving
    must route every token (capacity = tokens, the reference's
    no-drop inference dispatch)."""
    import dataclasses

    from deepspeed_tpu.parallel.moe import moe_ffn

    y = _norm(x, layer_params["ln2"], cfg.norm, cfg.norm_eps)
    flat = y[None] if y.ndim == 2 else y  # [1,T,H] / [S,Tq,H] groups
    gate = dataclasses.replace(cfg.gate, drop_tokens=False)
    out, _aux = moe_ffn(flat, layer_params["moe"]["router"],
                        layer_params["moe"]["experts"], gate,
                        activation=cfg.activation, train=False,
                        impl=getattr(cfg, "moe_impl", "auto"))
    return x + (out[0] if y.ndim == 2 else out)


@jax.named_scope("head")
def _unembed(cfg: TransformerConfig, params, x):
    """Final norm + unembedding: hidden [..., H] -> fp32 logits."""
    x = _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...h,vh->...v", x,
                            params["embed"]["tokens"].astype(x.dtype))
    else:
        logits = jnp.einsum("...h,hv->...v", x,
                            params["unembed"]["kernel"].astype(x.dtype))
    return logits.astype(jnp.float32)


# ---------------------------------------------------------------------------
# dense-cache path (v1 engine)
# ---------------------------------------------------------------------------


def init_dense_cache(cfg: TransformerConfig, batch: int, max_len: int,
                     dtype=None):
    """cache: [L, B, max_len, 2, kv_heads, head_dim]."""
    dtype = dtype or effective_dtype(cfg.dtype)
    return jnp.zeros((cfg.num_layers, batch, max_len, 2, cfg.kv_heads,
                      cfg.head_dim), dtype)


def forward_with_cache(cfg: TransformerConfig, params, tokens: jax.Array,
                       cache: jax.Array, start_pos) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, S] starting at absolute position start_pos (scalar);
    returns (logits [B, S, V] fp32, updated cache). Works for prefill
    (S = prompt len, start_pos = 0) and decode (S = 1)."""
    if cfg.ut_steps > 1:
        raise LoopedStackUnsupported(
            "forward_with_cache (the v1 engine's dense cache) keeps one K/V "
            "slot a layer; a looped stack needs one a pass and layer: serve "
            "it through InferenceEngineV2")
    B, S = tokens.shape
    dt = effective_dtype(cfg.dtype)
    max_len = cache.shape[2]
    positions = start_pos + jnp.arange(S)[None, :]  # [1, S] broadcasts to B

    x = vocab_parallel_lookup(params["embed"]["tokens"].astype(dt), tokens)
    if cfg.pos_emb == "learned":
        x = x + params["embed"]["positions"].astype(dt)[positions]

    key_pos = jnp.arange(max_len)  # absolute position of each cache row

    def layer_body(x, inputs):
        layer_params, kv_layer = inputs  # kv_layer [B, max_len, 2, nkv, hd]
        y = _norm(x, layer_params["ln1"], cfg.norm, cfg.norm_eps)
        q, k, v = _qkv(cfg, layer_params, y, positions)
        # append this step's kv at rows [start_pos, start_pos+S)
        kv_new = jnp.stack([k, v], axis=2).astype(kv_layer.dtype)  # [B,S,2,nkv,hd]
        kv_layer = lax.dynamic_update_slice(
            kv_layer, kv_new, (0, start_pos, 0, 0, 0))
        # grouped-query attention per KV head (k), its query heads as a
        # group axis (g), against the cache as it lies: g is 1 for a
        # multi-head model, k is 1 for multi-query
        qg = q.reshape(B, S, cfg.kv_heads, -1, cfg.head_dim)
        scores = jnp.einsum("bskgd,bmkd->bkgsm", qg,
                            kv_layer[:, :, 0].astype(dt))
        mask = key_pos[None, :] <= positions[0, :, None]  # [S, max_len]
        probs = _attention_probs(cfg, scores, mask)
        attn = jnp.einsum("bkgsm,bmkd->bskgd", probs,
                          kv_layer[:, :, 1].astype(dt)).reshape(q.shape)
        attn = _attn_out(cfg, layer_params, attn, "bsnd,ndh->bsh")
        if cfg.parallel_block:  # Falcon: both branches read pre-attn x
            return _mlp(cfg, layer_params, x) + attn, kv_layer
        x = x + attn
        return _mlp(cfg, layer_params, x), kv_layer

    x, new_cache = lax.scan(layer_body, x, (params["layers"], cache))
    return _unembed(cfg, params, x), new_cache


# ---------------------------------------------------------------------------
# ragged paged-KV path (v2 engine)
# ---------------------------------------------------------------------------


# What the serving engine asks of a runner beside its four programs (the same
# names in ``hybrid_runner``): the stores a configuration keeps per sequence,
# the tree its programs take, the smallest chunk bucket of the prefill
# program, whether a step may use the gather program, and what the programs
# count (these return the pools alone: no ``counters`` vector, none of them
# kept apart for the decode programs, no occupancy key ``stats`` always has)
COUNTERS = DECODE_COUNTERS = OCCUPANCY = ()


def store_specs(cfg: TransformerConfig, *, kv_blocks: int,
                kv_block_size: int, max_seqs: int,
                state_slots: Optional[int], dtype, quant_bits):
    """``(the paged pool's spec, the specs of the stores beside it)`` for
    an engine of these sizes: keys and values a layer and pass (a looped
    stack keeps each pass's own: ``ut_steps * num_layers`` slots from
    ``num_layers`` layers of weights), nothing beside."""
    return KVCacheConfig(
        num_layers=cfg.ut_steps * cfg.num_layers, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, block_size=kv_block_size,
        num_blocks=kv_blocks, dtype=dtype, quant_bits=quant_bits), []


def serving_params(cfg: TransformerConfig, params, donate: bool = False):
    """The tree the programs take: the model's own."""
    return params


def passes_per_token(cfg: TransformerConfig) -> int:
    """How often a token row runs the stack in one token step of any of the
    four programs: every pass of a looped stack (no row leaves early)."""
    return cfg.ut_steps


def min_segment(cfg: TransformerConfig) -> int:
    return 8


def has_gather(cfg: TransformerConfig) -> bool:
    return True


def gather_rows_computed(max_seqs: int, max_tokens: int) -> int:
    """The token rows one call of :func:`ragged_forward` computes, whatever
    it carries: the flat budget (what a ``dstpu/dispatch`` span gives as its
    ``padded_rows``)."""
    return max_tokens


def ragged_forward(cfg: TransformerConfig, params, kv_data: Dict,
                   token_ids: jax.Array, token_seq: jax.Array,
                   token_pos: jax.Array, block_table: jax.Array,
                   num_tokens) -> Tuple[jax.Array, jax.Array]:
    """One ragged step over flat tokens.

    kv_data     ``BlockedKVCache.kv_state``: ``kv`` [L, num_blocks, bs, 2,
                nkv, hd] and, for a quantized pool (then the int8
                payload), its fp32 ``scales`` [L, nb, bs, 2, nkv]
    token_ids   [T] int32 (padded); token_seq [T] slot ids; token_pos [T]
    block_table [S, Bm]; num_tokens scalar (true T, rest is padding)

    Returns (logits [T, V] fp32, kv_data'). Causal masking derives solely
    from token_pos: a query at position p attends cache rows 0..p of its
    sequence, which are exactly the rows written so far (plus this step's
    scatter, which lands before the attention reads). Padding tokens are
    routed to write into the reserved scratch block (last block id) so
    they never corrupt live pages.
    """
    kv_data, kv_scales = _kv_parts(kv_data)
    T = token_ids.shape[0]
    Bm = block_table.shape[1]
    bs = kv_data.shape[2]
    dt = effective_dtype(cfg.dtype)
    is_real = jnp.arange(T) < num_tokens  # [T]

    x = vocab_parallel_lookup(
        params["embed"]["tokens"].astype(dt), token_ids)  # [T, H]
    if cfg.pos_emb == "learned":
        x = x + params["embed"]["positions"].astype(dt)[token_pos]

    # destination page/offset per token; padded tokens write to the last
    # block's last row (block num_blocks-1 is reserved as scratch by the
    # engine) so they never corrupt live pages.
    page = block_table[token_seq, token_pos // bs]  # [T]
    offset = token_pos % bs
    scratch = kv_data.shape[1] - 1
    page = jnp.where(is_real, page, scratch)
    offset = jnp.where(is_real, offset, bs - 1)

    # context length per token's sequence, for causal masking
    max_ctx = Bm * bs
    key_pos = jnp.arange(max_ctx)  # [Lmax]

    def layer_body(carry, inputs):
        x, kv, kv_sc = carry             # kv [L, num_blocks, bs, 2, nkv, hd]
        layer_params, l = inputs
        with jax.named_scope("attn"):
            y = _norm(x, layer_params["ln1"], cfg.norm, cfg.norm_eps)
            # q [T,nh,hd] k/v [T,nkv,hd]
            q, k, v = _qkv(cfg, layer_params, y, token_pos)
        kv, kv_sc = _kv_write(kv, kv_sc, l, page, offset, k, v)
        with jax.named_scope("kv_gather"):
            # gather each slot's pages (and no others) into dense
            # [S, Lmax, 2, nkv, hd], lay the *sequences'* keys and values
            # out head-major, [S, nkv, Lmax, hd] each (an eighth of what
            # the tokens' are), then one row of the full context per
            # *token*, [T, nkv, Lmax, hd]: the layout the contractions
            # below read, so the take is the only pass that writes a
            # token's context
            k_seq, v_seq = _gather_sequences(cfg, kv, kv_sc, l, block_table,
                                             dt)
            k_seq, v_seq = k_seq[token_seq], v_seq[token_seq]
        with jax.named_scope("attn"):
            # grouped-query attention per KV head (k), its query heads as
            # a group axis (g): g is 1 for a multi-head model, k is 1 for
            # multi-query
            qg = q.reshape(T, cfg.kv_heads, -1, cfg.head_dim)
            scores = jnp.einsum("tkgd,tkmd->tkgm", qg, k_seq.astype(dt))
            mask = key_pos[None, None, None, :] \
                <= token_pos[:, None, None, None]
            probs = _attention_probs(cfg, scores, mask)
            attn = jnp.einsum("tkgm,tkmd->tkgd", probs,
                              v_seq.astype(dt)).reshape(q.shape)
            attn = _attn_out(cfg, layer_params, attn, "tnd,ndh->th")
        if cfg.parallel_block:  # Falcon: both branches read pre-attn x
            x = _mlp(cfg, layer_params, x) + attn
        else:
            x = _mlp(cfg, layer_params, x + attn)
        return (x, kv, kv_sc), None

    x, new_kv = _scan_layers(cfg, layer_body, x, params, kv_data, kv_scales)
    return _unembed(cfg, params, x), new_kv


# ---------------------------------------------------------------------------
# segmented prefill path: Pallas chunked-prefill kernel (v2 engine)
# ---------------------------------------------------------------------------




def _on_tp_mesh(kernel, mesh, q_spec, q, kv, layer, *meta):
    """Run a Pallas paged-attention kernel, wrapped for a multi-device
    mesh where there is one.

    Pallas calls can't run under plain GSPMD partitioning; shard_map
    makes the mesh manual so each shard runs the kernel on its local
    heads: q sharded on num_heads over tp, the KV pool sharded on
    kv_heads over tp (contiguous GQA grouping keeps q-head i's kv head
    on the same shard whenever tp divides kv_heads — the engine gates
    on that), layer and metadata replicated. Axes other than tp are
    unmentioned = replicated (the default inference mesh absorbs spare
    chips into dp). Reference: the TP-sharded ragged kernels of
    inference/v2 (kernels/ragged_ops + TP sharding).

    ``kv`` and ``layer`` as :func:`_kv_dense` hands them out.
    """
    pages = _kernel_pages()

    def call(q, kv, layer, *meta):
        return kernel(q, kv, *meta, layer=layer,
                      pages_per_compute_block=pages)

    layer = jnp.asarray(layer, jnp.int32)
    if mesh is None:
        return call(q, kv, layer, *meta)
    from jax.sharding import PartitionSpec as PS

    kv_spec = PS(None, None, None, None, "tp", None)
    in_specs = (q_spec, kv_spec) + (PS(),) * (1 + len(meta))
    return jax.shard_map(call, mesh=mesh, in_specs=in_specs,
                         out_specs=q_spec, check_vma=False)(
                             q, kv, layer, *meta)


def _paged_decode(mesh, q, kv, layer, block_table, context_lens):
    from jax.sharding import PartitionSpec as PS

    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_decode_attention

    return _on_tp_mesh(paged_decode_attention, mesh, PS(None, "tp", None),
                       q, kv, layer, block_table, context_lens)


def _paged_prefill(mesh, q, kv, layer, block_table, seg_pos0, ctx_lens):
    from jax.sharding import PartitionSpec as PS

    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_prefill_attention

    return _on_tp_mesh(paged_prefill_attention, mesh,
                       PS(None, None, "tp", None), q, kv, layer,
                       block_table, seg_pos0, ctx_lens)


def _segment_attention(cfg: TransformerConfig, q, kv, kv_sc, layer,
                       block_table, pos):
    """Causal attention of each segment's chunk over that segment's own
    pages, as plain products: q [S, Tq, nh, hd] at positions ``pos``
    [S, Tq] against the pool rows of ``block_table`` [S, Bm]. The context
    is read once a *sequence* ([S, nkv, Lmax, hd], a quantized pool
    dequantized on read) and contracted per KV head (k) with its query
    heads as a group axis (g); no per-token context exists. Rows past a
    token's position are masked, so whatever the pages hold beyond the
    chunk's end is never read into a result."""
    S, Tq = pos.shape
    dt = q.dtype
    with jax.named_scope("kv_gather"):
        k_seq, v_seq = _gather_sequences(cfg, kv, kv_sc, layer, block_table,
                                         dt)
    qg = q.reshape(S, Tq, cfg.kv_heads, -1, cfg.head_dim)
    scores = jnp.einsum("stkgd,skmd->stkgm", qg, k_seq.astype(dt))
    key_pos = jnp.arange(k_seq.shape[2])
    mask = key_pos <= pos[:, :, None, None, None]
    probs = _attention_probs(cfg, scores, mask)
    return jnp.einsum("stkgm,skmd->stkgd", probs,
                      v_seq.astype(dt)).reshape(q.shape)


def ragged_prefill_forward(cfg: TransformerConfig, params,
                           kv_data: Dict, seg_tokens: jax.Array,
                           seg_pos0: jax.Array, seg_nreal: jax.Array,
                           block_table: jax.Array, *, mesh=None
                           ) -> Tuple[jax.Array, jax.Array]:
    """Prefill chunks, one segment per sequence slot.

    Reference: the SplitFuse prefill path of inference/v2 (blocked flash
    over new chunks + paged history). Each segment s runs ``nreal[s]``
    new tokens at absolute positions pos0[s].. through the paged cache;
    padded rows (qi >= nreal) and dead segments (nreal == 0) write to the
    scratch page and emit garbage logits the engine never reads. The
    attention is plain products over each segment's own pages
    (:func:`_segment_attention`): on the chip the Pallas prefill kernel
    was never the faster of the two here (PERF.md, PR 38).

    seg_tokens [S, Tq] int32; seg_pos0/seg_nreal [S]; block_table [S, Bm]
    (``mesh`` is the step programs' common keyword: the plain products
    need no ``shard_map``.) Returns (logits [S, Tq, V] fp32, kv_data').
    """
    kv_data, kv_scales = _kv_parts(kv_data)
    Tq = seg_tokens.shape[1]
    bs = kv_data.shape[2]
    dt = effective_dtype(cfg.dtype)

    qi = jnp.arange(Tq)[None, :]                      # [1, Tq]
    pos = seg_pos0[:, None] + qi                      # [S, Tq]
    real = qi < seg_nreal[:, None]                    # [S, Tq]

    x = vocab_parallel_lookup(
        params["embed"]["tokens"].astype(dt), seg_tokens)  # [S, Tq, H]
    if cfg.pos_emb == "learned":
        x = x + params["embed"]["positions"].astype(dt)[pos]

    scratch = kv_data.shape[1] - 1
    page = jnp.take_along_axis(block_table, pos // bs, axis=1)  # [S, Tq]
    page = jnp.where(real, page, scratch)
    offset = jnp.where(real, pos % bs, bs - 1)

    def layer_body(carry, inputs):
        x, kv, kv_sc = carry
        layer_params, l = inputs
        with jax.named_scope("attn"):
            y = _norm(x, layer_params["ln1"], cfg.norm, cfg.norm_eps)
            q, k, v = _qkv(cfg, layer_params, y, pos)  # q [S,Tq,nh,hd]
        kv, kv_sc = _kv_write(kv, kv_sc, l, page, offset, k, v)
        with jax.named_scope("attn"):
            attn = _segment_attention(cfg, q.astype(dt), kv, kv_sc, l,
                                      block_table, pos)
            attn = _attn_out(cfg, layer_params, attn.astype(dt),
                             "stnd,ndh->sth")
        if cfg.parallel_block:  # Falcon: both branches read pre-attn x
            x = _mlp(cfg, layer_params, x) + attn
        else:
            x = _mlp(cfg, layer_params, x + attn)
        return (x, kv, kv_sc), None

    x, new_kv = _scan_layers(cfg, layer_body, x, params, kv_data, kv_scales)
    return _unembed(cfg, params, x), new_kv


# ---------------------------------------------------------------------------
# decode-only ragged path: Pallas paged-attention kernel (v2 engine)
# ---------------------------------------------------------------------------


def ragged_decode_forward(cfg: TransformerConfig, params, kv_data: Dict,
                          token_ids: jax.Array, token_pos: jax.Array,
                          block_table: jax.Array, context_lens: jax.Array,
                          *, mesh=None) -> Tuple[jax.Array, jax.Array]:
    """One decode step: exactly one new token per live slot.

    Reference: the blocked-flash decode kernels of inference/v2
    (ragged_ops/blocked_flash + linear_blocked_kv_rotary) — here the KV
    append is an XLA scatter and attention is the Pallas paged kernel
    (ops/pallas/paged_attention.py), so no per-token context is ever
    gathered. Dead slots have context_lens == 0: their K/V writes are
    routed to the scratch page and their logits are zeros.

    kv_data      [L, num_blocks, bs, 2, nkv, hd]
    token_ids    [S] int32;  token_pos [S];  block_table [S, Bm]
    context_lens [S] = token_pos + 1 for live slots, 0 for dead

    Returns (logits [S, V] fp32, kv_data').
    """
    kv_data, kv_scales = _kv_parts(kv_data)
    S = token_ids.shape[0]
    bs = kv_data.shape[2]
    dt = effective_dtype(cfg.dtype)
    alive = context_lens > 0

    x = vocab_parallel_lookup(
        params["embed"]["tokens"].astype(dt), token_ids)  # [S, H]
    if cfg.pos_emb == "learned":
        x = x + params["embed"]["positions"].astype(dt)[token_pos]

    scratch = kv_data.shape[1] - 1
    page = block_table[jnp.arange(S), token_pos // bs]
    page = jnp.where(alive, page, scratch)
    offset = jnp.where(alive, token_pos % bs, bs - 1)

    def layer_body(carry, inputs):
        x, kv, kv_sc = carry
        layer_params, l = inputs
        with jax.named_scope("attn"):
            y = _norm(x, layer_params["ln1"], cfg.norm, cfg.norm_eps)
            q, k, v = _qkv(cfg, layer_params, y, token_pos)  # q [S,nh,hd]
        kv, kv_sc = _kv_write(kv, kv_sc, l, page, offset, k, v)
        with jax.named_scope("attn"):
            attn = _paged_decode(mesh, q.astype(dt),
                                 *_kv_dense(kv, kv_sc, l, dt),
                                 block_table, context_lens)
            attn = _attn_out(cfg, layer_params, attn.astype(dt),
                             "snd,ndh->sh")
        if cfg.parallel_block:  # Falcon: both branches read pre-attn x
            x = _mlp(cfg, layer_params, x) + attn
        else:
            x = _mlp(cfg, layer_params, x + attn)
        return (x, kv, kv_sc), None

    x, new_kv = _scan_layers(cfg, layer_body, x, params, kv_data, kv_scales)
    return _unembed(cfg, params, x), new_kv


def ragged_multi_decode(cfg: TransformerConfig, params, kv_data: Dict,
                        token_ids: jax.Array, token_pos: jax.Array,
                        block_table: jax.Array, context_lens: jax.Array,
                        *, steps: int, mesh=None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``steps`` greedy decode steps in ONE device program.

    The autoregressive loop runs as a ``lax.scan`` over
    :func:`ragged_decode_forward` with the argmax token fed back on
    device, so the host pays ONE dispatch + fetch round trip per
    ``steps`` tokens instead of per token: the per-step host work
    (metadata assembly, sync) amortizes ``steps``-fold. TPU-serving
    analog of the reference's CUDA-graphed decode loop (inference/v2
    runs one graph per step; XLA gives us the whole loop as one
    program).

    The caller must have allocated KV blocks for ``steps`` more tokens
    per live slot (the block tables are fixed for the whole burst) and
    trims tokens past eos/max_new_tokens host-side — dead slots
    (context_lens == 0) stay dead, their writes going to the scratch
    page inside :func:`ragged_decode_forward`.

    The last row comes back once more as an array of its own: it is the
    ``token_ids`` of the burst that follows this one, so a caller whose
    batch is full can hand the device call n+1 (positions and context
    lengths plus ``steps``, block tables grown for both) before it has
    read call n, and reads call n's tokens while n+1 runs
    (``InferenceEngineV2._burst_step``; docs/serving.md, "A burst in
    flight"). The two calls run in order on one device stream, so the
    tokens are those of reading each call before issuing the next.

    Returns (tokens [steps, S] int32, kv_data', tokens[steps - 1]).
    """
    def body(carry, _):
        kv, tok, pos, ctx = carry
        logits, kv = ragged_decode_forward(
            cfg, params, kv, tok, pos, block_table, ctx, mesh=mesh)
        nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
        alive = ctx > 0
        nxt = jnp.where(alive, nxt, 0)
        return (kv, nxt, pos + 1, jnp.where(alive, ctx + 1, 0)), nxt

    (kv_data, last, *_), toks = lax.scan(
        body, (kv_data, token_ids, token_pos, context_lens), length=steps)
    return toks, kv_data, last

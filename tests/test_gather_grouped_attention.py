"""The gather program computes grouped-query attention per KV head.

``model_runner.ragged_forward`` views the queries as ``[T, kv_heads, rep,
head_dim]`` and contracts them against each token's gathered context as
it lies, so no per-query-head copy of the context exists. The plain
reference kept here is the formulation it replaced: ``jnp.repeat`` of K
and V to ``[T, Lmax, num_heads, head_dim]`` and one contraction a query
head. Same operands, same dtypes, same mask: the logits and the pool of a
mixed step (decode rows, one prompt chunk, padding rows) have to agree
for grouped-query (``rep`` 4), multi-head (``rep`` 1) and multi-query
(one KV head) models, over a bf16 and an int8 pool, and under a tp mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import engine_v2
from deepspeed_tpu.inference import model_runner as mr
from deepspeed_tpu.inference.ragged import BlockedKVCache, KVCacheConfig
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.ops.pallas.quantization import kv_dequantize, kv_unpack

T, S, BM, NB, BS = 24, 4, 6, 32, 8      # tokens, slots, pages/seq, pool, page
HEADS = {"gqa": (8, 2), "mha": (4, 4), "mqa": (4, 1)}   # (query, KV) heads


def repeat_forward(cfg, params, kv_data, token_ids, token_seq, token_pos,
                   block_table, num_tokens):
    """The gather program as it was before the grouped contraction: every
    token's context repeated ``rep`` times along the head axis."""
    kv_data, kv_scales = mr._kv_parts(kv_data)
    bs = kv_data.shape[2]
    dt = mr.effective_dtype(cfg.dtype)
    rep = cfg.num_heads // cfg.kv_heads
    is_real = jnp.arange(token_ids.shape[0]) < num_tokens
    x = mr.vocab_parallel_lookup(params["embed"]["tokens"].astype(dt),
                                 token_ids)
    if cfg.pos_emb == "learned":
        x = x + params["embed"]["positions"].astype(dt)[token_pos]
    page = jnp.where(is_real, block_table[token_seq, token_pos // bs],
                     kv_data.shape[1] - 1)
    offset = jnp.where(is_real, token_pos % bs, bs - 1)
    max_ctx = block_table.shape[1] * bs
    key_pos = jnp.arange(max_ctx)

    def layer_body(carry, inputs):
        x, kv, kv_sc = carry
        layer_params, l = inputs
        y = mr._norm(x, layer_params["ln1"], cfg.norm, cfg.norm_eps)
        q, k, v = mr._qkv(cfg, layer_params, y, token_pos)
        kv, kv_sc = mr._kv_write(kv, kv_sc, l, page, offset, k, v)
        gathered = kv[l, block_table]
        if kv_sc is not None:
            gathered = kv_dequantize(kv_unpack(gathered, mr._kv_bits(kv)),
                                     kv_sc[l, block_table], dtype=dt)
        gathered = gathered.reshape(block_table.shape[0], max_ctx, 2,
                                    cfg.kv_heads, cfg.head_dim)
        k_seq = jnp.repeat(gathered[:, :, 0][token_seq], rep, axis=2)
        v_seq = jnp.repeat(gathered[:, :, 1][token_seq], rep, axis=2)
        scores = jnp.einsum("tnd,tmnd->tnm", q, k_seq.astype(dt))
        scores = scores / jnp.sqrt(jnp.float32(cfg.head_dim)).astype(dt)
        mask = key_pos[None, None, :] <= token_pos[:, None, None]
        scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        attn = jnp.einsum("tnm,tmnd->tnd", probs, v_seq.astype(dt))
        attn = jnp.einsum("tnd,ndh->th", attn,
                          layer_params["attn"]["wo"].astype(dt))
        if cfg.use_biases:
            attn = attn + layer_params["attn"]["bo"].astype(dt)
        if cfg.parallel_block:
            return (mr._mlp(cfg, layer_params, x) + attn, kv, kv_sc), None
        return (mr._mlp(cfg, layer_params, x + attn), kv, kv_sc), None

    x, new_kv = mr._scan_layers(cfg, layer_body, x, params, kv_data,
                                  kv_scales)
    return mr._unembed(cfg, params, x), new_kv


def mixed_step(rng):
    """Three sequences decode one token each at contexts 13, 30 and 5, the
    fourth runs a prompt chunk of 17 tokens from position 9; 4 rows pad."""
    ctx = [13, 30, 5]
    seq = np.concatenate([np.arange(3), np.full(17, 3), np.zeros(4)])
    pos = np.concatenate([ctx, 9 + np.arange(17), np.zeros(4)])
    ids = rng.integers(0, 256, size=T)
    table = rng.permutation(NB - 1)[:S * BM].reshape(S, BM)
    as_i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    return as_i32(ids), as_i32(seq), as_i32(pos), as_i32(table), 20


def build(heads, dtype, quant_bits, **model_kw):
    nh, nkv = HEADS[heads]
    model = get_model("tiny", num_heads=nh, num_kv_heads=nkv, dtype=dtype,
                      param_dtype=dtype, **model_kw)
    cfg = model.config
    assert (cfg.num_heads, cfg.kv_heads) == (nh, nkv)
    params = model.init(jax.random.PRNGKey(3))
    cache = BlockedKVCache(KVCacheConfig(
        num_layers=cfg.num_layers, kv_heads=nkv, head_dim=cfg.head_dim,
        block_size=BS, num_blocks=NB, dtype=dtype, quant_bits=quant_bits))
    return cfg, params, cache


def seeded_pool(cache, dtype):
    """A pool with history in it: what earlier steps would have written."""
    data, scales = mr._kv_parts(cache.kv_state)
    key = jax.random.PRNGKey(7)
    if scales is None:
        return {"kv": jax.random.normal(key, data.shape,
                                        jnp.float32).astype(dtype)}
    payload = jax.random.randint(key, data.shape, -127, 128, jnp.int32)
    return {"kv": payload.astype(data.dtype),
            "scales": jax.random.uniform(key, scales.shape, jnp.float32,
                                         1e-3, 2e-2)}


def dense(kv_state):
    data, scales = mr._kv_parts(kv_state)
    if scales is None:
        return np.asarray(data, np.float32)
    return np.asarray(kv_dequantize(kv_unpack(data, mr._kv_bits(data)),
                                    scales, dtype=jnp.float32))


def agree(got, want, real, tol):
    """Real rows only; a padding row's logits are never read."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    dist = np.linalg.norm(got[:real] - want[:real]) \
        / np.linalg.norm(want[:real])
    assert dist <= tol, dist


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_gather_program_agrees_with_the_repeat_it_replaced(heads, pool):
    quant_bits = 8 if pool == "int8" else None
    cfg, params, cache = build(heads, jnp.bfloat16, quant_bits)
    step = mixed_step(np.random.default_rng(11))
    kv0 = seeded_pool(cache, jnp.bfloat16)
    want, want_kv = jax.jit(repeat_forward, static_argnums=0)(
        cfg, params, kv0, *step)
    got, got_kv = engine_v2._shared_step_fns(cfg, None)["step"](
        params, jax.tree.map(jnp.copy, kv0), *step)
    assert got.shape == (T, cfg.vocab_size) and got.dtype == jnp.float32
    # one rounding of a bf16 sum apart, where the two orders of summation
    # differ at all
    agree(got, want, step[-1], 4e-3)
    agree(dense(got_kv).reshape(NB, -1), dense(want_kv).reshape(NB, -1),
          NB - 1, 4e-3)                                  # all but scratch


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_float32_agrees_to_rounding_and_falcon_style_blocks_too(heads):
    """float32 leaves no room: the grouped contraction is the same sum.
    The multi-query case runs as Falcon lays it out (parallel block)."""
    cfg, params, cache = build(heads, jnp.float32, None,
                               parallel_block=heads == "mqa")
    step = mixed_step(np.random.default_rng(5))
    kv0 = seeded_pool(cache, jnp.float32)
    want, want_kv = jax.jit(repeat_forward, static_argnums=0)(
        cfg, params, kv0, *step)
    got, got_kv = engine_v2._shared_step_fns(cfg, None)["step"](
        params, jax.tree.map(jnp.copy, kv0), *step)
    agree(got, want, step[-1], 1e-5)
    np.testing.assert_allclose(dense(got_kv)[:, :NB - 1],
                               dense(want_kv)[:, :NB - 1], atol=1e-5)


def test_grouped_heads_under_a_tp_mesh(devices):
    """tp=2 over 2 KV heads: the head axis of ``q`` is sharded, the pool's
    KV-head axis too; the reshape to ``[.., kv_heads, rep, ..]`` has to
    leave the sharding on the KV heads. Same logits as one device."""
    from deepspeed_tpu.parallel import topology as topo
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    nh, nkv = HEADS["gqa"]
    model = get_model("tiny", num_heads=nh, num_kv_heads=nkv,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(3))
    step = mixed_step(np.random.default_rng(2))

    def run(mesh):
        topo._GLOBAL_MESH = None
        eng = engine_v2.InferenceEngineV2(
            model, params=params, mesh=mesh, dtype=jnp.float32,
            kv_blocks=NB, kv_block_size=BS, max_tokens_per_step=T,
            max_seqs_per_step=S, max_blocks_per_seq=BM)
        kv = eng.kv_cache.kv_state
        assert kv["kv"].shape[1] == NB
        with eng.mesh:
            logits, kv = eng._step_fn(eng.params, kv, *step)
        logits = np.asarray(logits)
        eng.close()
        return logits, np.asarray(kv["kv"])

    one, one_kv = run(None)
    two, two_kv = run(build_mesh(TopologyConfig(dp=4, tp=2)))
    agree(two, one, step[-1], 1e-5)
    np.testing.assert_allclose(two_kv[:, :NB - 1], one_kv[:, :NB - 1],
                               atol=1e-5)
    want, _ = jax.jit(repeat_forward, static_argnums=0)(
        model.config, params, {"kv": jnp.zeros(one_kv.shape, jnp.float32)},
        *step)
    agree(one, want, step[-1], 1e-5)


@pytest.mark.parametrize("heads", ["gqa", "mqa"])
def test_dense_cache_path_is_the_multi_head_model_with_repeated_kv(heads):
    """``forward_with_cache`` (the v1 engine's program) groups its query
    heads the same way. A grouped-query model *is* the multi-head model
    whose K and V projections are repeated per group: prefill then one
    decode step through both give the same logits, and the grouped
    model's cache is the multi-head one's at every ``rep``-th head."""
    nh, nkv = HEADS[heads]
    rep = nh // nkv
    grouped = get_model("tiny", num_heads=nh, num_kv_heads=nkv,
                        dtype=jnp.float32, param_dtype=jnp.float32)
    full = get_model("tiny", num_heads=nh, num_kv_heads=nh,
                     dtype=jnp.float32, param_dtype=jnp.float32)
    params = grouped.init(jax.random.PRNGKey(9))
    attn = dict(params["layers"]["attn"])
    for name in ("wk", "wv"):                  # [L, H, nkv, hd]
        attn[name] = jnp.repeat(attn[name], rep, axis=2)
    params_full = {**params, "layers": {**params["layers"], "attn": attn}}
    tokens = jnp.asarray(np.random.default_rng(4).integers(0, 256, (3, 12)),
                         jnp.int32)

    def run(model, p):
        cfg = model.config
        fwd = jax.jit(mr.forward_with_cache, static_argnums=0)
        cache = mr.init_dense_cache(cfg, 3, 32, dtype=jnp.float32)
        first, cache = fwd(cfg, p, tokens[:, :11], cache, 0)
        second, cache = fwd(cfg, p, tokens[:, 11:], cache, 11)
        return np.asarray(first), np.asarray(second), np.asarray(cache)

    g_first, g_second, g_cache = run(grouped, params)
    f_first, f_second, f_cache = run(full, params_full)
    np.testing.assert_allclose(g_first, f_first, atol=2e-5)
    np.testing.assert_allclose(g_second, f_second, atol=2e-5)
    np.testing.assert_allclose(g_cache, f_cache[:, :, :, :, ::rep], atol=2e-5)

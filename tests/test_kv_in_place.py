"""The KV pool is one buffer for the life of the engine.

Every serving step program donates the pool it is handed and carries it
through its layer scan, scattering the step's rows at ``[l, page, offset]``
and handing the Pallas kernels the whole pool with a layer index. Pinned
here, on the CPU: the compiled programs alias the pool's input to their
output; a step kills the handle it was given; the kernels read layer ``l``
of the pool exactly as they read ``kv[l]``; and after mixed, decode and
burst steps the pool is the starting pool with exactly the served rows
scattered in.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import engine_v2, model_runner
from deepspeed_tpu.inference.ragged import BlockedKVCache, KVCacheConfig
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, paged_prefill_attention)
from deepspeed_tpu.ops.pallas.quantization import kv_dequantize, kv_unpack

I32 = jnp.int32
T, S, BM, NB, BS = 16, 4, 8, 32, 8      # tokens, slots, pages/seq, pool, page
PROGRAMS = ["gather", "prefill", "decode", "multi_decode"]


@pytest.fixture(scope="module")
def tiny():
    model = get_model("tiny", dtype=jnp.float32, param_dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _lower(program, cfg, params, kv):
    fns = engine_v2._shared_step_fns(cfg, None)
    ids = lambda *shape: jax.ShapeDtypeStruct(shape, I32)  # noqa: E731
    if program == "gather":
        return fns["step"].lower(params, kv, ids(T), ids(T), ids(T),
                                 ids(S, BM), ids())
    if program == "prefill":
        return fns["prefill"].lower(params, kv, ids(2, 8), ids(2), ids(2),
                                    ids(2, BM))
    args = (params, kv, ids(S), ids(S), ids(S, BM), ids(S))
    if program == "decode":
        return fns["decode"].lower(*args)
    return fns["multi_decode"].lower(*args, steps=3)


# -- engagement --------------------------------------------------------------

@pytest.mark.parametrize("quant_bits", [None, 8])
@pytest.mark.parametrize("program", PROGRAMS)
def test_program_aliases_the_pool(tiny, program, quant_bits):
    """The whole pool (payload and scales of a quantized one) goes out in
    the buffer it came in."""
    model, params = tiny
    cfg = model.config
    cache = BlockedKVCache(KVCacheConfig(
        num_layers=cfg.num_layers, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, block_size=BS, num_blocks=NB,
        dtype=jnp.float32, quant_bits=quant_bits))
    kv = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                      cache.kv_state)
    compiled = _lower(program, cfg, jax.eval_shape(lambda: params),
                      kv).compile()
    assert "input_output_alias" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes >= _nbytes(kv)


def _engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("dtype", jnp.float32)
    return engine_v2.InferenceEngineV2(
        model, params=params, kv_blocks=64, kv_block_size=8,
        max_tokens_per_step=32, max_seqs_per_step=4, max_blocks_per_seq=8,
        prefix_cache=False, **kw)


PROMPTS = {1: [5, 9, 2, 14, 7], 2: [3, 1, 4], 3: [2] * 11}


def _put(eng, max_new=24):
    eng.put(list(PROMPTS), [np.asarray(p, np.int32) for p in PROMPTS.values()],
            max_new_tokens=max_new)


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_step_consumes_the_handle_it_was_given(tiny, program):
    eng = _engine(tiny, decode_steps=4 if program == "multi_decode" else 1)
    eng._use_paged_kernel = program != "gather"
    _put(eng)
    counter = engine_v2._TOKENS_OF[program]
    for _ in range(4):
        old, before = eng.kv_cache.data, eng.stats[counter]
        eng.serve_step()
        assert old.is_deleted() and not eng.kv_cache.data.is_deleted()
        if eng.stats[counter] > before:
            break
    else:
        pytest.fail(f"no {program} step in four: {eng.stats}")
    eng.close()


@pytest.mark.parametrize("quant_bits", [None, 8])
def test_write_blocks_is_in_place_and_touches_only_its_blocks(quant_bits):
    cfg = KVCacheConfig(num_layers=2, kv_heads=2, head_dim=16, block_size=4,
                        num_blocks=12, dtype=jnp.float32,
                        quant_bits=quant_bits)
    cache = BlockedKVCache(cfg)
    rng = np.random.default_rng(0)
    # + 0 and np.array: a buffer of the device's own that no numpy view
    # shares (a CPU program cannot be given a shared one to keep)
    cache.data = jnp.asarray(
        rng.integers(-100, 100, cache.data.shape), cache.data.dtype) + 0
    start = np.array(cache.data)
    payload, scales = cache.read_blocks_host([3, 7])
    old = cache.data
    cache.write_blocks([5, 1], payload, scales)
    assert old.is_deleted() and not cache.data.is_deleted()
    want = start.copy()
    want[:, [5, 1]] = start[:, [3, 7]]
    np.testing.assert_array_equal(np.asarray(cache.data), want)


# -- numerics: the kernels on the whole pool ---------------------------------

L, NKV, NH, HD = 3, 2, 4, 32


def _pool_case(seed=0):
    rng = np.random.default_rng(seed)
    kv = jnp.asarray(rng.standard_normal((L, NB, BS, 2, NKV, HD)),
                     jnp.float32)
    table = jnp.asarray(rng.integers(0, NB, (S, BM)), I32)
    return rng, kv, table


@pytest.mark.parametrize("pages", [0, 1, 2, 4])
def test_decode_kernel_on_the_pool_equals_the_layer_slice(pages):
    rng, kv, table = _pool_case()
    q = jnp.asarray(rng.standard_normal((S, NH, HD)), jnp.float32)
    ctx = jnp.asarray([1, 9, 0, BM * BS], I32)   # one row, cross-page, dead, full
    on_pool = jax.jit(lambda l: paged_decode_attention(
        q, kv, table, ctx, pages_per_compute_block=pages, layer=l))
    for l in range(L):
        want = paged_decode_attention(q, kv[l], table, ctx,
                                      pages_per_compute_block=pages)
        np.testing.assert_array_equal(np.asarray(on_pool(jnp.asarray(l, I32))),
                                      np.asarray(want))


@pytest.mark.parametrize("pages", [1, 2, 4])
def test_prefill_kernel_on_the_pool_equals_the_layer_slice(pages):
    rng, kv, table = _pool_case(1)
    tq = 8
    q = jnp.asarray(rng.standard_normal((S, tq, NH, HD)), jnp.float32)
    pos0 = jnp.asarray([0, 5, 0, 40], I32)
    ctx = jnp.asarray([8, 11, 0, 47], I32)       # whole, partial, dead, late
    on_pool = jax.jit(lambda l: paged_prefill_attention(
        q, kv, table, pos0, ctx, pages_per_compute_block=pages, layer=l))
    for l in range(L):
        want = paged_prefill_attention(q, kv[l], table, pos0, ctx,
                                       pages_per_compute_block=pages)
        np.testing.assert_array_equal(np.asarray(on_pool(jnp.asarray(l, I32))),
                                      np.asarray(want))


def test_a_pool_needs_its_layer_and_a_slice_takes_none():
    _, kv, table = _pool_case()
    q, ctx = jnp.zeros((S, NH, HD)), jnp.ones((S,), I32)
    with pytest.raises(ValueError, match="layer"):
        paged_decode_attention(q, kv, table, ctx)
    with pytest.raises(ValueError, match="5-D"):
        paged_decode_attention(q, kv[0], table, ctx, layer=1)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_kernel_on_the_pool_under_tp2_shard_map(devices, kernel):
    """q sharded on heads, the pool on KV heads (its fifth axis now), the
    layer replicated: each head's result is what one device computes (to
    the last float32 bit or two for the decode kernel)."""
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    mesh = build_mesh(TopologyConfig(dp=4, tp=2))
    rng, kv, table = _pool_case(2)
    if kernel == "decode":
        q = jnp.asarray(rng.standard_normal((S, NH, HD)), jnp.float32)
        meta = (table, jnp.asarray([3, 17, 0, 64], I32))
        fn = model_runner._paged_decode
    else:
        q = jnp.asarray(rng.standard_normal((S, 8, NH, HD)), jnp.float32)
        meta = (table, jnp.asarray([0, 5, 0, 40], I32),
                jnp.asarray([8, 11, 0, 47], I32))
        fn = model_runner._paged_prefill
    for l in range(L):
        layer = jnp.asarray(l, I32)
        with mesh:
            got = jax.jit(lambda q, kv, l: fn(mesh, q, kv, l, *meta))(
                q, kv, layer)
        want = jax.jit(lambda q, kv, l: fn(None, q, kv, l, *meta))(
            q, kv, layer)
        if kernel == "decode":
            # a shard multiplies its one KV head alone, one device both
            # together in one product: float32 sums grouped otherwise
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=0, atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- numerics: the pool after N steps ----------------------------------------

def _dense_rows(model, params, tokens, dtype):
    """K and V of every position and layer by the dense-cache forward (the
    v1 path: one ``dynamic_update_slice`` a layer, no pages): ``[L, n, 2,
    nkv, hd]`` float32."""
    cache = model_runner.init_dense_cache(model.config, 1, len(tokens), dtype)
    _, cache = model_runner.forward_with_cache(
        model.config, params, jnp.asarray(tokens, I32)[None], cache, 0)
    return np.asarray(cache[:, 0], np.float32)


@pytest.mark.parametrize("dtype,quant_bits,tol", [
    (jnp.float32, None, 1e-4), (jnp.bfloat16, None, 0.06),
    (jnp.float32, 8, 0.06)], ids=["float32", "bf16", "int8"])
def test_pool_after_steps_is_a_plain_scatter_of_the_served_rows(
        dtype, quant_bits, tol):
    """Mixed steps (prefill, then a prompt joining decoding sequences),
    single decode steps and bursts, from a pool that starts as noise: the
    rows the sequences hold are their keys and values (against the dense
    forward: to rounding in float32, to the storage type's step
    otherwise), and every other row but the scratch row is the noise it
    was, to the bit."""
    model = get_model("tiny", dtype=dtype, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    eng = _engine((model, params), dtype=dtype, kv_quant_bits=quant_bits,
                  decode_steps=1)
    kvc, rng = eng.kv_cache, np.random.default_rng(7)
    noise = rng.standard_normal(kvc.data.shape)
    kvc.data = jnp.asarray(noise * (20 if quant_bits else 1),
                           kvc.data.dtype) + 0
    if quant_bits:
        kvc.scales = jnp.asarray(rng.uniform(0.01, 0.02, kvc.scales.shape),
                                 jnp.float32) + 0
    start = jax.tree.map(np.array, kvc.kv_state)     # copies, not views

    _put(eng, max_new=40)
    for _ in range(3):                 # a prefill step, two decode steps
        eng.serve_step()
    eng.put([4], [np.asarray([7] * 13, np.int32)], max_new_tokens=40)
    for _ in range(2):                 # a mixed step, a decode step
        eng.serve_step()
    eng.decode_steps = 4
    for _ in range(2):                 # two bursts of four
        eng.serve_step()
    assert eng.stats["burst_steps"] == 2 and eng.stats["tokens_decode"] > 0
    assert (eng.stats["tokens_prefill_kernel"] + eng.stats["tokens_gather"]
            >= 4)
    # the batch is full, so a third burst is in flight and writing rows:
    # whatever looks at the engine from outside a step reads it first
    eng.snapshot()
    assert eng.stats["burst_steps"] == 3 and eng._inflight is None

    def dense(state):
        if quant_bits is None:
            return np.array(state["kv"], np.float32)
        return np.array(kv_dequantize(kv_unpack(jnp.asarray(state["kv"]),
                                                  quant_bits),
                                        jnp.asarray(state["scales"]),
                                        dtype=jnp.float32))

    got = jax.tree.map(np.array, kvc.kv_state)
    want, touched = dense(start), np.zeros(kvc.data.shape[1:3], bool)
    for seq in eng.state.seqs.values():
        n = seq.seen_tokens
        assert n > len(seq.input_tokens)
        tokens = np.concatenate([seq.input_tokens, seq.generated])[:n]
        rows = _dense_rows(model, eng.params, tokens, dtype)
        page, off = seq.kv_blocks[np.arange(n) // BS], np.arange(n) % BS
        want[:, page, off] = rows
        touched[page, off] = True
    scale = np.abs(want[:, touched]).max()
    np.testing.assert_allclose(dense(got)[:, touched], want[:, touched],
                               atol=tol * scale, rtol=0)
    touched[eng._scratch_block, BS - 1] = True    # padding rows land here
    for g, s in zip(jax.tree.leaves(got), jax.tree.leaves(start)):
        np.testing.assert_array_equal(g[:, ~touched], s[:, ~touched])
    eng.close()

"""A model with recurrent layers (models/hybrid.py) under the serving
engine: the second, slot-addressed pool beside the KV blocks — admission,
release, preemption — and what is refused for want of state snapshots."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.ragged import StateSnapshotUnsupported
from deepspeed_tpu.models.hybrid import HybridConfig, HybridLM
from deepspeed_tpu.models.moe_transformer import MoETransformerLM
from deepspeed_tpu.models.transformer import TransformerLM
from deepspeed_tpu.models.zoo import CONFIGS, get_model
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

F32 = jnp.float32


@pytest.fixture(scope="module")
def model():
    m = get_model("tiny-hybrid", param_dtype=F32, dtype=F32)
    return m, m.init(jax.random.PRNGKey(0))


def _engine(model, **kw):
    m, params = model
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    args = dict(kv_blocks=64, kv_block_size=16, max_tokens_per_step=64,
                max_seqs_per_step=4, max_blocks_per_seq=8, state_slots=4)
    args.update(kw)
    return InferenceEngineV2(m, mesh=mesh, params=params, dtype=F32, **args)


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).astype(np.int32) for n in lens]


def test_zoo_finds_the_class_by_the_configurations_type():
    assert isinstance(get_model("tiny-hybrid"), HybridLM)
    assert isinstance(get_model("tiny-moe"), MoETransformerLM)
    assert type(get_model("tiny")) is TransformerLM
    c = CONFIGS["qwen3-next-80b-a3b"]
    assert isinstance(c, HybridConfig)
    # the published values (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct)
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.head_dim,
            c.vocab_size) == (2048, 48, 16, 2, 256, 151936)
    assert (c.linear_num_key_heads, c.linear_num_value_heads,
            c.linear_key_head_dim, c.linear_value_head_dim,
            c.linear_conv_kernel_dim) == (16, 32, 128, 128, 4)
    assert (c.num_experts, c.top_k, c.moe_ffn_size, c.shared_ffn_size,
            c.held, c.periods, c.recurrent_layers) == (512, 10, 512, 512, 512,
                                                       12, 36)
    assert 79e9 < c.num_params() < 82e9
    with pytest.raises(ValueError, match="whole number of periods"):
        get_model("tiny-hybrid", num_layers=6)


def test_engine_keeps_of_each_mixer_only_the_layers_that_use_it(model):
    eng = _engine(model)
    c = eng.cfg
    assert set(eng.params) == {"embed", "final_norm", "unembed", "layers",
                               "experts", "gdn", "attn"}
    assert eng.params["gdn"]["wq"].shape[0] == c.recurrent_layers == 6
    assert eng.params["attn"]["wq"].shape[0] == c.kv_layers == 2
    assert eng.kv_cache.data.shape[0] == 2            # KV for full layers only
    pool = eng.kv_cache.state_pool
    assert pool.state.shape == (6, 5, c.linear_num_value_heads, 32, 32)
    assert pool.state.dtype == jnp.float32
    assert pool.conv.shape == (6, 5, 3, c.conv_channels)
    assert eng.stats["state_slots"] == 4
    eng.close()


def test_admission_counts_state_slots_and_finish_frees_them(model):
    """Two slots, four sequences a step allowed: the third request waits
    for a slot, is served when one is freed, and its tokens are those of a
    run that had room for all."""
    prompts = _prompts((20, 9, 31))
    wide = _engine(model)
    wide.put([1, 2, 3], prompts, max_new_tokens=6)
    want = wide.generate_all()
    eng = _engine(model, state_slots=2)
    eng.put([1, 2, 3], prompts, max_new_tokens=6)
    assert eng.stats["admitted"] == 2 and len(eng._queue) == 1
    assert eng.kv_cache.state_pool.free_slots == 0
    assert not eng.can_schedule(4)
    assert eng.generate_all() == want
    assert eng.stats["admitted"] == 3
    assert eng.kv_cache.state_pool.free_slots == 2
    for e in (wide, eng):
        e.close()


def test_preemption_by_recompute_frees_the_slot_and_restarts_from_zero(model):
    prompts = _prompts((17, 12), seed=1)
    calm = _engine(model)
    calm.put([1, 2], prompts, max_new_tokens=10)
    want = calm.generate_all()
    eng = _engine(model, decode_steps=1)
    eng.put([1, 2], prompts, max_new_tokens=10)
    got = {1: [], 2: []}
    for _ in range(4):
        for uid, toks in eng.serve_step().items():
            got[uid] += toks
    victim = eng.state.seqs[2]
    slot = victim.held["state"]
    eng._requeue(victim)                      # what a starved pool does
    assert 2 not in eng.state.seqs
    assert eng.kv_cache.state_pool.free_slots == 3 and slot >= 0
    for uid, toks in eng.generate_all().items():
        got[uid] += toks
    assert got == want and eng.stats["preempted"] == 1
    for e in (calm, eng):
        e.close()


def test_prefix_cache_takes_no_hit_for_a_model_with_recurrent_layers(model):
    """A skipped prefix without the state at its end is a wrong answer: the
    cache is off, the same long prompt twice is computed twice, and the
    second answer equals the first."""
    eng = _engine(model, prefix_cache=True)
    assert eng.kv_cache.prefix_cache is None
    prompt = _prompts((70,), seed=2)[0]
    eng.put([1], [prompt], max_new_tokens=5)
    first = eng.generate_all()[1]
    eng.put([2], [prompt], max_new_tokens=5)
    assert eng.generate_all()[2] == first
    assert eng.stats["prefix_hit_tokens"] == 0
    assert eng.scheduler.stats["prefill_tokens"] == 140
    assert eng.holds_prefix_blocks(prompt) == 0
    eng.close()


def test_host_tier_is_refused(model):
    with pytest.raises(StateSnapshotUnsupported, match="host KV tier"):
        _engine(model, host_kv_tier=True)
    eng = _engine(model)
    eng.put([1], _prompts((9,)), max_new_tokens=20)
    eng.serve_step()
    with pytest.raises(StateSnapshotUnsupported, match="page_out"):
        eng.page_out(1)
    eng.close()


def test_migration_is_refused(model):
    from deepspeed_tpu.serving import serialize_session

    eng = _engine(model)
    eng.put([1], _prompts((9,)), max_new_tokens=20)
    eng.serve_step()
    with pytest.raises(StateSnapshotUnsupported, match="migration"):
        eng.migrate_out_session(1)
    with pytest.raises(StateSnapshotUnsupported, match="migration"):
        eng.install_migrated_session(object())
    with pytest.raises(StateSnapshotUnsupported, match="migration"):
        serialize_session(eng, 1)
    assert 1 in eng.state.seqs                 # nothing was released
    eng.close()


def test_disagg_hand_off_is_refused(model):
    from deepspeed_tpu.serving.disagg import install_prefix, serialize_prefix

    eng = _engine(model)
    with pytest.raises(StateSnapshotUnsupported, match="hand-off"):
        serialize_prefix(eng, _prompts((40,))[0])
    with pytest.raises(StateSnapshotUnsupported, match="hand-off"):
        install_prefix(eng, None)
    eng.close()


def test_speculation_is_refused(model):
    with pytest.raises(StateSnapshotUnsupported, match="speculative"):
        _engine(model, spec_decode=True)
    with pytest.raises(StateSnapshotUnsupported, match="speculative"):
        _engine(model, drafter=object())


def test_a_quantized_kv_pool_is_refused_by_name(model):
    with pytest.raises(ValueError, match="kv_quant_bits"):
        _engine(model, kv_quant_bits=8)


def test_state_pool_zeroes_a_slot_when_it_is_taken_and_refuses_a_double_free():
    from deepspeed_tpu.inference.ragged import (RecurrentStatePool,
                                                StatePoolConfig)

    pool = RecurrentStatePool(StatePoolConfig(
        layers=2, slots=2, heads=1, key_dim=8, value_dim=8, conv_taps=4,
        conv_channels=16, dtype=F32))
    a = pool.allocate()
    pool.state = pool.state.at[:, a].set(3.0)
    pool.conv = pool.conv.at[:, a].set(2.0)
    b = pool.allocate()
    assert {a, b} == {0, 1} and pool.scratch_slot == 2
    with pytest.raises(MemoryError):
        pool.allocate()
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)
    assert pool.allocate() == a
    assert float(jnp.abs(pool.state[:, a]).max()) == 0.0
    assert float(jnp.abs(pool.conv[:, a]).max()) == 0.0
    assert pool.slots_in_use == 2 and pool.config.bytes_per_slot == 2 * (
        8 * 8 * 4 + 3 * 16 * 4)


def test_grouped_product_reads_one_layer_of_a_stack():
    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm, gmm_layer

    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    lhs = jax.random.normal(ks[0], (256, 64), F32)
    rhs = jax.random.normal(ks[1], (3, 5, 64, 128), F32)
    sizes = jnp.asarray([0, 100, 3, 0, 40], jnp.int32)     # 113 rows unrouted
    for layer in range(3):
        got = jax.jit(gmm_layer)(lhs, rhs, sizes, jnp.int32(layer))
        np.testing.assert_allclose(got, gmm(lhs, rhs[layer], sizes),
                                   rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(got[143:]).max()) == 0.0          # beyond the groups


def test_grouped_product_keeps_its_rows_when_most_rows_are_unrouted():
    """Several row tiles, groups that cover a part of the first one only,
    and more padding work items than uncovered tiles: the covered tile is
    not opened a second time (a share of the experts routes a quarter of
    the pairs here and leaves the rest beyond the groups' sum)."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm

    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    lhs = jax.random.normal(ks[0], (1024, 64), F32)
    rhs = jax.random.normal(ks[1], (16, 64, 128), F32)
    sizes = jnp.zeros((16,), jnp.int32).at[jnp.asarray([1, 5, 6, 12])].set(
        jnp.asarray([70, 3, 100, 27], jnp.int32))          # 200 of 1,024
    got = gmm(lhs, rhs, sizes, 256, 128, 64)
    group = np.repeat(np.arange(16), np.asarray(sizes))
    want = np.einsum("mk,mkn->mn", np.asarray(lhs[:200]),
                     np.asarray(rhs)[group])
    np.testing.assert_allclose(got[:200], want, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(got[200:]).max()) == 0.0

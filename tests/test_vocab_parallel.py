"""Vocab-parallel embedding lookup: no replicate-then-partition fallback.

The round-2 multichip dryrun passed correctness but logged XLA's "SPMD
will replicate the tensor and then partition it" warning on the embedding
gather under tp — the full table was all-gathered every step. These tests
pin the fix (runtime/sharding.py vocab_parallel_lookup): exact parity
with the plain gather, gradient parity, and an HLO assertion that the
compiled train step contains no full-table float all-gather.
Reference bar: vocab/column-parallel layers in
module_inject/layers.py:678 (reference keeps the table sharded too).
"""

import numpy as np

import jax
import jax.numpy as jnp

import deepspeed_tpu as dstpu
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
from deepspeed_tpu.parallel import topology as topo
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh
from deepspeed_tpu.runtime.sharding import vocab_parallel_lookup

TINY = TransformerConfig(
    vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
    max_seq_len=32, pos_emb="learned", norm="layernorm",
    activation="gelu", tie_embeddings=True, remat=False)


def _mesh(**sizes):
    mesh = build_mesh(TopologyConfig(**sizes))
    topo.set_global_mesh(mesh)
    return mesh


def test_lookup_matches_plain_gather(devices):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 64, (4, 10)).astype(np.int32))
    expect = np.asarray(table[ids])

    _mesh(dp=1, fsdp=2, tp=4)
    got = jax.jit(vocab_parallel_lookup)(table, ids)
    np.testing.assert_array_equal(np.asarray(got), expect)


def test_lookup_bf16_and_grads(devices):
    """bf16 path (CPU f32 shim) and the masked scatter-add backward."""
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 32, (6,)).astype(np.int32))

    def loss_plain(t):
        return jnp.sum(t.astype(jnp.bfloat16)[ids].astype(jnp.float32) ** 2)

    def loss_vp(t):
        rows = vocab_parallel_lookup(t.astype(jnp.bfloat16), ids)
        return jnp.sum(rows.astype(jnp.float32) ** 2)

    g_plain = jax.grad(loss_plain)(table)
    _mesh(dp=1, tp=8)
    out = jax.jit(lambda t: vocab_parallel_lookup(t.astype(jnp.bfloat16), ids))(table)
    assert out.dtype == jnp.bfloat16
    g_vp = jax.jit(jax.grad(loss_vp))(table)
    np.testing.assert_allclose(np.asarray(g_vp), np.asarray(g_plain),
                               rtol=1e-2, atol=1e-2)


def test_lookup_falls_back_without_tp(devices):
    table = jnp.ones((30, 8))  # 30 doesn't tile over tp=4 either
    ids = jnp.zeros((3,), jnp.int32)
    topo._GLOBAL_MESH = None
    np.testing.assert_array_equal(
        np.asarray(vocab_parallel_lookup(table, ids)), np.ones((3, 8)))
    _mesh(dp=2, tp=4)
    np.testing.assert_array_equal(
        np.asarray(vocab_parallel_lookup(table, ids)), np.ones((3, 8)))


def test_no_full_table_gather_in_hlo(devices):
    """Compiled train step on a tp×sp mesh must not all-gather the
    [V, H] table in a float type (the replicate-then-partition
    fallback the round-2 dryrun warned about)."""
    import re

    engine, *_ = dstpu.initialize(
        model=TransformerLM(TINY),
        config={"train_micro_batch_size_per_chip": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "steps_per_print": 1000},
        topology={"dp": 1, "fsdp": 1, "tp": 4, "sp": 2})
    it = iter(lambda: {"input_ids": np.zeros(
        (engine.micro_batch_size * engine.dp_world_size, 17), np.int32)}, None)
    batches = engine._next_microbatches(
        it, engine.gradient_accumulation_steps)
    hlo = engine._jit_train_step.lower(
        engine.params, engine.opt_state, engine.loss_scale_state,
        engine.step_count, batches).compile().as_text()
    bad = [l for l in hlo.splitlines()
           if re.search(r"all-gather[^=]*= (f32|bf16)\[64,32\]", l)]
    assert not bad, f"full-table gather survived:\n{bad[0]}"


def test_tp_training_matches_single_device(devices):
    """End-to-end: tp=4 training trajectory == replicated trajectory."""
    def run(topology, micro):
        engine, *_ = dstpu.initialize(
            model=TransformerLM(TINY),
            config={"train_micro_batch_size_per_chip": micro,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                    "steps_per_print": 1000},
            topology=topology)
        rng = np.random.default_rng(3)
        fixed = [{"input_ids": rng.integers(0, 64, (
            engine.micro_batch_size * engine.dp_world_size, 17)
        ).astype(np.int32)} for _ in range(2)]
        i = [0]

        def it():
            while True:
                yield fixed[i[0] % 2]
                i[0] += 1
        gen = it()
        return [float(engine.train_batch(gen)) for _ in range(5)]

    # equal global batch (16) so the trajectories are comparable
    ref = run({"dp": 8, "fsdp": 1, "tp": 1}, micro=2)
    got = run({"dp": 2, "fsdp": 1, "tp": 4}, micro=8)
    np.testing.assert_allclose(got, ref, rtol=2e-2)

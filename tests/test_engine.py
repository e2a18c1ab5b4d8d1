"""End-to-end engine tests on the 8-device CPU-sim mesh
(reference analog: tests/unit/runtime/test_ds_initialize.py + zero suites)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as dstpu
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM

TINY = TransformerConfig(
    vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
    max_seq_len=32, pos_emb="learned", norm="layernorm",
    activation="gelu", tie_embeddings=True, remat=False)


def data_iter(batch, seq=17, seed=0, n_fixed=2):
    """Cycle over a small fixed set of batches so the model can memorize
    (fresh random tokens would pin the loss at the uniform entropy)."""
    rng = np.random.default_rng(seed)
    fixed = [
        {"input_ids": rng.integers(0, 64, (batch, seq)).astype(np.int32)}
        for _ in range(n_fixed)
    ]
    i = 0
    while True:
        yield fixed[i % n_fixed]
        i += 1


def make_engine(zero_stage=1, gas=1, micro=2, extra=None, topology=None):
    cfg = {
        "train_micro_batch_size_per_chip": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 100,
    }
    if extra:
        cfg.update(extra)
    engine, _opt, _dl, _sched = dstpu.initialize(
        model=TransformerLM(TINY), config=cfg, topology=topology)
    return engine


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_train_batch_loss_decreases(stage, devices):
    engine = make_engine(zero_stage=stage)
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    losses = [float(engine.train_batch(it)) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.3, (stage, losses)
    assert engine.global_steps == 8


def test_zero_stages_agree(devices):
    """Stages 0-3 are different shardings of the same math — losses must
    match closely (reference tests compare zero vs torch DDP)."""
    seqs = {}
    for stage in (0, 2, 3):
        engine = make_engine(zero_stage=stage)
        it = data_iter(engine.micro_batch_size * engine.dp_world_size, seed=7)
        seqs[stage] = [float(engine.train_batch(it)) for _ in range(4)]
    np.testing.assert_allclose(seqs[0], seqs[2], rtol=2e-3)
    np.testing.assert_allclose(seqs[0], seqs[3], rtol=2e-3)


def test_stage3_params_sharded(devices):
    engine = make_engine(zero_stage=3)
    wq = engine.params["layers"]["attn"]["wq"]
    # embed dim sharded over fsdp=8
    assert wq.addressable_shards[0].data.shape[1] == wq.shape[1] // 8
    # master fp32 sharded too
    m = engine.opt_state.master["layers"]["attn"]["wq"]
    assert m.addressable_shards[0].data.shape[1] == m.shape[1] // 8
    assert m.dtype == jnp.float32
    # and so are the Adam moments, from init on — not whole on every
    # device until the first step reshards them
    for leaf in jax.tree.leaves(engine.opt_state.inner):
        if leaf.shape == m.shape:
            assert leaf.addressable_shards[0].data.size == leaf.size // 8


def test_stage1_params_replicated_opt_sharded(devices):
    engine = make_engine(zero_stage=1)
    wq = engine.params["layers"]["attn"]["wq"]
    assert wq.addressable_shards[0].data.shape == wq.shape  # replicated
    m = engine.opt_state.master["layers"]["attn"]["wq"]
    assert m.addressable_shards[0].data.shape[1] == m.shape[1] // 8


def test_gradient_accumulation_fused(devices):
    engine = make_engine(zero_stage=2, gas=4)
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    l0 = float(engine.train_batch(it))
    assert np.isfinite(l0)
    assert engine.global_steps == 1
    assert engine.train_batch_size == 4 * 2 * 8


def test_forward_backward_step_parity_api(devices):
    """The micro-step API must produce the same result as train_batch."""
    e1 = make_engine(zero_stage=2, gas=2)
    e2 = make_engine(zero_stage=2, gas=2)

    it = data_iter(e1.micro_batch_size * e1.dp_world_size, seed=3)
    batches = [next(it) for _ in range(2)]

    # engine 1: fused path
    l_fused = float(e1.train_batch(iter(batches)))

    # engine 2: micro-step path
    losses = []
    for mb in batches:
        loss = e2(mb)  # forward
        e2.backward(loss)
        e2.step()
    assert e2.is_gradient_accumulation_boundary()
    np.testing.assert_allclose(
        np.mean([float(l) for l in losses] or [l_fused]), l_fused, rtol=1e-4)

    w1 = np.asarray(e1.params["layers"]["mlp"]["wi"].astype(jnp.float32))
    w2 = np.asarray(e2.params["layers"]["mlp"]["wi"].astype(jnp.float32))
    np.testing.assert_allclose(w1, w2, atol=2e-2)


def test_lr_schedule_wired(devices):
    engine = make_engine(extra={
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_num_steps": 10,
                                 "warmup_min_lr": 0.0}}})
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    engine.train_batch(it)
    lr1 = engine.get_lr()[0]
    for _ in range(5):
        engine.train_batch(it)
    assert engine.get_lr()[0] > lr1


def test_gradient_clipping(devices):
    engine = make_engine(extra={"gradient_clipping": 0.01})
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    for _ in range(3):
        loss = engine.train_batch(it)
    assert np.isfinite(float(loss))


def test_checkpoint_save_load_roundtrip(devices, tmp_path):
    engine = make_engine(zero_stage=2)
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    for _ in range(3):
        engine.train_batch(it)
    w_before = np.asarray(
        engine.params["layers"]["mlp"]["wi"].astype(jnp.float32))
    path = engine.save_checkpoint(str(tmp_path), client_state={"note": "hi"})
    assert path and (tmp_path / "latest").exists()

    engine2 = make_engine(zero_stage=2)
    _, client = engine2.load_checkpoint(str(tmp_path))
    assert client["note"] == "hi"
    assert engine2.global_steps == 3
    w_after = np.asarray(
        engine2.params["layers"]["mlp"]["wi"].astype(jnp.float32))
    np.testing.assert_allclose(w_before, w_after)
    # training continues from restored state
    l = float(engine2.train_batch(it))
    assert np.isfinite(l)


def test_checkpoint_elastic_reshape(devices, tmp_path):
    """Save on fsdp=8, load on fsdp=2×dp=4 — universal-checkpoint analog."""
    e1 = make_engine(zero_stage=3)
    it = data_iter(e1.micro_batch_size * e1.dp_world_size)
    e1.train_batch(it)
    e1.save_checkpoint(str(tmp_path))

    e2 = make_engine(zero_stage=3, topology={"dp": 4, "fsdp": 2})
    e2.load_checkpoint(str(tmp_path))
    w1 = np.asarray(e1.params["layers"]["mlp"]["wi"].astype(jnp.float32))
    w2 = np.asarray(e2.params["layers"]["mlp"]["wi"].astype(jnp.float32))
    np.testing.assert_allclose(w1, w2)


def test_eval_batch(devices):
    engine = make_engine()
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    loss = engine.eval_batch(next(it))
    assert np.isfinite(float(loss))


def test_fp16_loss_scaling_engages(devices):
    engine = make_engine(extra={"fp16": {"enabled": True,
                                         "initial_scale_power": 8}})
    assert engine.loss_scale == 2.0 ** 8
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    l = float(engine.train_batch(it))
    assert np.isfinite(l)


def test_offload_reload_states(devices):
    """reference engine.offload_states/reload_states (engine.py:5573):
    params + optimizer state round-trip through pinned host memory and
    training resumes identically."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.zoo import get_model

    engine, *_ = dstpu.initialize(
        model=get_model("tiny", remat=False),
        config={"train_micro_batch_size_per_chip": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 1}})
    rng = np.random.default_rng(0)
    b = {"input_ids": rng.integers(
        0, 256, (engine.micro_batch_size * engine.dp_world_size,
                 17)).astype(np.int32)}
    l0 = float(engine.train_batch(iter([b])))

    engine.offload_states()
    kinds = {l.sharding.memory_kind
             for l in jax.tree.leaves(engine.params)}
    assert kinds == {"pinned_host"}
    okinds = {l.sharding.memory_kind
              for l in jax.tree.leaves(engine.opt_state)
              if isinstance(l, jax.Array)}
    assert okinds == {"pinned_host"}

    engine.reload_states()
    kinds = {l.sharding.memory_kind
             for l in jax.tree.leaves(engine.params)}
    assert kinds == {"device"}
    l1 = float(engine.train_batch(iter([b])))
    assert np.isfinite(l1) and l1 < l0 + 1.0

    with pytest.raises(ValueError, match="unknown offload_states"):
        engine.offload_states(include=["bogus"])

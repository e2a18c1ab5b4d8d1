"""ZeRO++ quantized-collective engine tests (reference analog:
tests/unit/runtime/zero/test_zeropp.py)."""

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


def make_engine(extra, topology=None, cfg_model=TINY):
    cfg = {
        "train_micro_batch_size_per_chip": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "steps_per_print": 1000,
    }
    cfg.update(extra)
    engine, *_ = dstpu.initialize(model=TransformerLM(cfg_model), config=cfg,
                                  topology=topology)
    return engine


def data_iter(gb, seed=0, n_fixed=2):
    rng = np.random.default_rng(seed)
    fixed = [{"input_ids": rng.integers(0, 64, (gb, 17)).astype(np.int32)}
             for _ in range(n_fixed)]
    i = 0
    while True:
        yield fixed[i % n_fixed]
        i += 1


TOPO = {"dp": -1, "fsdp": 1}  # ZeRO++ step shards over dp


def test_qgz_trains(devices):
    # no explicit topology: the default mesh must pick dp=-1 for ZeRO++
    engine = make_engine({"zero_optimization": {
        "stage": 1, "zero_quantized_gradients": True}})
    assert engine._zeropp
    assert engine.mesh.shape["dp"] == 8 and engine.mesh.shape["fsdp"] == 1
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    losses = [float(engine.train_batch(it)) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.3, losses
    assert int(engine._zeropp_state.step) == 8


def test_qgz_qwz_tracks_exact_path(devices):
    """Quantized collectives must track the exact (bf16-wire) step
    closely — int8 blockwise noise, not divergence."""
    exact = make_engine({"zero_optimization": {"stage": 1}}, topology=TOPO)
    quant = make_engine({"zero_optimization": {
        "stage": 1, "zero_quantized_gradients": True,
        "zero_quantized_weights": True}}, topology=TOPO)
    it_a = data_iter(exact.micro_batch_size * exact.dp_world_size, seed=7)
    it_b = data_iter(quant.micro_batch_size * quant.dp_world_size, seed=7)
    la = [float(exact.train_batch(it_a)) for _ in range(6)]
    lb = [float(quant.train_batch(it_b)) for _ in range(6)]
    # same trajectory within quantization noise
    np.testing.assert_allclose(lb, la, rtol=0.05)
    assert lb[-1] < lb[0] - 0.2


def test_qar_trains(devices):
    # qar: EQuARX-style int8 all-reduce replacing the fp32 grad
    # reduce-scatter — same default-mesh contract as qgZ
    engine = make_engine({"zero_optimization": {
        "stage": 1, "zero_quantized_allreduce": True}})
    assert engine._zeropp
    assert engine.mesh.shape["dp"] == 8
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    losses = [float(engine.train_batch(it)) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.3, losses
    assert int(engine._zeropp_state.step) == 8


def test_qar_tracks_exact_path(devices):
    """The quantized all-reduce's two int8 hops (scatter + gather) must
    track the exact step within blockwise quantization noise."""
    exact = make_engine({"zero_optimization": {"stage": 1}}, topology=TOPO)
    qar = make_engine({"zero_optimization": {
        "stage": 1, "zero_quantized_allreduce": True}}, topology=TOPO)
    it_a = data_iter(exact.micro_batch_size * exact.dp_world_size, seed=7)
    it_b = data_iter(qar.micro_batch_size * qar.dp_world_size, seed=7)
    la = [float(exact.train_batch(it_a)) for _ in range(6)]
    lb = [float(qar.train_batch(it_b)) for _ in range(6)]
    np.testing.assert_allclose(lb, la, rtol=0.05)
    assert lb[-1] < lb[0] - 0.2


def test_qar_qgz_mutually_exclusive(devices):
    # both knobs own the gradient wire: the config layer rejects the
    # combination before any mesh work happens
    with pytest.raises(ValueError, match="gradient wire"):
        make_engine({"zero_optimization": {
            "stage": 1, "zero_quantized_allreduce": True,
            "zero_quantized_gradients": True}}, topology=TOPO)


def test_zeropp_checkpoint_roundtrip(devices, tmp_path):
    engine = make_engine({"zero_optimization": {
        "stage": 2, "zero_quantized_gradients": True}}, topology=TOPO)
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    for _ in range(3):
        engine.train_batch(it)
    engine.save_checkpoint(str(tmp_path))
    l_ref = [float(engine.train_batch(it)) for _ in range(2)]

    engine2 = make_engine({"zero_optimization": {
        "stage": 2, "zero_quantized_gradients": True}}, topology=TOPO)
    engine2.load_checkpoint(str(tmp_path))
    it2 = data_iter(engine2.micro_batch_size * engine2.dp_world_size)
    for _ in range(3):
        next(it2)  # advance the iterator to the same position
    l_new = [float(engine2.train_batch(it2)) for _ in range(2)]
    np.testing.assert_allclose(l_new, l_ref, rtol=1e-4)


def test_load_without_optimizer_states_reseeds(devices, tmp_path):
    engine = make_engine({"zero_optimization": {
        "stage": 1, "zero_quantized_gradients": True}}, topology=TOPO)
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    for _ in range(2):
        engine.train_batch(it)
    engine.save_checkpoint(str(tmp_path))
    trained = engine.module_state_dict()

    engine2 = make_engine({"zero_optimization": {
        "stage": 1, "zero_quantized_gradients": True}}, topology=TOPO)
    engine2.load_checkpoint(str(tmp_path), load_optimizer_states=False)
    # params restored AND the next step must not roll back to init
    key = next(iter(trained))
    np.testing.assert_allclose(
        np.asarray(engine2.module_state_dict()[key], np.float32),
        np.asarray(trained[key], np.float32))
    it2 = data_iter(engine2.micro_batch_size * engine2.dp_world_size)
    engine2.train_batch(it2)
    after = np.asarray(engine2.module_state_dict()[key], np.float32)
    drift = np.abs(after - np.asarray(trained[key], np.float32)).mean()
    assert drift < 0.1, "post-load step rolled params back to init"


def test_unsupported_optimizer_disables_zeropp(devices):
    from unittest import mock

    from deepspeed_tpu.runtime import engine as engine_mod

    with mock.patch.object(engine_mod.logger, "warning") as warn:
        engine = make_engine({
            "optimizer": {"type": "lion", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1,
                                  "zero_quantized_gradients": True}})
    assert not engine._zeropp  # lion falls back to the standard path
    assert any("wired for" in str(c.args[0]) for c in warn.call_args_list)


def test_flags_warn_when_not_wired(devices):
    from unittest import mock

    from deepspeed_tpu.runtime import engine as engine_mod

    with mock.patch.object(engine_mod.logger, "warning") as warn:
        # fp16 is outside the quantized step's envelope (stage-3 qgZ is
        # wired since round 3, so the stage alone no longer triggers it)
        engine = make_engine({
            "fp16": {"enabled": True},
            "zero_optimization": {"stage": 1,
                                  "zero_quantized_gradients": True}})
    assert not engine._zeropp
    assert any("wired for" in str(c.args[0])
               for c in warn.call_args_list)


# ---------------------------------------------------------------------------
# stage-3 qwZ: int8 quantized parameter all-gather in the fsdp fetch path
# (reference partition_parameters.py:1446)
# ---------------------------------------------------------------------------

UNTIED = TransformerConfig(
    vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
    max_seq_len=32, pos_emb="learned", norm="layernorm",
    activation="gelu", tie_embeddings=False, remat=False)


def _run_qwz_worker(mode, timeout=420):
    """Fresh-process run of tests/qwz_worker.py (see its docstring: the
    CPU-sim thunk executor races concurrent collective rendezvous across
    independent while-loops; the reference isolates the same hazard with
    pytest --forked)."""
    import json
    import os
    import subprocess
    import sys

    from deepspeed_tpu.utils.hostsim import cpu_sim_env

    here = os.path.dirname(os.path.abspath(__file__))
    env = cpu_sim_env(n_devices=8)  # thread headroom on small hosts
    env["PYTHONPATH"] = (os.path.dirname(here) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "qwz_worker.py"), mode],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])["losses"]


def test_qwz_stage3_trains_and_tracks_exact(devices):
    exact = _run_qwz_worker("exact")
    quant = _run_qwz_worker("quant")
    # quantization noise, not divergence
    assert quant[-1] < quant[0] - 0.2, quant
    np.testing.assert_allclose(quant, exact, rtol=0.08)


def test_qwz_stage3_composes_with_tp(devices):
    losses = _run_qwz_worker("tp")
    assert losses[-1] < losses[0] - 0.3, losses


def test_qwz_stage3_hpz_mesh(devices):
    """hpZ grouping (fsdp=4 in-group shards x dp=2 replicas): the int8
    gather stays intra-fsdp-group by construction and training learns."""
    losses = _run_qwz_worker("hpz")
    assert losses[-1] < losses[0] - 0.2, losses


def test_qwz_int8_gather_in_hlo(devices):
    """The compiled train step must gather int8 payloads over fsdp, and the
    bf16/f32 gather bytes for the quantized weights must be gone."""
    from deepspeed_tpu.runtime import sharding as shard_lib

    engine = make_engine(cfg_model=UNTIED, extra={"zero_optimization": {
        "stage": 3, "zero_quantized_weights": True}},
        topology={"dp": 1, "fsdp": -1})
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    batches = engine._next_microbatches(
        it, engine.gradient_accumulation_steps)
    compiled = engine._jit_train_step.lower(
        engine.params, engine.opt_state, engine.loss_scale_state,
        engine.step_count, batches).compile()
    hlo = compiled.as_text()
    s8_gathers = [l for l in hlo.splitlines()
                  if "all-gather" in l and "s8[" in l]
    assert s8_gathers, "no int8 all-gather found in compiled HLO"
    # and no full-width float gather of a quantized weight remains (a
    # regression that double-gathers would still carry these shapes):
    # per-layer wq/wk/wv [32,4,8], wo [4,8,32], mlp [32,128]/[128,32],
    # unembed [32,64] (embed [64,32] is legitimately exact — excluded)
    import re
    bad = [l for l in hlo.splitlines()
           if re.search(r"all-gather[^=]*= (f32|bf16)"
                        r"\[(32,4,8|4,8,32|32,128|128,32|32,64)\]", l)]
    assert not bad, f"full-width gather of a quantized weight:\n{bad[0]}"


def test_qwz_inactive_without_flag(devices):
    from deepspeed_tpu.runtime import sharding as shard_lib

    engine = make_engine(cfg_model=UNTIED, extra={"zero_optimization": {"stage": 3}},
                          topology={"dp": 1, "fsdp": -1})
    assert not engine._qwz_stage3 and not shard_lib.qwz_active()


def test_zeropp_stage12_composes_with_tp(devices):
    """The stage-1/2 quantized step is partial-manual over dp, so tp
    shards the model inside the region (round-2 de-islanding)."""
    engine = make_engine({"zero_optimization": {
        "stage": 2, "zero_quantized_gradients": True,
        "zero_quantized_weights": True}},
        topology={"dp": 4, "fsdp": 1, "tp": 2})
    assert engine._zeropp
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    losses = [float(engine.train_batch(it)) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.2, losses


def test_zeropp_tp_tracks_pure_dp(devices):
    """tp=2 must follow the pure-dp trajectory (same global batch)."""
    a = make_engine({"zero_optimization": {
        "stage": 1, "zero_quantized_gradients": True}},
        topology={"dp": 8, "fsdp": 1})
    b = make_engine({"zero_optimization": {
        "stage": 1, "zero_quantized_gradients": True}},
        topology={"dp": 4, "fsdp": 1, "tp": 2})
    it_a = data_iter(a.micro_batch_size * a.dp_world_size, seed=5)
    it_b = data_iter(b.micro_batch_size * b.dp_world_size, seed=5)
    la = [float(a.train_batch(it_a)) for _ in range(5)]
    lb = [float(b.train_batch(it_b)) for _ in range(5)]
    # different dp degree -> different quantization grouping; same model,
    # same global batch, so trajectories must track closely
    np.testing.assert_allclose(lb, la, rtol=0.05)


def test_zeropp_set_lr(devices):
    """set_lr is a runtime operand of the ZeRO++ step (no rebuild)."""
    engine = make_engine({"zero_optimization": {
        "stage": 1, "zero_quantized_gradients": True}}, topology=TOPO)
    it = data_iter(engine.micro_batch_size * engine.dp_world_size)
    engine.train_batch(it)
    before = engine.module_state_dict()
    key = next(iter(before))
    snap = np.asarray(before[key], np.float32).copy()
    engine.set_lr(0.0)
    engine.train_batch(it)
    after = np.asarray(engine.module_state_dict()[key], np.float32)
    np.testing.assert_allclose(after, snap, atol=1e-6)  # lr=0: frozen
    assert engine.get_lr() == [0.0]
    engine.set_lr(1e-2)
    engine.train_batch(it)
    moved = np.asarray(engine.module_state_dict()[key], np.float32)
    assert np.abs(moved - snap).max() > 1e-5

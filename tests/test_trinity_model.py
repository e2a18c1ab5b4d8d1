"""``models/hybrid.py`` with a windowed layer kind, rotary by layer kind,
post-branch norms, a dense prologue counted from the published index and
the experts apart — at toy size (``tiny-trinity``): the configuration's
derived kinds, training through ``dstpu.initialize`` ->
``engine.train_batch`` with the model's counters in the step's row, and the
serving runner's refusal."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

JOB = {"train_micro_batch_size_per_chip": 2, "gradient_accumulation_steps": 1,
       "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
       "zero_optimization": {"stage": 3}, "bf16": {"enabled": True},
       "activation_checkpointing": {"policy": "nothing_saveable"},
       "steps_per_print": 10 ** 9}


def test_the_stack_is_cut_at_first_layer_and_kinds_follow_the_pattern():
    c = get_model("tiny-trinity").config
    assert c.layer_kinds == (True,) * 5                 # all softmax attention
    assert c.layer_windows == (24, 24, None, 24, 24)    # published layers 1-5
    assert c.dense_layers == 1 and not c.is_dense(1) and c.is_dense(0)
    assert c.stack_plan == (4, ((True, 1),))
    whole = get_model("trinity-mini").config
    assert whole.layer_windows[:5] == (2048, 2048, 2048, None, 2048)
    assert whole.dense_layers == 2 and sum(
        w is None for w in whole.layer_windows) == 8
    # the leading dense layers are counted from the published index
    assert dataclasses.replace(c, first_layer=0, num_layers=6).dense_layers == 2
    assert dataclasses.replace(c, first_layer=3, num_layers=4).dense_layers == 0
    kimi = get_model("tiny-kimi").config                # first_layer 0: as it was
    assert kimi.dense_layers == kimi.first_k_dense == 1
    with pytest.raises(ValueError, match="m | w | l"):
        dataclasses.replace(c, layer_pattern="wxwm" * 2)
    with pytest.raises(ValueError, match="sliding_window"):
        dataclasses.replace(c, sliding_window=None).layer_windows


def test_the_tree_holds_no_expert_slot_for_the_dense_layer():
    m = get_model("tiny-trinity")
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    assert shapes["experts"]["wg"].shape == (4, 4, 64, 32)
    assert shapes["dense"]["wg"].shape == (1, 64, 128)
    assert "experts" not in shapes["layers"]["moe"]
    assert set(shapes["layers"]) >= {"ln1", "ln1_post", "ln2", "ln2_post"}
    axes = m.logical_axes()
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(shapes)
    p = m.init(jax.random.PRNGKey(0))
    cut = hybrid.serving_params(m.config, p)
    assert cut["experts"] is p["experts"] and cut["attn"]["wq"].shape[0] == 5
    assert hybrid.serving_params(m.config, cut) is cut


def test_window_and_rotary_by_kind_enter_the_forward():
    """Dropping the window, or rotating the full layer, changes the logits;
    moving every position by a constant changes nothing (windowed layers
    rotate, which is relative; the full layer has no position at all)."""
    m = get_model("tiny-trinity", dtype="float32")
    c = m.config
    p = m.init(jax.random.PRNGKey(1))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 64)))
    base = m.apply(p, ids)
    wide = hybrid.apply(dataclasses.replace(c, sliding_window=64), p, ids)
    rotated = hybrid.apply(dataclasses.replace(c, partial_rotary_factor=1.0),
                           p, ids)
    assert float(jnp.max(jnp.abs(base - wide))) > 1e-3
    assert float(jnp.max(jnp.abs(base - rotated))) > 1e-3
    # the first 24 positions see the same keys with or without the window
    np.testing.assert_allclose(base[:, :24], wide[:, :24], atol=1e-5)
    shifted = m.apply(p, ids, jnp.broadcast_to(jnp.arange(64)[None] + 100,
                                               (2, 64)))
    np.testing.assert_allclose(base, shifted, atol=2e-4)


def test_trains_through_the_engine_with_its_counters_in_the_step_row():
    from deepspeed_tpu.observability.hub import peek_hub, reset_hub

    reset_hub()
    m = get_model("tiny-trinity")
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    engine, _, _, _ = dstpu.initialize(model=m, config=dict(JOB), mesh=mesh)
    ids = np.random.default_rng(0).integers(0, 256, (2, 65)).astype(np.int32)
    losses = [float(engine.train_batch(iter([{"input_ids": ids}])))
              for _ in range(5)]
    engine.synchronize()
    assert losses[-1] < losses[0] - 0.3 and all(np.isfinite(losses))
    hub = peek_hub()
    row = hub.step_history[-1].extras
    assert set(row) == set(hybrid.MOE_COUNTERS)
    assert row["moe_token_layers"] == 4 * 2 * 64 and row["moe_dropped_pairs"] == 0
    # top-2 of 16 with 4 held: half a pair a token and expert layer
    assert 0.2 < row["moe_local_pairs"] / row["moe_token_layers"] < 1.0
    assert 1 <= row["moe_experts_hit"] <= 16
    assert row["moe_max_expert_rows"] >= row["moe_local_pairs"] / 16
    assert hub.counters["train.moe_token_layers"] == 5 * 4 * 2 * 64
    # the held experts, the router and the shared expert moved; the bias,
    # which chooses and never weighs, did not (nor did weight decay move it)
    import optax

    mu = jax.tree.map(np.asarray,
                      optax.tree_utils.tree_get(engine.opt_state.inner, "mu"))
    assert np.any(mu["experts"]["wg"]) and np.any(mu["layers"]["moe"]["router"])
    assert not np.any(mu["layers"]["moe"]["router_bias"])
    engine.close()


def test_a_dense_model_step_carries_no_counters():
    from deepspeed_tpu.observability.hub import peek_hub, reset_hub

    reset_hub()
    m = get_model("tiny")
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    engine, _, _, _ = dstpu.initialize(model=m, config=dict(JOB), mesh=mesh)
    ids = np.random.default_rng(0).integers(
        0, m.config.vocab_size, (2, 33)).astype(np.int32)
    engine.train_batch(iter([{"input_ids": ids}]))
    engine.synchronize()
    assert peek_hub().step_history[-1].extras == {}
    engine.close()


def test_the_serving_runner_refuses_a_windowed_stack_by_name():
    from deepspeed_tpu.inference import hybrid_runner

    c = get_model("tiny-trinity").config
    with pytest.raises(NotImplementedError,
                       match="windowed gated softmax attention is not "
                             "served yet"):
        hybrid_runner._run_stack(c, {}, jnp.zeros((4, 64)), {}, None, None,
                                 None)


def test_the_bias_moves_by_the_load_and_by_no_gradient():
    """``bias_update``: ``rate`` up for an output under the mean load, down
    for one over it, the change's mean taken off; the engine adds a model's
    ``param_deltas`` to the master weights after the optimizer (the dense
    layer's slot gets none), and a rate of 0 holds the bias."""
    from deepspeed_tpu.parallel.moe import bias_update

    d = np.asarray(bias_update(jnp.asarray([[9, 1, 5, 5], [2, 2, 2, 2]]), 0.5))
    np.testing.assert_allclose(d, [[-0.5, 0.5, 0, 0], [0, 0, 0, 0]])
    d = np.asarray(bias_update(jnp.asarray([9, 1, 1, 1]), 0.1))
    np.testing.assert_allclose(d, [-0.15, 0.05, 0.05, 0.05], atol=1e-7)

    ids = np.random.default_rng(0).integers(0, 256, (2, 65)).astype(np.int32)
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    moved = {}
    for rate in (0.001, 0.0):
        m = get_model("tiny-trinity", bias_update_rate=rate)
        _, aux = m.loss(m.init(jax.random.PRNGKey(0)), {"input_ids": ids})
        engine, _, _, _ = dstpu.initialize(
            model=m, config=dict(JOB, optimizer={
                "type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.0}}),
            mesh=mesh)
        before = np.asarray(engine.opt_state.master["layers"]["moe"]
                            ["router_bias"])
        engine.train_batch(iter([{"input_ids": ids}]))
        engine.synchronize()
        after = engine.opt_state.master["layers"]["moe"]["router_bias"]
        moved[rate] = np.asarray(after) - before
        np.testing.assert_allclose(     # the compute copy follows the master
            np.asarray(engine.params["layers"]["moe"]["router_bias"],
                       np.float32), np.asarray(after), rtol=1e-2)
        engine.close()
        assert ("param_deltas" in aux) == bool(rate)
    assert not np.any(moved[0.0])
    d = moved[0.001]
    assert not np.any(d[0]) and np.all(np.any(d[1:], axis=1))   # layer 0: dense
    np.testing.assert_allclose(d[1:].mean(axis=1), 0, atol=1e-7)
    assert np.all(np.abs(d) <= 0.002 + 1e-7)


def test_a_step_that_drops_a_pair_stops_the_engine(monkeypatch):
    """The experts' row buffer is a rule of the shapes (one and a half times
    the share's expected rows, every pair where that is no fewer); a step
    whose routing overflows it has computed another model, and the engine
    says so instead of training on."""
    c = get_model("trinity-mini", experts_held=16).config
    assert hybrid.share_capacity(c, 16384) == 24576     # of 131,072 pairs
    assert hybrid.share_capacity(c, 8192) == 12288
    assert hybrid.share_capacity(get_model("trinity-mini").config, 8192) is None
    tiny = get_model("tiny-trinity").config
    assert hybrid.share_capacity(tiny, 128) == 256      # every pair
    monkeypatch.setattr(hybrid, "share_capacity", lambda cfg, tokens: 128)
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    engine, _, _, _ = dstpu.initialize(model=get_model("tiny-trinity"),
                                       config=dict(JOB), mesh=mesh)
    # 512 tokens, top-2, a quarter of the experts: some 250 pairs a layer
    ids = np.random.default_rng(0).integers(0, 256, (2, 257)).astype(np.int32)
    with pytest.raises(RuntimeError, match="moe_dropped_pairs"):
        engine.train_batch(iter([{"input_ids": ids}]))
        engine.synchronize()
    engine.close()

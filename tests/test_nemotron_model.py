"""``models/hybrid.py`` with blocks of one mixer each (``layer_pattern`` over
"M" | "E" | "*"), the Mamba-2 mixer (``recurrent_kind`` "mamba2",
``ops/pallas/mamba2.py``), ungated squared-ReLU experts and attention without
positions, QK-norm or gate — at toy size (``tiny-nemotron``): the
configuration's derived kinds and its refusals, a tree with no leaf for a
part a block lacks, the chunked scan against the recurrence as written,
training through ``dstpu.initialize`` -> ``engine.train_batch``, the named
activations of the expert feed-forward, the grouped product's tiles at
widths that are no power of two, and the serving runner's refusals."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.ops.pallas.mamba2 import ssd_chunk, ssd_recurrence
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

JOB = {"train_micro_batch_size_per_chip": 2, "gradient_accumulation_steps": 1,
       "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
       "zero_optimization": {"stage": 3}, "bf16": {"enabled": True},
       "activation_checkpointing": {"policy": "nothing_saveable"},
       "steps_per_print": 10 ** 9}


def test_the_pattern_carries_each_block_s_kind():
    c = get_model("tiny-nemotron").config
    assert c.one_mixer and c._held_pattern == "EMEM*"    # blocks 1-5 of 9
    assert c.mixer_kinds == (None, False, None, False, True)
    assert c.layer_kinds == (False, False, False, False, True)
    assert (c.kv_layers, c.recurrent_layers, c.expert_layers,
            c.dense_layers, c.window_layers) == (1, 2, 2, 0, 0)
    assert c.layer_windows == (None,) * 5
    # nothing tiles: one repeat, a run a block, an expert block's kind None
    assert c.stack_plan == (1, ((None, 1), (False, 1), (None, 1), (False, 1),
                                (True, 1)))
    assert (c.mamba_inner, c.conv_channels, c.conv_taps) == (64, 128, 4)
    assert c.expert_activation == "relu2"
    whole = get_model("nemotron3-nano").config
    assert whole.num_layers == len(whole.layer_pattern) == 52
    assert (whole.recurrent_layers, whole.expert_layers,
            whole.kv_layers) == (23, 23, 6)
    cut = dataclasses.replace(whole, num_layers=9, first_layer=34)
    assert cut._held_pattern == "EMEMEMEM*"
    assert cut.stack_plan[0] == 1 and len(cut.stack_plan[1]) == 9
    assert (cut.mamba_inner, cut.conv_channels) == (4096, 6144)
    # the accepted configurations keep their answers
    trinity = get_model("tiny-trinity").config
    assert not trinity.one_mixer and trinity.expert_layers == 4
    assert get_model("tiny-sala").config.expert_layers == 0
    assert get_model("tiny-hybrid").config.mixer_kinds == (
        False, False, False, True) * 2


@pytest.mark.parametrize("change,message", [
    ({"layer_pattern": "MEMEm*EME"}, "mixes layers"),
    ({"layer_pattern": "MEMEX*EME"}, r"M \| E \| \*"),
    ({"post_norms": True}, "norm -> mixer -> residual"),
    ({"first_k_dense": 1}, "norm -> mixer -> residual"),
    ({"attention_kind": "mla"}, "norm -> mixer -> residual"),
    ({"num_experts": 0, "experts_held": None, "activation": "swiglu"},
     "needs num_experts"),
    ({"recurrent_kind": "s4"}, "gdn | lightning | mamba2"),
    ({"mamba_n_groups": 3}, "multiple of the groups"),
])
def test_the_configuration_refuses_what_no_block_is(change, message):
    c = get_model("tiny-nemotron").config
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(c, **change)


def test_relu2_is_the_experts_own_activation():
    with pytest.raises(ValueError, match="the experts' own"):
        dataclasses.replace(get_model("tiny-sala").config, activation="relu2")
    with pytest.raises(ValueError, match="the experts' own"):
        dataclasses.replace(get_model("tiny-kimi").config, activation="relu2")


def test_the_tree_holds_no_leaf_for_a_part_a_block_lacks():
    m = get_model("tiny-nemotron")
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    assert set(shapes) == {"embed", "final_norm", "unembed", "layers",
                           "mamba2", "attn", "moe", "experts"}
    assert set(shapes["layers"]) == {"ln1"}             # one norm a block
    assert shapes["layers"]["ln1"]["scale"].shape == (5, 64)
    assert shapes["mamba2"]["w_in"].shape == (2, 64, 64 + 128 + 4)
    assert shapes["mamba2"]["conv_bias"].shape == (2, 128)
    assert set(shapes["attn"]) == {"wq", "wk", "wv", "wo"}   # no QK-norm
    assert shapes["attn"]["wq"].shape == (1, 64, 4, 32)      # no gate half
    assert set(shapes["experts"]) == {"wi", "wo"}            # no gate matrix
    assert shapes["experts"]["wi"].shape == (2, 4, 64, 32)
    assert set(shapes["moe"]["shared"]) == {"wi", "wo"}
    assert shapes["moe"]["router_bias"].shape == (2, 16)
    axes = m.logical_axes()
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(shapes)
    p = m.init(jax.random.PRNGKey(0))
    assert hybrid.serving_params(m.config, p) is p       # cut already
    assert jax.tree.structure(m.axes_for(p), is_leaf=lambda x: isinstance(
        x, tuple)) == jax.tree.structure(shapes)


def test_the_accepted_trees_are_as_they_were():
    """Every new field defaults to what the accepted hybrid configurations
    have: their leaves, a slot a layer, with QK-norm and gate matrices."""
    s = jax.eval_shape(get_model("tiny-hybrid").init, jax.random.PRNGKey(0))
    assert set(s["layers"]["attn"]) == {"wq", "wk", "wv", "wo", "q_norm",
                                        "k_norm"}
    assert set(s["layers"]["moe"]["experts"]) == {"wg", "wi", "wo"}
    assert set(s["layers"]["moe"]["shared"]) == {"wg", "wi", "wo"}
    assert s["layers"]["gdn"]["wq"].shape[0] == 8
    t = jax.eval_shape(get_model("tiny-trinity").init, jax.random.PRNGKey(0))
    assert set(t["experts"]) == {"wg", "wi", "wo"}
    assert t["layers"]["moe"]["router"].shape[0] == 5


def _scan_inputs(T, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    return (jax.random.normal(ks[0], (2, T, 4, 16), dtype),
            jax.nn.softplus(2.0 * jax.random.normal(ks[1], (2, T, 4))),
            -jnp.exp(jax.random.normal(ks[2], (4,))),
            jax.random.normal(ks[3], (2, T, 2, 16), dtype),
            jax.random.normal(ks[4], (2, T, 2, 16), dtype),
            jax.random.normal(ks[5], (4,)))


@pytest.mark.parametrize("T", [64, 50, 16, 7],
                         ids=["four_chunks", "a_part_chunk", "one_chunk",
                              "under_a_chunk"])
def test_the_chunked_scan_is_the_recurrence(T):
    """Values, the last state and every operand's gradient, float32: at a
    whole number of chunks and at lengths that are none (padded with tokens
    of ``dt = 0``, which decay nothing and write nothing). 1e-5 (5e-5 for
    a gradient, a sum over every token): both are float32 sums of the same
    terms in another order."""
    args = _scan_inputs(T)
    with jax.default_matmul_precision("highest"):
        y1, s1 = ssd_chunk(*args, chunk=16)
        y2, s2 = ssd_recurrence(*args)

        def grads(fn):
            return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a)[0])),
                            argnums=tuple(range(6)))(*args)

        g1, g2 = grads(lambda *a: ssd_chunk(*a, chunk=16)), grads(ssd_recurrence)
    assert y1.shape == (2, T, 4, 16) and y1.dtype == jnp.float32
    assert float(jnp.abs(y1 - y2).max() / jnp.abs(y2).max()) < 1e-5
    assert float(jnp.abs(s1 - s2).max() / jnp.abs(s2).max()) < 1e-5
    for a, b in zip(g1, g2):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 5e-5


def test_the_scan_forgets_fast_and_slow_heads_without_overflow():
    """A head that forgets in a token (dt |A| = 40) beside one that keeps
    everything: every pairwise decay is formed from a difference that is not
    positive, so nothing overflows and nothing is NaN, in values or
    gradients."""
    x, _, _, B, C, D = _scan_inputs(64)
    dt = jnp.ones((2, 64, 4)) * jnp.asarray([40.0, 1.0, 1e-3, 0.0])
    A = -jnp.ones((4,))
    y, g = jax.value_and_grad(
        lambda x_: jnp.sum(ssd_chunk(x_, dt, A, B, C, D, chunk=16)[0] ** 2))(x)
    assert np.isfinite(float(y)) and bool(jnp.all(jnp.isfinite(g)))
    # dt = 0: the head writes nothing, its output is the skip alone
    out = ssd_chunk(x, dt, A, B, C, D, chunk=16)[0]
    np.testing.assert_allclose(out[:, :, 3], x[:, :, 3] * D[3], rtol=1e-6)


def test_bf16_operands_accumulate_in_float32():
    args = _scan_inputs(64, jnp.bfloat16)
    y, s = ssd_chunk(*args, chunk=16)
    want, _ = ssd_recurrence(*args)
    assert y.dtype == s.dtype == jnp.float32
    assert float(jnp.linalg.norm(y - want) / jnp.linalg.norm(want)) < 2e-2


def test_the_mixer_s_pieces_are_the_published_equations():
    """The convolution has a bias and starts from a zero tail; the norm
    gates first and takes its mean squares a group."""
    c = get_model("tiny-nemotron", dtype="float32").config
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    mp = {"conv": jax.random.normal(ks[0], (4, 128)),
          "conv_bias": jax.random.normal(ks[1], (128,)),
          "norm": 1.0 + 0.1 * jax.random.normal(ks[2], (64,)),
          "w_out": jnp.eye(64)}
    x = jax.random.normal(ks[3], (1, 9, 128))
    got = hybrid.mamba2_conv(mp, x)
    past = np.concatenate([np.zeros((3, 128)), np.asarray(x[0])])
    want = sum(np.asarray(mp["conv"][i]) * past[i:i + 9] for i in range(4)) \
        + np.asarray(mp["conv_bias"])
    np.testing.assert_allclose(got[0], want / (1 + np.exp(-want)), rtol=2e-5,
                               atol=2e-6)
    o = jax.random.normal(ks[4], (1, 9, 4, 16))
    z = jax.random.normal(ks[5], (1, 9, 64))
    got = hybrid.mamba2_output(c, mp, o, z)
    g = (np.asarray(o).reshape(9, 64) * np.asarray(jax.nn.silu(z[0]))).reshape(
        9, 2, 32)
    g = g / np.sqrt((g * g).mean(-1, keepdims=True) + c.norm_eps)
    np.testing.assert_allclose(got[0], g.reshape(9, 64) * np.asarray(mp["norm"]),
                               rtol=2e-5, atol=2e-6)


def test_trains_through_the_engine_with_its_counters_in_the_step_row():
    from deepspeed_tpu.observability.hub import peek_hub, reset_hub

    reset_hub()
    m = get_model("tiny-nemotron")
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    engine, _, _, _ = dstpu.initialize(model=m, config=dict(JOB), mesh=mesh)
    ids = np.random.default_rng(0).integers(0, 256, (2, 65)).astype(np.int32)
    before = np.asarray(engine.opt_state.master["moe"]["router_bias"])
    losses = [float(engine.train_batch(iter([{"input_ids": ids}])))
              for _ in range(5)]
    engine.synchronize()
    assert losses[-1] < losses[0] - 0.3 and all(np.isfinite(losses))
    row = peek_hub().step_history[-1].extras
    assert set(row) == set(hybrid.MOE_COUNTERS)
    # two expert blocks of the five count; top-2 of 16 with 4 held
    assert row["moe_token_layers"] == 2 * 2 * 64 and row["moe_dropped_pairs"] == 0
    assert 0.2 < row["moe_local_pairs"] / row["moe_token_layers"] < 1.0
    import optax

    mu = jax.tree.map(np.asarray,
                      optax.tree_utils.tree_get(engine.opt_state.inner, "mu"))
    for group, leaf in (("mamba2", "w_in"), ("mamba2", "conv_bias"),
                        ("mamba2", "A_log"), ("mamba2", "dt_bias"),
                        ("mamba2", "D"), ("mamba2", "norm"), ("attn", "wq"),
                        ("experts", "wi"), ("moe", "router")):
        assert np.any(mu[group][leaf]), (group, leaf)
    # the bias chooses and never weighs: no gradient, moved by the load
    assert not np.any(mu["moe"]["router_bias"])
    moved = np.asarray(engine.opt_state.master["moe"]["router_bias"]) - before
    assert np.all(np.any(moved, axis=1)) and np.all(np.abs(moved) < 0.011)
    np.testing.assert_allclose(moved.mean(axis=1), 0, atol=1e-4)
    engine.close()


def test_flops_and_params_are_right_for_one_mixer_blocks():
    """Against a count by hand from the published sizes (the reference's
    leaf table is held to the same tree in tests/benchmarks): the cell's cut
    holds 667.0 M parameters; a token touches, of an expert block, six routed
    experts and the shared one, two matrices each."""
    c = get_model("nemotron3-nano", num_layers=9, first_layer=34,
                  experts_held=8, vocab_size=16384).config
    h, f, fs, R = 2688, 1856, 3712, 128
    mamba = h * (4096 + 6144 + 64) + 4096 * h + 4 * 6144 + 6144 + 3 * 64 + 4096
    attn = h * 128 * (32 + 2 + 2) + 32 * 128 * h
    expert_block = 8 * 2 * h * f + 2 * h * fs + h * R + R
    assert c.num_params() == (4 * mamba + attn + 4 * expert_block + 9 * h + h
                              + 2 * 16384 * h) == 666_963_456
    touched = c.num_params() - 4 * 8 * 2 * h * f + 4 * 6 * 2 * h * f
    assert c.flops_per_token() == 6.0 * touched
    tiny = get_model("tiny-nemotron")
    assert tiny.num_params() == sum(
        x.size for x in jax.tree.leaves(tiny.init(jax.random.PRNGKey(0))))
    # a gated two-branch stack counts as it did
    t = get_model("tiny-trinity").config
    assert t.flops_per_token() == 6.0 * (
        t.num_params() - 4 * 3 * 64 * 32 * 4 + 4 * 3 * 64 * (2 * 32 + 32)
        - 3 * 64 * 32)


@pytest.mark.parametrize("activation,ok", [
    ("swiglu", True), ("gelu", True), ("relu2", True), ("relu", False),
    ("gelu_tanh", False), (None, False)])
def test_the_expert_feed_forward_names_its_activations(activation, ok):
    from deepspeed_tpu.parallel.moe import _expert_ffn

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (128, 32))
    params = {"wg": jax.random.normal(ks[1], (2, 32, 16)) * 0.2,
              "wi": jax.random.normal(ks[2], (2, 32, 16)) * 0.2,
              "wo": jax.random.normal(ks[3], (2, 16, 32)) * 0.2}
    sizes = jnp.asarray([80, 48], jnp.int32)
    if not ok:
        with pytest.raises(ValueError, match="feed-forward activation"):
            _expert_ffn(x, sizes, params, activation, jnp.float32)
        return
    got = _expert_ffn(x, sizes, params, activation, jnp.float32)
    act = {"swiglu": lambda g, u: jax.nn.silu(g) * u,
           "gelu": lambda g, u: jax.nn.gelu(u),
           "relu2": lambda g, u: jnp.square(jax.nn.relu(u))}[activation]
    for e, rows in enumerate((slice(0, 80), slice(80, 128))):
        want = act(x[rows] @ params["wg"][e], x[rows] @ params["wi"][e]) \
            @ params["wo"][e]
        np.testing.assert_allclose(got[rows], want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("dim,want,tile", [
    (2048, 1024, 1024), (1536, 1024, 512), (7168, 512, 512), (512, 1024, 512),
    (64, 1024, 64),                         # as _pick_block gives them
    (2688, 512, 384), (2688, 1024, 896), (2688, 128, 128),   # 128 x 21
    (1856, 1024, 1856), (1856, 512, 1856)])                  # 64 x 29
def test_a_lane_tile_fills_lanes_or_is_the_whole_dim(dim, want, tile):
    assert gm._pick_lane_block(dim, want) == tile
    assert dim % tile == 0 and (tile % 128 == 0 or tile == dim)


def test_the_products_tiles_at_the_published_expert_widths():
    """8 held experts, 9,216 rows, 2688 x 1856: the up-projection takes the
    expert width whole and the hidden size in tiles of 384; the
    down-projection, whose contraction comes back whole, as many columns as
    the right-hand block's bytes hold; where a whole dim makes the blocks
    outgrow VMEM the row tile shrinks. The accepted geometries keep theirs."""
    assert gm.choose_tiles(9216, 2688, 1856, 8, jnp.bfloat16) == (512, 1856, 384)
    assert gm.choose_tiles(9216, 1856, 2688, 8, jnp.bfloat16) == (512, 384, 1856)
    # float32 (the witness run): fewer rows, so that the blocks fit VMEM
    assert gm.choose_tiles(9216, 2688, 1856, 8, jnp.float32) == (128, 1856, 384)
    assert gm.choose_tiles(9216, 1856, 2688, 8, jnp.float32) == (256, 128, 1856)
    assert gm.choose_tiles(24576, 2048, 1024, 16, jnp.float32) == (512, 512, 512)
    assert gm.choose_tiles(24576, 2048, 1024, 16, jnp.bfloat16) == (512, 1024, 512)
    assert gm.choose_tiles(32768, 4096, 14336, 8, jnp.bfloat16) == (512, 1024, 512)


@pytest.mark.parametrize("preset,change,error", [
    ("tiny-nemotron", {}, hybrid.OneMixerStackUnsupported),
    ("tiny-hybrid", {"recurrent_kind": "mamba2"}, hybrid.StateSpaceUnsupported),
])
def test_the_serving_runner_refuses_by_a_named_exception(preset, change, error):
    from deepspeed_tpu.inference import hybrid_runner

    c = dataclasses.replace(get_model(preset).config, **change)
    with pytest.raises(error, match="not served yet"):
        hybrid_runner.store_specs(c, kv_blocks=8, kv_block_size=16, max_seqs=2,
                                  state_slots=None, dtype=jnp.float32,
                                  quant_bits=None)
    assert issubclass(error, NotImplementedError)


def test_an_engine_is_not_built_for_a_one_mixer_stack():
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    m = get_model("tiny-nemotron", dtype="float32")
    with pytest.raises(hybrid.OneMixerStackUnsupported):
        InferenceEngineV2(m, params=m.init(jax.random.PRNGKey(0)),
                          kv_blocks=8, kv_block_size=16, max_seqs_per_step=2,
                          dtype=jnp.float32)

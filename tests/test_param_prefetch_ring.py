"""Prefetch-ring depth correctness: streamed_layers_prefetch at depth
{1, 2, 4} must be BIT-IDENTICAL to the plain lax.scan over the stack —
the ring only changes the copy schedule, never the math (acceptance
criterion: with fp8_mlp off and param_prefetch_depth=1 step losses are
bit-identical to the unstreamed baseline)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepspeed_tpu.runtime.param_stream import streamed_layers_prefetch

L, B, H = 5, 2, 8


def _stack(dtype):
    k = jax.random.PRNGKey(0)
    kw, kb = jax.random.split(k)
    return {
        "w": (jax.random.normal(kw, (L, H, H)) / np.sqrt(H)).astype(dtype),
        "b": (0.01 * jax.random.normal(kb, (L, H))).astype(dtype),
    }


def _layer(x, p, scale):
    return jnp.tanh(x @ p["w"] + p["b"]) * scale


def _x(dtype):
    return jax.random.normal(jax.random.PRNGKey(1), (B, H)).astype(dtype)


def _scan_ref(stack, x, scale):
    def body(c, p):
        return _layer(c, p, scale), None

    y, _ = lax.scan(body, x, stack)
    return y


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("depth", [1, 2, 4])
def test_forward_bit_identical_to_scan(dtype, depth):
    stack, x = _stack(dtype), _x(dtype)
    scale = jnp.asarray(1.0, dtype)
    ref = jax.jit(_scan_ref)(stack, x, scale)
    got = jax.jit(lambda s, x_, sc: streamed_layers_prefetch(
        _layer, s, x_, extra=(sc,), prefetch_depth=depth))(stack, x, scale)
    assert got.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("grads_to_host", [True, False])
@pytest.mark.parametrize("depth", [1, 2, 4])
def test_grads_bit_identical_to_scan(depth, grads_to_host):
    """The custom VJP (reverse-pipelined per-layer recompute, optional
    d2h grad landing) must produce the same cotangents as autodiff of
    the plain scan — the nothing_saveable remat of the same program."""
    stack, x = _stack(jnp.float32), _x(jnp.float32)
    scale = jnp.asarray(1.0, jnp.float32)

    def loss_ref(s, x_):
        return jnp.sum(_scan_ref(s, x_, scale) ** 2)

    def loss_stream(s, x_):
        y = streamed_layers_prefetch(
            _layer, s, x_, extra=(scale,), prefetch_depth=depth,
            grads_to_host=grads_to_host)
        return jnp.sum(y ** 2)

    gs_ref, gx_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1)))(stack, x)
    gs, gx = jax.jit(jax.grad(loss_stream, argnums=(0, 1)))(stack, x)
    np.testing.assert_array_equal(np.asarray(gx), np.asarray(gx_ref))
    for kk in ("w", "b"):
        np.testing.assert_allclose(
            np.asarray(gs[kk]), np.asarray(gs_ref[kk]),
            rtol=1e-6, atol=1e-6)


def test_remat_replay_composes_with_stream():
    """An outer jax.checkpoint over the streamed region (the pipelined
    wave body does exactly this) replays the custom-VJP forward; the
    replayed fetches must reproduce the same grads."""
    stack, x = _stack(jnp.float32), _x(jnp.float32)
    scale = jnp.asarray(1.0, jnp.float32)

    def region(s, x_):
        return streamed_layers_prefetch(
            _layer, s, x_, extra=(scale,), prefetch_depth=2)

    def loss_plain(s, x_):
        return jnp.sum(region(s, x_) ** 2)

    def loss_remat(s, x_):
        return jnp.sum(jax.checkpoint(region)(s, x_) ** 2)

    g_ref = jax.jit(jax.grad(loss_plain))(stack, x)
    g = jax.jit(jax.grad(loss_remat))(stack, x)
    for kk in ("w", "b"):
        np.testing.assert_array_equal(
            np.asarray(g[kk]), np.asarray(g_ref[kk]))


def test_depths_agree_with_each_other_bf16():
    """Depth is pure schedule: every K gives the same bits, bf16 too."""
    stack, x = _stack(jnp.bfloat16), _x(jnp.bfloat16)
    scale = jnp.asarray(1.0, jnp.bfloat16)
    outs = [
        np.asarray(jax.jit(lambda s, x_, d=d: streamed_layers_prefetch(
            _layer, s, x_, extra=(scale,), prefetch_depth=d))(stack, x))
        for d in (1, 2, 4)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_engine_param_prefetch_depth_reaches_model_config():
    """config.performance.param_prefetch_depth overrides the model's
    env-resolved prefetch_depth (engine wiring, runtime/engine.py)."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  TransformerLM)

    cfg = TransformerConfig(
        vocab_size=64, hidden_size=16, num_layers=2, num_heads=2,
        max_seq_len=16, pos_emb="learned", norm="layernorm",
        activation="gelu", tie_embeddings=True, remat=False,
        param_host_offload=True)
    model = TransformerLM(cfg)
    engine, _, _, _ = dstpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_chip": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "performance": {"param_prefetch_depth": 3}})
    assert engine.module.config.prefetch_depth == 3


# ---------------------------------------------------------------------------
# Per-layer overlap engine (overlap_depth: pin_stage staged scheduling)
# ---------------------------------------------------------------------------

OVERLAP_COMBOS = [(1, 1), (1, 2), (2, 2), (2, 4), (3, 4), (4, 4)]  # (k, depth)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k,depth", OVERLAP_COMBOS)
def test_overlap_forward_bit_identical(dtype, k, depth):
    """overlap_depth is pure schedule: the pin_stage barriers sequence
    the in-flight fetches against layer compute but never change the
    math — every (k, depth) must give the k=0 bits exactly."""
    stack, x = _stack(dtype), _x(dtype)
    scale = jnp.asarray(1.0, dtype)
    ref = jax.jit(lambda s, x_: streamed_layers_prefetch(
        _layer, s, x_, extra=(scale,), prefetch_depth=2,
        overlap_depth=0))(stack, x)
    got = jax.jit(lambda s, x_: streamed_layers_prefetch(
        _layer, s, x_, extra=(scale,), prefetch_depth=depth,
        overlap_depth=k))(stack, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("grads_to_host", [True, False])
@pytest.mark.parametrize("k,depth", OVERLAP_COMBOS)
def test_overlap_grads_bit_identical(k, depth, grads_to_host):
    """Backward staging (fetch ring + d2h grad sink pinned to layer i's
    recompute stage) must leave the cotangents bit-identical too."""
    stack, x = _stack(jnp.float32), _x(jnp.float32)
    scale = jnp.asarray(1.0, jnp.float32)

    def loss(od, d):
        def f(s, x_):
            y = streamed_layers_prefetch(
                _layer, s, x_, extra=(scale,), prefetch_depth=d,
                grads_to_host=grads_to_host, overlap_depth=od)
            return jnp.sum(y ** 2)
        return f

    gs_ref, gx_ref = jax.jit(
        jax.grad(loss(0, 2), argnums=(0, 1)))(stack, x)
    gs, gx = jax.jit(jax.grad(loss(k, depth), argnums=(0, 1)))(stack, x)
    np.testing.assert_array_equal(np.asarray(gx), np.asarray(gx_ref))
    for kk in ("w", "b"):
        np.testing.assert_array_equal(np.asarray(gs[kk]),
                                      np.asarray(gs_ref[kk]))


def test_overlap_remat_replay_composes():
    """jax.checkpoint over the staged region replays the custom-VJP
    forward with its barriers; grads must survive the replay bitwise."""
    stack, x = _stack(jnp.float32), _x(jnp.float32)
    scale = jnp.asarray(1.0, jnp.float32)

    def region(s, x_):
        return streamed_layers_prefetch(
            _layer, s, x_, extra=(scale,), prefetch_depth=2,
            overlap_depth=2)

    g_ref = jax.jit(jax.grad(
        lambda s, x_: jnp.sum(region(s, x_) ** 2)))(stack, x)
    g = jax.jit(jax.grad(
        lambda s, x_: jnp.sum(jax.checkpoint(region)(s, x_) ** 2)))(
        stack, x)
    for kk in ("w", "b"):
        np.testing.assert_array_equal(np.asarray(g[kk]),
                                      np.asarray(g_ref[kk]))


def test_overlap_zero_emits_no_barrier():
    """k=0 must lower today's barrier-free program (the bit-identical
    A/B baseline is structural, not numeric luck); k>0 must stage."""
    stack, x = _stack(jnp.float32), _x(jnp.float32)
    scale = jnp.asarray(1.0, jnp.float32)

    def lowered(k):
        return jax.jit(lambda s, x_: streamed_layers_prefetch(
            _layer, s, x_, extra=(scale,), prefetch_depth=2,
            overlap_depth=k)).lower(stack, x).as_text()

    assert "optimization_barrier" not in lowered(0)
    assert "optimization_barrier" in lowered(2)


def test_engine_overlap_depth_reaches_model_config():
    """config.performance.overlap_depth rides the same engine bridge as
    the prefetch ring depth (runtime/engine.py perf_updates)."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  TransformerLM)

    cfg = TransformerConfig(
        vocab_size=64, hidden_size=16, num_layers=2, num_heads=2,
        max_seq_len=16, pos_emb="learned", norm="layernorm",
        activation="gelu", tie_embeddings=True, remat=False,
        param_host_offload=True)
    engine, _, _, _ = dstpu.initialize(
        model=TransformerLM(cfg),
        config={"train_micro_batch_size_per_chip": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "performance": {"param_prefetch_depth": 2,
                                "overlap_depth": 2}})
    assert engine.module.config.overlap_depth == 2


def test_fsdp_stage3_overlap_parity(devices):
    """Stage-3 resident path: the fsdp_gather_slice/fsdp_scatter_grads
    streamer at overlap_depth=2 vs the plain scan (overlap_depth=0).
    Loss is bit-identical; grads compare to fp32 tolerance — the
    streamer's recompute-backward and the scan's saved-residual backward
    are different programs, so XLA may reassociate reductions (1-ulp
    differences observed), while the forward is the same math in the
    same order."""
    import dataclasses as _dc

    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  TransformerLM)
    from deepspeed_tpu.parallel import topology as topo
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    mesh = build_mesh(TopologyConfig(dp=2, fsdp=4))
    topo.set_global_mesh(mesh)  # conftest autouse fixture resets it
    base = TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
        max_seq_len=32, pos_emb="learned", norm="layernorm",
        activation="gelu", tie_embeddings=True, remat=False,
        dtype="float32")
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 64, (4, 17)).astype(np.int32))
    batch = {"input_ids": tokens, "labels": tokens}

    def run(od):
        cfg = _dc.replace(base, overlap_depth=od)
        m = TransformerLM(cfg)
        params = m.init(jax.random.PRNGKey(0))

        def loss_fn(p):
            return m.loss(p, batch)[0]

        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        return float(loss), grads

    l0, g0 = run(0)
    l2, g2 = run(2)
    assert l0 == l2  # forward: same math, same order — same bits
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# What a ZeRO-3 job on an fsdp mesh gets without a knob: the plain scan
# (the carried gathers are a named depth only: they lose on the chip) and,
# on a TPU, the gradient reduce-scatter out of its windowed form
# (Engine._resolve_gather_ahead, Engine._train_step_compiler_options)
# ---------------------------------------------------------------------------


def _cell_model(**overrides):
    """The four-chip cell's architecture at toy widths: RMSNorm, SwiGLU,
    rotary positions, grouped-query attention, untied head, bf16."""
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  TransformerLM)

    kw = dict(vocab_size=64, hidden_size=32, num_layers=3, num_heads=4,
              num_kv_heads=2, ffn_size=64, max_seq_len=16, pos_emb="rope",
              norm="rmsnorm", activation="swiglu", tie_embeddings=False,
              use_biases=False, dtype=jnp.bfloat16)
    kw.update(overrides)
    return TransformerLM(TransformerConfig(**kw))


def _cell_job(**extra):
    job = {"train_micro_batch_size_per_chip": 1,
           "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
           "zero_optimization": {"stage": 3},
           "bf16": {"enabled": True},
           "activation_checkpointing": {"policy": "nothing_saveable"},
           "steps_per_print": 10 ** 9}
    for k, v in extra.items():
        job[k] = dict(job.get(k, {}), **v) if isinstance(v, dict) else v
    return job


def _engine(job, topology=None, model=None):
    """The job on four of the CPU's devices, or on one (no mesh)."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    mesh = build_mesh(TopologyConfig(**(topology or {"dp": 1})),
                      devices=jax.devices()[:4 if topology else 1])
    engine, _, _, _ = dstpu.initialize(model=model or _cell_model(),
                                       config=job, mesh=mesh)
    return engine


def _tokens(engine, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 64, (engine.train_batch_size, 16)).astype(np.int32)


def _sub_jaxprs(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _sub_jaxprs(inner)


def _scans_carrying(engine, shape):
    """How many scans of the traced train step carry a value of
    ``shape`` (one gathered layer leaf riding ahead of the compute), and
    the step's jaxpr as text."""
    batches = engine._next_microbatches(
        iter([{"input_ids": _tokens(engine)}]), 1)
    closed = jax.make_jaxpr(engine._jit_train_step)(
        engine.params, engine.opt_state, engine.loss_scale_state,
        engine.step_count, batches)
    n = 0
    for eqn in _sub_jaxprs(closed.jaxpr):
        if eqn.primitive.name == "scan":
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            n += any(tuple(v.aval.shape) == shape
                     for v in eqn.invars[nc:nc + nk])
    return n, str(closed)


FSDP4 = {"dp": 1, "fsdp": 4}
# id, job extras, mesh, model overrides, depth, what the reason names
RESOLUTIONS = [
    ("stage3_fsdp4", {}, FSDP4, {}, 0, "no depth named"),
    ("named_zero", {"performance": {"overlap_depth": 0}}, FSDP4, {}, 0,
     "overlap_depth 0 named by the job"),
    ("named_two", {"performance": {"overlap_depth": 2}}, FSDP4, {}, 2,
     "overlap_depth 2 named by the job"),
    ("named_on_the_model", {}, FSDP4, {"overlap_depth": 1}, 1,
     "overlap_depth 1 named by the job"),
    ("stage2_named", {"zero_optimization": {"stage": 2},
                      "performance": {"overlap_depth": 1}}, FSDP4, {}, 1,
     "named by the job"),
    ("dots_saveable", {"activation_checkpointing":
                       {"policy": "dots_saveable"}}, FSDP4, {}, 0,
     "no depth named"),
    ("no_mesh", {}, None, {}, 0, "no fsdp axis"),
    ("no_mesh_named", {"performance": {"overlap_depth": 2}}, None, {}, 0,
     "no fsdp axis"),
]


@pytest.mark.parametrize("case", RESOLUTIONS, ids=[c[0] for c in RESOLUTIONS])
def test_gather_ahead_resolution(case):
    """What the engine says of the job's layer stack is what the traced
    step does: at a named depth the forward scan and the backward scan
    each carry the next layer's gathered leaves; with none named (the
    chip's reading: the carried gathers lose) and wherever the staged
    path cannot run, no scan carries a layer leaf. The gauge reads the
    depth and the event the reason, published when the step is traced."""
    from deepspeed_tpu.observability.hub import get_hub, reset_hub

    _, extra, topology, overrides, depth, names = case
    reset_hub()
    engine = _engine(_cell_job(**extra), topology, _cell_model(**overrides))
    hub, events = get_hub(), []
    hub.record_event = lambda kind, **f: events.append((kind, f))
    try:
        assert engine.layer_gather_ahead[0] == depth
        assert names in engine.layer_gather_ahead[1]
        # wq of one layer, gathered: [hidden, heads, head_dim]
        carried, _ = _scans_carrying(engine, (32, 4, 8))
        assert carried == (2 if depth else 0)
        gauges = hub.snapshot()["gauges"]
        assert gauges["train.layer_gather_ahead"] == depth
        assert gauges["train.reduce_scatter_windowed"] == 1.0   # the CPU's
        assert ("layer_gather_ahead",
                {"depth": depth, "reason": engine.layer_gather_ahead[1]}
                ) in events
    finally:
        engine.close()
        reset_hub()


@pytest.mark.parametrize("case", [
    ("pipeline", {"pipeline": {"microbatches": 2},
                  "performance": {"overlap_depth": 1}},
     {"pp": 2, "dp": 1, "fsdp": 2}, "pipeline axis"),
    ("host_offload", {"zero_optimization": {
        "offload_optimizer": {"device": "cpu"},
        "offload_param": {"device": "cpu"}}}, FSDP4,
     "parameter host offload"),
], ids=lambda c: c[0])
def test_gather_ahead_stands_aside(case):
    """The paths that fetch a layer's parameters their own way keep it,
    a named depth or not, and the gauge says 0 for them."""
    _, extra, topology, names = case
    engine = _engine(_cell_job(**extra), topology)
    try:
        assert engine.layer_gather_ahead[0] == 0
        assert names in engine.layer_gather_ahead[1]
    finally:
        engine.close()


def test_one_chip_step_is_the_step_without_the_resolution(monkeypatch):
    """No mesh: the engine leaves the model's config as it was handed
    in, passes the compiler nothing, and the train step it traces is,
    equation for equation, the one an engine without the resolution
    traces."""
    from deepspeed_tpu.runtime.engine import Engine

    model = _cell_model()
    before = model.config
    engine = _engine(_cell_job(), None, model)
    try:
        assert engine.module.config == before
        _, with_resolution = _scans_carrying(engine, (32, 4, 8))
    finally:
        engine.close()
    monkeypatch.setattr(Engine, "_resolve_gather_ahead",
                        lambda self, *a: (0, "as the parent"))
    parent = _engine(_cell_job(), None, _cell_model())
    try:
        _, without = _scans_carrying(parent, (32, 4, 8))
    finally:
        parent.close()
    assert with_resolution == without
    assert "optimization_barrier" not in with_resolution
    assert "custom_vjp" not in with_resolution


RS_UNWINDOWED = {"xla_tpu_enable_windowed_einsum_for_reduce_scatter": False}
# id, stage, mesh, platform, the options
COMPILER_OPTIONS = [
    ("zero3_fsdp4_on_a_tpu", 3, {"dp": 1, "fsdp": 4, "tp": 1}, "tpu",
     RS_UNWINDOWED),
    ("zero3_dp2_fsdp4_on_a_tpu", 3, {"dp": 2, "fsdp": 4}, "tpu",
     RS_UNWINDOWED),
    ("on_the_cpu", 3, {"dp": 1, "fsdp": 4}, "cpu", {}),
    ("stage2", 2, {"dp": 1, "fsdp": 4}, "tpu", {}),
    ("stage1", 1, {"dp": 1, "fsdp": 4}, "tpu", {}),
    ("tensor_parallel_axis", 3, {"fsdp": 2, "tp": 2}, "tpu", {}),
    ("sequence_parallel_axis", 3, {"fsdp": 2, "sp": 2}, "tpu", {}),
    ("pipeline_axis", 3, {"pp": 2, "fsdp": 2}, "tpu", {}),
    ("no_fsdp_axis", 3, {"dp": 4, "fsdp": 1}, "tpu", {}),
    ("one_chip", 3, {"dp": 1, "fsdp": 1}, "tpu", {}),
]


@pytest.mark.parametrize("case", COMPILER_OPTIONS,
                         ids=[c[0] for c in COMPILER_OPTIONS])
def test_train_step_compiler_options_follow_the_job(case):
    """ZeRO-3 over data axes with fsdp among them, on a TPU: the
    gradient reduce-scatter leaves the windowed form. Any other stage,
    mesh or backend compiles as before."""
    from deepspeed_tpu.runtime.engine import zero3_compiler_options

    _, stage, shape, platform, want = case
    assert zero3_compiler_options(stage, shape, platform) == want


@pytest.mark.parametrize("options,windowed", [({}, 1.0),
                                              (RS_UNWINDOWED, 0.0)],
                         ids=["compiler_default", "taken_off"])
def test_layer_schedule_says_what_the_reduce_scatter_is(options, windowed):
    """``train.reduce_scatter_windowed`` reads 0 where the engine took
    the gradient reduce-scatter out of the windowed form, with the
    options as an event; 1 and no event where the compiler's default
    stands."""
    from deepspeed_tpu.observability.hub import get_hub, reset_hub
    from deepspeed_tpu.runtime.param_stream import export_layer_schedule

    reset_hub()
    hub, events = get_hub(), []
    hub.record_event = lambda kind, **f: events.append((kind, f))
    try:
        export_layer_schedule(0, "no depth named", options)
        assert hub.snapshot()["gauges"][
            "train.reduce_scatter_windowed"] == windowed
        assert [f for k, f in events
                if k == "train_step_compiler_options"] == (
                    [options] if options else [])
    finally:
        reset_hub()


def test_a_compiler_that_refuses_the_options_gets_none(monkeypatch):
    """The CPU's compiler knows no ``xla_tpu_`` option: asked for the
    TPU's, the engine finds that out with a program of nothing and
    compiles the step with the compiler's defaults; the cell's job as it
    is (on the CPU) passes none in the first place."""
    from deepspeed_tpu.runtime import engine as engine_mod

    engine = _engine(_cell_job(), FSDP4)
    try:
        assert engine._train_step_compiler_options() == {}
        monkeypatch.setattr(engine_mod, "zero3_compiler_options",
                            lambda *a: dict(RS_UNWINDOWED))
        assert engine._train_step_compiler_options() == {}
    finally:
        engine.close()


PARITY = [
    ("cell", {}),
    ("cell_float32", {"dtype": jnp.float32}),
    ("cell_multi_head", {"num_kv_heads": 4}),
]


@pytest.mark.parametrize("case", PARITY, ids=[c[0] for c in PARITY])
def test_fsdp_stage3_named_depth_parity(case):
    """The cell's own settings through ``dstpu.initialize``: the job as
    the cell writes it (no ``performance`` block: the plain
    rematerialised scan) against the same job at a named depth of one
    (the carried gathers, whose custom VJP recomputes a layer from its
    saved input as ``nothing_saveable`` does): the loss is the same bits,
    the gradients agree to the compute dtype's rounding."""
    _, overrides = case
    bf16 = overrides.get("dtype", jnp.bfloat16) == jnp.bfloat16
    job = _cell_job(bf16={"enabled": bf16})

    def run(extra):
        engine = _engine(dict(job, **extra), FSDP4, _cell_model(**overrides))
        try:
            assert engine.layer_gather_ahead[0] == (1 if extra else 0)
            batch = engine.shard_batch({"input_ids": _tokens(engine)})
            loss, grads = engine._jit_fwd_bwd(
                engine.params, batch, jnp.asarray(1.0, jnp.float32))
            return float(loss), jax.tree.map(np.asarray, grads)
        finally:
            engine.close()

    loss_plain, g_plain = run({})
    loss_staged, g_staged = run({"performance": {"overlap_depth": 1}})
    assert loss_staged == loss_plain
    tol = dict(rtol=2e-2, atol=2e-3) if bf16 else dict(rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree.leaves(g_staged), jax.tree.leaves(g_plain)):
        np.testing.assert_allclose(a, b, **tol)

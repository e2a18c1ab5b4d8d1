"""Activation checkpointing tests (reference analog:
tests/unit/runtime/activation_checkpointing/test_activation_checkpointing.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.runtime import activation_checkpointing as ac


@pytest.fixture(autouse=True)
def _reset_config():
    ac._GLOBAL_CONFIG.clear()
    yield
    ac._GLOBAL_CONFIG.clear()


def f(x, w):
    return jnp.tanh(x @ w) @ w.T


def test_checkpoint_matches_plain(devices):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    for policy in ("nothing_saveable", "dots_saveable", "none"):
        wrapped = ac.checkpoint_wrapper(f, policy=policy)
        out = wrapped(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(f(x, w)),
                                   rtol=1e-6)
        # gradients identical too (remat is semantics-preserving)
        g1 = jax.grad(lambda x: wrapped(x, w).sum())(x)
        g2 = jax.grad(lambda x: f(x, w).sum())(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-5)


def test_direct_call_form(devices):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    out = ac.checkpoint(f, x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f(x, w)),
                               rtol=1e-6)


def test_configure_from_config_model(devices):
    from deepspeed_tpu.config.config import ActivationCheckpointingConfig

    cfg = ActivationCheckpointingConfig(partition_activations=True,
                                        policy="dots_saveable")
    state = ac.configure(cfg)
    assert state["partition_activations"] is True
    assert state["policy"] == "dots_saveable"
    assert ac.is_configured()


def test_unknown_policy_raises():
    with pytest.raises(ValueError, match="unknown activation"):
        ac.resolve_policy("bogus")


def test_named_save_policies_resolve_and_train():
    # named policies map to save_only_these_names over the
    # checkpoint_name annotations in models/transformer.py _layer
    for name in ("save_qkv_proj", "save_attn_out", "save_qkv_attn_out",
                 "save_attn_mlp"):
        assert ac.resolve_policy(name) is not None

    from deepspeed_tpu.models.zoo import get_model

    model = get_model("tiny", remat=True, remat_policy="save_qkv_attn_out")
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)

    def loss(p):
        out = model.loss(p, {"input_ids": tokens})
        return out[0] if isinstance(out, tuple) else out

    val, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(val))
    # grads flow to attention weights despite the named saves
    leaf = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in leaf)


def test_cpu_checkpointing_selects_offload():
    ac.configure(cpu_checkpointing=True)
    p = ac.resolve_policy()
    assert p is not None and p != "everything"


def test_partition_activations_preserves_math(devices):
    from deepspeed_tpu.parallel import topology as topo

    mesh = topo.build_mesh(topo.TopologyConfig(tp=4, dp=-1))
    topo.set_global_mesh(mesh)
    ac.configure(partition_activations=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    wrapped = ac.checkpoint_wrapper(f)
    with mesh:
        out = jax.jit(wrapped)(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f(x, w)),
                               rtol=1e-5, atol=1e-6)


def test_remat_reduces_saved_memory(devices):
    """Compiled peak memory with remat <= without (the point of the
    subsystem)."""
    from deepspeed_tpu.profiling import profile_compiled

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256))

    def stack(fn):
        def loss(x, w):
            for _ in range(8):
                x = fn(x, w)
            return (x ** 2).sum()
        return loss

    plain = profile_compiled(jax.grad(stack(f)), x, w)
    remat = profile_compiled(
        jax.grad(stack(ac.checkpoint_wrapper(f, policy="nothing_saveable"))),
        x, w)
    if plain["peak_bytes"] and remat["peak_bytes"]:
        assert remat["peak_bytes"] <= plain["peak_bytes"] * 1.05


# -- names a model keeps under every policy (checkpoint_wrapper(kept_names=)) --

def _wrapper_before(function, policy=None, **_):
    """``checkpoint_wrapper`` as it stood before it took ``kept_names``."""
    resolved = ac.resolve_policy(policy)
    if resolved == "everything":
        return function
    if resolved is None:
        return jax.checkpoint(function)
    return jax.checkpoint(function, policy=resolved)


@pytest.mark.parametrize("policy", ["nothing_saveable", "save_attn_mlp",
                                    "none"])
def test_without_names_a_dense_layer_lowers_as_before(policy, monkeypatch):
    """A model that keeps no names (``models/transformer.py``) gets the
    program it got: the gradient of the tiny dense stack lowers to the same
    text through ``checkpoint_wrapper`` and through what it was."""
    from deepspeed_tpu.models.zoo import get_model

    tokens = jnp.zeros((2, 17), jnp.int32)

    def lowered():
        model = get_model("tiny", remat=True, remat_policy=policy)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        return jax.jit(jax.grad(
            lambda p: model.loss(p, {"input_ids": tokens})[0])).lower(
                params).as_text()

    now = lowered()
    monkeypatch.setattr(ac, "checkpoint_wrapper", _wrapper_before)
    assert now == lowered()


def _tagged(x, w):
    from jax.ad_checkpoint import checkpoint_name

    h = checkpoint_name(jnp.tanh(x @ w), "attn_out")
    order = checkpoint_name(jnp.argsort(h[:, 0]), "order")
    return checkpoint_name(jnp.sin(h[order]), "other") @ w.T


@pytest.mark.parametrize("policy,floats", [
    ("nothing_saveable", 0),    # the names alone
    ("save_attn_out", 1),       # a policy of names: the union
    ("offload_dots_host", 1),   # the product goes to the host, the name stays
    ("none", None)])            # no checkpoint: nothing to keep a name across
def test_kept_names_cross_the_checkpoint_under_every_policy(policy, floats):
    """``kept_names`` are saved on the device beside whatever the policy
    saves, and nothing else changes: the sort whose result is named runs
    once in the gradient's program, the other names stay the policy's."""
    from jax._src.ad_checkpoint import saved_residuals

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    kept = ac.checkpoint_wrapper(_tagged, policy=policy,
                                 kept_names=("order",))
    if floats is None:
        assert kept is _tagged
        return
    saved = [(a, why) for a, why in saved_residuals(kept, x, w)
             if not why.startswith("from the argument")]
    ints = [a for a, why in saved if "named 'order'" in why]
    assert len(ints) == 1 and ints[0].dtype == jnp.int32 \
        and "host" not in str(ints[0])
    others = [a for a, why in saved if "named 'order'" not in why]
    assert [a.shape for a in others] == [(4, 8)] * floats
    assert ["host" in str(a) for a in others] \
        == [policy == "offload_dots_host"] * floats

    def grad(fn):
        return jax.grad(lambda x, w: fn(x, w).sum(), argnums=(0, 1))

    assert str(jax.make_jaxpr(grad(kept))(x, w)).count(" sort[") == 1
    plain = ac.checkpoint_wrapper(_tagged, policy=policy)
    assert str(jax.make_jaxpr(grad(plain))(x, w)).count(" sort[") == 2
    if policy != "offload_dots_host":   # (the CPU has no pinned host to run)
        for g, want in zip(grad(kept)(x, w), grad(_tagged)(x, w)):
            np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)

"""utils/memspace.py: the single policy every memory-space placement
goes through. The installed CPU backend (jax 0.9.0) exposes ``device``,
``pinned_host`` and ``unpinned_host`` like a TPU does, so placements are
real there; on a backend with one space every placement must degrade to
identity — preserving the array's existing placement AND exact numerics
(``single_space`` forces that branch)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.utils import memspace


def test_backend_memory_kinds_nonempty():
    kinds = memspace.backend_memory_kinds()
    assert isinstance(kinds, frozenset)
    assert kinds  # CPU sim exposes at least unpinned_host


@pytest.fixture()
def single_space(monkeypatch):
    """A backend whose devices expose no pinned_host."""
    monkeypatch.setattr(memspace, "memories_supported", lambda: False)


def test_cpu_sim_has_host_and_device_spaces():
    assert {"device", "pinned_host"} <= memspace.backend_memory_kinds()
    assert memspace.memories_supported() is True
    assert memspace.space("device") is jax.memory.Space.Device
    assert memspace.space("pinned_host") is jax.memory.Space.Host


def test_single_space_backend_has_no_targets(single_space):
    assert memspace.space("device") is None
    assert memspace.space("pinned_host") is None


def test_space_rejects_unknown_kind():
    with pytest.raises(AssertionError):
        memspace.space("unpinned_host")


def test_put_places_and_preserves_numerics():
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    for kind in ("device", "pinned_host"):
        y = memspace.put(x, kind)  # a real device_put on this backend
        assert memspace.memory_kind_of(y) in memspace.backend_memory_kinds()
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    host = jax.device_put(x, memspace.with_memory_kind(x.sharding,
                                                       "pinned_host"))
    assert memspace.is_on_host(host)
    np.testing.assert_array_equal(np.asarray(host), np.asarray(x))


def test_put_degrades_to_identity_preserving_numerics(single_space):
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    for kind in ("device", "pinned_host"):
        y = memspace.put(x, kind)
        assert y is x  # identity, not a copy — placement preserved
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_put_tree_maps_every_leaf(single_space):
    tree = {"a": jnp.ones((2, 2)), "b": [jnp.zeros(3), jnp.arange(4)]}
    out = memspace.put_tree(tree, "pinned_host")
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert b is a


def test_put_safe_inside_jit():
    # the no-op branch resolves at trace time; jit must not see a
    # device_put with a None target
    @jax.jit
    def f(x):
        return memspace.put(x, "pinned_host") * 2.0

    np.testing.assert_allclose(f(jnp.ones(4)), 2.0 * np.ones(4))


def test_with_memory_kind_places_and_degrades(monkeypatch):
    mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(8),
                             ("fsdp",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    assert memspace.with_memory_kind(sh, "pinned_host").memory_kind \
        == "pinned_host"
    assert memspace.with_memory_kind(None, "pinned_host") is None
    monkeypatch.setattr(memspace, "memories_supported", lambda: False)
    assert memspace.with_memory_kind(sh, "pinned_host") is sh


def test_with_memory_kind_swallows_backend_rejection(monkeypatch):
    # force the supported path so the ValueError-degradation branch runs
    monkeypatch.setattr(memspace, "memories_supported", lambda: True)

    class Rejecting:
        def with_memory_kind(self, kind):
            raise ValueError("no such memory space")

    sh = Rejecting()
    assert memspace.with_memory_kind(sh, "pinned_host") is sh

    class Accepting:
        def with_memory_kind(self, kind):
            return ("placed", kind)

    assert memspace.with_memory_kind(Accepting(), "pinned_host") == (
        "placed", "pinned_host")


def test_is_on_host_false_for_device_arrays():
    x = jnp.ones(3)
    assert memspace.is_on_host(x) is False
    assert memspace.memory_kind_of(object()) is None

"""Backend-portable memory-space placement.

The offload tier talks to XLA memory spaces through two jax APIs:

  * ``jax.memory.Space.Device`` / ``.Host`` as ``device_put`` targets;
  * ``Sharding.with_memory_kind("pinned_host" | "device")`` — raises on
    backends whose devices expose no such space; there every placement
    lands in the same memory, so degrading to the array's existing
    placement preserves the exact numerics.

Every memory-space placement in the tree goes through this module so
the TPU path and the CPU test path share one policy instead of
per-call-site try/excepts.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax

_PLACEABLE = ("device", "pinned_host")


@functools.lru_cache(maxsize=None)
def backend_memory_kinds() -> frozenset:
    """Memory kinds addressable by device 0 (initializes the backend)."""
    try:
        return frozenset(
            m.kind for m in jax.devices()[0].addressable_memories())
    except Exception:
        return frozenset()


def memories_supported() -> bool:
    """True when the backend has distinct device/host memory spaces."""
    return "pinned_host" in backend_memory_kinds()


def space(kind: str) -> Optional[Any]:
    """A ``jax.device_put`` target for ``kind`` ('device'/'pinned_host'),
    or None when the backend has no such space (caller must no-op)."""
    assert kind in _PLACEABLE, kind
    if not memories_supported():
        return None
    return (jax.memory.Space.Device if kind == "device"
            else jax.memory.Space.Host)


def put(a: Any, kind: str) -> Any:
    """``device_put`` into the given memory space; identity when the
    backend has only one space. Safe inside jit (the no-op branch is
    resolved at trace time)."""
    tgt = space(kind)
    return a if tgt is None else jax.device_put(a, tgt)


def put_tree(tree: Any, kind: str) -> Any:
    return jax.tree.map(lambda a: put(a, kind), tree)


def with_memory_kind(sharding: Any, kind: str) -> Any:
    """``sharding.with_memory_kind(kind)`` degrading to identity when the
    backend lacks the space (or the sharding has no memory-kind API)."""
    if sharding is None or not memories_supported():
        return sharding
    try:
        return sharding.with_memory_kind(kind)
    except (ValueError, AttributeError):
        return sharding


def memory_kind_of(a: Any) -> Optional[str]:
    """The array's memory kind, or None when unknowable."""
    return getattr(getattr(a, "sharding", None), "memory_kind", None)


def is_on_host(a: Any) -> bool:
    """True when ``a`` demonstrably lives in the pinned-host space. On
    single-space backends this is always False — callers branching on it
    treat device placement as the degenerate truth."""
    return memory_kind_of(a) == "pinned_host"

"""Init-time device/dtype scoping.

Reference: ``OnDevice`` (deepspeed/utils/init_on_device.py) — a context
manager that builds models directly on a target device ("meta" for
shape-only instantiation, used to stand up trillion-param models without
materializing weights).

TPU re-design: JAX params are explicit trees, so the context simply
scopes *how* ``model.init`` materializes them:

  * ``device="meta"``  → ``jax.eval_shape`` abstract tree (no memory) —
    the ``zero.Init``-adjacent path; engines later do shard-aware init.
  * ``device="cpu"``   → host-side arrays (init big models in host RAM).
  * ``device="device"``→ default backend placement (the normal path).

Model constructors cooperate via ``OnDevice.current()`` (TransformerLM
checks it inside ``init``); any other init function can be wrapped with
``OnDevice.apply(fn, *args)``.
"""

from __future__ import annotations

import contextlib
from typing import Any, Optional

import jax


def _inside_trace() -> bool:
    """True while a jit/scan/grad trace is being staged."""
    return jax.core.get_opaque_trace_state() != _EAGER_TRACE_STATE


_EAGER_TRACE_STATE = jax.core.get_opaque_trace_state()


class OnDevice:
    """``with OnDevice(dtype=jnp.bfloat16, device="meta"): model.init(...)``"""

    _stack: list = []

    def __init__(self, dtype: Optional[Any] = None, device: str = "device",
                 enabled: bool = True):
        if device not in ("meta", "cpu", "device"):
            raise ValueError(f"device must be meta|cpu|device, got {device!r}")
        self.dtype = dtype
        self.device = device
        self.enabled = enabled
        self._cm = None

    @classmethod
    def current(cls) -> Optional["OnDevice"]:
        return cls._stack[-1] if cls._stack else None

    def __enter__(self):
        OnDevice._stack.append(self)
        if self.enabled and self.device == "cpu":
            self._cm = jax.default_device(jax.devices("cpu")[0])
            self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        OnDevice._stack.pop()
        if self._cm is not None:
            self._cm.__exit__(*exc)
            self._cm = None
        return False

    @classmethod
    def apply(cls, init_fn, *args, **kwargs):
        """Run ``init_fn`` under the active context: abstract under
        "meta", eager otherwise; float leaves cast to the context dtype.

        Inside a jit trace the context is ignored: engines jit their init
        (runtime/engine.py), and an abstract/host-pinned tree cannot be a
        traced output — the context governs only eager construction.
        """
        ctx = cls.current()
        tracing = _inside_trace()
        if ctx is None or not ctx.enabled or tracing:
            return init_fn(*args, **kwargs)

        def cast(tree):
            if ctx.dtype is None:
                return tree
            import jax.numpy as jnp

            def one(x):
                if jnp.issubdtype(x.dtype, jnp.floating):
                    if isinstance(x, jax.ShapeDtypeStruct):
                        return jax.ShapeDtypeStruct(x.shape, ctx.dtype)
                    return x.astype(ctx.dtype)
                return x

            return jax.tree.map(one, tree)

        if ctx.device == "meta":
            return cast(jax.eval_shape(lambda: init_fn(*args, **kwargs)))
        return cast(init_fn(*args, **kwargs))


@contextlib.contextmanager
def on_device(dtype=None, device: str = "device", enabled: bool = True):
    """Functional alias of OnDevice (reference exports both styles)."""
    with OnDevice(dtype=dtype, device=device, enabled=enabled) as ctx:
        yield ctx

"""Count XLA compilations, by the benchmark's own listener on JAX's
monitoring events: set-up may compile, the measured window may not."""

from __future__ import annotations

_COUNT = {"n": 0}
SEEN = []        # (seconds, what jax said of it), newest last
_INSTALLED = False
_EVENT = "/jax/core/compile/backend_compile_duration"


def install() -> None:
    global _INSTALLED
    if _INSTALLED:
        return
    import jax.monitoring

    def on_duration(event, duration, **kw):
        if event == _EVENT:
            _COUNT["n"] += 1
            SEEN.append((round(duration, 3), str(kw)[:200]))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    _INSTALLED = True


def count() -> int:
    return _COUNT["n"]

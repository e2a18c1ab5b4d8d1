"""Host spans and names on the profiler's clock.

Reference: ``instrument_w_nvtx`` (deepspeed/utils/nvtx.py:25) wraps hot
functions in NVTX ranges for nsight timelines.

TPU: ``jax.profiler.TraceAnnotation`` writes a host span into the
profiler's own trace (the ``.xplane.pb`` host plane, the device trace's
clock), and ``jax.named_scope`` names a region of the compiled HLO. The
spans are unconditional: a profiler session turns them on, and with none
a span costs an atomic load and a Python object (docs/observability.md,
"Profiler spans and names", lists the vocabulary).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax

SPAN_PREFIX = "dstpu/"


def span(name: str, **ids):
    """``with span("dispatch", program="decode"):`` — the host span
    ``dstpu/dispatch``; ``ids`` (numbers or short strings) become the
    event's stats, which is how a span names its step or its request."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **ids)


def step_span(name: str, step_num: int, **ids):
    """The step form: xprof groups device work by ``step_num``."""
    return jax.profiler.StepTraceAnnotation(SPAN_PREFIX + name,
                                            step_num=step_num, **ids)


def named(fn: Callable, name: str) -> Callable:
    """``jax.jit(named(fn, "dstpu_train_step"))``: the program's name in
    the HLO and on the device trace's module line (``jit_<name>``). A
    function keeps its local name in the source and gets the stable one
    here; metadata only, the compiled code is the same."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def instrument_w_profiler(fn: Callable = None, name: str = None) -> Callable:
    """Decorator: run ``fn`` inside a host span + named_scope of one
    name (reference instrument_w_nvtx)."""
    if fn is None:
        return functools.partial(instrument_w_profiler, name=name)
    label = name or getattr(fn, "__qualname__", getattr(fn, "__name__", "fn"))

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with span(label), jax.named_scope(label):
            return fn(*args, **kwargs)

    return wrapped

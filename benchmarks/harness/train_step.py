"""Shared by the readers of what ISSUE 43 added to the training step: the
model's counters of each step (``StepTrace.extras`` in the program's hub),
and leaf device time under a scope or in named kernels inside the train
step's executions. Every function returns None where the program has no
such counter, scope or kernel (a parent commit, another architecture)."""

import re

from benchmarks.harness import program_trace as P
from benchmarks.harness import trace as T


def counted_steps(result=None, which: str = "timed",
                  key: str = "moe_token_layers"):
    """The counters of each step the hub remembers that counted ``key``,
    one dict a step in order; None where none did. With a runner's
    ``result`` the steps are the run's own: ``"traced"``, the steps under
    the profiler (what a device trace's times belong to), or ``"timed"``,
    those and the window's (the run's last ``attempted`` steps, the traced
    ones first: ``runners/train.py``); without it, every step."""
    try:
        from deepspeed_tpu.observability.hub import peek_hub
        rows = [dict(s.extras) for s in peek_hub().step_history]
    except Exception:
        return None
    if result is not None:
        timed = int(result.get("attempted", 0))
        if not 0 < timed <= len(rows):
            return None
        rows = rows[-timed:]
        if which == "traced":
            rows = rows[:int(result["facts"].get("traced_steps", 0))]
    return [r for r in rows if r.get(key)] or None


def counted(result=None, which: str = "timed"):
    """``{counter: mean per step}`` over :func:`counted_steps`, and how
    many they were; None where there are none."""
    rows = counted_steps(result, which)
    if rows is None:
        return None
    names = set().union(*rows)
    return {n: sum(r.get(n, 0) for r in rows) / len(rows) for n in names}, \
        len(rows)


_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def scope_elements(op_name: str):
    """The scope path's elements with JAX's transform wrappers taken off:
    a forward operation of ``named_scope("moe")`` under ``value_and_grad``
    reads ``.../jvp(moe)/moe_route/...``, its transpose
    ``.../transpose(jvp(..))/.../checkpoint/moe/...`` and what a checkpoint
    recomputes ``.../checkpoint/rematted_computation/moe/...``."""
    out = []
    for el in op_name.split("/"):
        while _WRAPPED.match(el):
            el = _WRAPPED.match(el).group(1)
        out.append(el)
    return out


def scope_ms_per_step(ctx, result, scope: str):
    """Device ms a train step spends under ``scope``, ``{"ms", "steps",
    "parts_ms": forward / recomputation / backward by JAX's own marks}``."""
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    names, runs = pt.scopes.get(P.TRAIN_STEP), pt.executions(P.TRAIN_STEP)
    if not names or not runs:
        return None

    def region(op_name):
        if not op_name:
            return P.OTHER
        els = scope_elements(op_name)
        if scope not in els:
            return P.OTHER
        if "rematted_computation" in els:
            return "recompute"
        return "bwd" if "transpose(" in op_name else "fwd"

    if not any(region(n) != P.OTHER for n in names.values()):
        return None
    ops = pt.trace.device_ops
    by = P.seconds_by_region(ops[min(ops)], names, runs, region)
    parts = {k: 1e3 * by.get(k, 0.0) / len(runs)
             for k in ("fwd", "recompute", "bwd")}
    return {"ms": sum(parts.values()), "steps": len(runs), "parts_ms": parts}


def kernel_seconds_in_step(pt, classify, classes):
    """(leaf seconds, events by class) of the kernels ``classify`` puts in
    ``classes``, inside the train step's executions on the first chip."""
    runs = pt.executions(P.TRAIN_STEP)
    ops = pt.trace.device_ops[min(pt.trace.device_ops)]
    spent, events, j = 0.0, {c: 0 for c in classes}, 0
    for name, start, dur in T.leaves(ops):
        while j < len(runs) and runs[j][1] <= start:
            j += 1
        c = classify(name)
        if j < len(runs) and runs[j][0] <= start and c in events:
            spent, events[c] = spent + dur, events[c] + 1
    return spent, events, len(runs)

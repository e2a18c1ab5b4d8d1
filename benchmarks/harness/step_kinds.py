"""A serve step by its kind, as the profile and the counters have it.

The engine books each ``serve_step`` under one kind
(``engine_v2.STEP_KINDS``: ``mixed``, ``prefill``, ``lone``, ``burst``,
``empty``), decided by ``engine_v2.step_kind`` from the ``(program,
token_steps)`` of the program calls whose results the step read, and counts
a kind's steps, wall seconds, seconds blocked on the device and tokens in
``stats`` (``steps_<kind>``, ``step_s_<kind>``, ``step_wait_s_<kind>``,
``step_tokens_<kind>``). The readers here put a profile's
``dstpu/serve_step`` spans into the same kinds by the same function, from
the ``dstpu/dispatch`` spans that name the step (``step_id``): a step's own,
but for a burst issued ahead (``ahead=1``), which the engine reads, and
counts, in the step after the one that issued it. So the profile and the
counters cannot disagree on what a mixed step is.

Why the kinds: ``tpot_p90_ms`` is a gap between two tokens of one request,
and in a closed loop whose window holds admissions the 90th percentile gap
is the single token of a step that also read a new prompt (PERF.md section
7, "Open since PR 53" (00)): a ``mixed`` step, two programs with the host's
schedule, two batch builds and a fetch one after the other. A mean over all
steps does not show it.

A program without the function or the counters (the parent of the PR that
added them) yields nothing: every reader returns None.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional

from benchmarks.harness import program_trace as P
from benchmarks.harness import trace as T

try:
    from deepspeed_tpu.inference.engine_v2 import STEP_KINDS, step_kind
except ImportError:         # an engine that names no kinds: nothing to read
    STEP_KINDS, step_kind = (), None

COUNTED = ("steps", "step_s", "step_wait_s", "step_tokens")


def calls_by_step(spans: List[P.Span]) -> Dict[int, List]:
    """``{step_id: [(program, token_steps), ...]}`` of the calls whose
    results each step read, from every ``dispatch`` span of the profile
    (not of the slice alone: the call a slice's first step collects was
    issued before it)."""
    out: Dict[int, List] = {}
    for s in spans:
        if s.name == "dispatch" and "step_id" in s.ids and "program" in s.ids:
            reads = s.ids["step_id"] + (1 if s.ids.get("ahead") else 0)
            out.setdefault(reads, []).append(
                (s.ids["program"], s.ids.get("token_steps", 1)))
    return out


def steps_by_kind(pt: P.ProgramTrace) -> Optional[Dict[str, List[P.Span]]]:
    """The traced slice's ``serve_step`` spans, by kind (every kind has a
    list); None where the program names no kinds or the slice no step."""
    steps = [s for s in pt.named("serve_step") if "step_id" in s.ids]
    if step_kind is None or not steps:
        return None
    calls = calls_by_step(pt.spans)
    out: Dict[str, List[P.Span]] = {k: [] for k in STEP_KINDS}
    for s in steps:
        out[step_kind(calls.get(s.ids["step_id"], ()))].append(s)
    return out


def durations(by_kind: Dict[str, List[P.Span]]) -> Dict[str, Dict]:
    """Count and median milliseconds of each kind that has a step."""
    return {k: {"steps": len(v),
                "median_ms": 1e3 * statistics.median(s.dur_s for s in v)}
            for k, v in by_kind.items() if v}


def idle_by_phase(pt: P.ProgramTrace, by_kind: Dict[str, List[P.Span]]
                  ) -> Optional[Dict[str, Dict]]:
    """Device-idle milliseconds a step, by kind: ``ms_per_step`` and
    ``by_phase`` (the innermost span that was open, ``(self)`` the step's
    own code between its phases: ``program_trace.exposed_by_child``). First
    chip; None where the run has no device trace."""
    ops = pt.trace.device_ops
    if not ops:
        return None
    busy = T.merge((s, s + d) for _, s, d in ops[min(ops)])
    out = {}
    for kind, steps in by_kind.items():
        by: Dict[str, float] = {}
        for step in steps:
            for k, v in P.exposed_by_child(busy, step,
                                           pt.children(step)).items():
                by[k] = by.get(k, 0.0) + v
        if steps:
            out[kind] = {
                "steps": len(steps),
                "ms_per_step": 1e3 * sum(by.values()) / len(steps),
                "by_phase": {k: 1e3 * v / len(steps) for k, v in sorted(
                    by.items(), key=lambda kv: -kv[1])}}
    return out


def counted(result) -> Optional[Dict[str, Dict]]:
    """The window's steps by kind, from the engine's counters: for each
    kind its ``steps``, ``step_tokens``, and the mean ``step_ms`` and
    ``wait_ms`` of one; None where the engine does not count them."""
    c = result.get("counters", {}).get("engine", {})
    keys = [f"{what}_{k}" for what in COUNTED for k in STEP_KINDS]
    if not keys or any(k not in c for k in keys):
        return None
    return {k: {"steps": c[f"steps_{k}"], "tokens": c[f"step_tokens_{k}"],
                "step_ms": 1e3 * c[f"step_s_{k}"] / c[f"steps_{k}"],
                "wait_ms": 1e3 * c[f"step_wait_s_{k}"] / c[f"steps_{k}"]}
            for k in STEP_KINDS if c[f"steps_{k}"]}

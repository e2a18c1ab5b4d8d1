"""What the *program* put into a run's profile: its ``dstpu/`` host spans
with their ids, its device programs by name, and the scope of every
device operation.

``harness/trace.py`` reads the device's operations and the benchmark's own
``bench/`` spans; this module re-opens the same ``.xplane.pb`` (under
``ctx.trace_dir``) for the rest and reuses that module's interval
arithmetic. Where each name lives in a TPU profile (looked at by hand,
PERF.md section 3):

* the program: the ``XLA Modules`` line, ``jit_dstpu_train_step(<id>)``;
* the kernel: the event's own name, the HLO instruction
  (``%flash_fwd.17 = ... custom-call(...)``);
* the scope path (``jax.named_scope``, and JAX's own ``jvp(..)`` /
  ``transpose(jvp(..))`` / ``rematted_computation`` marks): *not* on the
  device events. The profile stores each module's HLO proto in the
  ``/host:metadata`` plane, and there every instruction carries its
  ``op_name``; ``jax.profiler.ProfileData`` does not hand out event
  metadata, so the few protobuf fields needed are read from the file's
  wire format directly (nothing but the standard library).

A profile of a program without these names (the parent of the PR that
added them) yields no spans, no named programs and no scopes; every
reader then finds nothing and returns None.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from benchmarks.harness import trace as T

SPAN_PREFIX = "dstpu/"
TRAIN_STEP, SERVE_GATHER = "jit_dstpu_train_step", "jit_dstpu_serve_gather"
SERVE_PREFILL = "jit_dstpu_serve_prefill"


class Span(NamedTuple):
    name: str            # without the prefix: "serve_step", "dispatch", ...
    start_s: float
    dur_s: float
    ids: Dict            # step_num / step_id / uid / program / seqs / tokens
    thread: str

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s


# --------------------------------------------------------------------------
# protobuf wire format: just enough for XSpace -> HloProto -> op_name
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def fields(buf) -> Iterable[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for bytes, strings and sub-messages."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield num, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


# field numbers: tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto,
# xla/xla_data.proto
_XSPACE_PLANES, _XPLANE_NAME, _XPLANE_EVENT_METADATA = 1, 2, 4
_MAP_VALUE, _XEVENTMETADATA_NAME, _XEVENTMETADATA_STATS = 2, 2, 5
_XSTAT_BYTES = 6
_HLOPROTO_MODULE, _HLOMODULE_COMPUTATIONS, _HLOCOMPUTATION_INSTRUCTIONS = 1, 3, 2
_HLOINSTRUCTION_NAME, _HLOINSTRUCTION_METADATA, _OPMETADATA_OP_NAME = 1, 7, 2
METADATA_PLANE = "/host:metadata"


def module_of(event_name: str) -> str:
    """``jit_dstpu_train_step(13559369136983055942)`` -> the name alone."""
    return event_name.split("(", 1)[0]


def instruction_of(event_name: str) -> str:
    """A device event is named by its whole HLO instruction: ``%fusion.3 =
    bf16[..] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for num, module in fields(hlo_proto):
        if num != _HLOPROTO_MODULE:
            continue
        for num2, comp in fields(module):
            if num2 != _HLOMODULE_COMPUTATIONS:
                continue
            for num3, ins in fields(comp):
                if num3 != _HLOCOMPUTATION_INSTRUCTIONS:
                    continue
                name = op_name = None
                for num4, v in fields(ins):
                    if num4 == _HLOINSTRUCTION_NAME:
                        name = _text(v)
                    elif num4 == _HLOINSTRUCTION_METADATA:
                        for num5, w in fields(v):
                            if num5 == _OPMETADATA_OP_NAME:
                                op_name = _text(w)
                if name and op_name:
                    out[name] = op_name
    return out


def read_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: op_name}}`` from the HLO protos the
    profile stores in its metadata plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in fields(space):
        if num != _XSPACE_PLANES:
            continue
        name, entries = None, []
        for num2, v in fields(plane):
            if num2 == _XPLANE_NAME:
                name = _text(v)
            elif num2 == _XPLANE_EVENT_METADATA:
                entries.append(v)
        if name != METADATA_PLANE:
            continue
        for entry in entries:
            for num3, meta in fields(entry):
                if num3 != _MAP_VALUE:
                    continue
                program, protos = None, []
                for num4, v in fields(meta):
                    if num4 == _XEVENTMETADATA_NAME:
                        program = module_of(_text(v))
                    elif num4 == _XEVENTMETADATA_STATS:
                        protos += [w for num5, w in fields(v)
                                   if num5 == _XSTAT_BYTES]
                for proto in protos:
                    out.setdefault(program, {}).update(_hlo_op_names(proto))
    return out


# --------------------------------------------------------------------------
# the profile as the program named it
# --------------------------------------------------------------------------

def read_spans(path: str) -> List[Span]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    ids = {k: v for k, v in ev.stats if not k.startswith("_")}
                    out.append(Span(ev.name[len(SPAN_PREFIX):],
                                    ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                                    ids, line.name))
    return sorted(out, key=lambda s: (s.start_s, -s.dur_s))


class ProgramTrace:
    """The ``harness/trace.py`` Trace of a run (device operations per chip,
    module executions, the traced window) plus the program's spans and
    each program's ``{instruction: op_name}``."""

    def __init__(self, trace: T.Trace, spans: List[Span],
                 scopes: Dict[str, Dict[str, str]]):
        self.trace, self.spans, self.scopes = trace, spans, scopes
        self.t0, self.t1 = trace.t0, trace.t1

    # -- programs ------------------------------------------------------
    def executions(self, program: str, chip: Optional[int] = None
                   ) -> List[Tuple[float, float]]:
        """``(start, end)`` of each execution of ``program`` that lies
        inside the traced window, on one chip (default: the first)."""
        mods = self.trace.modules
        if not mods:
            return []
        chip = min(mods) if chip is None else chip
        return sorted((s, s + d) for name, s, d in mods[chip]
                      if module_of(name) == program
                      and s >= self.t0 and s + d <= self.t1)

    def programs(self) -> Dict[str, int]:
        """Executions in the window by program name, first chip."""
        mods = self.trace.modules
        out: Dict[str, int] = {}
        for name, s, d in (mods[min(mods)] if mods else []):
            if s >= self.t0 and s + d <= self.t1:
                out[module_of(name)] = out.get(module_of(name), 0) + 1
        return out

    # -- spans ---------------------------------------------------------
    def named(self, name: str) -> List[Span]:
        """Spans of one name that lie inside the traced window."""
        return [s for s in self.spans if s.name == name
                and s.start_s >= self.t0 and s.end_s <= self.t1]

    def children(self, parent: Span) -> List[Span]:
        return [s for s in self.spans if s is not parent
                and s.thread == parent.thread
                and s.start_s >= parent.start_s and s.end_s <= parent.end_s]


_CACHE: Dict[Tuple[str, float], ProgramTrace] = {}


def newest_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def open_run(ctx, result) -> Optional[ProgramTrace]:
    """The ProgramTrace of this run's capture, or None where there is no
    device trace to read (a CPU rehearsal, an untraced run)."""
    trace = result.get("trace")
    if trace is None or not trace.device_ops:
        return None
    path = newest_xplane(ctx.trace_dir)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = ProgramTrace(trace, read_spans(path), read_scopes(path))
    return _CACHE[key]


# --------------------------------------------------------------------------
# reductions: plain lists in, numbers out
# --------------------------------------------------------------------------

FWD, BWD, OPT, OTHER = "fwd", "bwd", "opt", "unattributed"
_TRANSPOSED = re.compile(r"transpose\(|rematted_computation")


def train_region(op_name: Optional[str]) -> str:
    """Which part of the train step an operation belongs to, from its
    scope path. Under ``forward_backward`` JAX itself marks what is
    transposed (``transpose(jvp(..))``) and what a checkpoint policy
    recomputes (``rematted_computation``): both are backward time."""
    if not op_name:
        return OTHER
    parts = op_name.split("/")
    if "optimizer" in parts:
        return OPT
    if "forward_backward" in parts:
        return BWD if _TRANSPOSED.search(op_name) else FWD
    return OTHER


def seconds_by_region(events: List[T.Event], op_names: Dict[str, str],
                      inside: List[Tuple[float, float]], region=train_region
                      ) -> Dict[str, float]:
    """Leaf device time by region, over the leaves that lie inside one of
    the ``inside`` intervals (a program's executions). An operation that
    carries no ``op_name`` (a copy or a bitcast the compiler put in)
    takes the region of the innermost enclosing operation that has one:
    the ``while`` whose body it runs in."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: Dict[str, float] = {}
    stack: List[Tuple[float, str]] = []          # (end, region)
    eps = 1e-12
    leaf = T.leaves(evs)
    leaf_keys = {(e[0], e[1]) for e in leaf}
    j = 0
    for name, start, dur in evs:
        while stack and stack[-1][0] <= start + eps:
            stack.pop()
        r = region(op_names.get(instruction_of(name)))
        if r == OTHER and stack:
            r = stack[-1][1]
        if (name, start) in leaf_keys:
            while j < len(inside) and inside[j][1] <= start:
                j += 1
            if j < len(inside) and inside[j][0] <= start + eps \
                    and start + dur <= inside[j][1] + eps:
                out[r] = out.get(r, 0.0) + dur
        else:
            stack.append((start + dur, r))
    return out


def train_split(pt: ProgramTrace) -> Optional[Dict[str, float]]:
    """Device milliseconds a train step spends in each region, mean over
    chips: ``fwd``, ``bwd``, ``opt``, ``unattributed``, their sum
    ``step_ms`` and the count of ``steps`` read."""
    op_names = pt.scopes.get(TRAIN_STEP)
    if not op_names:
        return None
    per_chip = []
    for chip, events in pt.trace.device_ops.items():
        runs = pt.executions(TRAIN_STEP, chip)
        if runs:
            by = seconds_by_region(events, op_names, runs)
            per_chip.append({k: 1e3 * v / len(runs) for k, v in by.items()})
    if not per_chip:
        return None
    out = {k: sum(c.get(k, 0.0) for c in per_chip) / len(per_chip)
           for k in (FWD, BWD, OPT, OTHER)}
    out["step_ms"] = sum(out.values())
    out["steps"] = len(pt.executions(TRAIN_STEP))
    return out


def intersect(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
              ) -> List[Tuple[float, float]]:
    """``a`` and ``b``, both disjoint and sorted, where they overlap."""
    return T.subtract(a, T.subtract(a, b))


def exposed_by_child(busy: List[Tuple[float, float]], parent: Span,
                     children: List[Span]) -> Dict[str, float]:
    """Device-idle seconds inside one parent span, by the innermost span
    that was open: each child gets the idle time that falls inside it and
    outside its own children; ``(self)`` is the parent's own code between
    its children. One gap may straddle several spans (the device waits
    from the end of one step's work through the host's bookkeeping to the
    next dispatch), so gaps are cut at span borders, not given whole to
    the span over their middle. ``busy``: the merged busy intervals."""
    whole = [(parent.start_s, parent.end_s)]
    gaps = T.subtract(whole, T.clip(busy, parent.start_s, parent.end_s))
    out: Dict[str, float] = {}
    for c in children:
        inner = T.merge((g.start_s, g.end_s) for g in children
                        if g is not c and g.start_s >= c.start_s
                        and g.end_s <= c.end_s)
        own = T.subtract([(c.start_s, c.end_s)], inner)
        idle = T.measure(intersect(gaps, own))
        if idle > 0:
            out[c.name] = out.get(c.name, 0.0) + idle
    rest = T.measure(T.subtract(gaps, T.merge(
        (c.start_s, c.end_s) for c in children)))
    if rest > 0:
        out["(self)"] = rest
    return out


def host_exposed(pt: ProgramTrace, parent_name: str) -> Optional[Dict]:
    """Device-idle time inside the ``parent_name`` spans of the window:
    ``ms_per_span``, the ``spans`` counted and ``by_child`` (seconds in
    all, by innermost child span). First chip."""
    parents = pt.named(parent_name)
    ops = pt.trace.device_ops
    if not parents or not ops:
        return None
    busy = T.merge((s, s + d) for _, s, d in ops[min(ops)])
    by: Dict[str, float] = {}
    for p in parents:
        for k, v in exposed_by_child(busy, p, pt.children(p)).items():
            by[k] = by.get(k, 0.0) + v
    return {"ms_per_span": 1e3 * sum(by.values()) / len(parents),
            "spans": len(parents),
            "by_child": dict(sorted(by.items(), key=lambda kv: -kv[1]))}


def mean_execution_ms(pt: ProgramTrace, program: str) -> Optional[float]:
    runs = pt.executions(program)
    if not runs:
        return None
    return 1e3 * sum(e - s for s, e in runs) / len(runs)


def counter_ratio(result, num: str, den, scale: float = 1.0
                  ) -> Optional[float]:
    """``scale * delta[num] / delta[den]`` over the window's engine
    counters (``den``: a key, or several to add up). None where the
    program has no such counter, or the denominator is 0."""
    c = result.get("counters", {}).get("engine", {})
    dens = [den] if isinstance(den, str) else list(den)
    if num not in c or any(d not in c for d in dens):
        return None
    total = sum(c[d] for d in dens)
    return scale * c[num] / total if total else None

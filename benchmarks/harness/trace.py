"""Capture a profiler trace of a few seconds, and reduce it to numbers.

Capture writes the ``.xplane.pb`` under the checkout's ``.bench_out/`` and
reads it back with ``jax.profiler.ProfileData`` (nothing but JAX). The
reduction works on plain lists of ``(name, start_s, duration_s)``, so a
hand-made timeline pins every rule in a test:

* busy: the union of the intervals in which an operation ran on a device;
  idle share: 1 - busy / window;
* kernel time: the summed durations of the events a kernel's own name
  pattern matches;
* exposed collective time: the time in collective operations during
  which no compute operation runs on that device. Events nest (a
  ``while`` spans its body), so only *leaf* events count as work;
* idle gaps, each attributed to the innermost of the benchmark's own host
  spans (``bench/...`` TraceAnnotations) that covers its middle.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_s, duration_s
SPAN_PREFIX = "bench/"
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)", re.I)
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


_MOSAIC = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = .*custom-call\(.*"
                     r"custom_call_target=\"tpu_custom_call\"", re.S)


def kernel_name(event_name: str) -> Optional[str]:
    """The name the program gave a Pallas (Mosaic) kernel, or None for any
    other event. A device event is named by its whole HLO instruction, and
    a kernel's instruction by the ``name=`` of its ``pallas_call``:
    ``%flash_fwd.17 = (...) custom-call(...), custom_call_target=
    "tpu_custom_call"`` -> ``flash_fwd`` (PERF.md section 3)."""
    m = _MOSAIC.match(event_name)
    return m.group(1) if m else None


def span(name: str):
    """A host span of the benchmark's own, on the profiler's clock."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


# --------------------------------------------------------------------------
# reduction: plain lists in, numbers out
# --------------------------------------------------------------------------

def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals as a sorted disjoint list."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def measure(disjoint: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in disjoint)


def clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def subtract(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]
             ) -> List[Tuple[float, float]]:
    """``a`` minus ``b``; both disjoint and sorted."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _ivals(events: Iterable[Event]):
    return [(s, s + d) for _, s, d in events]


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that contain no other event (a ``while`` or a ``call`` spans
    its body and is not work of its own)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[Event] = []
    stack: List[Tuple[Event, bool]] = []   # (event, has_child)
    eps = 1e-12

    def close(until: float):
        while stack and stack[-1][0][1] + stack[-1][0][2] <= until + eps:
            ev, parent = stack.pop()
            if not parent:
                out.append(ev)

    for ev in evs:
        close(ev[1])
        if stack and ev[1] + ev[2] <= stack[-1][0][1] + stack[-1][0][2] + eps:
            stack[-1] = (stack[-1][0], True)    # contained: a true child
        stack.append((ev, False))
    close(float("inf"))
    return sorted(out, key=lambda e: e[1])


def busy_seconds(events: Sequence[Event], t0: float, t1: float) -> float:
    return measure(clip(merge(_ivals(events)), t0, t1))


def idle_share(events: Sequence[Event], t0: float, t1: float) -> float:
    return 1.0 - busy_seconds(events, t0, t1) / (t1 - t0)


def time_by_name(events: Sequence[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, _, d in leaves(events):
        out[name] = out.get(name, 0.0) + d
    return out


def kernel_seconds(events: Sequence[Event], match) -> Tuple[float, int]:
    """Summed duration and count of the leaf events whose name matches: a
    regular expression, or a predicate on the name."""
    if isinstance(match, str):
        match = re.compile(match).search
    hit = [d for name, _, d in leaves(events) if match(name)]
    return sum(hit), len(hit)


_HLO = re.compile(r"^%?([\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])?.*? ([a-z\-]+)\(")


def short_name(name: str) -> str:
    """An HLO instruction as the device trace names it, cut to what a
    reader needs: ``fusion.307 fusion f32[4096,32000]``, and for a fusion
    the first parameter it reads (which says whose weights they are)."""
    m = _HLO.match(name)
    if not m:
        return name[:96]
    out = f"{m.group(1)} {m.group(3)} {(m.group(2) or '').lstrip('(')}"
    arg = re.search(r"%((?:opt_state|params)[\w.]*)", name)
    if "tpu_custom_call" in name:
        out += " tpu_custom_call"
    return (out + (f" <- {arg.group(1)}" if arg else ""))[:120]


def exposed_collective_seconds(events: Sequence[Event]) -> float:
    lv = leaves(events)
    coll = merge(_ivals(e for e in lv if COLLECTIVE.match(e[0])))
    comp = merge(_ivals(e for e in lv if not COLLECTIVE.match(e[0])))
    return measure(subtract(coll, comp))


def idle_gaps(events: Sequence[Event], host_spans: Sequence[Event],
              t0: float, t1: float, floor_s: float = 20e-6
              ) -> Dict[str, float]:
    """Idle seconds of one device inside ``[t0, t1]`` by what the host was
    doing: each gap goes to the innermost benchmark span over its middle."""
    gaps = subtract([(t0, t1)], clip(merge(_ivals(events)), t0, t1))
    out: Dict[str, float] = {}
    for s, e in gaps:
        if e - s < floor_s:
            continue
        mid, best = (s + e) / 2, None
        for name, hs, hd in host_spans:
            if hs <= mid <= hs + hd and (best is None or hd < best[1]):
                best = (name, hd)
        key = best[0] if best else "unattributed"
        out[key] = out.get(key, 0.0) + (e - s)
    return out


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


# --------------------------------------------------------------------------
# the trace as this benchmark reads it
# --------------------------------------------------------------------------

class Trace:
    """Device operations per chip, the benchmark's host spans, and the
    traced window ``[t0, t1]``, all in seconds on the trace's clock."""

    def __init__(self, device_ops: Dict[int, List[Event]],
                 host_spans: List[Event], t0: float, t1: float,
                 modules: Optional[Dict[int, List[Event]]] = None):
        self.device_ops, self.host_spans = device_ops, host_spans
        self.modules = modules or {}
        self.t0, self.t1 = t0, t1

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def between(self, t0: float, t1: float) -> "Trace":
        """The window ``[t0, t1]`` alone: the device events that lie whole
        inside it and the host spans that reach into it. (A capture opened
        from a thread begins and ends inside a step; its reader cuts it
        back to the steps it holds whole.)"""
        def inside(events):
            return [e for e in events if e[1] >= t0 and e[1] + e[2] <= t1]

        return Trace({c: inside(ev) for c, ev in self.device_ops.items()},
                     [s for s in self.host_spans
                      if s[1] < t1 and s[1] + s[2] > t0], t0, t1,
                     {c: inside(ev) for c, ev in self.modules.items()})

    def busy_s(self) -> float:
        """Mean over the chips used."""
        per = [busy_seconds(ev, self.t0, self.t1)
               for ev in self.device_ops.values()]
        return sum(per) / len(per) if per else 0.0

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def kernel_seconds(self, match) -> Tuple[float, int]:
        """Mean over chips of matching leaf time; count on the first."""
        per = [kernel_seconds(ev, match) for ev in self.device_ops.values()]
        if not per:
            return 0.0, 0
        return sum(p[0] for p in per) / len(per), per[0][1]

    def exposed_collective_share(self) -> float:
        """Worst device."""
        return max((exposed_collective_seconds(ev) / self.window_s
                    for ev in self.device_ops.values()), default=0.0)

    def breakdown(self) -> Dict:
        first = self.device_ops[min(self.device_ops)] if self.device_ops else []
        by = {}
        for name, seconds in time_by_name(first).items():
            by[short_name(name)] = by.get(short_name(name), 0.0) + seconds
        return {"device_ops": top(by),
                "idle_gaps": top(idle_gaps(first, self.host_spans, self.t0,
                                           self.t1))}


def read_xplane(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    mods: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dst = ops if line.name == OPS_LINE else mods
                dst.setdefault(int(m.group(1)), []).extend(
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend((ev.name[len(SPAN_PREFIX):], ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9) for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    window = [s for s in spans if s[0] == "traced_window"]
    if window:
        t0, t1 = window[0][1], window[0][1] + window[0][2]
    else:
        every = [e for evs in ops.values() for e in evs] + spans
        t0 = min((e[1] for e in every), default=0.0)
        t1 = max((e[1] + e[2] for e in every), default=t0 + 1e-9)
    return Trace(ops, [s for s in spans if s[0] != "traced_window"], t0, t1,
                 mods)


class Capture:
    """``with Capture(dir) as c: ...`` traces the block; ``c.trace`` is the
    reduced-ready Trace afterwards. The Python tracer is off: it slows the
    host that the serving engine runs on. Stopping collects and writes the
    profile, seconds of work mostly outside the interpreter's lock, and
    reading the file back holds the lock for seconds more: a caller that
    opens the capture from a thread beside a loop it must not hold passes
    ``read_on_exit=False`` and calls ``read()`` when the loop has ended."""

    def __init__(self, out_dir: str, read_on_exit: bool = True):
        self.out_dir, self.read_on_exit = out_dir, read_on_exit
        self.trace: Optional[Trace] = None
        self.wall_s = 0.0

    def __enter__(self):
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._t = time.perf_counter()
        self._window = span("traced_window")
        self._window.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._window.__exit__(None, None, None)
        self.wall_s = time.perf_counter() - self._t
        jax.profiler.stop_trace()
        if exc[0] is None and self.read_on_exit:
            self.read()
        return False

    def read(self) -> Optional[Trace]:
        files = glob.glob(os.path.join(self.out_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if files:
            self.trace = read_xplane(max(files, key=os.path.getmtime))
        return self.trace

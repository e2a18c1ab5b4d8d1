"""Every collective of a traced train step, one row an instruction.

Reads the newest ``.xplane.pb`` under a capture directory (a traced
benchmark run leaves one under ``.bench_out/trace/<cell>``) with the
benchmark's own readers and prints, for the executions of
``jit_dstpu_train_step`` that lie whole inside the capture, on one chip:

* each collective instruction: what it is and its result, calls a step,
  milliseconds a call and a step, the part of the step (``fwd`` / ``bwd`` /
  ``opt``, as ``train_fwd_ms`` and its siblings split it) and the loop it
  runs in (the ``while`` event that encloses it, or ``-`` outside both
  scans);
* whether ``collective_exposed_share`` counts it: the metric matches an
  event by its opcode, so the compiler's asynchronous fusions
  (``async-collective-start`` / ``-done``) read there as compute. Their
  wait is listed here all the same;
* the totals by kind, and the step's time in products and kernels;
* each scan (``while``) by what its time is made of and, with
  ``--timeline``, the operations of one iteration of each in time order.

The core runs one operation at a time, so a collective's time on the
``XLA Ops`` line is time the core spent issuing it or waiting for it.

    python tools/collective_table.py CAPTURE_DIR [--chip N] [--md] [--timeline]
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import program_trace as P  # noqa: E402
from benchmarks.harness import trace as T  # noqa: E402

_ASYNC = re.compile(r"^async-collective-(start|done)")
_RESULT = re.compile(r"= \(?([a-z0-9]+\[[\d,]*\])")


def kind_of(event_name: str) -> Tuple[str, bool]:
    """``(kind, counted)`` of a device event, or ``("", False)`` for what
    is no collective. ``counted``: the exposed-share metric sees it."""
    ins = P.instruction_of(event_name)
    if T.COLLECTIVE.match(event_name):
        return re.sub(r"\.\d+$", "", ins), True
    m = _ASYNC.match(ins)
    if m:
        return f"async-collective-{m.group(1)}", False
    return "", False


def result_of(event_name: str) -> str:
    m = _RESULT.search(event_name)
    return m.group(1) if m else "?"


def rows_of(events: List[T.Event], op_names: Dict[str, str],
            runs: List[Tuple[float, float]]) -> Tuple[List[Dict], Dict]:
    """One row an instruction over the leaf events inside ``runs``, and
    the totals (seconds over all runs) by kind."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    leaf_keys = {(e[0], e[1]) for e in T.leaves(evs)}
    stack: List[Tuple[float, str, str]] = []     # end, instruction, region
    rows: Dict[str, Dict] = {}
    totals: Dict[str, float] = {}
    eps, j = 1e-12, 0
    for name, start, dur in evs:
        while stack and stack[-1][0] <= start + eps:
            stack.pop()
        ins = P.instruction_of(name)
        region = P.train_region(op_names.get(ins))
        if region == P.OTHER and stack:
            region = stack[-1][2]
        if (name, start) not in leaf_keys:
            stack.append((start + dur, ins, region))
            continue
        while j < len(runs) and runs[j][1] <= start:
            j += 1
        if not (j < len(runs) and runs[j][0] <= start + eps
                and start + dur <= runs[j][1] + eps):
            continue
        kind, counted = kind_of(name)
        if not kind:
            key = "kernel" if T.kernel_name(name) else "other compute"
            totals[key] = totals.get(key, 0.0) + dur
            continue
        totals[kind] = totals.get(kind, 0.0) + dur
        loops = [s[1] for s in stack if s[1].startswith("while")]
        r = rows.setdefault(ins, {
            "instruction": ins, "kind": kind, "result": result_of(name),
            "counted": counted, "region": region,
            "loop": loops[-1] if loops else "-", "calls": 0, "seconds": 0.0,
            "scope": "/".join((op_names.get(ins) or "").split("/")[-3:])})
        r["calls"] += 1
        r["seconds"] += dur
    return sorted(rows.values(), key=lambda r: -r["seconds"]), totals


def table(capture_dir: str, chip: int = -1, markdown: bool = False,
          with_timeline: bool = False) -> List[str]:
    path = P.newest_xplane(capture_dir)
    if path is None:
        return [f"no .xplane.pb under {capture_dir}"]
    trace = T.read_xplane(path)
    pt = P.ProgramTrace(trace, [], P.read_scopes(path))
    op_names = pt.scopes.get(P.TRAIN_STEP, {})
    chips = sorted(trace.device_ops)
    if not chips:
        return ["no device plane in the capture"]
    if chip < 0:            # the chip the exposed-share metric reports
        chip = max(chips, key=lambda c: T.exposed_collective_seconds(
            trace.device_ops[c]))
    runs = pt.executions(P.TRAIN_STEP, chip)
    if not runs:
        return [f"no whole execution of {P.TRAIN_STEP} on chip {chip}"]
    n = len(runs)
    rows, totals = rows_of(trace.device_ops[chip], op_names, runs)
    step_ms = 1e3 * sum(e - s for s, e in runs) / n
    out = [f"chip {chip} of {chips}; {n} steps of {step_ms:.2f} ms; "
           f"exposed share on this chip "
           f"{100 * T.exposed_collective_seconds(trace.device_ops[chip]) / trace.window_s:.2f}% "
           f"of the window"]
    head = ["instruction", "result", "part", "loop", "calls/step", "ms/call",
            "ms/step", "counted", "scope"]
    sep = " | " if markdown else "  "
    out.append(sep.join(head))
    if markdown:
        out.append(sep.join("---" for _ in head))
    for r in rows:
        out.append(sep.join([
            r["instruction"], r["result"], r["region"], r["loop"],
            f"{r['calls'] / n:.2f}", f"{1e3 * r['seconds'] / r['calls']:.3f}",
            f"{1e3 * r['seconds'] / n:.3f}", "yes" if r["counted"] else "no",
            r["scope"]]))
    out.append("by kind, ms a step: " + ", ".join(
        f"{k} {1e3 * v / n:.2f}" for k, v in
        sorted(totals.items(), key=lambda kv: -kv[1])))
    loops = loops_of(trace.device_ops[chip], runs)
    for r in loops:
        out.append(
            f"{r['loop']}: {r['iterations'] / n:.0f} iterations a step, "
            f"{1e3 * r['seconds'] / n:.2f} ms a step: counted collectives "
            f"{1e3 * r['counted'] / n:.2f}, asynchronous fusions "
            f"{1e3 * r['async'] / n:.2f}, everything else "
            f"{1e3 * r['compute'] / n:.2f}")
    if with_timeline:
        for r in loops:
            out += timeline(trace.device_ops[chip], runs, r["loop"])
    return out


def loops_of(events: List[T.Event], runs: List[Tuple[float, float]]
             ) -> List[Dict]:
    """Each ``while`` of the program's executions: iterations a step (its
    direct children on the ``XLA Ops`` line are the body's operations, so
    the count is of the loop's first body operation), milliseconds a
    step, and of those the leaf time in collectives the metric counts, in
    asynchronous fusions' starts and dones, and in everything else."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    leaf_keys = {(e[0], e[1]) for e in T.leaves(evs)}
    out: Dict[str, Dict] = {}
    stack: List[Tuple[float, str]] = []
    eps, j = 1e-12, 0
    for name, start, dur in evs:
        while stack and stack[-1][0] <= start + eps:
            stack.pop()
        while j < len(runs) and runs[j][1] <= start:
            j += 1
        inside = (j < len(runs) and runs[j][0] <= start + eps
                  and start + dur <= runs[j][1] + eps)
        ins = P.instruction_of(name)
        if (name, start) not in leaf_keys:
            if inside and ins.startswith("while"):
                r = out.setdefault(ins, {"loop": ins, "seconds": 0.0,
                                         "counted": 0.0, "async": 0.0,
                                         "compute": 0.0, "first": None,
                                         "iterations": 0})
                r["seconds"] += dur
            stack.append((start + dur, ins))
            continue
        loops = [s[1] for s in stack if s[1] in out]
        if not inside or not loops:
            continue
        r = out[loops[-1]]
        if r["first"] is None:
            r["first"] = ins
        r["iterations"] += ins == r["first"]
        kind, counted = kind_of(name)
        r["counted" if counted else "async" if kind else "compute"] += dur
    return sorted(out.values(), key=lambda r: -r["seconds"])


def timeline(events: List[T.Event], runs, loop: str, floor_s: float = 10e-6
             ) -> List[str]:
    """The leaf operations of one iteration of ``loop`` (the middle one
    of the middle execution), in time order: offset, duration, name."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    s0, e0 = runs[len(runs) // 2]
    body = [e for e in evs if P.instruction_of(e[0]) == loop
            and e[1] >= s0 and e[1] + e[2] <= e0]
    if not body:
        return [f"no execution of {loop}"]
    ws, we = body[0][1], body[0][1] + body[0][2]
    inner = [e for e in T.leaves(evs) if e[1] >= ws and e[1] + e[2] <= we]
    first = P.instruction_of(inner[0][0])
    starts = [e[1] for e in inner if P.instruction_of(e[0]) == first]
    k = len(starts) // 2
    t0 = starts[k]
    t1 = starts[k + 1] if k + 1 < len(starts) else we
    out = [f"{loop}: iteration {k} of {len(starts)}, {1e3 * (t1 - t0):.3f} ms"]
    for name, start, dur in inner:
        if t0 <= start < t1 and dur >= floor_s:
            out.append(f"{1e3 * (start - t0):9.3f} {1e3 * dur:8.3f}  "
                       f"{T.short_name(name)}")
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print(__doc__)
        return 2
    chip = int(argv[argv.index("--chip") + 1]) if "--chip" in argv else -1
    print("\n".join(table(argv[0], chip, "--md" in argv,
                          "--timeline" in argv)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

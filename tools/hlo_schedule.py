"""The schedule of a compiled program's collectives, read from its HLO text.

A scheduled TPU module (``compiled.as_text()``) lists each computation's
instructions in the order the core runs them. For every computation that
holds a collective this prints that order cut to what decides overlap:
synchronous collectives (the core waits through them), the start and the
done of asynchronous ones (``async-collective-start`` / ``-done`` fusions,
``collective-permute-start`` / ``-done``) with how many matrix products
and kernels run between the two, the products and kernels themselves, and
the fusions that hold a collective beside a product (``fused <op>``).

    python tools/hlo_schedule.py PROGRAM.txt [--all]

``--all`` prints every instruction of those computations, not the cut.
Bytes, never times: a time comes from a device trace.
"""

from __future__ import annotations

import re
import sys
from typing import Dict, List, NamedTuple, Optional

_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INS = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_BODY = re.compile(r"body=%?([\w.\-]+)")
_COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all"
                         r"|collective-permute|collective-broadcast)")
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
         "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1}


class Ins(NamedTuple):
    name: str
    shape: str
    op: str
    rest: str


def parse(text: str) -> Dict[str, List[Ins]]:
    comps: Dict[str, List[Ins]] = {}
    cur: Optional[List[Ins]] = None
    for line in text.split("\n"):
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        m = _INS.match(line)
        if m:
            cur.append(Ins(m.group(1), m.group(2), m.group(3), m.group(4)))
    return comps


def nbytes(shape: str) -> int:
    """Bytes of the largest array in a result shape (an async start's
    tuple holds its operand, its result and semaphores)."""
    best = 0
    for dt, dims in _SHAPE.findall(shape):
        n = _ITEM.get(dt, 0)
        for d in dims.split(","):
            if d:
                n *= int(d)
        best = max(best, n)
    return best


def plain_shape(shape: str) -> str:
    found = _SHAPE.findall(shape)
    if not found:
        return shape[:40]
    dt, dims = max(found, key=lambda f: nbytes(f"{f[0]}[{f[1]}]"))
    return f"{dt}[{dims}]"


def _holds(comps, name: str, pattern: re.Pattern, seen=None) -> Optional[str]:
    """The first opcode matching ``pattern`` in a computation or in one
    it calls."""
    seen = seen if seen is not None else set()
    if name in seen or name not in comps:
        return None
    seen.add(name)
    for ins in comps[name]:
        if pattern.match(ins.op):
            return ins.op
        for callee in _CALLS.findall(ins.rest):
            hit = _holds(comps, callee, pattern, seen)
            if hit:
                return hit
    return None


_PRODUCT = re.compile(r"^(convolution|dot)$")


def kind_of(comps, ins: Ins) -> Optional[str]:
    """``sync <op>``, ``start <op>``, ``done``, ``product``, ``kernel`` or
    None for what does not decide overlap."""
    if ins.op.endswith("-start") and _COLLECTIVE.match(ins.op):
        return f"start {ins.op[:-6]}"
    if ins.op.endswith("-done") and _COLLECTIVE.match(ins.op):
        return "done"
    if _COLLECTIVE.match(ins.op):
        return f"sync {ins.op}"
    if ins.op == "custom-call" and "tpu_custom_call" in ins.rest:
        return "kernel"
    if ins.op == "fusion":
        callee = _CALLS.search(ins.rest)
        callee = callee.group(1) if callee else ""
        if ins.name.startswith("async-collective-start"):
            inner = _holds(comps, callee, _COLLECTIVE) or "collective"
            return f"start {inner}"
        if ins.name.startswith("async-collective-done"):
            return "done"
        inner = _holds(comps, callee, _COLLECTIVE)
        if inner:               # a product that reduce-scatters as it runs
            return f"fused {inner}"
        if _holds(comps, callee, _PRODUCT):
            return "product"
    if _PRODUCT.match(ins.op):
        return "product"
    return None


def scope_of(ins: Ins) -> str:
    m = re.search(r'op_name="([^"]*)"', ins.rest)
    if not m:
        return ""
    parts = [p for p in m.group(1).split("/") if p]
    keep = [p for p in parts if p.startswith(("transpose", "jvp", "remat"))
            or p in ("attn", "mlp", "embed", "head_loss", "optimizer",
                     "forward_backward", "while", "body")]
    return "/".join(keep[-4:])


def report(text: str, everything: bool = False) -> List[str]:
    comps = parse(text)
    loops = {}
    for cname, body in comps.items():
        for ins in body:
            if ins.op == "while":
                b = _BODY.search(ins.rest)
                if b:
                    loops[b.group(1)] = f"body of {ins.name} in {cname}"
    fused = {callee for body in comps.values() for ins in body
             if ins.op == "fusion" for callee in _CALLS.findall(ins.rest)}
    out: List[str] = []
    for cname, body in comps.items():
        kinds = [kind_of(comps, i) for i in body]
        if not any(k and k != "product" and k != "kernel" for k in kinds):
            continue
        if cname in fused:      # a fusion's inside is one operation outside
            continue
        out.append(f"== {cname} ({loops.get(cname, 'not a loop body')}; "
                   f"{len(body)} instructions)")
        open_starts: Dict[str, int] = {}
        work = 0
        for ins, kind in zip(body, kinds):
            if kind in ("product", "kernel") or (kind or "").startswith("fused"):
                work += 1
            if kind is None and not everything:
                continue
            line = f"  {kind or '':<26}{ins.name:<34}{plain_shape(ins.shape):<26}"
            if kind and kind.startswith(("sync", "start")):
                line += f"{nbytes(ins.shape) / 2**20:8.2f} MiB  "
            if kind and kind.startswith("start"):
                open_starts[ins.name.replace("start", "done")] = work
            if kind == "done":
                since = open_starts.pop(ins.name, None)
                if since is None:      # a done names its start as operand
                    for k in list(open_starts):
                        if k.replace("done", "start") in ins.rest:
                            since = open_starts.pop(k)
                            break
                if since is not None:
                    line += f"products and kernels since its start: {work - since}  "
            if kind in ("product", "kernel") and not everything:
                line = f"  {kind:<26}{ins.name:<34}{plain_shape(ins.shape):<26}"
            out.append(line + scope_of(ins))
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print(__doc__)
        return 2
    with open(argv[0]) as f:
        print("\n".join(report(f.read(), "--all" in argv[1:])))
    return 0


if __name__ == "__main__":
    sys.exit(main())

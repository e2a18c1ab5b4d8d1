"""Device milliseconds per decode token step spent in operations whose
*output* is the KV pool or one layer's slice of it, inside the decode
programs (``jit_dstpu_serve_decode``, ``jit_dstpu_serve_multi_decode``, by
the module line) of the traced window.

A step changes 32 rows a layer of a pool of gigabytes. Where the step
program keeps the pool in place, the only operation that *produces* the
pool is the scatter of those rows, which takes microseconds; where it
slices each layer out, restacks it and copies the result beside its
argument, three pool-sized passes show here (about 20 ms a token step at
2 GiB and 819 GB/s). The shapes come from the run's own sizes (layers and
KV heads of ``facts["arch"]``, blocks and block size of the cell's
``engine``), matched against the output type in the event's HLO
instruction, whatever the element type.
"""

import re

from benchmarks.harness import program_trace as P
from benchmarks.harness import trace as T

DECODE_PROGRAMS = ("jit_dstpu_serve_decode", "jit_dstpu_serve_multi_decode")
_OPCODE = re.compile(r" [a-z][a-z\-]*\(")
_DIMS = re.compile(r"[a-z][a-z0-9]*\[([0-9,]*)\]")


def output_dims(event_name: str):
    """The dimensions of every array an HLO instruction puts out:
    ``%f.2 = (bf16[2,8]{1,0}, f32[2]{0}) fusion(...)`` -> ``["2,8", "2"]``."""
    _, eq, rest = event_name.partition(" = ")
    if not eq:
        return []
    m = _OPCODE.search(rest)
    return _DIMS.findall(rest[:m.start()] if m else rest)


def pool_dims(arch, engine):
    pool = [arch.num_hidden_layers, engine["kv_blocks"],
            engine["kv_block_size"], 2, arch.num_key_value_heads,
            arch.head_dim]
    return (",".join(map(str, pool)), ",".join(map(str, pool[1:])))


def read(ctx, result):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    runs = T.merge(r for p in DECODE_PROGRAMS for r in pt.executions(p))
    lo, hi = result["facts"]["traced_steps"]
    token_steps = sum(s["decode_kernel_steps"]
                      for s in result["served"].steps[lo:hi])
    if not runs or not token_steps:
        return None
    shapes = pool_dims(result["facts"]["arch"], ctx.config["engine"])
    ops = pt.trace.device_ops[min(pt.trace.device_ops)]
    by, j = {}, 0
    for name, start, dur in T.leaves(ops):
        while j < len(runs) and runs[j][1] <= start:
            j += 1
        if j < len(runs) and runs[j][0] <= start \
                and any(d in shapes for d in output_dims(name)):
            key = T.short_name(name)
            by[key] = by.get(key, 0.0) + dur
    ctx.note({"kv_pool_copy_ms": {
        "pool": f"[{shapes[0]}]", "token_steps": token_steps,
        "program_executions": len(runs),
        "ms_per_token_step_by_op": {k: 1e3 * v / token_steps
                                    for k, v in T.top(by, 8)}}})
    return 1e3 * sum(by.values()) / token_steps

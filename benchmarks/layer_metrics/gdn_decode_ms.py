"""Device milliseconds of one decode token step spent in the recurrent
layers: the operations of ``jit_dstpu_serve_decode`` and
``jit_dstpu_serve_multi_decode`` whose scope path lies under ``gdn`` (norm,
projections, ``gdn_conv``, the ``gdn_decode`` kernel, output gate and
projection), over the decode token steps of the traced window. With
``moe_decode_ms`` it splits ``decode_step_ms``; a program without the scope
(any model without recurrent layers) reads nothing.
"""

from benchmarks.harness import program_trace as P
from benchmarks.layer_metrics.kv_pool_copy_ms import DECODE_PROGRAMS


def scope_seconds(pt, programs, scope: str):
    """Leaf device seconds under ``scope`` inside the executions of
    ``programs`` (first chip), and those executions; None where no program
    carries the scope."""
    ops = pt.trace.device_ops
    events = ops[min(ops)]
    total, runs, seen = 0.0, [], False

    def region(op_name):
        return scope if op_name and scope in op_name.split("/") else P.OTHER

    for program in programs:
        names, inside = pt.scopes.get(program), pt.executions(program)
        if not names or not inside:
            continue
        seen = seen or any(region(n) == scope for n in names.values())
        total += P.seconds_by_region(events, names, inside, region).get(
            scope, 0.0)
        runs += inside
    return (total, runs) if seen else None


def decode_token_steps(result) -> int:
    lo, hi = result["facts"]["traced_steps"]
    return sum(s["decode_kernel_steps"] for s in result["served"].steps[lo:hi])


def per_token_step_ms(ctx, result, scope: str):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    got, steps = scope_seconds(pt, DECODE_PROGRAMS, scope), \
        decode_token_steps(result)
    if got is None or not steps:
        return None
    return 1e3 * got[0] / steps


def read(ctx, result):
    return per_token_step_ms(ctx, result, "gdn")


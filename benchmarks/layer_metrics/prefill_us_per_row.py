"""Device microseconds of the prefill program per token row it really
carried: the executions of ``jit_dstpu_serve_prefill`` in the traced slice,
each joined to its ``dstpu/dispatch`` span (``harness/program_calls.py``),
their summed device time over the summed ``tokens`` of their spans.
``prefill_call_ms`` over the rows of a call: what a call's padding and the
once-a-call read of the weights cost a prompt token. Prints, as a note,
the calls by ``(S, tq)`` with their mean device time, each program's mean
lead from dispatch to execution, and the calls dropped at the slice's
borders. The dotted names (``.burst``, ``.gen``) are this reader: cells
that report different end-to-end metrics need a name each."""

from benchmarks.harness import program_calls as C
from benchmarks.harness import program_trace as P


def read(ctx, result):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    joined = C.join_run(pt)
    if joined is None:
        return None
    ctx.note({"program_calls": C.summary(joined)})
    return C.us_per_row(joined, "prefill")

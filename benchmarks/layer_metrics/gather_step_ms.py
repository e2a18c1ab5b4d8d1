"""Mean device time of one execution of the gather program
(``jit_dstpu_serve_gather``, today's mixed prefill step) in the traced
window, by the program's name on the device's module line. The dotted
name (``.burst``) is this reader: a cell that reports another end-to-end
metric needs a name of its own (``serve-gen-closed`` would, but its traced
slice, the window's first 4 s, holds decode bursts only: PERF.md section
7)."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    ctx.note({"programs_in_trace": pt.programs()})
    return P.mean_execution_ms(pt, P.SERVE_GATHER)

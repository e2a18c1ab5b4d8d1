"""Mean device time of one execution of the gather program
(``jit_dstpu_serve_gather``: the mixed prefill step of a model whose
runner has no split by program, the hybrid runner's) in the traced window,
by the program's name on the device's module line. The dotted name
(``.gen``) is this reader. Since PR 38 no dense cell runs the program; a
trace without it reads nothing."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    ctx.note({"programs_in_trace": pt.programs()})
    return P.mean_execution_ms(pt, P.SERVE_GATHER)

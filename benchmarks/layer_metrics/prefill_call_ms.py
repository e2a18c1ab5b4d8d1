"""Mean device time of one execution of the prefill program
(``jit_dstpu_serve_prefill``: since PR 38 every prompt chunk of a dense
model goes through it, several chunks a call where they fit) in the traced
window, by the program's name on the device's module line. What a prompt's
step costs the device; the host's share of the step is not in it. The
dotted names (``.burst``, ``.gen``) are this reader: cells that report
different end-to-end metrics need a name each."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    ctx.note({"programs_in_trace": pt.programs()})
    return P.mean_execution_ms(pt, P.SERVE_PREFILL)

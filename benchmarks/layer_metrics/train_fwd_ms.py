"""Device milliseconds of one train step spent in the forward pass: the
operations of ``jit_dstpu_train_step`` whose scope path lies under
``forward_backward`` and that JAX marks neither as transposed
(``transpose(jvp(..))``) nor as recomputed (``rematted_computation``).
With ``train_bwd_ms`` and ``train_opt_ms`` one reduction
(``harness/program_trace.py::train_split``); this reader prints, as a
note, what the three leave out: the step's device time under no scope."""

from benchmarks.harness import program_trace as P


def split(ctx, result, announce=False):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    s = P.train_split(pt)
    if s is None:
        return None
    if announce:
        runs = pt.executions(P.TRAIN_STEP)
        program_ms = 1e3 * sum(e - b for b, e in runs) / len(runs)
        named = s[P.FWD] + s[P.BWD] + s[P.OPT]
        ctx.note({"train_step_split": {
            "steps": s["steps"], "program_ms": program_ms,
            "fwd_ms": s[P.FWD], "bwd_ms": s[P.BWD], "opt_ms": s[P.OPT],
            # the remainder: operations under no scope, and the device's
            # pauses between operations inside the program
            "remainder_ms": program_ms - named,
            "unattributed_ops_ms": s[P.OTHER],
            "between_ops_ms": program_ms - s["step_ms"],
            "remainder_share": (program_ms - named) / program_ms}})
    return s


def read(ctx, result):
    s = split(ctx, result, announce=True)
    return None if s is None else s[P.FWD]

"""Share of the window's tokens that steps of the ``mixed`` kind returned:
``100 * step_tokens_mixed`` over the sum of ``step_tokens_<kind>``, window
delta of the engine's counters. Such a token waited for a prompt chunk's
call beside its own, so above 10% the 90th percentile gap
(``tpot_p90_ms``) is a mixed step's. Prints, as a note, each kind's steps,
tokens and mean wall and wait milliseconds over the window. None where the
engine does not count its steps by kind, or the window holds no mixed
step."""

from benchmarks.harness import program_trace as P
from benchmarks.harness import step_kinds as K


def read(ctx, result):
    by = K.counted(result)
    if not by:
        return None
    ctx.note({"window_steps_by_kind": by})
    if "mixed" not in by:
        return None
    return P.counter_ratio(result, "step_tokens_mixed",
                           [f"step_tokens_{k}" for k in K.STEP_KINDS], 100.0)

"""Milliseconds the device sat idle inside a ``mixed`` step (a
``dstpu/serve_step`` span of the traced slice whose calls were a prompt
chunk's and token rows': ``harness/step_kinds.py``), a step: the host work
such a step exposes, which a mean over all steps
(``host_exposed_ms_per_step.gen``) spreads over the bursts and lone steps
beside it. Prints, as a note, the idle milliseconds a step of every kind by
the innermost phase that was open. None where the slice holds no mixed
step."""

from benchmarks.harness import program_trace as P
from benchmarks.harness import step_kinds as K


def read(ctx, result):
    pt = P.open_run(ctx, result)
    by_kind = pt and K.steps_by_kind(pt)
    idle = by_kind and K.idle_by_phase(pt, by_kind)
    if not idle:
        return None
    ctx.note({"idle_by_kind": idle})
    return idle["mixed"]["ms_per_step"] if "mixed" in idle else None

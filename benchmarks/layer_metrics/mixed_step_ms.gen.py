"""Median milliseconds of a ``mixed`` step: a ``dstpu/serve_step`` span of
the traced slice whose calls were a prompt chunk's and token rows' (the
step's kind by ``engine_v2.step_kind``: ``harness/step_kinds.py``). The one
token such a step hands each decoding request is the gap ``tpot_p90_ms``
reads wherever more than a tenth of a window's gaps are of this kind
(``mixed_step_token_share.gen``). Prints, as a note, the count and median
of every kind. None where the slice holds no mixed step."""

from benchmarks.harness import program_trace as P
from benchmarks.harness import step_kinds as K


def read(ctx, result):
    pt = P.open_run(ctx, result)
    by_kind = pt and K.steps_by_kind(pt)
    if not by_kind:
        return None
    by = K.durations(by_kind)
    ctx.note({"steps_by_kind": by})
    return by["mixed"]["median_ms"] if "mixed" in by else None

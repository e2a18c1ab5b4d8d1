"""Device milliseconds of one pass of the looped stack in a decode token
step: the operations of ``jit_dstpu_serve_decode`` and
``jit_dstpu_serve_multi_decode`` whose scope path lies under ``ut_pass``
(every layer of every pass: norms, projections, the K/V write, the paged
decode kernel, the feed-forward), over the decode token steps of the traced
window and the passes a token step makes (``total_ut_steps``). What is left
of ``decode_step_ms`` is the norms between passes (``pass_norm``), the
embedding and the head. A program without the scope (any stack run once a
token, and the parent of the PR that added it) reads nothing."""

from benchmarks.layer_metrics.gdn_decode_ms import per_token_step_ms


def read(ctx, result):
    a = result["facts"]["arch"]
    if not hasattr(a, "total_ut_steps"):
        return None
    step_ms = per_token_step_ms(ctx, result, "ut_pass")
    return None if step_ms is None else step_ms / a.total_ut_steps

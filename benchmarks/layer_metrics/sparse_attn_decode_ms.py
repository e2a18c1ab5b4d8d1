"""Device milliseconds of one decode token step spent choosing pages and
attending over them: the operations of the two decode programs whose scope
path lies under ``sparse_select`` (gathering the sequence's compressed keys,
the windows' softmax, the blocks' scores, top-k, the page lists) or under
``sparse_attn`` (the paged decode kernel over the chosen pages), over the
decode token steps of the traced window. A program without the scopes (any
model without the block-sparse rule) reads nothing."""

from benchmarks.harness import program_trace as P
from benchmarks.layer_metrics.gdn_decode_ms import (decode_token_steps,
                                                    scope_seconds)
from benchmarks.layer_metrics.kv_pool_copy_ms import DECODE_PROGRAMS

SCOPES = ("sparse_select", "sparse_attn")


def seconds(ctx, result):
    """Device seconds under either scope in the traced decode programs, or
    None."""
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    got = [scope_seconds(pt, DECODE_PROGRAMS, s) for s in SCOPES]
    if any(g is None for g in got):
        return None
    return sum(g[0] for g in got)


def read(ctx, result):
    spent, steps = seconds(ctx, result), decode_token_steps(result)
    if spent is None or not steps:
        return None
    return 1e3 * spent / steps

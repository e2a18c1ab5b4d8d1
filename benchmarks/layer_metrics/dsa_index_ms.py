"""Device milliseconds of one decode token step spent choosing the context
of the full latent layers: the operations of the two decode programs whose
scope path lies under ``dsa_index`` (the selector's projections, its key's
write, the scores of every cached key) or ``dsa_select`` (the exact top-k),
over the decode token steps of the traced window. A program without the
scopes (any model without the selector) reads nothing."""

from benchmarks.harness import program_trace as P
from benchmarks.layer_metrics.gdn_decode_ms import (decode_token_steps,
                                                    scope_seconds)
from benchmarks.layer_metrics.kv_pool_copy_ms import DECODE_PROGRAMS

SCOPES = ("dsa_index", "dsa_select")


def seconds(ctx, result, scopes=SCOPES):
    """Device seconds under the scopes in the traced decode programs, or
    None where a scope is missing."""
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    got = [scope_seconds(pt, DECODE_PROGRAMS, s) for s in scopes]
    if any(g is None for g in got):
        return None
    return sum(g[0] for g in got)


def per_step_ms(ctx, result, scopes):
    spent, steps = seconds(ctx, result, scopes), decode_token_steps(result)
    if spent is None or not steps:
        return None
    return 1e3 * spent / steps


def read(ctx, result):
    return per_step_ms(ctx, result, SCOPES)

"""Device milliseconds one execution of the gather program
(``jit_dstpu_serve_gather``) spends making each token's dense context:
the operations whose scope path lies under ``kv_gather`` (the pages of
every sequence taken from the pool, a quantized pool's dequantisation,
and one row of the whole context per *token*), summed over the layers,
over the program's executions in the traced window. It is the part of
``gather_step_ms`` that a step layout which reads the context per
sequence (ROADMAP S8 b+c) would remove, and did for the dense models in
PR 38. The dotted name (``.gen``) is this reader; a program without the
scope reads nothing."""

from benchmarks.harness import program_trace as P
from benchmarks.layer_metrics.gdn_decode_ms import scope_seconds


def read(ctx, result):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    got = scope_seconds(pt, (P.SERVE_GATHER,), "kv_gather")
    if got is None or not got[1]:
        return None
    return 1e3 * got[0] / len(got[1])

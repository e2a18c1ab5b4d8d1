"""Device milliseconds one execution of the gather program
(``jit_dstpu_serve_gather``) spends in the chunked recurrence: the
operations whose scope path lies under ``gdn_chunk``, summed over the
recurrent layers, over the program's executions in the traced window. The
number a Pallas kernel for the chunked form would start from."""

from benchmarks.harness import program_trace as P
from benchmarks.layer_metrics.gdn_decode_ms import scope_seconds


def read(ctx, result):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    got = scope_seconds(pt, (P.SERVE_GATHER,), "gdn_chunk")
    if got is None or not got[1]:
        return None
    return 1e3 * got[0] / len(got[1])

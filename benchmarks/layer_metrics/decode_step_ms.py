"""Device time of the decode programs (``decode`` and ``multi_decode``
of ``_shared_step_fns``) per token step they produce: the device-busy time
inside the executions of ``jit_dstpu_serve_decode`` and
``jit_dstpu_serve_multi_decode`` (told by the module line, as
``kv_pool_copy_ms`` tells them) in the traced window, over the decode token
steps the engine's counter shows for the steps of that window.
"""

from benchmarks.harness import program_trace as P
from benchmarks.harness import trace as T
from benchmarks.layer_metrics.kv_pool_copy_ms import DECODE_PROGRAMS


def read(ctx, result):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    runs = [r for p in DECODE_PROGRAMS for r in pt.executions(p)]
    lo, hi = result["facts"]["traced_steps"]
    token_steps = sum(s["decode_kernel_steps"]
                      for s in result["served"].steps[lo:hi])
    if not runs or not token_steps:
        return None
    ops = T.merge((s, s + d) for _, s, d in
                  pt.trace.device_ops[min(pt.trace.device_ops)])
    busy = sum(T.measure(T.clip(ops, start, end)) for start, end in runs)
    return 1e3 * busy / token_steps

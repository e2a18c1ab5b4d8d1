"""Device time of the decode programs (``decode`` and ``multi_decode``
of ``_shared_step_fns``) per token step they produce. The device's module
line names every program ``jit__unknown`` (PERF.md, Open questions), so the
programs are told by when they ran: the device-busy time inside the
benchmark's own ``serve_step`` spans of the traced window whose step the
engine's counter shows as decode-only, over the decode steps it counted.
"""

from benchmarks.harness import trace as T


def read(ctx, result):
    tr = result.get("trace")
    if tr is None or not tr.device_ops:
        return None
    lo, hi = result["facts"]["traced_steps"]
    steps = result["served"].steps[lo:hi]
    spans = sorted((s for s in tr.host_spans if s[0] == "serve_step"),
                   key=lambda s: s[1])
    if len(spans) != len(steps):      # the profiler dropped host events
        ctx.note({"decode_step_ms": f"{len(spans)} spans for {len(steps)} "
                                    "steps: paired in order from the first"})
    ops = tr.device_ops[min(tr.device_ops)]
    busy = n = 0.0
    for (_, start, dur), step in zip(spans, steps):
        if step["decode_kernel_steps"]:
            busy += T.busy_seconds(ops, start, start + dur)
            n += step["decode_kernel_steps"]
    return 1e3 * busy / n if n else None

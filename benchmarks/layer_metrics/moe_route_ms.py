"""Device milliseconds of one train step spent under ``moe_route``: the
router's scores, the top-k, the sort of the (token, expert) pairs by local
expert and their counts, forward, recomputed and transposed: the part of an
expert layer that is bound by latency, not by the products."""

from benchmarks.harness.train_step import scope_ms_per_step


def read(ctx, result):
    got = scope_ms_per_step(ctx, result, "moe_route")
    if got is None:
        return None
    ctx.note({"moe_route_ms": got})
    return got["ms"]

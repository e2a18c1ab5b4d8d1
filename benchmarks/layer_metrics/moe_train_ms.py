"""Device milliseconds of one train step spent in the expert layers: the
operations of ``jit_dstpu_train_step`` whose scope path lies under ``moe``
(routing, the grouped products and their gradients, the shared expert, the
scatter and its transpose), forward, recomputation and backward together;
the three parts are in the run's notes. A program without the scope (a
dense block) reads nothing."""

from benchmarks.harness.train_step import scope_ms_per_step


def read(ctx, result):
    got = scope_ms_per_step(ctx, result, "moe")
    if got is None:
        return None
    ctx.note({"moe_train_ms": got})
    return got["ms"]

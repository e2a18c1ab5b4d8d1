"""Device milliseconds of one train step spent in the Mamba-2 mixers: the
operations of ``jit_dstpu_train_step`` whose scope path lies under
``mamba2`` (both projections, the convolution, the scan, the gated norm),
forward, recomputation and backward together; the three parts, and the
mixer's own parts (``mamba2_proj``, ``mamba2_conv``, ``ssd_chunk``,
``mamba2_norm``), are in the run's notes. A program without the scope reads
nothing."""

from benchmarks.harness.train_step import scope_ms_per_step

PARTS = ("mamba2_proj", "mamba2_conv", "ssd_chunk", "mamba2_norm")


def read(ctx, result):
    got = scope_ms_per_step(ctx, result, "mamba2")
    if got is None:
        return None
    for part in PARTS:
        inner = scope_ms_per_step(ctx, result, part)
        got[part + "_ms"] = None if inner is None else inner["ms"]
    ctx.note({"mamba_train_ms": got})
    return got["ms"]

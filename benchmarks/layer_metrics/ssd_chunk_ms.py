"""Device milliseconds of one train step spent under ``ssd_chunk``: the
Mamba-2 scan alone (``deepspeed_tpu/ops/pallas/mamba2.py``), of every Mamba-2
block, forward, recomputed and backward; the three parts are in the run's
notes. A program without the scope reads nothing."""

from benchmarks.harness.train_step import scope_ms_per_step
from benchmarks.kernels import ssd_chunk_train as K


def read(ctx, result):
    got = scope_ms_per_step(ctx, result, K.SCOPE)
    if got is None:
        return None
    ctx.note({"ssd_chunk_ms": got})
    return got["ms"]

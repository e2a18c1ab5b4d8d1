"""Device milliseconds of one train step spent in the backward pass:
under ``forward_backward`` and marked by JAX as transposed or as
recomputed (``nothing_saveable`` replays the forward here). The reduction
is ``train_fwd_ms``'s."""

from benchmarks.harness import program_trace as P
from benchmarks.layer_metrics.train_fwd_ms import split


def read(ctx, result):
    s = split(ctx, result)
    return None if s is None else s[P.BWD]

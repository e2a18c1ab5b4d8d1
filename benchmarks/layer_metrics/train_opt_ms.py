"""Device milliseconds of one train step spent under the ``optimizer``
scope (clip, cast, AdamW, reshard). A fusion carries one scope path: where
XLA fuses an update into the matrix product that made its gradient, that
time reads as backward. The reduction is ``train_fwd_ms``'s."""

from benchmarks.harness import program_trace as P
from benchmarks.layer_metrics.train_fwd_ms import split


def read(ctx, result):
    s = split(ctx, result)
    return None if s is None else s[P.OPT]

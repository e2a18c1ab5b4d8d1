"""Passes over the layers' weights per token delivered: the sum of
``token_steps_<program>`` over all programs (a call reads the weights once
a token step, whatever it carries) over the tokens the window emitted (the
four ``tokens_*`` counters), window delta. ``1 / max_seqs`` where every
pass is a full decode step; above it by the chunk calls and by the steps
that carry fewer rows than there are slots."""

from benchmarks.harness import program_calls as C
from benchmarks.layer_metrics.gather_token_share import TOKENS


def read(ctx, result):
    return C.ratio(result, C.per_program("token_steps"), TOKENS)

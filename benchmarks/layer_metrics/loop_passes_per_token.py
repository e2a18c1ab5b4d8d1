"""Passes of the stack per token row of the decode programs: the window's
delta of ``ut_passes_decode`` + ``ut_passes_multi_decode`` (token rows times
the passes the program ran over them, counted where a call is issued from
the program's own pass count) over ``rows_decode`` + ``rows_multi_decode``.
``total_ut_steps`` (4.0 as published) while every token runs every pass:
with ``correct``, the guard that a later change drops no pass, and the
number an early-exit configuration would move. A program without the
counter (the parent of the PR that added it) reads nothing."""

from benchmarks.harness import program_calls as C
from benchmarks.layer_metrics.loop_serve_mfu import DECODE


def read(ctx, result):
    return C.ratio(result, C.per_program("ut_passes", DECODE),
                   C.per_program("rows", DECODE))

"""Share of the blocks a query could see that its KV heads chose to read:
``sparse_blocks_selected`` over ``sparse_blocks_visible`` of the engine
(window delta; both count (query, KV head, layer) pairs of the queries past
``dense_len``). With ``topk`` 64 it reads ``min(1, 64 / blocks visible)``
averaged over the window's contexts by their visible blocks."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    return P.counter_ratio(result, "sparse_blocks_selected",
                           "sparse_blocks_visible", 100.0)

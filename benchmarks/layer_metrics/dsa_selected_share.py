"""Share of the cached tokens a full layer's queries could see that they
attended over: ``dsa_rows_selected`` over ``dsa_rows_visible`` of the engine
(window delta; both summed over queries, full layers and steps). With
``index_topk`` 2,048 it reads ``min(1, 2048 / context)`` averaged over the
window's contexts by their length."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    return P.counter_ratio(result, "dsa_rows_selected", "dsa_rows_visible",
                           100.0)

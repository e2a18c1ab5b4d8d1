"""Calls of the prefill program per prompt chunk scheduled:
``prefill_chunk_calls`` over ``prefill_chunks``, window delta of the
engine's counters. 1.0 where every chunk had a call to itself; under it
where the chunks of one step shared calls (``_split_by_program``'s
packing, ``S x tq <= 2 x max_tokens``). An engine without the split has
no such counter and reads nothing. The dotted names (``.burst``,
``.gen``) are this reader: cells that report different end-to-end metrics
need a name each."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    return P.counter_ratio(result, "prefill_chunk_calls", "prefill_chunks")

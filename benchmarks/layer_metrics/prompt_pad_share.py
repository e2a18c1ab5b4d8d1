"""Share of the rows the prompt programs computed that carried no token:
``1 - (rows_prefill + rows_gather) / (padded_rows_prefill +
padded_rows_gather)``, window delta of the engine's per-program counters,
in percent. The prefill and the gather program are the two a prompt chunk
can go through (a speculative round's verification is counted under
``spec``, not here); a call computes its program's whole padded layout
whatever it carries: ``S x tq`` of the prefill program, the flat budget of
the gather program or, where the runner lays the step out anew inside it
(the hybrid runner's chunked recurrence), ``max_seqs x max_tokens``. An
engine that does not count its calls by program reads nothing. The dotted
names (``.burst``, ``.gen``) are this reader: cells that report different
end-to-end metrics need a name each."""

from benchmarks.harness import program_calls as C

PROMPT_PROGRAMS = ("prefill", "gather")


def read(ctx, result):
    real = C.ratio(result, C.per_program("rows", PROMPT_PROGRAMS),
                   C.per_program("padded_rows", PROMPT_PROGRAMS))
    return None if real is None else 100.0 * (1.0 - real)

"""Steps a prompt takes: (request, step) pairs in which a prompt chunk was
scheduled (``prefill_chunks``) over requests admitted, window delta. The
token budget of a step sets it; the kernel a chunk runs in does not."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    return P.counter_ratio(result, "prefill_chunks", "admitted")

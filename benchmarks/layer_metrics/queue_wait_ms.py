"""Mean wait of a request between ``put`` and its admission to the KV
pool: the engine's ``admission_wait_s`` over ``admitted``, window delta.
The wait before ``put`` is the generator's (``generator_lag_p90_ms``)."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    return P.counter_ratio(result, "admission_wait_s", "admitted", 1e3)

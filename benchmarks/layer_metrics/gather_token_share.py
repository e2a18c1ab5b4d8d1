"""Share of the window's emitted tokens that came out of gather steps:
``tokens_gather`` over all four ``tokens_*`` counters of the engine
(window delta). ``tpot_p90_ms`` sits in the gather steps while this is
over 10% (PERF.md section 2)."""

from benchmarks.harness import program_trace as P

TOKENS = ("tokens_gather", "tokens_prefill_kernel", "tokens_decode",
          "tokens_multi_decode")


def read(ctx, result):
    return P.counter_ratio(result, "tokens_gather", TOKENS, 100.0)

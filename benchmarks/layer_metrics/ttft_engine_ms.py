"""Mean time from ``put`` to a request's first token as the engine counts
it: ``ttft_s`` over ``first_tokens``, window delta. What it leaves out is
the wait before ``put``: printed as a note beside the mean generator lag
and the mean time to first token the clients saw (from the scheduled
arrival), whose sum it closes: lag + engine = time to first token."""

from benchmarks.harness import program_trace as P
from benchmarks.harness import stats


def read(ctx, result):
    value = P.counter_ratio(result, "ttft_s", "first_tokens", 1e3)
    window = result.get("window", {})
    if value is not None and window.get("generator_lag_s"):
        lag = window["generator_lag_s"]
        seen = stats.ttfts(result["served"].deliveries, window["scheduled"],
                           window["t0"], window["t1"])
        lag_ms = 1e3 * sum(lag) / len(lag)
        seen_ms = 1e3 * sum(seen) / len(seen)
        ctx.note({"ttft_sum": {
            "ttft_engine_ms": value, "generator_lag_mean_ms": lag_ms,
            "ttft_mean_ms": seen_ms, "requests": len(seen),
            "engine_plus_lag_minus_seen_ms": value + lag_ms - seen_ms}})
    return value

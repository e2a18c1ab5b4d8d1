"""How late the load generator ran: 90th percentile over the window's
requests of (time ``put`` returned) - (scheduled arrival). A starved
generator must not read as a fast server."""

from benchmarks.harness import stats


def read(ctx, result):
    lag = result["window"].get("generator_lag_s")
    if not lag:
        return None
    return 1e3 * stats.percentile(lag, 90)

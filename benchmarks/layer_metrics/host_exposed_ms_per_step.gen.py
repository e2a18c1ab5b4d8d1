from benchmarks.layer_metrics.host_exposed_ms_per_step import read  # noqa: F401

from benchmarks.layer_metrics.prefill_us_per_row import read  # noqa: F401

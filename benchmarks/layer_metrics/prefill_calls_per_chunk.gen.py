from benchmarks.layer_metrics.prefill_calls_per_chunk import read  # noqa: F401

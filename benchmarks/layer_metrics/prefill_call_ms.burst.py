from benchmarks.layer_metrics.prefill_call_ms import read  # noqa: F401

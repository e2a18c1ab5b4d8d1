from benchmarks.layer_metrics.gather_kv_ms import read  # noqa: F401

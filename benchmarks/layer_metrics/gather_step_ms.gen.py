from benchmarks.layer_metrics.gather_step_ms import read  # noqa: F401

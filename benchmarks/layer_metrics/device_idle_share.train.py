from benchmarks.layer_metrics.device_idle_share import read  # noqa: F401

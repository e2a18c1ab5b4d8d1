from benchmarks.layer_metrics.prompt_pad_share import read  # noqa: F401

"""The tail beside the judged median: 90th percentile of time to first
token over the window's requests (some tens of samples, so it is recorded
and not judged)."""


def read(ctx, result):
    return result["end_to_end"].get("ttft_p90_ms")

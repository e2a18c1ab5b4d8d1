"""Engine-busy time per request: the benchmark's clock inside its calls
to ``serve_step``, summed over the window, over the requests completed in
it. What the engine costs per request with the queueing taken out."""


def read(ctx, result):
    return result["end_to_end"].get("serve_busy_ms_per_req")

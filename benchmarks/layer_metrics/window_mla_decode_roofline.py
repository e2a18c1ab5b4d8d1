"""Windowed latent decode attention's share of its roofline: the least time
the chip could take to read the last ``min(context, window)`` tokens' latents
once and do the absorbed form's two products
(benchmarks/kernels/window_mla_decode.py), summed over the decode token steps
of the traced window and the sliding layers, over the device time under the
scope ``wmla_attn`` of the decode programs (the ``mla_decode`` kernel over the
ring's pages). The floor counts 1,088 values a token in the window; the
kernel fetches whole pages of rows of 1,152, so the share reads low and never
above 100%."""

from benchmarks.kernels import window_mla_decode
from benchmarks.layer_metrics.dsa_index_ms import seconds
from benchmarks.layer_metrics.dsa_index_roofline import floor_share


def read(ctx, result):
    a = result["facts"]["arch"]
    if result.get("trace") is None or not hasattr(a, "sliding_window_size"):
        return None
    return floor_share(
        ctx, result, "window_mla_decode_roofline",
        seconds(ctx, result, ("wmla_attn",)),
        lambda c: window_mla_decode.call(c, *window_mla_decode.sizes(a)),
        window_mla_decode.sliding_layers(a))

"""Selected latent attention's share of its roofline: the least time the
chip could take to read the ``min(context, index_topk)`` chosen tokens'
latents once and do the absorbed form's two products
(benchmarks/kernels/dsa_attn.py), summed over the decode token steps of the
traced window and the full layers, over the device time under the scope
``dsa_attn`` of the decode programs (the ``mla_decode`` kernel with the
choice in its mask). The floor counts 576 values a *chosen* token once; a
program that walks every page of the context reads rows of 640 of ten times
as many tokens at contexts of 20k, so the share reads well below 100%."""

from benchmarks.kernels import dsa_attn, dsa_index
from benchmarks.layer_metrics.dsa_index_ms import seconds
from benchmarks.layer_metrics.dsa_index_roofline import floor_share


def read(ctx, result):
    a = result["facts"]["arch"]
    if result.get("trace") is None or not hasattr(a, "index_topk"):
        return None
    return floor_share(
        ctx, result, "dsa_attn_roofline", seconds(ctx, result, ("dsa_attn",)),
        lambda c: dsa_attn.call(c, *dsa_attn.sizes(a)),
        dsa_index.full_layers(a))

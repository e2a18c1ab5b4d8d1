"""The selector's share of its roofline: the least time the chip could take
to read every visible token's indexer key once and score it
(benchmarks/kernels/dsa_index.py; memory-bound), summed over the decode token
steps of the traced window and the full layers, over the device time under
the scopes ``dsa_index`` and ``dsa_select`` of the decode programs (what
``dsa_index_ms`` reads: scoring and selection)."""

from benchmarks.harness import device
from benchmarks.kernels import dsa_index, flash
from benchmarks.layer_metrics.dsa_index_ms import SCOPES, seconds


def floor_share(ctx, result, name, spent, step_floor, layers):
    """``100 * floor / spent`` with the floor summed over the traced decode
    steps (``step_floor(contexts) -> (flops, bytes)``, times ``layers``), or
    None; the note says what was divided."""
    if not spent or not layers:
        return None
    lo, hi = result["facts"]["traced_steps"]
    peaks = device.peaks(ctx.device["kind"])
    need, bound = 0.0, None
    for s in result["served"].steps[lo:hi]:
        if s["decode_kernel_steps"]:
            t, bound = flash.floor_seconds(*step_floor(s["decode_contexts"]),
                                           peaks)
            need += t * layers
    if not need:
        return None
    ctx.note({name: {"floor_s": need, "device_s": spent, "bound": bound,
                     "layers": layers}})
    return 100.0 * need / spent


def read(ctx, result):
    a = result["facts"]["arch"]
    if result.get("trace") is None or not hasattr(a, "index_topk"):
        return None
    return floor_share(
        ctx, result, "dsa_index_roofline", seconds(ctx, result, SCOPES),
        lambda c: dsa_index.call(c, *dsa_index.sizes(a)),
        dsa_index.full_layers(a))

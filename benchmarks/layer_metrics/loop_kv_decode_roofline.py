"""The paged decode-attention kernel's share of its roofline under a looped
stack: the least time the chip could take to read the keys and values of the
batch's contexts (benchmarks/kernels/paged_decode.py; memory-bound), summed
over the decode steps of the traced window and the token's ``cache_layers``
layer slots (one kernel call a pass and layer, each over its own slot of the
pool), over the device time of the kernel's events in the trace. The
existing kernel at group size 1 (as many key/value heads as query heads).
``paged_decode_roofline`` counts ``num_hidden_layers`` calls a step, a
quarter of these."""

from benchmarks.harness import device
from benchmarks.kernels import flash, paged_decode


def read(ctx, result):
    tr = result.get("trace")
    a = result["facts"]["arch"]
    if tr is None or not tr.device_ops or not hasattr(a, "cache_layers"):
        return None
    lo, hi = result["facts"]["traced_steps"]
    peaks = device.peaks(ctx.device["kind"])
    need, calls = 0.0, 0
    for s in result["served"].steps[lo:hi]:
        k = s["decode_kernel_steps"]
        if not k:
            continue
        ctxs = s["decode_contexts"]
        for j in range(k):
            t, _ = flash.floor_seconds(*paged_decode.call(
                ctxs[j::k], a.num_attention_heads, a.num_key_value_heads,
                a.head_dim), peaks)
            need += t * a.cache_layers
            calls += a.cache_layers
    spent, events = tr.kernel_seconds(
        lambda n: paged_decode.classify(n) == "decode")
    if not spent or not need:
        return None
    ctx.note({"loop_kv_decode_roofline": {
        "calls_expected": calls, "events": events, "floor_s": need,
        "device_s": spent, "bound": "memory"}})
    return 100.0 * need / spent

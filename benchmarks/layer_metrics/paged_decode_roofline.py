"""The paged decode-attention kernel's share of its roofline: the least
time the chip could take to read the keys and values of the batch's
contexts (benchmarks/kernels/paged_decode.py; memory-bound), summed over
the decode steps of the traced window and the layers, over the device
time of the kernel's events in the trace."""

from benchmarks.harness import device
from benchmarks.kernels import flash, paged_decode


def read(ctx, result):
    tr = result.get("trace")
    if tr is None or not tr.device_ops:
        return None
    a = result["facts"]["arch"]
    lo, hi = result["facts"]["traced_steps"]
    peaks = device.peaks(ctx.device["kind"])
    need, calls = 0.0, 0
    for s in result["served"].steps[lo:hi]:
        if not s["decode_kernel_steps"]:
            continue
        k = s["decode_kernel_steps"]
        ctxs = s["decode_contexts"]
        per_step = [ctxs[j::k] for j in range(k)]
        for c in per_step:
            t, _ = flash.floor_seconds(*paged_decode.call(
                c, a.num_attention_heads, a.num_key_value_heads, a.head_dim),
                peaks)
            need += t * a.num_hidden_layers
            calls += a.num_hidden_layers
    spent, events = tr.kernel_seconds(lambda n: paged_decode.classify(n) == "decode")
    if not spent or not need:
        return None
    ctx.note({"paged_decode_roofline": {"calls_expected": calls,
                                        "events": events, "floor_s": need,
                                        "device_s": spent, "bound": "memory"}})
    return 100.0 * need / spent

"""The ``lightning_decode`` kernel's share of its roofline: the least time
the chip could take to read and write the state of the batch's live
sequences (benchmarks/kernels/lightning_decode.py; memory-bound), summed
over the decode token steps of the traced window and the lightning layers,
over the device time of the kernel's events in the trace."""

from benchmarks.harness import device
from benchmarks.kernels import flash, lightning_decode


def read(ctx, result):
    tr = result.get("trace")
    a = result["facts"]["arch"]
    if tr is None or not tr.device_ops or not hasattr(a, "lightning_nh"):
        return None
    lo, hi = result["facts"]["traced_steps"]
    peaks = device.peaks(ctx.device["kind"])
    layers = lightning_decode.lightning_layers(a)
    need, calls = 0.0, 0
    for s in result["served"].steps[lo:hi]:
        k = s["decode_kernel_steps"]
        if not k:
            continue
        live = len(s["decode_contexts"]) // k
        t, _ = flash.floor_seconds(*lightning_decode.call(
            live, a.lightning_nh, a.lightning_head_dim), peaks)
        need += t * layers * k
        calls += layers * k
    spent, events = tr.kernel_seconds(
        lambda n: lightning_decode.classify(n) == "decode")
    if not spent or not need:
        return None
    ctx.note({"lightning_decode_roofline": {
        "calls_expected": calls, "events": events, "floor_s": need,
        "device_s": spent, "bound": "memory"}})
    return 100.0 * need / spent

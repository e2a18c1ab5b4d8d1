"""A decode token step's share of its roofline under a looped stack: the
least time the chip could take to read the layers' weights once a pass, the
head, and the contexts' keys and values in every layer slot
(benchmarks/kernels/loop_decode_step.py; memory-bound), summed over the
decode token steps of the traced window, over the device-busy time inside
the executions of the two decode programs there (what ``decode_step_ms``
reads). Four fifths of the floor is the re-read of the weights that the
loop forces."""

from benchmarks.harness import device
from benchmarks.kernels import flash, loop_decode_step
from benchmarks.layer_metrics import decode_step_ms


def read(ctx, result):
    a = result["facts"]["arch"]
    if result.get("trace") is None or not hasattr(a, "cache_layers"):
        return None
    device_ms = decode_step_ms.read(ctx, result)
    if not device_ms:
        return None
    lo, hi = result["facts"]["traced_steps"]
    peaks = device.peaks(ctx.device["kind"])
    need, steps = 0.0, 0
    for s in result["served"].steps[lo:hi]:
        k = s["decode_kernel_steps"]
        for j in range(k):
            need += flash.floor_seconds(*loop_decode_step.step(
                a, s["decode_contexts"][j::k]), peaks)[0]
        steps += k
    floor_ms = 1e3 * need / steps
    ctx.note({"loop_decode_step_roofline": {
        "token_steps": steps, "floor_ms_per_step": floor_ms,
        "device_ms_per_step": device_ms, "bound": "memory"}})
    return 100.0 * floor_ms / device_ms

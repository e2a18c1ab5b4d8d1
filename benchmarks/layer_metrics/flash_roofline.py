"""The flash-attention kernels' share of their roofline in the training
step: for every forward and backward call in the traced window, the least
time the chip could take for that call (benchmarks/kernels/flash.py)
over the device time the trace shows. The backward is two kernels (dK/dV
and dQ); a pair is one backward. Says which bound applies on an earlier
line."""

from benchmarks.harness import device
from benchmarks.kernels import flash


def read(ctx, result):
    tr = result.get("trace")
    if tr is None or not tr.device_ops:
        return None
    f = result["facts"]
    a = f["arch"]
    shape = (f["micro_per_chip"], f["seq"], a.num_attention_heads,
             a.num_key_value_heads, a.head_dim)
    peaks = device.peaks(ctx.device["kind"])
    t_fwd, bound_f = flash.floor_seconds(*flash.fwd(*shape), peaks)
    t_bwd, bound_b = flash.floor_seconds(*flash.bwd(*shape), peaks)
    s_fwd, n_fwd = tr.kernel_seconds(lambda n: flash.classify(n) == "fwd")
    s_bwd, n_half = tr.kernel_seconds(lambda n: flash.classify(n) == "bwd")
    n_bwd = n_half // 2
    spent = s_fwd + s_bwd
    if not spent:
        return None
    ctx.note({"flash_roofline": {
        "fwd_calls": n_fwd, "bwd_calls": n_bwd, "fwd_s": s_fwd,
        "bwd_s": s_bwd, "fwd_floor_s": t_fwd, "bwd_floor_s": t_bwd,
        "bound": {"fwd": bound_f, "bwd": bound_b}}})
    return 100.0 * (n_fwd * t_fwd + n_bwd * t_bwd) / spent

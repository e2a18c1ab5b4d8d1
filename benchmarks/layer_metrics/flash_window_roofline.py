"""The windowed flash-attention kernels' share of their roofline in the
training step: for every forward and backward call in the traced window
(``flash_window_fwd``; ``flash_window_bwd_dkdv`` and ``flash_window_bwd_dq``,
a pair is one backward), the least time the chip could take for that call
over the band's pairs (benchmarks/kernels/flash_window.py) over the device
time the trace shows. A program without the kernels reads nothing."""

from benchmarks.harness import device
from benchmarks.kernels import flash, flash_window as K


def read(ctx, result):
    tr = result.get("trace")
    f = result["facts"]
    a = f["arch"]
    window = getattr(a, "sliding_window", None)
    if tr is None or not tr.device_ops or not window:
        return None
    shape = (f["micro_per_chip"], f["seq"], a.num_attention_heads,
             a.num_key_value_heads, a.head_dim, window)
    peaks = device.peaks(ctx.device["kind"])
    fwd, bwd = K.fwd(*shape), K.bwd(*shape)
    t_fwd, bound_f = flash.floor_seconds(*fwd, peaks)
    t_bwd, bound_b = flash.floor_seconds(*bwd, peaks)
    s_fwd, n_fwd = tr.kernel_seconds(lambda n: K.classify(n) == "fwd")
    s_bwd, n_half = tr.kernel_seconds(lambda n: K.classify(n) == "bwd")
    n_bwd = n_half // 2
    spent = s_fwd + s_bwd
    if not spent:
        return None
    ctx.note({"flash_window_roofline": {
        "window": window, "band_pairs": K.band_pairs(f["seq"], window),
        "fwd_calls": n_fwd, "bwd_calls": n_bwd, "fwd_s": s_fwd,
        "bwd_s": s_bwd, "fwd_floor_s": t_fwd, "bwd_floor_s": t_bwd,
        "fwd_flops_bytes": fwd, "bwd_flops_bytes": bwd,
        "bound": {"fwd": bound_f, "bwd": bound_b}}})
    return 100.0 * (n_fwd * t_fwd + n_bwd * t_bwd) / spent

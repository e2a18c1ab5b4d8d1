"""Model-FLOP/s utilization: tokens per second times the operations a
trained token requires (the architecture's own count, in its reference
module: ``train_flops_per_token``) over the chip's peak."""

from __future__ import annotations


def mfu(tokens_per_s_chip: float, flops_per_token: float,
        peak_flops: float) -> float:
    return tokens_per_s_chip * flops_per_token / peak_flops

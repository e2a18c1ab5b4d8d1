"""End-to-end model-FLOP/s utilization of the whole optimizer step:
tokens/s/chip x operations the forward and backward passes require per
token (recomputation not counted) over the chip's published bf16 peak.
Not a kernel's roofline share. Needs a chip: a CPU rate is no device
metric."""

from benchmarks.harness import device, flops


def read(ctx, result):
    if ctx.device["platform"] != "tpu":
        return None
    rate = result["end_to_end"]["train_tokens_per_s_chip"]
    peak = device.peaks(ctx.device["kind"])["bf16_flops"]
    return 100.0 * flops.mfu(rate, result["facts"]["flops_per_token"], peak)

"""The Mamba-2 scan's share of its roofline in the training step: the least
time the chip could take for the scan's work, forward and backward
(benchmarks/kernels/ssd_chunk_train.py: from the tokens a step sends through
it, the Mamba-2 blocks held, heads, head size, groups, state and chunk, all
from the run's facts), over the device time under the scope ``ssd_chunk``
in the traced steps, forward, recomputation and backward (the recomputed
forward is the program's choice: it costs time and adds no work, so it
lowers the share)."""

from benchmarks.harness import device
from benchmarks.harness.train_step import scope_ms_per_step
from benchmarks.kernels import flash, ssd_chunk_train as K


def read(ctx, result):
    got = scope_ms_per_step(ctx, result, K.SCOPE)
    f = result["facts"]
    a = f["arch"]
    if got is None or not got["ms"] or not hasattr(a, "ssm_state_size"):
        return None
    tokens = f["micro_per_chip"] * f["seq"] * a.blocks_of("M")
    ops, nbytes = K.step_calls(tokens, a.mamba_num_heads, a.mamba_head_dim,
                               a.n_groups, a.ssm_state_size, a.chunk_size)
    need, bound = flash.floor_seconds(ops, nbytes,
                                      device.peaks(ctx.device["kind"]))
    ctx.note({"ssd_chunk_train_roofline": {
        "steps": got["steps"], "tokens_per_step": tokens, "flops": ops,
        "bytes": nbytes, "floor_ms": need * 1e3, "device_ms": got["ms"],
        "parts_ms": got["parts_ms"], "bound": bound}})
    return 100.0 * need * 1e3 / got["ms"]

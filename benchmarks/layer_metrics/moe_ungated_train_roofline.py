"""The grouped products' share of their roofline in the training step of a
stack with *ungated* experts: the least time the chip could take for what
was routed here (benchmarks/kernels/moe_ungated_train.py: two forward and
four backward products a pair, recomputation not counted, the bytes of the
experts that got a row), over the device time of the ``grouped_matmul`` and
``grouped_matmul_dw`` events inside the train step's executions. What was
routed comes from the step counters ``moe_local_pairs`` and
``moe_experts_hit`` of the traced steps alone, as
``moe_grouped_train_roofline`` reads them and for its reason."""

from benchmarks.harness import device
from benchmarks.harness import program_trace as P
from benchmarks.harness.train_step import counted, kernel_seconds_in_step
from benchmarks.kernels import flash, moe_ungated_train as K


def read(ctx, result):
    pt, got = P.open_run(ctx, result), counted(result, "traced")
    if pt is None or got is None:
        return None
    c, counted_steps = got
    a = result["facts"]["arch"]
    spent, events, steps = kernel_seconds_in_step(pt, K.classify,
                                                  ("gmm", "dw"))
    if not spent or not steps:
        return None
    ops, nbytes = K.step_calls(c["moe_local_pairs"] * steps,
                               c["moe_experts_hit"] * steps, a.hidden_size,
                               a.moe_intermediate_size)
    need, bound = flash.floor_seconds(ops, nbytes,
                                      device.peaks(ctx.device["kind"]))
    ctx.note({"moe_ungated_train_roofline": {
        "steps": steps, "counted_steps": counted_steps,
        "pairs_per_step": c["moe_local_pairs"],
        "experts_hit_per_step": c["moe_experts_hit"], "events": events,
        "flops": ops, "bytes": nbytes, "floor_s": need, "device_s": spent,
        "bound": bound}})
    return 100.0 * need / spent

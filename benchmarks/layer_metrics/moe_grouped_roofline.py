"""The grouped expert products' share of their roofline in decode steps:
the least time the chip could take for what was routed here
(benchmarks/kernels/moe_grouped.py: the larger of the pairs' operations and
the bytes of the experts *that got a token*), over the device time of the
``grouped_matmul`` events inside the two decode programs' executions.

What was routed comes from the engine's counters ``moe_local_pairs_decode``
and ``moe_experts_hit_decode`` (decode programs only, summed over layers):
their mean per decode token step over the *window*, times the decode token
steps of the traced slice. Routing does not drift inside a window, so the
slice's own sum differs from that by its sampling noise only."""

from benchmarks.harness import device
from benchmarks.harness import program_trace as P
from benchmarks.harness import trace as T
from benchmarks.kernels import flash, moe_grouped
from benchmarks.layer_metrics.gdn_decode_ms import decode_token_steps
from benchmarks.layer_metrics.kv_pool_copy_ms import DECODE_PROGRAMS


def read(ctx, result):
    pt = P.open_run(ctx, result)
    c = result.get("counters", {}).get("engine", {})
    if pt is None or not c.get("moe_experts_hit_decode") \
            or not c.get("decode_kernel_steps"):
        return None
    a = result["facts"]["arch"]
    steps = decode_token_steps(result)
    scale = steps / c["decode_kernel_steps"]
    pairs = c["moe_local_pairs_decode"] * scale
    hit = c["moe_experts_hit_decode"] * scale
    need, bound = flash.floor_seconds(*moe_grouped.layer_calls(
        pairs, hit, a.hidden_size, a.moe_intermediate_size),
        device.peaks(ctx.device["kind"]))
    runs = T.merge(r for p in DECODE_PROGRAMS for r in pt.executions(p))
    ops = pt.trace.device_ops[min(pt.trace.device_ops)]
    spent, events, j = 0.0, 0, 0
    for name, start, dur in T.leaves(ops):
        while j < len(runs) and runs[j][1] <= start:
            j += 1
        if j < len(runs) and runs[j][0] <= start \
                and moe_grouped.classify(name) == "gmm":
            spent, events = spent + dur, events + 1
    if not spent or not need:
        return None
    ctx.note({"moe_grouped_roofline": {
        "decode_token_steps": steps, "pairs": pairs, "experts_hit": hit,
        "pairs_per_token_layer": c["moe_local_pairs"]
        / max(1, c["moe_token_layers"]),
        "events": events, "floor_s": need, "device_s": spent,
        "bound": bound}})
    return 100.0 * need / spent

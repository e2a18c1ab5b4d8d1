"""Block-sparse decode attention's share of its roofline: the least time the
chip could take to read the compressed keys of each context's whole windows
and the keys and values of the blocks it chooses
(benchmarks/kernels/sparse_decode.py; memory-bound), summed over the decode
token steps of the traced window and the ``minicpm4`` layers, over the device
time under the scopes ``sparse_select`` and ``sparse_attn`` of the decode
programs (what ``sparse_attn_decode_ms`` reads). The note sets the blocks the
floor counts as chosen beside the program's own counter over the window."""

from benchmarks.harness import device
from benchmarks.harness import program_trace as P
from benchmarks.kernels import flash, sparse_decode
from benchmarks.layer_metrics.sparse_attn_decode_ms import seconds


def read(ctx, result):
    a = result["facts"]["arch"]
    if result.get("trace") is None or not hasattr(a, "sparse"):
        return None
    spent = seconds(ctx, result)
    lo, hi = result["facts"]["traced_steps"]
    peaks = device.peaks(ctx.device["kind"])
    layers = sparse_decode.sparse_layers(a)
    need, chosen, visible = 0.0, 0, 0
    for s in result["served"].steps[lo:hi]:
        if not s["decode_kernel_steps"]:
            continue
        t, _ = flash.floor_seconds(*sparse_decode.call(
            s["decode_contexts"], a.sparse, a.num_attention_heads,
            a.num_key_value_heads, a.head_dim), peaks)
        need += t * layers
        for n in s["decode_contexts"]:
            c, v = sparse_decode.blocks_read(n, a.sparse)
            chosen, visible = chosen + c, visible + v
    if not spent or not need:
        return None
    ctx.note({"sparse_decode_roofline": {
        "floor_s": need, "device_s": spent, "bound": "memory",
        "floor_selected_share": chosen / max(1, visible),
        "counter_selected_share": P.counter_ratio(
            result, "sparse_blocks_selected", "sparse_blocks_visible")}})
    return 100.0 * need / spent

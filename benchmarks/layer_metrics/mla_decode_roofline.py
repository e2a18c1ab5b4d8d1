"""Latent decode attention's share of its roofline: the least time the chip
could take to read each context token's latent once and do the absorbed
form's two products (benchmarks/kernels/mla_decode.py), summed over the
decode token steps of the traced window and the layers, over the device time
under the scope ``mla_attn`` of the decode programs (the ``mla_decode``
kernel). The floor counts context *tokens* at the latent's own width; the
kernel fetches whole pages of rows padded to whole lane tiles (576 -> 640),
so the share reads low and never above 100%. The note sets the tokens the
pages held whose copies the kernel counted as it started them
(``mla_pages_read`` x the page) beside the tokens it was asked to read
(``mla_context_tokens``), over the window."""

from benchmarks.harness import device
from benchmarks.harness import program_trace as P
from benchmarks.kernels import flash, mla_decode
from benchmarks.layer_metrics.gdn_decode_ms import scope_seconds
from benchmarks.layer_metrics.kv_pool_copy_ms import DECODE_PROGRAMS


def read(ctx, result):
    a = result["facts"]["arch"]
    if result.get("trace") is None or not hasattr(a, "kv_lora_rank"):
        return None
    pt = P.open_run(ctx, result)
    got = pt and scope_seconds(pt, DECODE_PROGRAMS, "mla_attn")
    if not got or not got[0]:
        return None
    lo, hi = result["facts"]["traced_steps"]
    peaks = device.peaks(ctx.device["kind"])
    need, tokens, bound = 0.0, 0, None
    for s in result["served"].steps[lo:hi]:
        if not s["decode_kernel_steps"]:
            continue
        t, bound = flash.floor_seconds(*mla_decode.call(
            s["decode_contexts"], *mla_decode.sizes(a)), peaks)
        need += t * a.num_hidden_layers
        tokens += sum(s["decode_contexts"]) * a.num_hidden_layers
    if not need:
        return None
    c = result.get("counters", {}).get("engine", {})
    page = ctx.config["engine"]["kv_block_size"]
    ctx.note({"mla_decode_roofline": {
        "floor_s": need, "device_s": got[0], "bound": bound,
        "floor_context_tokens": tokens,
        "window_context_tokens": c.get("mla_context_tokens"),
        "window_page_tokens": c.get("mla_pages_read", 0) * page}})
    return 100.0 * need / got[0]

"""Model-FLOP/s utilization of the whole serving window under a looped
stack: the operations the token rows computed in the window require (the
architecture's own count, ``serve_flops_per_token`` of its reference module:
every pass of the layers for every row, the head, and for the rows of the
decode programs attention over their mean context; a prompt chunk's
attention, under 2% of its row, is left out, so the share reads low and
never high) over the window's seconds and the chip's published bf16 peak.
Rows: the window's delta of ``rows_<program>``, the rows a call really
carried. Not a kernel's roofline share: a decode step is bound by the
memory, and this is how little of the arithmetic peak the loop's re-reads
leave (about 3%). Needs a chip: a CPU rate is no device metric."""

from benchmarks.harness import device, manifest
from benchmarks.harness import program_calls as C

DECODE = ("decode", "multi_decode")


def read(ctx, result):
    a = result["facts"]["arch"]
    ref = manifest.reference_of(ctx.config, ctx.bench_dir)
    if ctx.device["platform"] != "tpu" \
            or not hasattr(ref, "serve_flops_per_token"):
        return None
    rows = C.counted(result, C.per_program("rows"))
    decode_rows = C.counted(result, C.per_program("rows", DECODE))
    w = result.get("window", {})
    seconds = w.get("t_end", w.get("t1", 0.0)) - w.get("t0", 0.0)
    if not rows or decode_rows is None or seconds <= 0:
        return None
    ctxs = [c for s in result["served"].steps
            for c in s.get("decode_contexts", ()) if c > 0]
    mean_ctx = sum(ctxs) / len(ctxs) if ctxs else 0.0
    need = (decode_rows * ref.serve_flops_per_token(a, mean_ctx)
            + (rows - decode_rows) * ref.serve_flops_per_token(a, 0.0))
    peak = device.peaks(ctx.device["kind"])["bf16_flops"]
    ctx.note({"loop_serve_mfu": {
        "rows": rows, "decode_rows": decode_rows, "seconds": seconds,
        "mean_decode_context": mean_ctx,
        "gflop_per_decode_row": ref.serve_flops_per_token(a, mean_ctx) / 1e9}})
    return 100.0 * need / seconds / peak

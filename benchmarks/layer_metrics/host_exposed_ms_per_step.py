"""Milliseconds the device sat idle inside one of the program's own step
spans (``dstpu/train_batch``, ``dstpu/serve_step``): the host time a step
exposes. Prints, as a note, the idle seconds by the innermost ``dstpu/``
span that was open: which host phase the device waited for. The dotted
names (``.train``, ``.gen``) are this one reader: cells that report
different end-to-end metrics need a name each."""

from benchmarks.harness import program_trace as P

PARENT = {"train": "train_batch", "serve": "serve_step"}


def read(ctx, result):
    pt = P.open_run(ctx, result)
    if pt is None:
        return None
    out = P.host_exposed(pt, PARENT[ctx.config["kind"]])
    if out is None:
        return None
    ctx.note({"host_exposed": dict(out, parent=PARENT[ctx.config["kind"]])})
    return out["ms_per_span"]

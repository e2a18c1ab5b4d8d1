#!/usr/bin/env python
"""kernel_bench — per-kernel win/loss micro-bench vs the XLA fallback.

The dispatch layer (ops/registry.py + ops/kernel_table.py) routes each
op by a measured per-(kernel, shape-bucket) win/loss table instead of a
static seq-length threshold. This harness produces that table: for every
kernel tier entry it times the Pallas kernel against the XLA fallback on
the same shapes (fwd+bwd where the kernel is differentiable), sweeps the
legal block-geometry candidates, and records the best geometry + the
win ratio (xla_ms / kernel_ms; >= 1.0 means the kernel earns its slot).

Rows are persisted with :func:`kernel_table.record` — on TPU straight
into ``docs/autotuned/kernel_table.json`` (the committed artifact the
dispatcher consults), elsewhere into a scratch table unless
``KERNEL_BENCH_RECORD_PATH`` says otherwise, so a CPU smoke run never
rewrites TPU measurements. Entries are backend-scoped either way.

Gates (fail-loud, ``make bench-kernels`` exits nonzero):
  - numerics: every kernel's forward must match its XLA fallback
    (allclose at output dtype tolerance) on every benched bucket;
  - dispatch consultation: after recording, a losing bucket must route
    through ``multi_head_attention`` to XLA **bit-identically**, and a
    winning bucket must dispatch to the kernel — the off-switch assert
    quantization established, applied to the kernel tier.

Env knobs: KERNEL_BENCH_KERNELS (csv of flash,paged,gmm,blocksparse),
KERNEL_BENCH_FULL=1 (real-shape sweep — slow tier, see
tests/slow_tests.txt), KERNEL_BENCH_ITERS, KERNEL_BENCH_RECORD_PATH.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

SCHEMA = "kernel_bench/v1"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _iters() -> int:
    if os.environ.get("KERNEL_BENCH_ITERS"):
        return max(1, int(os.environ["KERNEL_BENCH_ITERS"]))
    return 10 if _on_tpu() else 2


def _time_ms(fn, *args) -> float:
    """Median wall ms of a jitted callable (compile excluded)."""
    jitted = jax.jit(fn)
    out = jitted(*args)  # compile + warmup
    jax.block_until_ready(out)
    times = []
    for _ in range(_iters()):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def _allclose(a, b, dtype) -> bool:
    tol = 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 2e-5
    return bool(np.allclose(np.asarray(a, np.float32),
                            np.asarray(b, np.float32),
                            rtol=tol, atol=tol))


# ---------------------------------------------------------------------------
# per-kernel arms: each returns one win/loss row
#   {kernel, bucket, kernel_ms, xla_ms, ratio, blocks, numerics_ok}
# ---------------------------------------------------------------------------


def bench_flash(seq: int, head_dim: int, heads: int = 4, kv_heads: int = None,
                batch: int = 1, causal: bool = True,
                block_candidates: Optional[List[Tuple[int, int]]] = None,
                ) -> Dict[str, Any]:
    """Flash attention vs xla_attention, fwd+bwd, block sweep."""
    from deepspeed_tpu.ops import kernel_table
    from deepspeed_tpu.ops.attention import xla_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    kv_heads = kv_heads or heads
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((batch, seq, heads, head_dim)), dt)
    k = jnp.asarray(rng.standard_normal((batch, seq, kv_heads, head_dim)), dt)
    v = jnp.asarray(rng.standard_normal((batch, seq, kv_heads, head_dim)), dt)

    def xla_loss(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=causal)
                       .astype(jnp.float32))

    xla_ms = _time_ms(jax.value_and_grad(xla_loss, argnums=(0, 1, 2)),
                      q, k, v)
    xla_out = xla_attention(q, k, v, causal=causal)

    if block_candidates is None:
        block_candidates = [(b, b) for b in (128, 256, 512, 1024)
                            if b <= seq and seq % b == 0] or [(seq, seq)]
    best = None
    numerics_ok = True
    for bq, bk in block_candidates:
        def loss(q, k, v, bq=bq, bk=bk):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           block_q=bq, block_k=bk)
                           .astype(jnp.float32))

        ms = _time_ms(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, k, v)
        out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
        numerics_ok = numerics_ok and _allclose(out, xla_out, dt)
        if best is None or ms < best[0]:
            best = (ms, {"block_q": bq, "block_k": bk})
    return {"kernel": "flash_attention",
            "bucket": kernel_table.attention_bucket(seq, head_dim, causal),
            "kernel_ms": round(best[0], 4), "xla_ms": round(xla_ms, 4),
            "ratio": round(xla_ms / best[0], 4), "blocks": best[1],
            "numerics_ok": numerics_ok}


def _paged_xla_reference(q, kv_layer, block_table, context_lens):
    """Gather-path XLA fallback: pull each sequence's pages dense, mask,
    softmax — what the serving step runs when the kernel loses."""
    S, nh, hd = q.shape
    nb, bs, _, nkv, _ = kv_layer.shape
    Bm = block_table.shape[1]
    gathered = kv_layer[block_table]              # [S, Bm, bs, 2, nkv, hd]
    kvs = gathered.reshape(S, Bm * bs, 2, nkv, hd)
    keys, values = kvs[:, :, 0], kvs[:, :, 1]
    rep = nh // nkv
    keys = jnp.repeat(keys, rep, axis=2)
    values = jnp.repeat(values, rep, axis=2)
    s = jnp.einsum("snd,smnd->snm", q.astype(jnp.float32),
                   keys.astype(jnp.float32)) / jnp.sqrt(float(hd))
    pos = jnp.arange(Bm * bs)[None, None, :]
    s = jnp.where(pos < context_lens[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("snm,smnd->snd", p, values.astype(jnp.float32))
    return jnp.where((context_lens > 0)[:, None, None],
                     out.astype(q.dtype), 0)


def bench_paged(S: int, heads: int, kv_heads: int, head_dim: int,
                block_size: int, max_pages: int,
                page_candidates: Optional[List[int]] = None
                ) -> Dict[str, Any]:
    """Paged decode attention vs the gather-path XLA fallback, sweeping
    pages_per_compute_block (fwd only — decode is inference)."""
    from deepspeed_tpu.ops import kernel_table
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_decode_attention

    rng = np.random.default_rng(1)
    nb = S * max_pages + 2
    kv = jnp.asarray(rng.standard_normal(
        (nb, block_size, 2, kv_heads, head_dim)), jnp.float32)
    ctx = np.full((S,), max_pages * block_size, np.int32)
    table = np.zeros((S, max_pages), np.int32)
    used = 1
    for s in range(S):
        for j in range(max_pages):
            table[s, j] = used
            used += 1
    q = jnp.asarray(rng.standard_normal((S, heads, head_dim)), jnp.float32)
    table, ctx = jnp.asarray(table), jnp.asarray(ctx)

    xla_ms = _time_ms(_paged_xla_reference, q, kv, table, ctx)
    xla_out = _paged_xla_reference(q, kv, table, ctx)

    if page_candidates is None:
        page_candidates = [p for p in (1, 2, 4, 8) if p <= max_pages]
    best = None
    numerics_ok = True
    for p in page_candidates:
        def run(q, kv, table, ctx, p=p):
            return paged_decode_attention(q, kv, table, ctx,
                                          pages_per_compute_block=p)

        ms = _time_ms(run, q, kv, table, ctx)
        out = run(q, kv, table, ctx)
        numerics_ok = numerics_ok and _allclose(out, xla_out, jnp.float32)
        if best is None or ms < best[0]:
            best = (ms, {"pages_per_compute_block": p})
    seq = max_pages * block_size
    return {"kernel": "paged_attention",
            "bucket": kernel_table.attention_bucket(seq, head_dim, True),
            "kernel_ms": round(best[0], 4), "xla_ms": round(xla_ms, 4),
            "ratio": round(xla_ms / best[0], 4), "blocks": best[1],
            "numerics_ok": numerics_ok}


def bench_gmm(M: int, K: int, N: int, groups: int,
              tile_candidates: Optional[List[Tuple[int, int, int]]] = None
              ) -> Dict[str, Any]:
    """Grouped matmul vs the dense masked-matmul XLA fallback (the
    capacity-einsum shape MoE runs without the kernel), fwd+bwd."""
    from deepspeed_tpu.ops import kernel_table
    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm

    rng = np.random.default_rng(2)
    dt = jnp.bfloat16
    lhs = jnp.asarray(rng.standard_normal((M, K)), dt)
    rhs = jnp.asarray(rng.standard_normal((groups, K, N)), dt)
    sizes = np.full((groups,), M // groups, np.int32)
    sizes[-1] += M - sizes.sum()
    group_sizes = jnp.asarray(sizes)
    gid = jnp.asarray(np.repeat(np.arange(groups), sizes), jnp.int32)

    def xla_loss(lhs, rhs):
        out = jnp.zeros((M, N), jnp.float32)
        for e in range(groups):
            mask = (gid == e).astype(jnp.float32)[:, None]
            out = out + mask * (lhs.astype(jnp.float32)
                                @ rhs[e].astype(jnp.float32))
        return jnp.sum(out)

    xla_ms = _time_ms(jax.value_and_grad(xla_loss, argnums=(0, 1)),
                      lhs, rhs)
    want = jnp.concatenate(
        [lhs[int(sizes[:e].sum()):int(sizes[:e + 1].sum())] @ rhs[e]
         for e in range(groups)], axis=0)

    if tile_candidates is None:
        tile_candidates = [(128, 128, 128), (256, 256, 128),
                           (512, 1024, 512)]
    best = None
    numerics_ok = True
    for bm, bn, bk in tile_candidates:
        def loss(lhs, rhs, t=(bm, bn, bk)):
            return jnp.sum(gmm(lhs, rhs, group_sizes, *t)
                           .astype(jnp.float32))

        ms = _time_ms(jax.value_and_grad(loss, argnums=(0, 1)), lhs, rhs)
        out = gmm(lhs, rhs, group_sizes, bm, bn, bk)
        numerics_ok = numerics_ok and _allclose(out, want, dt)
        if best is None or ms < best[0]:
            best = (ms, {"block_m": bm, "block_n": bn, "block_k": bk})
    return {"kernel": "grouped_matmul",
            "bucket": kernel_table.gmm_bucket(M, K, N, groups),
            "kernel_ms": round(best[0], 4), "xla_ms": round(xla_ms, 4),
            "ratio": round(xla_ms / best[0], 4), "blocks": best[1],
            "numerics_ok": numerics_ok}


def bench_blocksparse(seq: int, head_dim: int, heads: int = 4,
                      batch: int = 1, block: int = 128) -> Dict[str, Any]:
    """Pallas block-sparse forward vs the differentiable XLA form on the
    same layout (forward-only — the Pallas path is the no-grad tier)."""
    from deepspeed_tpu.ops import kernel_table
    from deepspeed_tpu.ops.pallas.blocksparse_attention import (
        FixedSparsityConfig, blocksparse_attention,
        blocksparse_attention_pallas)

    sparsity = FixedSparsityConfig(block=block, num_local_blocks=2)
    rng = np.random.default_rng(3)
    dt = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((batch, seq, heads, head_dim)), dt)
    k = jnp.asarray(rng.standard_normal((batch, seq, heads, head_dim)), dt)
    v = jnp.asarray(rng.standard_normal((batch, seq, heads, head_dim)), dt)

    def xla_run(q, k, v):
        return blocksparse_attention(q, k, v, sparsity, causal=True)

    def pallas_run(q, k, v):
        return blocksparse_attention_pallas(q, k, v, sparsity, causal=True)

    xla_ms = _time_ms(xla_run, q, k, v)
    kernel_ms = _time_ms(pallas_run, q, k, v)
    numerics_ok = _allclose(pallas_run(q, k, v), xla_run(q, k, v), dt)
    return {"kernel": "blocksparse_attention",
            "bucket": kernel_table.attention_bucket(seq, head_dim, True),
            "kernel_ms": round(kernel_ms, 4), "xla_ms": round(xla_ms, 4),
            "ratio": round(xla_ms / kernel_ms, 4),
            "blocks": {"block": block}, "numerics_ok": numerics_ok}


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def _shapes(full: bool) -> Dict[str, Dict[str, Any]]:
    """Bench shapes: the smoke tier runs everywhere in seconds; the full
    tier sweeps the real-shape buckets (8L·131k-vocab model attention at
    its training seq) and belongs in tests/slow_tests.txt."""
    if full:
        return {
            "flash": {"seq": 4096, "head_dim": 64, "heads": 8,
                      "kv_heads": 8, "batch": 4},
            "paged": {"S": 8, "heads": 16, "kv_heads": 2, "head_dim": 128,
                      "block_size": 16, "max_pages": 16},
            "gmm": {"M": 8192, "K": 1024, "N": 4096, "groups": 8},
            "blocksparse": {"seq": 2048, "head_dim": 64, "heads": 8},
        }
    return {
        "flash": {"seq": 256, "head_dim": 32, "heads": 4, "kv_heads": 4,
                  "batch": 1},
        "paged": {"S": 2, "heads": 8, "kv_heads": 2, "head_dim": 64,
                  "block_size": 16, "max_pages": 4},
        "gmm": {"M": 256, "K": 128, "N": 256, "groups": 4},
        "blocksparse": {"seq": 256, "head_dim": 32, "heads": 4},
    }


_ARMS = {"flash": bench_flash, "paged": bench_paged, "gmm": bench_gmm,
         "blocksparse": bench_blocksparse}


def _record_path() -> str:
    """Where measured rows land. TPU runs refresh the committed table;
    elsewhere default to a scratch file so a CPU smoke run neither
    rewrites TPU measurements nor changes later CPU dispatch."""
    from deepspeed_tpu.ops import kernel_table

    if os.environ.get("KERNEL_BENCH_RECORD_PATH"):
        return os.environ["KERNEL_BENCH_RECORD_PATH"]
    if os.environ.get("DSTPU_KERNEL_TABLE"):
        return os.environ["DSTPU_KERNEL_TABLE"]
    if _on_tpu():
        return str(kernel_table.DEFAULT_TABLE)
    import tempfile

    return os.path.join(tempfile.gettempdir(), "dstpu_kernel_table.json")


def _dispatch_probe(rows: List[Dict[str, Any]], path: str
                    ) -> List[Dict[str, Any]]:
    """The off-switch assert: the freshly recorded table must actually
    steer multi_head_attention. A losing flash bucket must produce the
    XLA result bit-for-bit; a winning one must dispatch to the kernel."""
    from deepspeed_tpu.ops import attention as attn_ops
    from deepspeed_tpu.ops import kernel_table

    violations = []
    flash_rows = [r for r in rows if r["kernel"] == "flash_attention"]
    if not flash_rows:
        return violations
    old_env = os.environ.get("DSTPU_KERNEL_TABLE")
    os.environ["DSTPU_KERNEL_TABLE"] = path
    kernel_table.invalidate_cache()
    try:
        for row in flash_rows:
            # reconstruct the benched shape from the bucket label
            seq = int(row["bucket"].split("_")[0][1:])
            hd = int(row["bucket"].split("_")[1][1:])
            rng = np.random.default_rng(7)
            dt = jnp.bfloat16
            q = jnp.asarray(rng.standard_normal((1, seq, 4, hd)), dt)
            k = jnp.asarray(rng.standard_normal((1, seq, 4, hd)), dt)
            v = jnp.asarray(rng.standard_normal((1, seq, 4, hd)), dt)
            before = attn_ops.dispatch_stats()
            out = attn_ops.multi_head_attention(q, k, v, causal=True)
            after = attn_ops.dispatch_stats()
            won = row["ratio"] >= 1.0
            took_pallas = after["pallas"] > before["pallas"]
            if won and not took_pallas:
                violations.append(
                    {"gate": "dispatch_consults_table", "row": row,
                     "detail": f"winning bucket {row['bucket']} did not "
                               f"dispatch to the kernel"})
            if not won:
                want = attn_ops.xla_attention(q, k, v, causal=True)
                if took_pallas or not bool(
                        jnp.array_equal(out, want)):
                    violations.append(
                        {"gate": "losing_bucket_bit_identical", "row": row,
                         "detail": f"losing bucket {row['bucket']} must "
                                   f"route to XLA bit-identically"})
    finally:
        if old_env is None:
            os.environ.pop("DSTPU_KERNEL_TABLE", None)
        else:
            os.environ["DSTPU_KERNEL_TABLE"] = old_env
        kernel_table.invalidate_cache()
    return violations


def run_kernel_bench() -> Tuple[str, Dict[str, Any], bool]:
    """Run the selected arms, record rows, gate, and report.

    Returns (markdown table, JSON payload, ok).
    """
    from deepspeed_tpu.ops import attention as attn_ops
    from deepspeed_tpu.ops import kernel_table
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    full = bool(int(os.environ.get("KERNEL_BENCH_FULL", "0")))
    names = [n.strip() for n in os.environ.get(
        "KERNEL_BENCH_KERNELS", "flash,paged,gmm,blocksparse").split(",")
        if n.strip()]
    shapes = _shapes(full)
    rows, errors = [], []
    for name in names:
        if name not in _ARMS:
            errors.append({"gate": "unknown_kernel", "detail": name})
            continue
        try:
            rows.append(_ARMS[name](**shapes[name]))
        except Exception as e:  # a broken arm is a finding, not a crash
            errors.append({"gate": "arm_crashed", "kernel": name,
                           "detail": str(e)[:300]})

    path = _record_path()
    for row in rows:
        kernel_table.record(row["kernel"], row["bucket"],
                            row["kernel_ms"], row["xla_ms"],
                            blocks=row["blocks"], path=path)

    violations = list(errors)
    violations += [{"gate": "numerics", "row": r,
                    "detail": f"{r['kernel']} forward diverged from the "
                              f"XLA fallback on {r['bucket']}"}
                   for r in rows if not r["numerics_ok"]]
    violations += _dispatch_probe(rows, path)

    winning = sorted(f"{r['kernel']}:{r['bucket']}"
                     for r in rows if r["ratio"] >= 1.0)
    ratios = [r["ratio"] for r in rows]
    geomean = float(np.exp(np.mean(np.log(ratios)))) if ratios else 0.0
    payload = {
        "schema": SCHEMA,
        "metric": "kernel_win_ratio_geomean",
        "value": round(geomean, 4),
        "unit": "x",
        "backend": jax.default_backend(),
        "full": full,
        "table_path": path,
        "entries": rows,
        "winning_kernels": winning,
        "violations": violations,
        "ok": not violations,
    }
    lines = ["### kernel win/loss — Pallas vs XLA fallback "
             f"({payload['backend']}, {'full' if full else 'smoke'} tier)",
             "",
             "| kernel | bucket | kernel ms | XLA ms | ratio | blocks | "
             "verdict |",
             "|---|---|---|---|---|---|---|"]
    for r in rows:
        verdict = "WIN" if r["ratio"] >= 1.0 else "loss"
        if not r["numerics_ok"]:
            verdict = "NUMERICS-FAIL"
        blocks = ",".join(f"{k}={v}" for k, v in r["blocks"].items())
        lines.append(f"| {r['kernel']} | {r['bucket']} | "
                     f"{r['kernel_ms']} | {r['xla_ms']} | {r['ratio']} | "
                     f"{blocks} | {verdict} |")
    lines += ["", f"table → {path}"]
    if violations:
        lines += ["", f"{len(violations)} gate violation(s) — exit nonzero"]
    return "\n".join(lines), payload, not violations


def main() -> int:
    table, payload, ok = run_kernel_bench()
    print(table)
    print(json.dumps(payload))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())

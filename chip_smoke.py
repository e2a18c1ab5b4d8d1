"""The quickest proof that the system starts on the chip.

    python chip_smoke.py              # one TPU chip: train, serve, kernels
    python chip_smoke.py --chips 4    # four chips: the cross-chip phase only

Drives the two main paths through the entry points a user calls —
``dstpu.initialize`` -> ``engine.train_batch`` and
``InferenceEngineV2.put`` -> ``generate_all`` — at the full published width
of ``mistral-7b`` (h=4096, 32 q / 8 kv heads, D=128, ffn 14336, vocab
32000) with depth cut to what one 16 GB v5e chip holds and random weights
from ``--seed``; then runs every Pallas kernel of those paths once against
its ``jax.numpy`` reference. Everything runs in this one process (a chip
belongs to one process). One JSON object per phase goes to stdout; the
last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The run fails — ``"ok": false`` last, non-zero exit — when JAX finds no
TPU, when a phase raises, or when a check does not hold. The times it
prints are smoke output of one run, not benchmark results.

``--rehearse`` runs the same control flow at toy sizes wherever it is
(the CPU included) to find wrong paths and arguments before a chip call.
It can never report success: without a TPU the last line is still
``"ok": false``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
import traceback


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run holds on the device. REAL is sized from the AOT compiles
    of the whole step programs for a described v5e (memory_analysis, GiB;
    PERF.md "Cells"): the deepest ZeRO-3 train step that fits the chip's
    15.75 is 3 layers, at micro 2 (14.95; 4 layers of state alone are
    14.8); the one-device side of the cross-chip comparison needs the
    whole global batch of 4 on one chip, which fits at 2 layers (12.7);
    the serving programs at 16 layers and a 2.0 GiB KV pool peak at 14.1
    in the multi-step decode, which holds the pool three and a half times
    over (20 layers: 15.7)."""

    model_overrides: dict
    train_layers: int
    train_micro: int
    cross_chip_layers: int
    train_seq: int
    serve_layers: int
    kv_blocks: int
    prompt_lens: tuple          # one request each; the first two share
    shared_prefix: int          # this many leading tokens
    new_tokens: int
    max_tokens_per_step: int
    kernel_seq: int             # flash S (x4 for the long case)
    kernel_ctx_pages: int       # paged attention: pages per sequence
    gmm: tuple                  # M, K, N, E


REAL = Sizes(
    model_overrides={}, train_layers=3, train_micro=2, cross_chip_layers=2,
    train_seq=2048, serve_layers=16, kv_blocks=2080,
    prompt_lens=(384, 448, 256, 320, 512, 288, 480, 352), shared_prefix=256,
    new_tokens=64, max_tokens_per_step=256,
    kernel_seq=2048, kernel_ctx_pages=64, gmm=(8192, 4096, 14336, 8))

TOY = Sizes(
    model_overrides=dict(hidden_size=128, num_heads=4, num_kv_heads=2,
                         ffn_size=256, vocab_size=512),
    train_layers=3, train_micro=2, cross_chip_layers=2, train_seq=128,
    serve_layers=2, kv_blocks=96,
    prompt_lens=(48, 56, 32, 40, 64, 36, 60, 44), shared_prefix=32,
    new_tokens=12, max_tokens_per_step=64,
    kernel_seq=256, kernel_ctx_pages=4, gmm=(256, 128, 256, 4))

KV_BLOCK = 16
# bf16 kernels against their references: max |a-b| over max |b|
KERNEL_TOL = 3e-2
# sharded vs one-device losses: same math, other reduction order, bf16
CROSS_CHIP_RTOL = 2e-2


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _on_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def _peak_bytes():
    import jax

    stats = jax.local_devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _compile_events() -> int:
    from deepspeed_tpu.observability.hub import compile_stats

    return int(compile_stats()["events"])


def _train_config(micro: int) -> dict:
    # README.md "Quickstart", with the batch sized for one chip
    return {
        "train_micro_batch_size_per_chip": micro,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": True},
        "activation_checkpointing": {"policy": "nothing_saveable"},
        "steps_per_print": 1_000_000,
    }


def _train_engine(sz: Sizes, seed: int, layers: int, micro: int, **mesh_kw):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.zoo import get_model

    model = get_model("mistral-7b", num_layers=layers,
                      max_seq_len=sz.train_seq, **sz.model_overrides)
    config = dict(_train_config(micro), seed=seed)
    engine, _, _, _ = dstpu.initialize(model=model, config=config,
                                       **mesh_kw)
    return model, engine


def _drop_state(engine) -> None:
    """``Engine.close()`` drains the window and stops the engine's own
    threads; process-wide hooks (flight recorder, signal handlers) still
    hold the object, and the next phase needs the chip's memory, so its
    device state is dropped by hand."""
    engine.close()
    engine.params = engine.opt_state = None
    gc.collect()


def _repeated_batch(vocab: int, batch: int, seq: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    fixed = {"input_ids": rng.integers(0, vocab, (batch, seq + 1))
             .astype(np.int32)}
    while True:
        yield fixed


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def phase_train(sz: Sizes, seed: int) -> dict:
    """ZeRO-3 training on one chip: 2 warm-up + 5 timed steps on one
    repeated batch."""
    import jax

    from deepspeed_tpu.ops import attention as attn_ops

    t0 = time.perf_counter()
    attn_ops._reset_dispatch_stats()
    model, engine = _train_engine(sz, seed, sz.train_layers, sz.train_micro)
    data = _repeated_batch(model.config.vocab_size, engine.train_batch_size,
                           sz.train_seq, seed)
    losses = [float(engine.train_batch(data)) for _ in range(2)]
    setup_s = time.perf_counter() - t0

    compiles0 = _compile_events()
    t1 = time.perf_counter()
    timed = [engine.train_batch(data) for _ in range(5)]
    jax.block_until_ready(timed)
    step_s = (time.perf_counter() - t1) / len(timed)
    compiles = _compile_events() - compiles0
    losses += [float(x) for x in timed]

    dispatch = attn_ops.dispatch_stats()
    check(all(math.isfinite(x) for x in losses),
          f"train: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall on a repeated batch: {losses}")
    check(compiles == 0,
          f"train: {compiles} compilation events inside the timed steps")
    if _on_tpu():
        check(dispatch["pallas"] > 0 and dispatch["xla"] == 0,
              f"train: attention did not take the Pallas flash path: "
              f"{dispatch}")
    tokens = engine.train_batch_size * sz.train_seq
    peak = _peak_bytes()
    _drop_state(engine)
    return {"phase": "train", "ok": True, "note": "smoke output, one run",
            "model": "mistral-7b", "layers": sz.train_layers,
            "params": model.num_params(), "seq": sz.train_seq,
            "global_batch": engine.train_batch_size, "zero_stage": 3,
            "losses": [round(x, 4) for x in losses],
            "step_ms": round(step_s * 1e3, 2),
            "tokens_per_s": round(tokens / step_s, 1),
            "attention_dispatch": dispatch,
            "compiles_in_timed_steps": compiles,
            "peak_bytes_in_use": peak,
            "setup_s": round(setup_s, 2)}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _prompts(sz: Sizes, vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
               for n in sz.prompt_lens]
    prompts[1][:sz.shared_prefix] = prompts[0][:sz.shared_prefix]
    return prompts


def phase_serve(sz: Sizes, seed: int) -> dict:
    """Greedy serving on one chip, twice over the same weights: the
    second engine must reproduce the first one's streams."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.zoo import get_model

    t0 = time.perf_counter()
    model = get_model("mistral-7b", num_layers=sz.serve_layers,
                      max_seq_len=1024, param_dtype=jnp.bfloat16,
                      remat=False, **sz.model_overrides)
    cfg = model.config
    prompts = _prompts(sz, cfg.vocab_size, seed)
    uids = list(range(len(prompts)))
    blocks_per_seq = -(-(max(sz.prompt_lens) + sz.new_tokens + 1)
                       // KV_BLOCK) + 2

    def engine(params=None):
        return InferenceEngineV2(
            model, params=params, kv_blocks=sz.kv_blocks,
            kv_block_size=KV_BLOCK,
            max_tokens_per_step=sz.max_tokens_per_step,
            max_seqs_per_step=len(prompts),
            max_blocks_per_seq=blocks_per_seq, dtype=jnp.bfloat16,
            seed=seed)

    def run(eng):
        eng.put(uids, prompts, max_new_tokens=sz.new_tokens)
        t = time.perf_counter()
        out = eng.generate_all()
        return out, time.perf_counter() - t

    first = engine()
    kv_bytes = first.kv_cache.data.nbytes
    streams, first_s = run(first)
    setup_s = time.perf_counter() - t0  # init + every compile + first run
    stats = first.log_summary()
    params = first.params
    first.close()
    del first
    gc.collect()  # the first engine's KV pool goes before the second's

    compiles0 = _compile_events()
    second = engine(params)
    again, second_s = run(second)
    compiles = _compile_events() - compiles0
    second.close()

    for uid in uids:
        check(len(streams.get(uid, ())) == sz.new_tokens,
              f"serve: request {uid} finished with "
              f"{len(streams.get(uid, ()))} of {sz.new_tokens} tokens")
    check(streams == again,
          "serve: a second engine on the same weights and prompts gave "
          "different token streams")
    check(stats["decode_kernel_steps"] > 0,
          "serve: no decode step took the paged-attention kernel")
    if not sz.model_overrides:
        check(kv_bytes >= 2e9, f"serve: KV pool of {kv_bytes} bytes is not "
                               "a real one (>= 2 GB)")
    new = len(uids) * sz.new_tokens
    return {"phase": "serve", "ok": True, "note": "smoke output, one run",
            "model": "mistral-7b", "layers": sz.serve_layers,
            "params": model.num_params(), "kv_pool_bytes": kv_bytes,
            "requests": len(uids), "prompt_tokens": sum(sz.prompt_lens),
            "shared_prefix_tokens": sz.shared_prefix,
            "new_tokens_per_request": sz.new_tokens,
            "streams_identical": True,
            "decode_kernel_steps": stats["decode_kernel_steps"],
            "prefill_kernel_steps": stats["prefill_kernel_steps"],
            "prefill_gather_fallbacks": stats["prefill_gather_fallbacks"],
            "fallback_reasons": stats["fallback_reasons"],
            "prefix_hit_tokens": stats["prefix_hit_tokens"],
            "second_run_s": round(second_s, 3),
            "second_run_new_tokens_per_s": round(new / second_s, 1),
            "compiles_in_second_run": compiles,
            "peak_bytes_in_use": _peak_bytes(),
            "setup_s": round(setup_s, 2)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _rel_err(got, want) -> float:
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-9))


def _paged_reference(q, kv, block_table, q_pos):
    """The gather path (model_runner.ragged_forward's attention): pull
    each sequence's pages into a dense context, mask by position.
    q [S, Tq, nh, hd]; q_pos [S, Tq] absolute positions."""
    import jax
    import jax.numpy as jnp

    S, _, nh, hd = q.shape
    nkv = kv.shape[3]
    ctx = kv[block_table].reshape(S, -1, 2, nkv, hd).astype(jnp.float32)
    k = jnp.repeat(ctx[:, :, 0], nh // nkv, axis=2)  # [S, L, nh, hd]
    v = jnp.repeat(ctx[:, :, 1], nh // nkv, axis=2)
    s = jnp.einsum("stnd,slnd->stnl", q.astype(jnp.float32), k) / hd ** 0.5
    visible = (jnp.arange(k.shape[1])[None, None, None, :]
               <= q_pos[:, :, None, None])
    p = jax.nn.softmax(jnp.where(visible, s, -1e30), axis=-1)
    return jnp.einsum("stnl,slnd->stnd", p, v)


def phase_kernels(sz: Sizes, seed: int) -> dict:
    """Each Pallas kernel of the main paths once, at the main path's
    widths, against its jax.numpy reference."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.zoo import get_model
    from deepspeed_tpu.ops.attention import xla_attention
    from deepspeed_tpu.ops.pallas import (blocksparse_attention,
                                          flash_attention, grouped_matmul,
                                          paged_attention, quantization)

    t0 = time.perf_counter()
    on_tpu = _on_tpu()
    modules = (flash_attention, paged_attention, grouped_matmul,
               blocksparse_attention, quantization)
    if on_tpu:
        for mod in modules:
            check(mod._interpret() is False,
                  f"kernels: {mod.__name__} selects interpret mode on a TPU")
    cfg = get_model("mistral-7b", **sz.model_overrides).config
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    bf16 = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    cases = {}

    def rand(shape, dtype=bf16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def ran_on_device(fn, *args):
        # interpret=False leaves a Mosaic custom call in the program
        if on_tpu:
            check("tpu_custom_call" in jax.jit(fn).lower(*args).as_text(),
                  "kernels: no tpu_custom_call in the lowered program")

    def record(name, err, tol=KERNEL_TOL):
        cases[name] = round(err, 5)
        check(err <= tol, f"kernels: {name} is off its reference by "
                          f"{err:.4g} (tolerance {tol})")

    # -- flash attention: fwd + bwd, plain causal and packed segments ------
    B, S = 2, sz.kernel_seq
    blk = min(512, S)
    q, k, v = (rand((B, S, nh, hd)), rand((B, S, nkv, hd)),
               rand((B, S, nkv, hd)))
    # three packed documents per row
    seg = jnp.broadcast_to(
        (jnp.arange(S) >= S // 4).astype(jnp.int32)
        + (jnp.arange(S) >= (5 * S) // 8).astype(jnp.int32), (B, S))

    def flash(q, k, v, seg=None):
        return flash_attention.flash_attention(
            q, k, v, causal=True, segment_ids=seg, block_q=blk, block_k=blk)

    def ref(q, k, v, seg=None):
        return xla_attention(q, k, v, causal=True, segment_ids=seg)

    def both(fn):
        def loss(q, k, v, seg):
            return jnp.sum(fn(q, k, v, seg).astype(jnp.float32) ** 2)

        return jax.jit(lambda q, k, v, seg: (
            fn(q, k, v, seg), jax.grad(loss, argnums=(0, 1, 2))(q, k, v,
                                                               seg)))

    for name, sids in (("flash", None), ("flash_segment_ids", seg)):
        ran_on_device(flash, q, k, v, sids)
        out, grads = both(flash)(q, k, v, sids)
        want, want_grads = both(ref)(q, k, v, sids)
        record(f"{name}_fwd", _rel_err(out, want))
        for g, got_g, want_g in zip("qkv", grads, want_grads):
            record(f"{name}_d{g}", _rel_err(got_g, want_g))

    # long sequence, the 1024 block (fewer heads: the reference's scores
    # are [B, heads, S, S] in fp32)
    S4, hq4, hkv4 = 4 * S, max(nh // 4, 1), max(nkv // 4, 1)
    blk4 = min(1024, S4)
    q4, k4, v4 = (rand((1, S4, hq4, hd)), rand((1, S4, hkv4, hd)),
                  rand((1, S4, hkv4, hd)))
    record("flash_long_fwd", _rel_err(
        jax.jit(lambda q, k, v: flash_attention.flash_attention(
            q, k, v, causal=True, block_q=blk4, block_k=blk4))(q4, k4, v4),
        jax.jit(ref)(q4, k4, v4)))

    # -- paged attention: decode (the kernel's own block, then 1 and 4 pages
    # a block), chunked prefill ------------------------------------------
    seqs, pages = 16, sz.kernel_ctx_pages
    pool = rand((seqs * pages + 1, KV_BLOCK, 2, nkv, hd))
    table = (jax.random.permutation(next(keys), seqs * pages)
             .reshape(seqs, pages).astype(jnp.int32))
    max_ctx = pages * KV_BLOCK
    ctx_lens = jax.random.randint(next(keys), (seqs,), 1, max_ctx + 1)
    qd = rand((seqs, nh, hd))
    want = _paged_reference(qd[:, None], pool, table,
                            (ctx_lens - 1)[:, None])[:, 0]
    for ppb, name in ((0, "paged_decode_own_block"),
                      (1, "paged_decode_pages1"), (4, "paged_decode_pages4")):
        def decode(q, kv, bt, ctx, ppb=ppb):
            return paged_attention.paged_decode_attention(
                q, kv, bt, ctx, pages_per_compute_block=ppb)

        ran_on_device(decode, qd, pool, table, ctx_lens)
        record(name, _rel_err(
            jax.jit(decode)(qd, pool, table, ctx_lens), want))

    for tq in (64, 256):
        tq = min(tq, max_ctx // 2)
        segs = 4
        pos0 = jax.random.randint(next(keys), (segs,), 0, max_ctx - tq + 1)
        qp = rand((segs, tq, nh, hd))
        got = jax.jit(paged_attention.paged_prefill_attention)(
            qp, pool, table[:segs], pos0, pos0 + tq)
        ran_on_device(paged_attention.paged_prefill_attention,
                      qp, pool, table[:segs], pos0, pos0 + tq)
        want_p = _paged_reference(qp, pool, table[:segs],
                                  pos0[:, None] + jnp.arange(tq)[None])
        record(f"paged_prefill_tq{tq}", _rel_err(got, want_p))

    # -- grouped matmul (the MoE expert GEMM), fwd + bwd, uneven groups ----
    M, K, N, E = sz.gmm
    lhs, rhs = rand((M, K)), rand((E, K, N)) * 0.05
    cuts = jnp.sort(jax.random.randint(next(keys), (E - 1,), 0, M + 1))
    sizes = jnp.diff(jnp.concatenate(
        [jnp.zeros(1, cuts.dtype), cuts, jnp.full(1, M, cuts.dtype)])
    ).astype(jnp.int32)
    group = jnp.repeat(jnp.arange(E), sizes, total_repeat_length=M)

    def gmm_ref(lhs, rhs):
        # a plain matmul per expert, rows picked by their group
        out = jnp.zeros((M, N), jnp.float32)
        for e in range(E):
            rows = (group == e)[:, None]
            out = out + jnp.where(rows, jnp.dot(
                lhs, rhs[e], preferred_element_type=jnp.float32), 0.0)
        return out

    def gmm_both(fn):
        def loss(lhs, rhs):
            return jnp.sum(fn(lhs, rhs).astype(jnp.float32) ** 2) * 1e-3

        return jax.jit(lambda lhs, rhs: (
            fn(lhs, rhs), jax.grad(loss, argnums=(0, 1))(lhs, rhs)))

    def gmm(lhs, rhs):
        return grouped_matmul.gmm(lhs, rhs, sizes)

    ran_on_device(gmm, lhs, rhs)
    out, (dl, dr) = gmm_both(gmm)(lhs, rhs)
    want, (wdl, wdr) = gmm_both(gmm_ref)(lhs, rhs)
    record("gmm_fwd", _rel_err(out, want))
    record("gmm_dlhs", _rel_err(dl, wdl))
    record("gmm_drhs", _rel_err(dr, wdr))

    # -- quantizers: integer payloads within one step, scales exact --------
    # (520 rows: off the kernel's 256-row grid, so the tail tile counts)
    for name, x, bits, block in (
            ("quant_int8", rand((520, 2048)), 8, 2048),
            ("quant_int4", rand((512, 2048)), 4, 2048),
            ("kv_quant_int8", rand((256, 2, nkv, hd)), 8, hd)):
        if on_tpu and block % 128 == 0:
            ran_on_device(lambda x, b=bits, k=block:
                          quantization.quantize_blockwise(x, b, k), x)
        qv, sc = jax.jit(lambda x, b=bits, k=block:
                         quantization.quantize_blockwise(x, b, k))(x)
        wq, wsc = quantization._quantize_ref(x, bits, block)
        step = int(jnp.max(jnp.abs(qv.astype(jnp.int32)
                                   - wq.astype(jnp.int32))))
        cases[f"{name}_max_step_diff"] = step
        check(step <= 1, f"kernels: {name} payload differs from "
                         f"_quantize_ref by {step} steps")
        record(f"{name}_scales", _rel_err(sc, wsc), tol=1e-6)

    return {"phase": "kernels", "ok": True, "tolerance": KERNEL_TOL,
            "metric": "max|got-ref| / max|ref|",
            "device_kernels": on_tpu, "cases": cases,
            "seconds": round(time.perf_counter() - t0, 2)}


# ---------------------------------------------------------------------------
# cross-chip (--chips 4)
# ---------------------------------------------------------------------------

def phase_cross_chip(sz: Sizes, seed: int, chips: int) -> dict:
    """ZeRO-3 over fsdp=chips against the same seed and global batch on a
    one-device mesh."""
    import jax

    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    check(jax.device_count() == chips,
          f"cross-chip: {jax.device_count()} devices, wanted {chips}")
    global_batch = chips
    steps = 3

    def run(mesh):
        model, engine = _train_engine(sz, seed, sz.cross_chip_layers,
                                      global_batch // mesh.size, mesh=mesh)
        check(engine.train_batch_size == global_batch,
              f"cross-chip: global batch {engine.train_batch_size}")
        data = _repeated_batch(model.config.vocab_size, global_batch,
                               sz.train_seq, seed)
        state = (engine.params, engine.opt_state)
        fractions = sorted({
            round(leaf.addressable_shards[0].data.size / leaf.size, 4)
            for leaf in jax.tree.leaves(state) if leaf.size > 4096})
        losses = [float(engine.train_batch(data)) for _ in range(steps)]
        _drop_state(engine)
        return model, losses, fractions

    t0 = time.perf_counter()
    _, ref_losses, _ = run(build_mesh(TopologyConfig(),
                                      devices=jax.devices()[:1]))
    model, losses, fractions = run(build_mesh(TopologyConfig(dp=1,
                                                             fsdp=chips)))

    check(all(math.isfinite(x) for x in losses + ref_losses),
          f"cross-chip: non-finite loss {losses} / {ref_losses}")
    for got, want in zip(losses, ref_losses):
        check(abs(got - want) <= CROSS_CHIP_RTOL * abs(want),
              f"cross-chip: sharded losses {losses} left the one-device "
              f"losses {ref_losses} (rtol {CROSS_CHIP_RTOL})")
    # every large parameter, master and moment leaf holds 1/chips of
    # itself on a device, from init on: nothing sits whole on device 0
    check(fractions == [round(1 / chips, 4)],
          f"cross-chip: per-device shard fractions {fractions}, wanted "
          f"[{1 / chips}]")
    return {"phase": "cross_chip", "ok": True,
            "note": "smoke output, one run", "model": "mistral-7b",
            "layers": sz.cross_chip_layers, "params": model.num_params(),
            "mesh": {"fsdp": chips}, "global_batch": global_batch,
            "seq": sz.train_seq, "losses_sharded": losses,
            "losses_one_device": ref_losses, "rtol": CROSS_CHIP_RTOL,
            "per_device_shard_fractions": fractions,
            "peak_bytes_in_use": _peak_bytes(),
            "seconds": round(time.perf_counter() - t0, 2)}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train, serve, kernels on one chip (default); "
                         "4: only the cross-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, any backend; cannot report success "
                         "without a TPU")
    args = ap.parse_args(argv)

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.logging import logger

    for handler in logger.handlers:  # stdout carries the JSON lines only
        handler.setStream(sys.stderr)
    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_tpu = device["platform"] == "tpu"
    if not (on_tpu or args.rehearse):
        emit({"ok": False, "device": device,
              "error": "JAX found no TPU: nothing was run"})
        return 2
    if (on_tpu or args.chips > 1) and len(devs) != args.chips:
        emit({"ok": False, "device": device,
              "error": f"--chips {args.chips} but JAX sees {len(devs)}"})
        return 2

    sz = TOY if args.rehearse else REAL
    emit({"phase": "start", "device": device, "chips": args.chips,
          "seed": args.seed, "rehearse": args.rehearse,
          "bytes_limit": (devs[0].memory_stats() or {}).get("bytes_limit"),
          "compile_cache_dir": cache_dir, "jax": jax.__version__})
    if args.chips == 1:
        phases = [lambda: phase_train(sz, args.seed),
                  lambda: phase_serve(sz, args.seed),
                  lambda: phase_kernels(sz, args.seed)]
    else:
        phases = [lambda: phase_cross_chip(sz, args.seed, args.chips)]
    t0 = time.perf_counter()
    try:
        for phase in phases:
            emit(phase())
            gc.collect()
    except BaseException:
        # a failed phase fails the run: say so on the last line, and leave
        traceback.print_exc()
        emit({"ok": False, "device": device,
              "error": traceback.format_exc().strip().splitlines()[-1]})
        return 1
    emit({"phase": "done", "seconds": round(time.perf_counter() - t0, 1)})
    if not on_tpu:
        emit({"ok": False, "device": device,
              "error": "rehearsal passed, but not on a TPU"})
        return 2
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())

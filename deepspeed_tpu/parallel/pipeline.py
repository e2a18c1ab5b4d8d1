"""Pipeline parallelism: microbatched stage pipeline over the pp mesh axis.

Reference: runtime/pipe/ — ``PipelineModule`` (module.py:86) partitions
layers into stages, ``PipelineEngine`` (engine.py:60) interprets a 1F1B
instruction schedule (schedule.py:189) with explicit P2P send/recv
(p2p.py:46,67).

TPU-native redesign: the schedule is a ``lax.scan`` over
``M + P - 1`` pipeline steps inside a shard_map that is *manual only over
pp* (other mesh axes stay under GSPMD, so fsdp/tp/sp sharding of each
stage's weights keeps working inside). Stage-to-stage transfer is a
``ppermute`` ring shift — the P2P of p2p.py as an ICI/DCN collective.
Autodiff through scan+ppermute yields the backward pipeline (reverse
schedule, reversed ring) with no instruction interpreter; remat on the
stage body keeps per-microbatch liveness at the stage boundary, the role
of the reference's activation-checkpoint interval (pipe/module.py:340).

GPipe-flavored: all M forward steps run before backward begins (autodiff
order), so weight versioning/interleaving issues don't arise; bubble
fraction is (P-1)/(M+P-1) per direction — choose M >= 2P.

1F1B-depth memory: the reference's TrainSchedule (pipe/schedule.py:189)
bounds in-flight microbatches to the stage depth so activation memory
stays O(P) as M grows. Here the M microbatches run in *waves* of
``window`` (default 2P) with the wave body rematerialized: the backward
replays one wave at a time, so live stage-boundary activations are
O(window + P) regardless of M — memory flat as M doubles (asserted via
compiled memory_analysis in tests/test_pipeline.py).

Tied embeddings (reference TiedLayerSpec pipe/module.py:77 + tied-grad
allreduce pipe/engine.py:274): structurally unnecessary here — only the
stacked layer dim shards over pp; embedding/unembed weights stay
replicated over pp under GSPMD, which inserts the gradient psum across
their two uses itself (parity test: tests/test_pipeline.py tied test).
"""

from __future__ import annotations

from contextlib import nullcontext
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.parallel import topology as topo


def pipeline_enabled(mesh: Optional[Mesh]) -> bool:
    return mesh is not None and mesh.shape.get("pp", 1) > 1


# trace-scoped schedule defaults (config.pipeline.{microbatches,window,
# schedule}): the engine enters this around its own model traces, so two
# engines in one process cannot contaminate each other's pipeline schedule
_CONFIG_MICROBATCHES = 0
_CONFIG_WINDOW = 0
_CONFIG_SCHEDULE = "waves"


class schedule_defaults:
    """``with schedule_defaults(m, w, s): model.loss(...)`` —
    engine-config defaults for pipelined_layers, scoped to the trace."""

    def __init__(self, microbatches: int = 0, window: int = 0,
                 schedule: str = "waves"):
        self._mws = (microbatches, window, schedule)

    def __enter__(self):
        global _CONFIG_MICROBATCHES, _CONFIG_WINDOW, _CONFIG_SCHEDULE
        self._prev = (_CONFIG_MICROBATCHES, _CONFIG_WINDOW, _CONFIG_SCHEDULE)
        _CONFIG_MICROBATCHES, _CONFIG_WINDOW, _CONFIG_SCHEDULE = self._mws

    def __exit__(self, *a):
        global _CONFIG_MICROBATCHES, _CONFIG_WINDOW, _CONFIG_SCHEDULE
        _CONFIG_MICROBATCHES, _CONFIG_WINDOW, _CONFIG_SCHEDULE = self._prev
        return False


def pipelined_layers(layer_fn: Callable, stacked_params: Any, x: jax.Array,
                     num_microbatches: Optional[int] = None,
                     window: Optional[int] = None,
                     with_aux: bool = False,
                     schedule: Optional[str] = None):
    """Run ``scan(layer_fn)`` over [L, ...]-stacked params as a pp-stage
    pipeline.

    layer_fn(carry, layer_params) -> carry, with carry [mb, S, H]; when
    ``with_aux`` it returns (carry, aux_scalar) and the pipeline threads a
    per-microbatch float32 accumulator alongside the activations (MoE
    aux/z losses — the reference accumulates these across the pipe via the
    engine's loss reduction, pipe/engine.py:592).
    x: [B, S, H]; B must divide into num_microbatches (default 2*pp).
    ``window`` caps in-flight microbatches per rematted wave (1F1B-depth
    memory; default 2*pp). Returns [B, S, H] replicated over pp (and,
    when ``with_aux``, the aux *averaged over microbatches* — the same
    mean reduction the reference's pipe engine applies to losses, so the
    aux-loss scale is invariant to the pipeline's microbatch count).

    ``schedule``: "waves" remats each window-sized wave (memory
    O(window+P) for any M, one extra forward per wave); "save_boundaries"
    runs one un-rematted pass whose scan residuals are exactly the
    per-step stage-boundary activations — zero recompute above the
    per-stage remat, memory O(M+P) boundaries (config
    pipeline.schedule).
    """
    mesh = topo.get_global_mesh()
    PP = mesh.shape["pp"]
    B = x.shape[0]
    M = num_microbatches or _CONFIG_MICROBATCHES or min(B, 2 * PP)
    M = min(M, B)
    while B % M != 0:
        M -= 1
    assert M >= 1
    sched = schedule or _CONFIG_SCHEDULE or "waves"
    if sched not in ("waves", "save_boundaries"):
        raise ValueError(f"pipeline schedule must be 'waves' or "
                         f"'save_boundaries', got {sched!r}")
    if sched == "save_boundaries":
        W = M  # single pass; the wave body is not rematted when W == M
    else:
        W = window or _CONFIG_WINDOW or 2 * PP
        W = min(W, M)
        while M % W != 0:
            W -= 1

    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % PP == 0, f"num_layers {L} must divide pp {PP}"

    def per_stage(params_stage, xs_local):
        # params_stage leaves: [L/PP, ...]; xs_local: [M, mb, S, H]
        stage = lax.axis_index("pp")
        fwd_perm = [(i, (i + 1) % PP) for i in range(PP)]

        def stage_fn(inp, params_stage):
            act, aux = inp

            def one_layer(c, p):
                if with_aux:
                    a, l_aux = layer_fn(c[0], p)
                    return (a, c[1] + l_aux), None
                return (layer_fn(c[0], p), c[1]), None

            (act, aux), _ = lax.scan(one_layer, (act, aux), params_stage)
            return act, aux

        stage_fn = jax.checkpoint(stage_fn)

        def wave(xs_wave):
            """One W-microbatch pipeline pass: [W, mb, S, H] →
            (ys [W, mb, S, H] on the last stage, aux scalar)."""
            steps = W + PP - 1

            def body(carry, t):
                buf, aux_buf = carry  # arriving from the previous stage
                mb_idx = jnp.clip(t, 0, W - 1)
                inp = jnp.where(stage == 0, xs_wave[mb_idx], buf)
                aux_in = jnp.where(stage == 0, 0.0, aux_buf)
                out, aux_out = stage_fn((inp, aux_in), params_stage)
                nxt = lax.ppermute(out, "pp", fwd_perm)
                aux_nxt = lax.ppermute(aux_out, "pp", fwd_perm)
                is_valid = jnp.logical_and(stage == PP - 1, t >= PP - 1)
                y = jnp.where(is_valid, out, jnp.zeros_like(out))
                y_aux = jnp.where(is_valid, aux_out, 0.0)
                return (nxt, aux_nxt), (y, y_aux)

            init = (jnp.zeros_like(xs_wave[0]),
                    jnp.asarray(0.0, jnp.float32))
            _, (ys, aux_ys) = lax.scan(body, init, jnp.arange(steps))
            return ys[PP - 1:], aux_ys[PP - 1:].sum()

        if W == M:
            ys, aux_total = wave(xs_local)
        else:
            # waves of W microbatches, wave body rematted: the backward
            # replays one wave at a time, so live boundary activations
            # stay O(W + P) however large M grows (1F1B-depth memory)
            wave_ck = jax.checkpoint(wave)
            xs_waves = xs_local.reshape(M // W, W, *xs_local.shape[1:])
            _, (ys_w, aux_w) = lax.scan(
                lambda c, xw: (c, wave_ck(xw)), 0, xs_waves)
            ys = ys_w.reshape(M, *xs_local.shape[1:])
            aux_total = aux_w.sum()

        # replicate the last stage's result to every stage (out_specs P())
        ys = lax.psum(jnp.where(stage == PP - 1, ys,
                                jnp.zeros_like(ys)), "pp")
        aux_total = lax.psum(jnp.where(stage == PP - 1, aux_total, 0.0), "pp")
        return ys, aux_total

    from deepspeed_tpu.runtime.sharding import force_f32, manual_axes

    # XLA's CPU backend crashes ("Invalid binary instruction opcode copy")
    # on bf16 inside a partial-manual shard_map; upcast the pipeline region
    # to f32 on CPU only (simulation/tests). TPU runs native bf16.
    cast_f32 = (jax.default_backend() == "cpu"
                and any(l.dtype == jnp.bfloat16
                        for l in jax.tree.leaves((stacked_params, x))))
    orig_dtype = x.dtype
    if cast_f32:
        to32 = lambda t: (t.astype(jnp.float32)
                          if t.dtype == jnp.bfloat16 else t)
        stacked_params = jax.tree.map(to32, stacked_params)
        x = to32(x)
    xs = x.reshape(M, B // M, *x.shape[1:])  # [M, mb, S, H]

    param_specs = jax.tree.map(lambda _: P("pp"), stacked_params)
    ctx2 = force_f32() if cast_f32 else nullcontext()
    # the region is manual over pp ONLY: activation constraints and the
    # qwZ int8 fetch stay live inside the stage body with the pp axis
    # stripped from their specs (sharding.manual_axes — same construction
    # as the ZeRO++ dp region, runtime/zeropp.py:116), so fsdp/tp/sp
    # sharding and quantized gathers compose with pipeline stages
    with manual_axes({"pp"}), ctx2:
        out, aux = jax.shard_map(
            per_stage,
            mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=(P(), P()),
            axis_names=frozenset({"pp"}),
            check_vma=False,
        )(stacked_params, xs)
    out = out.reshape(B, *x.shape[1:])
    if cast_f32:
        out = out.astype(orig_dtype)
    if with_aux:
        return out, aux / M
    return out

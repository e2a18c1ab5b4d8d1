"""The training engine.

TPU-native analog of ``DeepSpeedEngine`` (reference: runtime/engine.py:235)
and ``deepspeed.initialize`` (__init__.py:93). The reference wraps eager
autograd and hand-schedules partitioning/communication; here the whole
GAS boundary compiles into ONE XLA program:

  * ``train_batch`` jits a scan over microbatches; gradient accumulation is
    the backward of that scan, so gradients are reduced ONCE per boundary —
    the comm schedule ZeRO-1 builds by hand (stage_1_and_2.py:1125
    bucketed reduction at boundary), and strictly less communication than
    the reference's per-microbatch stage-2 reduce — while remat keeps
    activation memory at one microbatch.
  * ZeRO stages are sharding constraints (runtime/sharding.py): XLA emits
    the reduce-scatter (stage 2), parameter all-gathers with prefetch
    (stage 3 ≈ partitioned_param_coordinator.py), and overlaps them
    (overlap_comm ≈ the latency-hiding scheduler).
  * ``forward``/``backward``/``step`` keep the reference's micro-step API
    (engine.py:2675,3066,3241) for parity: forward computes loss+grads in
    one jitted call, backward accumulates, step applies at the GAS
    boundary.

``initialize`` returns the reference's 4-tuple
(engine, optimizer, dataloader, lr_scheduler).
"""

from __future__ import annotations

import os
import time
from collections import deque
from functools import partial
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.config.config import Config, load_config
from deepspeed_tpu.parallel import topology as topo
from deepspeed_tpu.runtime import sharding as shard_lib
from deepspeed_tpu.runtime.loss_scaler import (
    LossScaleState, has_overflow, init_loss_scale, update_loss_scale)
from deepspeed_tpu.runtime.lr_schedules import get_lr_schedule
from deepspeed_tpu.runtime.optimizer import (
    MixedPrecisionState, apply_mixed_precision_update, get_base_optimizer,
    init_mixed_precision)
from deepspeed_tpu.runtime.param_stream import export_layer_schedule
from deepspeed_tpu.runtime.prefetch import PrefetchingIterator
from deepspeed_tpu.utils import memspace
from deepspeed_tpu.utils.annotate import named, span, step_span
from deepspeed_tpu.utils.compile_cache import enable_compile_cache
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (
    BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER,
    SynchronizedWallClockTimer, ThroughputTimer, TRAIN_BATCH_TIMER)


def initialize(
    args=None,
    model=None,
    optimizer=None,
    model_parameters=None,
    training_data=None,
    lr_scheduler=None,
    mesh=None,
    topology=None,
    dist_init_required: Optional[bool] = None,
    collate_fn=None,
    config=None,
    config_params=None,
):
    """Reference-parity entry point (deepspeed/__init__.py:93).

    `model` is a model object exposing ``init(rng) -> params``,
    ``loss(params, batch) -> (loss, aux)`` and ``logical_axes()`` (see
    models/transformer.py TransformerLM), or any ``(loss_fn, params)``
    pair passed as (model=loss_fn, model_parameters=params).
    Returns (engine, optimizer_view, dataloader, lr_scheduler_fn).
    """
    assert model is not None, "deepspeed_tpu.initialize: model is required"
    config = config if config is not None else config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)

    enable_compile_cache()
    comm.init_distributed(dist_init_required=dist_init_required)
    engine = Engine(
        model=model,
        config=load_config(config),
        mesh=mesh,
        topology=topology,
        model_parameters=model_parameters,
        training_data=training_data,
        lr_scheduler=lr_scheduler,
        collate_fn=collate_fn,
        client_optimizer=optimizer,
    )
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def zero3_compiler_options(stage: int, mesh_shape: Dict[str, int],
                           platform: str) -> Dict[str, Any]:
    """Compiler options of the train step program that follow from what
    the job is, not from a knob. ZeRO-3 on a TPU mesh whose only wide
    axes are data axes with fsdp among them: the gradient reduce-scatter
    leaves the windowed-einsum form. There the compiler turns each weight
    gradient's product-and-reduce-scatter into a ring of
    collective-permutes whose last hops have nothing left to run under at
    the end of a layer's backward, and the first synchronous collective of
    the next layer waits out the skew they leave: a sixth of
    train-z3-fsdp4-2k's step. As one fused product-and-reduce-scatter the
    same bytes leave while the product runs (PERF.md, PR 44). With a
    tensor- or sequence-parallel axis the windowed form is how those axes'
    products overlap their collectives, so such a mesh keeps the
    compiler's default, and so does every other stage and backend."""
    wide = [a for a, n in mesh_shape.items() if n > 1]
    if (platform == "tpu" and stage == 3 and mesh_shape.get("fsdp", 1) > 1
            and all(a in ("dp", "fsdp") for a in wide)):
        return {"xla_tpu_enable_windowed_einsum_for_reduce_scatter": False}
    return {}


class _FnModel:
    """Adapts a bare (loss_fn, params) pair to the model protocol."""

    def __init__(self, loss_fn: Callable, params):
        self._loss_fn = loss_fn
        self._params = params

    def init(self, rng):
        return self._params

    def loss(self, params, batch):
        out = self._loss_fn(params, batch)
        return out if isinstance(out, tuple) else (out, {})

    def logical_axes(self):
        # unannotated: every dim eligible for fsdp via first-dim fallback
        return jax.tree.map(lambda p: tuple("embed" if i == 0 else None
                                            for i in range(jnp.ndim(p))),
                            self._params)


class _InflightStep:
    """One dispatched-but-unresolved train step (dispatch-ahead window):
    the async metrics plus everything the deferred host reads need —
    snapshotted at dispatch so drain-time logging reports the step's own
    numbers, not the engine's current ones."""

    __slots__ = ("step", "metrics", "struct", "samples", "host_ms",
                 "dispatch_t", "host_t0", "sync")

    def __init__(self, step, metrics, struct, samples, host_ms,
                 dispatch_t, host_t0, sync):
        self.step = step
        self.metrics = metrics
        self.struct = struct          # abstract batch (shapes/dtypes)
        self.samples = samples        # global_samples after this step
        self.host_ms = host_ms        # host time from entry to dispatch
        self.dispatch_t = dispatch_t  # perf_counter at dispatch return
        self.host_t0 = host_t0        # perf_counter at train_batch entry
        self.sync = sync              # dispatched under the blocking loop


class Engine:
    """Owns params/optimizer state, the compiled step functions, timers,
    monitors and checkpointing (reference DeepSpeedEngine engine.py:235)."""

    def __init__(self, model, config: Config, mesh: Optional[Mesh] = None,
                 topology=None, model_parameters=None, training_data=None,
                 lr_scheduler=None, collate_fn=None, client_optimizer=None,
                 seed: Optional[int] = None):
        if callable(model) and not hasattr(model, "loss"):
            model = _FnModel(model, model_parameters)
        self.model = self.module = model
        self.config = config

        # -- mesh (engine.py:1627 _configure_distributed_model analog) ----
        # known before mesh selection: a client optimizer disqualifies the
        # ZeRO++ step, so the default mesh must not assume it
        self._client_optimizer_present = client_optimizer is not None
        if mesh is None:
            mesh = self._default_mesh(topology)
        self.mesh = mesh
        topo.set_global_mesh(mesh)
        self.dp_world_size = topo.get_data_parallel_world_size(mesh)
        config.resolve_batch_size(self.dp_world_size)
        self.plan = shard_lib.make_sharding_plan(config, mesh)
        comm.configure(config)
        from deepspeed_tpu.runtime import activation_checkpointing as act_ckpt

        act_ckpt.configure(config.activation_checkpointing)
        from deepspeed_tpu.utils import memory as mem_util

        mem_util.configure(config.memory_breakdown)
        mem_util.see_memory_usage("engine init: before model setup")
        from deepspeed_tpu.ops import attention as attn_ops

        if config.sparse_attention is not None:
            import dataclasses as _dc

            from deepspeed_tpu.ops.pallas.blocksparse_attention import \
                from_config as sparse_from_config

            scfg = config.sparse_attention
            kblk = getattr(getattr(config, "kernels", None),
                           "blocksparse_block", 0)
            if kblk and kblk != scfg.block:
                # kernels.blocksparse_block overrides the layout/kernel
                # block granularity (0 = follow sparse_attention.block)
                scfg = _dc.replace(scfg, block=kblk)
            attn_ops.set_sparse_config(sparse_from_config(scfg))
            if getattr(getattr(model, "config", None), "attn_impl",
                       None) != "blocksparse":
                logger.warning(
                    "sparse_attention configured but the model's "
                    "attn_impl is not 'blocksparse' — dense attention "
                    "will run; set attn_impl='blocksparse' on the model "
                    "config to activate the layout")
            if config.sparse_attention.attention == "bidirectional":
                logger.warning(
                    "sparse_attention.attention='bidirectional': "
                    "causality comes from the model (the LM stack is "
                    "causal); the layout is applied either way")
        else:
            # a previous engine in this process may have installed a
            # layout into the process-global dispatcher — clear it
            attn_ops.set_sparse_config(None)

        # kernel geometry + dispatch policy (config.kernels): block sizes
        # and the cost-table dispatch mode feed the same process-global
        # dispatcher the sparse layout uses — multi_head_attention and the
        # paged serving path read them at trace time
        attn_ops.set_kernel_config(getattr(config, "kernels", None))

        # -- MoE expert execution engine selection (config.moe.impl) ------
        mcfg = getattr(model, "config", None)
        if (config.moe.impl != "auto" and mcfg is not None
                and hasattr(mcfg, "moe_impl")
                and mcfg.moe_impl != config.moe.impl):
            import dataclasses as _dc

            model.config = _dc.replace(mcfg, moe_impl=config.moe.impl)

        # -- performance block → model config (docs/performance.md) -------
        # fp8 MLP matmuls and the layer-prefetch ring depth live on the
        # model config (they change the traced program); the engine is
        # the bridge from the DeepSpeed-style config block. An explicit
        # performance.param_prefetch_depth beats the model/env default.
        perf = getattr(config, "performance", None)
        mcfg = getattr(model, "config", None)
        perf_updates = {}
        if perf is not None and mcfg is not None:
            if getattr(perf, "fp8_mlp", False) \
                    and hasattr(mcfg, "fp8_mlp") and not mcfg.fp8_mlp:
                perf_updates["fp8_mlp"] = True
            ppd = getattr(perf, "param_prefetch_depth", None)
            if ppd is not None and hasattr(mcfg, "prefetch_depth") \
                    and mcfg.prefetch_depth != int(ppd):
                perf_updates["prefetch_depth"] = int(ppd)
            od = getattr(perf, "overlap_depth", None)
            if od is not None and hasattr(mcfg, "overlap_depth") \
                    and mcfg.overlap_depth != int(od):
                perf_updates["overlap_depth"] = int(od)
        if perf_updates:
            import dataclasses as _dc

            model.config = _dc.replace(mcfg, **perf_updates)

        # -- sequence-parallel planner (parallel/auto_sp.py) --------------
        # When the mesh has an sp axis AND sp was opted into (model flag
        # or sequence_parallel.size > 1 — an sp mesh axis alone also
        # serves sequence-sharded activations without sp attention, so
        # it is not treated as opt-in), compose the long-context plan
        # onto the model config at init. SPPlan.apply is conservative:
        # only fields still at their defaults change;
        # sequence_parallel.auto_plan=False opts out entirely.
        sp_cfg = getattr(config, "sequence_parallel", None)
        mcfg = getattr(model, "config", None)
        if (sp_cfg is not None and getattr(sp_cfg, "auto_plan", True)
                and mcfg is not None and hasattr(mcfg, "num_heads")
                and int(dict(mesh.shape).get("sp", 1)) > 1
                and (getattr(mcfg, "sequence_parallel", False)
                     or getattr(sp_cfg, "size", 1) > 1)):
            from deepspeed_tpu.parallel.auto_sp import \
                plan_sequence_parallel

            budget_gb = getattr(sp_cfg, "hbm_budget_gb", None)
            try:
                _dbytes = int(jnp.dtype(mcfg.dtype).itemsize)
            except Exception:
                _dbytes = 2
            sp_plan = plan_sequence_parallel(
                mcfg.max_seq_len, mcfg.num_heads,
                getattr(mcfg, "num_kv_heads", None), mesh,
                int(budget_gb * 2 ** 30) if budget_gb else None,
                head_dim=mcfg.head_dim, hidden_size=mcfg.hidden_size,
                batch_size=config.train_micro_batch_size_per_chip or 1,
                dtype_bytes=_dbytes)
            self.sp_plan = sp_plan
            new_mcfg = sp_plan.apply(mcfg)
            if new_mcfg is not mcfg:
                model.config = new_mcfg
                log_dist("sp planner: " + "; ".join(sp_plan.reasons),
                         ranks=[0])
        else:
            self.sp_plan = None

        # after the planner: a depth it put on the model is a named one
        self.layer_gather_ahead = self._resolve_gather_ahead(model, config,
                                                             mesh)

        self.micro_batch_size = config.train_micro_batch_size_per_chip
        self.gradient_accumulation_steps = config.gradient_accumulation_steps
        self.train_batch_size = config.train_batch_size
        self.compute_dtype = config.compute_dtype
        self._post_step_hooks = []

        # -- 1-bit compressed-comm optimizers (runtime/onebit.py) ---------
        opt_name = ((config.optimizer.type if config.optimizer else "")
                    or "").lower().replace("_", "").replace("-", "")
        from deepspeed_tpu.runtime.onebit import (
            ONEBIT_OPTIMIZERS, validate_onebit_config)

        self._onebit = opt_name in ONEBIT_OPTIMIZERS
        if self._onebit:
            validate_onebit_config(config)

        # -- optimizer (engine.py:1901 _configure_optimizer analog) -------
        if self._onebit:
            self.tx = None
            from deepspeed_tpu.runtime.onebit import parse_onebit_params

            self._onebit_params = parse_onebit_params(
                opt_name, (config.optimizer.params or {})
                if config.optimizer else {})
            self._base_lr = self._onebit_params["lr"]
            self.lr_schedule = get_lr_schedule(config.scheduler,
                                               base_lr=self._base_lr)
        elif client_optimizer is not None:
            self.tx = client_optimizer  # user-supplied optax transform
            self._base_lr = None
        else:
            sched = get_lr_schedule(config.scheduler,
                                    base_lr=self._config_lr())
            self.lr_schedule = sched
            self.tx, self._base_lr = get_base_optimizer(config.optimizer, sched)
        if not hasattr(self, "lr_schedule"):
            self.lr_schedule = None
        self.lr_scheduler = lr_scheduler or self.lr_schedule

        # -- offload (ZeRO-Offload/Infinity analog) -----------------------
        off_cfg = config.zero_optimization.offload_optimizer
        self._offload_device = (off_cfg.device if off_cfg is not None
                                else "none") or "none"
        self._offload = None  # built in _build_state when enabled
        self._zenflow = None  # built alongside _offload when configured
        if config.zero_optimization.zenflow is not None and \
                self._offload_device != "cpu":
            raise ValueError(
                "zero_optimization.zenflow requires "
                "offload_optimizer.device='cpu' (ZenFlow keeps masters "
                "host-resident; the NVMe swap tier does not apply), got "
                f"device={self._offload_device!r}")

        # -- ZeRO++ quantized-collective step (runtime/zeropp.py) ---------
        self._zeropp = self._zeropp_applicable(config) and not self._onebit
        self._zeropp_state = None
        # set_lr under a compiled runtime-lr step (zeropp/onebit): the lr
        # rides as an operand, NaN = use the traced schedule
        self._lr_override = None
        zq = config.zero_optimization
        # stage-3 qwZ: int8 parameter all-gather in the GSPMD fetch path
        # (reference partition_parameters.py:1446). Composes with tp/sp/
        # hpZ/MiCS since it is just a constraint pair around the gather;
        # armed per-engine via the sharding module switch.
        # pp composes since round 4: the pipeline region is manual over
        # pp only, so the int8 fetch constraints stay live in stage
        # bodies (parallel/pipeline.py manual_axes). pp×fsdp×tp composes
        # since round 5: the partitioner CHECK that used to kill that
        # mesh class was the vocab-parallel lookup's gather (see
        # sharding.py vocab_parallel_lookup), not the qwZ fetch pair.
        self._qwz_stage3 = (zq.stage == 3 and zq.zero_quantized_weights
                            and not config.moe.enabled)
        if (zq.stage == 3 and zq.zero_quantized_weights
                and not self._qwz_stage3):
            from deepspeed_tpu.utils import telemetry

            reason = "moe"
            telemetry.count("zeropp.qwz_disabled", reason)
            logger.warning(
                f"ZeRO++ qwZ stage-3 is inert for this config ({reason}) "
                "— layer gathers stay full-width bf16")
        if self._qwz_stage3:
            log_dist("ZeRO++ qwZ: stage-3 int8 quantized parameter "
                     "all-gather enabled (fsdp axis)", ranks=[0])
        # qgZ for the GSPMD path (stages 2-3): per-group grads (vmap over
        # batch shards) + explicit int8[/int4 hierarchical] all-to-all
        # reduction (runtime/qgz.py; reference coalesced_collectives.py:31
        # all_to_all_quant_reduce). Composes with tp and sp (sp grads
        # reduce full-width inside each group's backward — intra-slice
        # ICI; the fsdp/dp reduction, the DCN-bound wire, is quantized)
        # and with optimizer offload/zenflow (the wire quantizes before
        # the host grad copy — grad_step runs the same construction).
        # Stage 2 with fsdp>1 routes here too, retiring the legacy
        # manual-dp step's fsdp rejection (runtime/zeropp.py:74).
        # MoE/ep composes since round 5: the ep token-group axis reduces
        # expert grads onto the expert-stacked dim with int8 wire
        # (expert-dim-aware grouping, runtime/qgz.py level 2); the
        # grouped MoE dispatch falls back to the einsum path under the
        # per-group vmap (parallel/moe.py — a shard_map can't map a
        # vmapped token axis). Remaining exclusion: pp.
        self._qgz_stage3 = (
            zq.stage >= 2 and zq.zero_quantized_gradients
            and self.mesh.shape.get("pp", 1) <= 1
            and self.mesh.shape.get("fsdp", 1) > 1)
        if self._qgz_stage3:
            log_dist(
                "ZeRO++ qgZ: stage-3 quantized gradient reduction enabled "
                f"(int8 over fsdp={self.mesh.shape['fsdp']}"
                + (f", int8 expert-grads over ep={self.mesh.shape['ep']}"
                   if self.mesh.shape.get("ep", 1) > 1 else "")
                + (f", int4 over dp={self.mesh.shape['dp']}"
                   if self.mesh.shape.get("dp", 1) > 1 else "") + ")",
                ranks=[0])
        elif zq.stage == 3 and zq.zero_quantized_gradients:
            from deepspeed_tpu.utils import telemetry

            telemetry.count("zeropp.qgz_disabled",
                            "config outside qgZ support matrix")
            logger.warning(
                "ZeRO++ qgZ at stage 3 requires no optimizer offload, "
                "no pp axis, and fsdp > 1 — this config fails that, so "
                "gradients reduce at full width")
        if (zq.zero_quantized_weights or zq.zero_quantized_gradients) \
                and not self._zeropp and not self._qwz_stage3 \
                and not self._qgz_stage3:
            logger.warning(
                "ZeRO++ flags (qwZ/qgZ) are wired for: stage 1-2 with "
                "adam/adamw (no client optimizer), bf16, no optimizer "
                "offload, no MoE, no sp/pp axes (tp composes), no "
                "hpZ/MiCS grouping, no 1-bit optimizer; or stage-3 "
                "zero_quantized_weights/zero_quantized_gradients (dense "
                "models). This config fails those, so the quantized path "
                "is disabled and the standard step runs")

        # -- state init (sharded; zero.Init analog is in abstract init) ---
        # streamed-param subtrees (offload_param): the host_param_paths
        # protocol (runtime/param_stream.py) or TransformerLM's "layers"
        _proto = getattr(model, "host_param_paths", None)
        self._host_param_paths = (tuple(_proto) if _proto is not None
                                  else ("layers",))
        self._rng = jax.random.PRNGKey(seed if seed is not None else config.seed)
        self._axes = model.logical_axes()
        self.train_step_compiler_options = self._train_step_compiler_options()
        self._build_state()
        self._build_step_fns()

        # -- observability ------------------------------------------------
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size,
            steps_per_output=config.steps_per_print)
        self.monitor = self._build_monitor()
        # unified observability hub: per-step StepTrace rows, stall
        # watchdog, on-demand profiler capture (docs/observability.md)
        self.hub = None
        self.watchdog = None
        self.flight = None
        self._trace_capture = None
        self._obs_cfg = getattr(config, "observability", None)
        if self._obs_cfg is None or self._obs_cfg.enabled:
            try:
                from deepspeed_tpu.observability import (StallWatchdog,
                                                         TraceCapture,
                                                         get_hub)

                self.hub = get_hub()
                self.hub.configure(self._obs_cfg,
                                   rank=jax.process_index())
                self.watchdog = StallWatchdog.from_config(
                    getattr(self._obs_cfg, "watchdog", None),
                    report_fn=self._on_stall_report)
                self._trace_capture = TraceCapture.from_env()
            except Exception as e:
                logger.warning(f"observability hub disabled: {e}")
            try:
                # crash flight recorder: ring of step/collective/
                # checkpoint events, dumped on crash/SIGTERM/watchdog
                # fire (docs/observability.md "Flight recorder")
                from deepspeed_tpu.observability import flight_recorder \
                    as _fr
                from deepspeed_tpu.observability.fleet import \
                    resolve_run_dir

                self.flight = _fr.get_flight_recorder()
                self.flight.configure(
                    capacity=getattr(self._obs_cfg, "flight_events", None),
                    rank=jax.process_index(),
                    run_dir=resolve_run_dir(self._obs_cfg))
                if not self.flight.enabled:
                    self.flight = None
                else:
                    _fr.install_crash_handlers()
            except Exception as e:
                logger.warning(f"flight recorder disabled: {e}")

        # -- resilience (resilience block; docs/resilience.md) ------------
        # PreemptionGuard: SIGTERM → drain + emergency checkpoint at the
        # next GAS boundary (second SIGTERM escalates through the flight
        # recorder's chained dump-and-kill handler, installed above).
        # Chaos injector: armed only when DSTPU_CHAOS is set — one `is
        # None` check per step/input-pull otherwise.
        self.preempted = False
        self.loaded_data_cursor = None  # manifest cursor from last load
        self._last_save_dir = None      # emergency-save fallback target
        self._last_data_iter = None     # data_cursor loader-state source
        self._resilience_cfg = rcfg = getattr(config, "resilience", None)
        self._preempt_guard = None
        self._chaos = None
        try:
            from deepspeed_tpu.resilience.chaos import get_chaos_injector

            inj = get_chaos_injector()
            self._chaos = inj if inj.armed else None
        except Exception as e:
            logger.warning(f"chaos injector unavailable: {e}")
        if rcfg is None or (rcfg.enabled and rcfg.preemption_guard):
            try:
                from deepspeed_tpu.resilience.preemption import \
                    PreemptionGuard

                self._preempt_guard = PreemptionGuard(
                    save_deadline_s=getattr(
                        rcfg, "preemption_save_deadline_s", 60.0)
                    if rcfg is not None else 60.0)
                self._preempt_guard.install()
            except Exception as e:
                logger.warning(f"preemption guard disabled: {e}")
                self._preempt_guard = None
        self._flops_per_token = None   # cached model.flops_per_token()
        self._last_batches_struct = None  # abstract batch for roofline()
        self._roofline_cost = None     # cached XLA cost analysis
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._pending = None  # (loss, grads) between forward() and backward()
        self._grad_acc = None  # accumulation buffer for the micro-step path

        # -- pipelined loop (performance block; docs/performance.md) ------
        # dispatch-ahead: up to pipeline_depth steps stay in flight; the
        # deferred host reads run when each step drains. 0 = the blocking
        # loop. DSTPU_DISPATCH_AHEAD env beats the config block.
        perf = getattr(config, "performance", None)
        env_depth = os.environ.get("DSTPU_DISPATCH_AHEAD", "")
        self._dispatch_ahead = (int(env_depth) if env_depth != ""
                                else int(getattr(perf, "pipeline_depth", 0)
                                         or 0))
        self._prefetch_depth = int(getattr(perf, "prefetch_depth", 0) or 0)
        self._inflight: deque = deque()  # _InflightStep, oldest first
        self._prefetcher = None       # PrefetchingIterator over data_iter
        self._prefetch_source = None  # the data_iter the prefetcher owns
        self._last_drain_t = None     # perf_counter at the previous drain
        self._last_grad_norm = None   # get_global_grad_norm()
        self._closed = False
        if self._dispatch_ahead > 0:
            log_dist(f"pipelined loop: dispatch-ahead depth "
                     f"{self._dispatch_ahead}, input prefetch depth "
                     f"{self._prefetch_depth}", ranks=[0])

        # -- curriculum learning (reference engine curriculum_learning
        # config + set_custom_curriculum_learning_schedule) ---------------
        self.curriculum_scheduler = None
        de = config.data_efficiency
        if de.enabled and de.curriculum_metrics:
            from deepspeed_tpu.runtime.data_pipeline import \
                CurriculumScheduler

            if len(de.curriculum_metrics) > 1:
                logger.warning(
                    "data_efficiency: multiple curriculum metrics "
                    f"configured ({sorted(de.curriculum_metrics)}); the "
                    "engine schedules only the first — drive the others "
                    "via DeepSpeedDataSampler directly")
            first = next(iter(de.curriculum_metrics.values()))
            self.curriculum_scheduler = CurriculumScheduler(first)

        # -- dataloader (engine.py:364 deepspeed_io analog) ---------------
        self.training_dataloader = None
        if training_data is not None:
            from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader

            self.training_dataloader = DeepSpeedDataLoader(
                training_data, batch_size=self.micro_batch_size,
                collate_fn=collate_fn)

        from deepspeed_tpu.checkpoint.state import CheckpointIO

        self._ckpt_io = CheckpointIO(self)

        n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
            jax.eval_shape(lambda: self.params)))
        # multi-host sanity: every process must have resolved the same
        # topology/batch/model geometry (reference
        # assert_ints_same_as_other_ranks at ZeRO init)
        comm.assert_same_across_processes(
            "engine_init", [
                self.micro_batch_size, self.gradient_accumulation_steps,
                self.train_batch_size, config.zero_optimization.stage,
                n_params,
            ] + [f"{a}={s}" for a, s in self.mesh.shape.items()])
        log_dist(
            f"engine ready: {n_params/1e6:.1f}M params, zero_stage="
            f"{config.zero_optimization.stage}, dp={self.dp_world_size}, "
            f"micro={self.micro_batch_size}, gas="
            f"{self.gradient_accumulation_steps}", ranks=[0])

        # -- quantization telemetry (observability/quant_stats.py) --------
        # Quantized collectives without error measurement are the failure
        # mode ROADMAP item 1 names: warn once when qwZ/qgZ run blind,
        # collect quant.* metrics (init-time param-side sample + flight
        # dump context) when collection is configured.
        zq_flags = config.zero_optimization
        if (zq_flags.zero_quantized_weights
                or zq_flags.zero_quantized_gradients):
            try:
                from deepspeed_tpu.observability import quant_stats as _qs

                if _qs.collection_configured(self._obs_cfg):
                    _qs.install_engine_collector(self)
                else:
                    from deepspeed_tpu.utils.logging import warning_once

                    warning_once(
                        "ZeRO++ quantization (zero_quantized_weights/"
                        "zero_quantized_gradients) is enabled but no "
                        "quant.* collection is configured — quantization "
                        "error and wire bytes are unmeasured. Set "
                        "observability.quant_stats=true or "
                        "DSTPU_QUANT_STATS=1 (docs/quantized_comm.md).")
            except Exception as e:
                logger.warning(f"quant telemetry unavailable: {e}")
        mem_util.see_memory_usage("engine init: ready")

    # ------------------------------------------------------------------
    def _resolve_gather_ahead(self, model, config, mesh) -> Tuple[int, str]:
        """``(depth, reason)`` of the carried gather-ahead path in force
        for this job's layer stack (runtime/param_stream.py
        resolve_gather_ahead): what ``train.layer_gather_ahead`` reads
        when the step is traced. It mirrors, in order, the branches of
        models/transformer.py apply_hidden."""
        from deepspeed_tpu.models.transformer import TransformerLM
        from deepspeed_tpu.runtime.param_stream import resolve_gather_ahead

        mcfg = getattr(model, "config", None)
        streams = (isinstance(model, TransformerLM)
                   and not mcfg.fpdt_host_residual)
        explicit = getattr(getattr(config, "performance", None),
                           "overlap_depth", None)
        if explicit is None and streams and (
                mcfg.overlap_depth
                or os.environ.get("DSTPU_OVERLAP_DEPTH", "") != ""):
            explicit = mcfg.overlap_depth      # the model's field, the env
        zo = config.zero_optimization
        depth, reason = resolve_gather_ahead(
            explicit, streams_layers=streams, mesh_shape=dict(mesh.shape),
            param_offload=(zo.offload_param is not None
                           and zo.offload_param.device != "none"))
        log_dist(f"layer gather-ahead: depth {depth} ({reason})", ranks=[0])
        return depth, reason

    def _train_step_compiler_options(self) -> Dict[str, Any]:
        """:func:`zero3_compiler_options` for this engine's job, or
        nothing where the mesh's compiler does not know them: it refuses
        an unknown option by name, so it is asked with a program of
        nothing before the train step depends on it."""
        device = self.mesh.devices.flat[0]
        options = zero3_compiler_options(
            self.config.zero_optimization.stage, dict(self.mesh.shape),
            device.platform)
        if not options:
            return {}
        try:
            probe = jax.ShapeDtypeStruct(
                (), jnp.float32,
                sharding=jax.sharding.SingleDeviceSharding(device))
            jax.jit(lambda a: a).lower(probe).compile(
                compiler_options=options)
        except Exception as e:
            logger.warning(f"the train step compiles with the compiler's "
                           f"defaults: it refused {options}: {e}")
            return {}
        log_dist(f"train step compiler options: {options}", ranks=[0])
        return options

    def _config_lr(self) -> float:
        if self.config.optimizer and "lr" in (self.config.optimizer.params or {}):
            return self.config.optimizer.params["lr"]
        return 1e-3

    def _zeropp_applicable(self, config) -> bool:
        """ZeRO++ step preconditions knowable from config + ctor args (the
        1-bit exclusion is checked at the call sites). Model-parallel
        axes, hpZ/MiCS grouping, fp16, MoE, offload, and client
        optimizers all fall back to the standard path (with a warning)."""
        from deepspeed_tpu.runtime.zeropp import zeropp_enabled

        z = config.zero_optimization
        off = z.offload_optimizer
        offdev = (off.device if off is not None else "none") or "none"
        opt = ((config.optimizer.type if config.optimizer else "")
               or "adamw").lower().replace("_", "").replace("-", "")
        return (zeropp_enabled(config) and offdev == "none"
                and not config.fp16.enabled
                and not config.moe.enabled
                and not getattr(self, "_client_optimizer_present", False)
                and config.sequence_parallel.size == 1
                and config.pipeline.stages == 1
                and z.zero_hpz_partition_size <= 1
                and z.mics_shard_size <= 0
                # fsdp/sp/ep/pp meshes route to the per-group qgZ
                # construction instead (build_zeropp_step is manual over
                # dp only and would reject them, zeropp.py:74). During
                # default-mesh selection (self.mesh not set yet) the
                # mesh WILL be dp-only if this returns True, so the
                # axes check passes vacuously.
                and all(m.shape.get(a, 1) == 1
                        for a in ("fsdp", "sp", "ep", "pp")
                        for m in [getattr(self, "mesh", None)] if m)
                and opt in ("adam", "adamw", "fusedadam", "fusedadamw"))

    def _default_mesh(self, topology) -> Mesh:
        if topology is not None:
            return topo.build_mesh(topology)
        cfg = self.config
        sizes = dict(pp=cfg.pipeline.stages,
                     tp=cfg.tensor_parallel.size,
                     sp=cfg.sequence_parallel.size,
                     ep=cfg.moe.ep_size if cfg.moe.enabled else 1)
        if self._zeropp_applicable(cfg):
            # the quantized-collective step shards its masters over dp
            sizes.update(dp=-1, fsdp=1)
        elif cfg.zero_optimization.stage >= 1:
            # hpZ and MiCS are the same construction: shard state within a
            # group of `size` chips (ICI), replicate across groups (DCN) —
            # fsdp=group, dp=replicas (reference mics.py / hpZ
            # partition_parameters.py:1806)
            hpz = cfg.zero_optimization.zero_hpz_partition_size
            mics = cfg.zero_optimization.mics_shard_size
            group = hpz if hpz > 1 else (mics if mics > 0 else 0)
            if group > 1:
                sizes.update(fsdp=group, dp=-1)
            else:
                sizes.update(fsdp=-1, dp=1)
        else:
            sizes.update(dp=-1, fsdp=1)
        return topo.build_mesh(topo.TopologyConfig(**sizes))

    # ------------------------------------------------------------------
    def _build_state(self):
        """Init params (compute dtype) + fp32 master/optimizer state, all
        born sharded: init runs under jit with sharding constraints so the
        full replicated model never materializes (zero.Init analog,
        partition_parameters.py:884)."""
        plan, mesh = self.plan, self.mesh
        param_sh = plan.param_shardings(self._axes)
        opt_sh = plan.opt_shardings(self._axes)
        cdt = self.compute_dtype

        if self._onebit:
            # masters/moments replicated over dp (stage<=1 layout); error
            # feedback is per-rank: leading dp axis, sharded over dp
            from deepspeed_tpu.runtime.onebit import (OneBitState,
                                                      build_onebit_step)

            init_fn, step_fn = build_onebit_step(
                self.model, mesh, self.config, self._onebit_params,
                param_sh, self.lr_schedule)
            self._onebit_step_fn = step_fn
            rep = NamedSharding(mesh, P())
            err_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, P("dp")), param_sh)
            master_sh = param_sh
            out_sh = (param_sh, OneBitState(master=master_sh, m=master_sh,
                                            v=master_sh, error=err_sh,
                                            step=rep))
            with jax.set_mesh(mesh):
                self.params, self._onebit_state = jax.jit(
                    init_fn, out_shardings=out_sh)(self._rng)
            self.opt_state = None
        elif self._zeropp:
            # ZeRO++ quantized-collective step: fp32 masters live as
            # [dp, shard] arrays (the ZeRO-1/2 partition), params
            # replicated in compute dtype
            from deepspeed_tpu.runtime.zeropp import (ZeroppState,
                                                      build_zeropp_step)

            ocfg_params = dict((self.config.optimizer.params or {})
                               if self.config.optimizer else {})
            z = self.config.zero_optimization
            init_fn, step_fn = build_zeropp_step(
                self.model, mesh, self.gradient_accumulation_steps,
                base_lr=self._config_lr(), lr_schedule=self.lr_schedule,
                betas=tuple(ocfg_params.get("betas", (0.9, 0.999))),
                eps=float(ocfg_params.get("eps", 1e-8)),
                weight_decay=float(ocfg_params.get("weight_decay", 0.01)),
                grad_clip=self.config.gradient_clipping,
                qg_enabled=z.zero_quantized_gradients, qg_bits=8,
                qw_enabled=z.zero_quantized_weights, qw_bits=8,
                compute_dtype=cdt, param_shardings=param_sh,
                qar_enabled=z.zero_quantized_allreduce, qar_bits=8)
            self._zeropp_step_fn = step_fn
            rep = NamedSharding(mesh, P())
            sh = NamedSharding(mesh, P("dp"))
            master_sh = jax.tree.map(lambda _: sh, param_sh)
            out_sh = (param_sh, ZeroppState(master=master_sh, m=master_sh,
                                            v=master_sh, step=rep))
            with jax.set_mesh(mesh):
                self.params, self._zeropp_state = jax.jit(
                    init_fn, out_shardings=out_sh)(self._rng)
            self.opt_state = None
        elif self._offload_device in ("cpu", "nvme"):
            # fp32 init sharded like optimizer state and written STRAIGHT
            # to pinned host memory (out_shardings memory kind): the full
            # fp32 model never resides in HBM, so multi-B-param offload
            # configs initialize on one 16GB chip (zero.Init analog for
            # the offload tier; reference stage_1_and_2.py cpu_offload /
            # stage3.py offload_optimizer paths).
            def init32(rng):
                p32 = self.model.init(rng)
                return _constrain_tree(p32, opt_sh)

            # the CPU simulator can't lower in-jit host placement
            # ("side-effect ops cannot be replicated"); there the fp32
            # tree is small — init on device and move below
            host_init = jax.default_backend() == "tpu"
            out_sh = (jax.tree.map(
                lambda s: memspace.with_memory_kind(s, "pinned_host"),
                opt_sh)
                if host_init else opt_sh)
            with jax.set_mesh(mesh):
                p32 = jax.jit(init32, out_shardings=out_sh)(self._rng)
            if not host_init:
                def _pin(a):
                    try:
                        return jax.device_put(
                            a, memspace.with_memory_kind(
                                a.sharding, "pinned_host"))
                    except Exception:
                        # multi-process CPU sim: jax routes this
                        # device_put through a jit reshard (device order
                        # differs across processes) and the CPU backend
                        # rejects in-jit host placement ("side-effect
                        # ops cannot be replicated"). Memory kind is
                        # simulation-moot there — keep device placement.
                        return a

                p32 = jax.tree.map(_pin, p32)
            from deepspeed_tpu.runtime.offload import HostOffloadOptimizer

            ocfg = self.config.optimizer
            off = self.config.zero_optimization.offload_optimizer
            poff = self.config.zero_optimization.offload_param
            host_prefixes = (
                tuple(f"['{k}']" for k in self._host_param_paths)
                if poff is not None and poff.device != "none" else ())
            self._offload = HostOffloadOptimizer(
                p32,
                optimizer_name=(ocfg.type if ocfg else "adamw") or "adamw",
                optimizer_params=dict((ocfg.params or {}) if ocfg else {}),
                compute_dtype=cdt,
                grad_clip=self.config.gradient_clipping,
                nvme_path=(off.nvme_path
                           if self._offload_device == "nvme" else None),
                host_memory_leaf_prefixes=host_prefixes)
            # ZenFlow masters come from the TRUE fp32 init
            self._zenflow = self._maybe_build_zenflow(p32)
            # the compute-dtype params must land back in DEVICE memory —
            # XLA would otherwise propagate the staged inputs' host space
            # into the outputs. TPU: out_shardings memory kind; CPU sim:
            # explicit device_put (in-jit placement doesn't lower there).
            if host_init:
                cast = jax.jit(
                    lambda t: jax.tree.map(lambda m: m.astype(cdt), t),
                    out_shardings=jax.tree.map(
                        lambda s: memspace.with_memory_kind(s, "device"),
                        param_sh))
                self.params = cast(p32)
            else:
                cast = jax.jit(
                    lambda t: _constrain_tree(
                        jax.tree.map(lambda m: m.astype(cdt), t), param_sh))
                self.params = jax.tree.map(
                    lambda a: jax.device_put(
                        a, memspace.with_memory_kind(a.sharding, "device")),
                    cast(p32))
            if host_prefixes and isinstance(p32, dict):
                # streamed params stay the pinned fp32 masters (the
                # compiled step fetches one layer at a time); drop the
                # device bf16 copies the cast produced
                self.params = dict(self.params)
                for key in getattr(self, "_host_param_paths", ("layers",)):
                    if key in p32:
                        self.params[key] = p32[key]
            self.opt_state = None
        else:
            def init_fn(rng):
                p32 = self.model.init(rng)
                p32 = _constrain_tree(p32, opt_sh)
                mp = init_mixed_precision(p32, self.tx, shardings=opt_sh)
                params = jax.tree.map(lambda m: m.astype(cdt), mp.master)
                params = _constrain_tree(params, param_sh)
                return params, mp

            with jax.set_mesh(mesh):
                self.params, self.opt_state = jax.jit(init_fn)(self._rng)
        self._param_shardings = param_sh
        self._opt_shardings = opt_sh
        self._setup_param_host_offload()
        # scalars live replicated on the mesh so every jitted fn (and every
        # checkpoint restore) sees one consistent device set
        rep = NamedSharding(mesh, P())
        self.loss_scale_state = jax.device_put(
            init_loss_scale(self.config.fp16), rep)
        self.step_count = jax.device_put(jnp.asarray(0, jnp.int32), rep)

    # ------------------------------------------------------------------
    def _build_step_fns(self):
        cfg = self.config
        plan = self.plan
        grad_sh = plan.grad_shardings(self._axes)
        param_sh = self._param_shardings
        cdt = self.compute_dtype
        gas = self.gradient_accumulation_steps
        fp16 = cfg.fp16.enabled
        grad_clip = cfg.gradient_clipping

        # trace-scoped qwZ arming: only THIS engine's traces see the
        # quantized fetch (a second engine in the process must not flip it)
        qwz_bits = 8 if self._qwz_stage3 else None

        from deepspeed_tpu.parallel import pipeline as pipe_mod

        pp_defaults = pipe_mod.schedule_defaults(cfg.pipeline.microbatches,
                                                 cfg.pipeline.window,
                                                 cfg.pipeline.schedule)

        def model_loss(params, batch):
            export_layer_schedule(*self.layer_gather_ahead,
                                  self.train_step_compiler_options)
            with shard_lib.qwz_context(qwz_bits), pp_defaults:
                return self.model.loss(params, batch)

        def loss_of(params, batch, scale):
            loss, aux = model_loss(params, batch)
            return loss * scale, (loss, aux)

        def step_aux(aux):
            """What of a model's ``aux`` the step carries out: the token
            count; ``counters`` — int32 scalars the model counted in its
            forward (an expert layer's routing), summed over the
            microbatches into the step's metrics; and ``param_deltas`` —
            state that no gradient moves (a router's balancing bias, moved
            by what the step routed): a part of the parameter tree, float32,
            its mean over the microbatches added to the master weights
            after the optimizer's update."""
            return (jnp.asarray(aux.get("ntokens", 0.0), jnp.float32),
                    {k: jnp.asarray(v, jnp.int32)
                     for k, v in aux.get("counters", {}).items()},
                    jax.tree.map(lambda d: lax.stop_gradient(
                        jnp.asarray(d, jnp.float32)),
                        aux.get("param_deltas", {})))

        def fwd_bwd(params, batch, scale):
            """One microbatch: loss + fp32 grads (grad-sharding applied →
            stage-2 reduce-scatter happens here)."""
            (scaled, (loss, _aux)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch, scale)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            grads = _constrain_tree(grads, grad_sh)
            return loss, grads

        def apply_update(params, opt_state, ls_state, step, grads, ntokens,
                         deltas=None):
            overflow = (has_overflow(grads) if fp16
                        else jnp.asarray(False))
            scale = ls_state.scale if fp16 else None
            params, opt_state, gnorm = apply_mixed_precision_update(
                opt_state, grads, self.tx, cdt, grad_clip=grad_clip,
                grad_scale=scale, skip=overflow if fp16 else None,
                param_deltas=deltas)
            params = _constrain_tree(params, param_sh)
            new_ls = (update_loss_scale(ls_state, overflow, cfg.fp16)
                      if fp16 else ls_state)
            new_step = step + jnp.where(overflow, 0, 1).astype(jnp.int32)
            lr = (self.lr_schedule(step) if self.lr_schedule
                  else jnp.asarray(self._base_lr or 0.0))
            metrics = {"grad_norm": gnorm, "lr": lr,
                       "loss_scale": new_ls.scale,
                       "overflow": overflow}
            return params, opt_state, new_ls, new_step, metrics

        qgz = self._qgz_stage3
        if qgz:
            from deepspeed_tpu.runtime.qgz import qgz_reduce_tree

            n_groups = int(np.prod([self.mesh.shape.get(a, 1)
                                    for a in topo.BATCH_AXES]))
            sp_n = self.mesh.shape.get("sp", 1)

            def _group_batches(batches):
                """[gas, B, ...] leaves → [gas, G, B/G, ...] with the
                group dim on the batch axes. The sequence dim is left
                unconstrained — the model's own activation constraints
                re-pin it to sp inside each group's trace, and a "sp"
                entry here trips an XLA SPMD-partitioner grouped-sharding
                CHECK (num_groups mismatch) when combined with the
                vmapped group axis."""
                def reshape(x):
                    return lax.with_sharding_constraint(
                        x.reshape(x.shape[0], n_groups,
                                  x.shape[1] // n_groups, *x.shape[2:]),
                        NamedSharding(self.mesh, P(None, topo.BATCH_AXES)))

                return jax.tree.map(reshape, batches)

        def train_step(params, opt_state, ls_state, step, batches):
            """Fused GAS boundary: grads of a scan over microbatches —
            one reduction per boundary, remat caps activation memory."""
            scale = ls_state.scale if fp16 else jnp.asarray(1.0, jnp.float32)

            def total_loss(params):
                if gas == 1:
                    # no microbatch loop: a scan-of-one still nests a
                    # while-loop around the model's own (chunk/tile)
                    # loops, and on TPU that extra level can push the
                    # hosted-FPDT backward's DMA loop nests past the
                    # compiler's int32 bounds check
                    mb = jax.tree.map(lambda b: b[0], batches)
                    scaled, (loss, aux) = loss_of(params, mb, scale)
                    ntok, counted, deltas = step_aux(aux)
                    return scaled, (loss[None], ntok[None], counted, deltas)

                def body(carry, mb):
                    scaled, (loss, aux) = loss_of(params, mb, scale)
                    return carry + scaled / gas, (loss,) + step_aux(aux)
                total, (losses, ntoks, counted, deltas) = lax.scan(
                    body, jnp.asarray(0.0, jnp.float32), batches)
                return total, (losses, ntoks, jax.tree.map(
                    lambda c: jnp.sum(c, axis=0), counted), jax.tree.map(
                    lambda d: jnp.mean(d, axis=0), deltas))

            if qgz:
                # qgZ: one gradient per batch-shard group (no implicit
                # GSPMD reduction), then explicit quantized-wire reduce
                def per_group(params, mbs):
                    def body(carry, mb):
                        scaled, (loss, aux) = loss_of(params, mb, scale)
                        return (carry + scaled / gas,
                                (loss, aux.get("ntokens", 0.0)))
                    total, (losses, ntoks) = lax.scan(
                        body, jnp.asarray(0.0, jnp.float32), mbs)
                    return total, (losses, ntoks)

                from deepspeed_tpu.runtime import sharding as shard_lib

                grouped = _group_batches(batches)
                # the group dim carries the batch axes; activation
                # constraints inside the mapped trace must not re-pin
                # them (sharding.vmapped_axes)
                with jax.named_scope("forward_backward"):
                    with shard_lib.vmapped_axes(topo.BATCH_AXES):
                        (_, (losses_g, ntoks_g)), g_groups = jax.vmap(
                            jax.value_and_grad(per_group, has_aux=True),
                            in_axes=(None, 1))(params, grouped)
                    g_groups = jax.tree.map(
                        lambda g: g.astype(jnp.float32), g_groups)
                    grads = qgz_reduce_tree(g_groups, grad_sh, self.mesh)
                losses = jnp.mean(losses_g, axis=0)
                ntoks = jnp.sum(ntoks_g, axis=0)
                counted = deltas = {}
            else:
                with jax.named_scope("forward_backward"):
                    (_, (losses, ntoks, counted, deltas)), grads = \
                        jax.value_and_grad(total_loss, has_aux=True)(params)
                    grads = jax.tree.map(
                        lambda g: g.astype(jnp.float32), grads)
                    grads = _constrain_tree(grads, grad_sh)
            with jax.named_scope("optimizer"):
                params, opt_state, new_ls, new_step, metrics = apply_update(
                    params, opt_state, ls_state, step, grads, ntoks, deltas)
            metrics["loss"] = jnp.mean(losses)
            if counted:
                metrics["model_counters"] = counted
            return params, opt_state, new_ls, new_step, metrics

        opt_sh = self._opt_shardings
        off_cfg = cfg.zero_optimization.offload_optimizer
        grad_xfer_bf16 = (off_cfg is not None
                          and off_cfg.grad_transfer_dtype == "bf16")

        def grad_step(params, batches, scale):
            """Offload path: (loss-scaled) grads only — the update happens
            host-side in the native CPU optimizer (runtime/offload.py),
            which unscales by grad_scale. grad_transfer_dtype=bf16 halves
            device->host volume and feeds the native bf16-grad kernel.
            Under qgZ the cross-shard reduction is the quantized-wire
            construction (the wire quantizes BEFORE the host grad copy —
            reference applies all_to_all_quant_reduce in offload configs
            too, coalesced_collectives.py:31)."""

            def total_loss(params):
                if gas == 1:
                    # see train_step.total_loss: no scan-of-one wrapper
                    mb = jax.tree.map(lambda b: b[0], batches)
                    loss, aux = model_loss(params, mb)
                    return loss * scale, loss[None]

                def body(carry, mb):
                    loss, aux = model_loss(params, mb)
                    return carry + loss * scale / gas, loss

                total, losses = lax.scan(body, jnp.asarray(0.0, jnp.float32),
                                         batches)
                return total, losses

            if qgz:
                def per_group(p, mbs):
                    def body(carry, mb):
                        loss, aux = model_loss(p, mb)
                        return carry + loss * scale / gas, loss

                    total, losses = lax.scan(
                        body, jnp.asarray(0.0, jnp.float32), mbs)
                    return total, losses

                from deepspeed_tpu.runtime import sharding as shard_lib

                grouped = _group_batches(batches)
                with shard_lib.vmapped_axes(topo.BATCH_AXES):
                    (_, losses_g), g_groups = jax.vmap(
                        jax.value_and_grad(per_group, has_aux=True),
                        in_axes=(None, 1))(params, grouped)
                g_groups = jax.tree.map(
                    lambda g: g.astype(jnp.float32), g_groups)
                grads = qgz_reduce_tree(g_groups, grad_sh, self.mesh)
                losses = jnp.mean(losses_g, axis=0)
            else:
                (_, losses), grads = jax.value_and_grad(
                    total_loss, has_aux=True)(params)
            xfer = jnp.bfloat16 if grad_xfer_bf16 else jnp.float32
            grads = jax.tree.map(lambda g: g.astype(xfer), grads)
            grads = _constrain_tree(grads, opt_sh)
            return grads, jnp.mean(losses)

        donate = (0, 1, 2, 3)
        # stable program names: the device trace's module line and the
        # HLO say jit_dstpu_train_step, not jit_train_step or _unknown
        self._jit_train_step = jax.jit(
            named(train_step, "dstpu_train_step"), donate_argnums=donate,
            compiler_options=self.train_step_compiler_options or None)
        self._jit_grad_step = jax.jit(named(grad_step, "dstpu_grad_step"))
        if self._onebit:
            self._jit_onebit = jax.jit(
                named(self._onebit_step_fn, "dstpu_onebit_step"),
                donate_argnums=(0, 1))
        if self._zeropp:
            self._jit_zeropp = jax.jit(
                named(self._zeropp_step_fn, "dstpu_zeropp_step"),
                donate_argnums=(0, 1))
        # offload resharding hops: host-updated (optimizer-sharded) tree →
        # param sharding = the "allgather updated partitions" collective,
        # compiled by XLA over ICI; and grad-acc → optimizer sharding.
        self._jit_reshard_to_params = jax.jit(lambda t: t,
                                              out_shardings=param_sh)
        stream_paths = [
            k for k in getattr(self, "_host_param_paths", ("layers",))
            if isinstance(param_sh, dict) and k in param_sh]
        if getattr(self, "_param_host_offload", False) and stream_paths:
            # updated streamed params land straight in pinned host memory
            # — the full stack must never materialize in HBM (the point
            # of offload_param). XLA rejects host-kind out_shardings on
            # replicated leaves inside jit ("side-effect ops cannot be
            # replicated"), so this reshard runs as an out-of-jit
            # device_put over a sharding tree instead.
            host_sh = dict(param_sh)
            for key in stream_paths:
                host_sh[key] = jax.tree.map(
                    lambda s: memspace.with_memory_kind(s, "pinned_host"),
                    param_sh[key])
            self._jit_reshard_to_params = lambda t: jax.device_put(
                t, host_sh)
        self._jit_to_opt_sharding = jax.jit(
            lambda t: t, out_shardings=opt_sh)
        self._jit_fwd_bwd = jax.jit(named(fwd_bwd, "dstpu_fwd_bwd"))
        self._jit_apply = jax.jit(named(apply_update, "dstpu_apply_update"),
                                  donate_argnums=(0, 1, 2, 3, 4))
        self._jit_eval = jax.jit(named(model_loss, "dstpu_eval"))
        self._jit_accumulate = jax.jit(
            lambda acc, g, c: jax.tree.map(lambda a, b: a + b * c, acc, g),
            donate_argnums=(0,))

    # ------------------------------------------------------------------
    # data plumbing
    # ------------------------------------------------------------------
    def _batch_sharding(self, leading_dims: int = 1):
        spec = [topo.BATCH_AXES] + [None] * 0
        if leading_dims == 2:  # [gas, batch, ...]
            spec = [None, topo.BATCH_AXES]
        return NamedSharding(self.mesh, P(*spec))

    def shard_batch(self, batch, leading_dims: int = 1):
        """Host batch (numpy tree, per-process slice) → global device arrays."""
        sh = self._batch_sharding(leading_dims)

        def put(x):
            x = np.asarray(x)
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sh, x)
            return jax.device_put(x, sh)

        return jax.tree.map(put, batch)

    def _next_microbatches(self, data_iter, n: int):
        out = []
        for i in range(n):
            if self._chaos is not None:
                self._chaos.on_input_batch()
            try:
                out.append(next(data_iter))
            except StopIteration:
                if i == 0:
                    raise  # clean end-of-data at a boundary
                raise RuntimeError(
                    f"data iterator exhausted mid-gradient-accumulation "
                    f"(got {i} of {n} microbatches): wrap the loader in "
                    "deepspeed_tpu.runtime.dataloader.RepeatingLoader so "
                    "epochs restart at the boundary") from None
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *out)
        return self.shard_batch(stacked, leading_dims=2)

    def _next_batches(self, data_iter):
        """Stacked+sharded microbatches for one boundary, routed through
        the background prefetcher when the caller is streaming.

        Promotion heuristic: the first time an iterator is seen it is
        pulled synchronously (a one-shot ``iter([batch])`` must not be
        consumed ahead of the caller); passing the SAME iterator again
        means the caller treats it as a stream, so it is handed to a
        :class:`PrefetchingIterator` whose worker pulls/stacks/transfers
        the next boundaries while the current step computes. Multi-host
        runs stay synchronous (cross-host transfer issue order)."""
        gas = self.gradient_accumulation_steps
        if self._prefetch_depth <= 0 or jax.process_count() > 1:
            return self._next_microbatches(data_iter, gas)
        if data_iter is self._prefetch_source:
            if self._prefetcher is None:
                self._prefetcher = PrefetchingIterator(
                    lambda: self._next_microbatches(data_iter, gas),
                    depth=self._prefetch_depth, name="train-input")
            return next(self._prefetcher)
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        self._prefetch_source = data_iter
        return self._next_microbatches(data_iter, gas)

    # ------------------------------------------------------------------
    # reference-parity training API
    # ------------------------------------------------------------------
    def _effective_depth(self) -> int:
        """Dispatch-ahead window for the next step. Paths that must read
        host values inside the step (host-optimizer offload) or that
        observe engine state step-by-step (post-step hooks) force the
        blocking loop."""
        if self._dispatch_ahead <= 0:
            return 0
        if self._offload is not None:
            return 0  # host optimizer reads grads/gnorm synchronously
        if self._post_step_hooks:
            return 0  # hooks expect a settled engine after every step
        return self._dispatch_ahead

    def train_batch(self, data_iter=None) -> jax.Array:
        """One full training step (micro × GAS) — the fast path
        (reference PipelineEngine.train_batch pipe/engine.py:337 naming).

        With ``performance.pipeline_depth`` K >= 1 the returned loss is
        an async ``jax.Array``: up to K dispatched steps stay in flight
        and the per-step host reads (overflow accounting, steps_per_print
        logging, monitor/hub rows) defer until each step's metrics
        resolve at drain time, so the host never sits on the device
        critical path. ``synchronize()`` drains the window. K = 0 is the
        blocking loop, bit-identical to the pre-pipelined behavior."""
        if data_iter is None:
            if self.training_dataloader is None:
                raise ValueError("train_batch needs data_iter or training_data")
            data_iter = iter(self.training_dataloader)
        self._last_data_iter = data_iter  # data_cursor loader-state source
        step_no = self.global_steps + 1
        if self._trace_capture is not None:
            self._trace_capture.on_step_begin(step_no)
        # the step's host phases as spans on the profiler's clock
        # (utils/annotate.py): recorded only while a profiler session runs
        with step_span("train_batch", step_no):
            loss = self._train_batch(data_iter, step_no)
        if self._trace_capture is not None:
            self._trace_capture.stop_if_due()
        return loss

    def _train_batch(self, data_iter, step_no: int) -> jax.Array:
        depth = self._effective_depth()
        sync = depth == 0
        host_t0 = time.perf_counter()
        if sync:
            self.timers(TRAIN_BATCH_TIMER).start()
            self.tput_timer.start()
        with span("next_batches"):
            batches = self._next_batches(data_iter)
        if self._chaos is not None:
            self._chaos.on_step(step_no)
        if self.flight is not None:
            self.flight.record("step_entry", step=step_no,
                               inflight=len(self._inflight))
        if sync and self.watchdog is not None:
            # armed until the step's results are blocked on below: a
            # wedged collective fires a stack/memory report
            self.watchdog.arm(step_no)
        with span("dispatch"), topo.use_mesh(self.mesh):
            metrics = self._dispatch_train_step(batches)
        dispatch_t = time.perf_counter()
        if self.flight is not None:
            self.flight.record(
                "step_dispatch", step=step_no,
                host_ms=round((dispatch_t - host_t0) * 1000.0, 3))
        # dispatch-order bookkeeping; the host READS defer to the drain
        self.global_steps += 1
        self.global_samples += self.train_batch_size
        with span("ckpt_commit"):
            for hook in self._post_step_hooks:
                hook(self)
            self._ckpt_io.maybe_commit()
        self._inflight.append(_InflightStep(
            step=step_no, metrics=metrics,
            struct=jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batches),
            samples=self.global_samples,
            host_ms=(dispatch_t - host_t0) * 1000.0,
            dispatch_t=dispatch_t, host_t0=host_t0, sync=sync))
        if not sync and self.watchdog is not None:
            # one deadline budgets the whole in-flight window (the oldest
            # step's deadline scaled by the window size) — between
            # train_batch calls the window stays armed, so a wedged
            # collective inside it still fires a report
            self.watchdog.arm(self._inflight[0].step,
                              window=len(self._inflight))
        while len(self._inflight) > depth:
            self._drain_one()
        if (self._preempt_guard is not None
                and self._preempt_guard.should_checkpoint()):
            # GAS boundary after a preemption notice: drain the window
            # and land an emergency checkpoint before the grace runs out
            self._emergency_checkpoint()
        return metrics["loss"]

    def _drain_one(self) -> None:
        """Resolve the oldest in-flight step: block on its metrics, then
        run its deferred host reads and emit its trace row."""
        entry = self._inflight.popleft()
        metrics = entry.metrics
        with span("drain_wait"):
            # the one block on the step's results; every host read
            # below finds them resolved
            jax.block_until_ready(metrics)
        self._last_grad_norm = metrics.get("grad_norm")
        for name in getattr(self.model, "fatal_counters", ()):
            n = int(metrics.get("model_counters", {}).get(name, 0))
            if n:
                raise RuntimeError(
                    f"step {entry.step}: the model counted {name} = {n}; "
                    f"what it computed is not the model (its parameters "
                    f"have taken that step's update)")
        if entry.sync:
            # blocking path: identical ordering to the classic loop
            with span("after_step_host"):
                self._after_step_host(metrics, entry.step, entry.samples)
            self.timers(TRAIN_BATCH_TIMER).stop(block=metrics["loss"])
            wall_ms = self._last_step_wall_ms()
            if self._trace_capture is not None:
                self._trace_capture.on_step_end(entry.step)
            if self.watchdog is not None:
                self.watchdog.disarm()
                self.watchdog.observe(wall_ms / 1000.0, entry.step)
            self._last_drain_t = time.perf_counter()
        else:
            resolved_t = time.perf_counter()
            # drain-to-drain span ≈ this step's device time once the
            # pipeline is full; during fill it degrades to dispatch→done
            base = (entry.host_t0 if self._last_drain_t is None
                    else max(self._last_drain_t, entry.host_t0))
            wall_ms = (resolved_t - base) * 1000.0
            self._last_drain_t = resolved_t
            with span("after_step_host"):
                self._after_step_host(metrics, entry.step, entry.samples,
                                      wall_s=wall_ms / 1000.0)
            self.timers(TRAIN_BATCH_TIMER).record_ms(wall_ms)
            if self._trace_capture is not None:
                self._trace_capture.on_step_end(entry.step)
            if self.watchdog is not None:
                self.watchdog.observe(wall_ms / 1000.0, entry.step)
                if self._inflight:
                    self.watchdog.arm(self._inflight[0].step,
                                      window=len(self._inflight))
                else:
                    self.watchdog.disarm()
        if self.flight is not None:
            self.flight.record("step_drain", step=entry.step,
                               wall_ms=round(wall_ms, 3),
                               inflight=len(self._inflight))
        if self.hub is not None:
            with span("step_trace"):
                self._emit_step_trace(entry.step, metrics, entry.struct,
                                      wall_ms, host_gap_ms=entry.host_ms,
                                      samples=entry.samples,
                                      inflight=len(self._inflight))

    def synchronize(self) -> "Engine":
        """Drain every dispatched-but-unresolved train step (pipeline
        barrier for the dispatch-ahead loop): blocks until all in-flight
        metrics resolve and their deferred host reads — overflow/skip
        counts, logging, monitor and hub rows — have run. The engine
        calls it at checkpoint/eval/state-export boundaries; call it
        manually before reading engine counters mid-run or at exit. A
        no-op under the blocking loop."""
        while self._inflight:
            self._drain_one()
        return self

    def close(self) -> None:
        """Drain the in-flight window and stop what this engine started:
        the input prefetcher's worker and the stall watchdog's thread; the
        hub's Prometheus page is written once more. Idempotent. The
        process-wide hub and flight recorder stay for other engines;
        device state goes with the object."""
        if self._closed:
            return
        self._closed = True
        self.synchronize()
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.hub is not None:
            self.hub.write_prometheus()

    def _emergency_checkpoint(self) -> None:
        """Preemption-notice path: drain, save, force-commit — bounded by
        ``resilience.preemption_save_deadline_s``. Sets ``preempted`` so
        the training loop can exit cleanly; a torn save is harmless (no
        manifest ⇒ auto-resume falls back to the previous good tag)."""
        guard = self._preempt_guard
        rcfg = self._resilience_cfg
        save_dir = ((getattr(rcfg, "emergency_save_dir", None)
                     if rcfg is not None else None)
                    or self._last_save_dir)
        self.preempted = True
        if self.flight is not None:
            self.flight.record("preempt_drain", step=self.global_steps,
                               inflight=len(self._inflight))
        self.synchronize()
        if save_dir is None:
            logger.error(
                "resilience: preemption notice but no checkpoint dir is "
                "known (no prior save_checkpoint and no "
                "resilience.emergency_save_dir) — exiting WITHOUT an "
                "emergency save")
            if self.flight is not None:
                self.flight.record("preempt_save_skipped", reason="no_dir")
            return
        from deepspeed_tpu.resilience.policy import (_DeadlineExpired,
                                                     run_with_deadline)

        t0 = time.perf_counter()

        def _save():
            self.save_checkpoint(save_dir)
            self._ckpt_io.commit_pending()  # async engines: force durable

        try:
            if jax.process_count() > 1:
                # multi-host publish runs collectives that must issue
                # from this thread in lockstep on every rank — the
                # deadline is advisory there (the scheduler's SIGKILL is
                # the real bound)
                _save()
            else:
                run_with_deadline(_save, guard.save_deadline_s,
                                  name="preempt_save")
        except _DeadlineExpired:
            logger.error(
                f"resilience: emergency checkpoint blew its "
                f"{guard.save_deadline_s:g}s deadline — exiting with the "
                "save incomplete (manifest validation will reject it and "
                "resume from the previous good tag)")
            if self.flight is not None:
                self.flight.record("preempt_save_timeout",
                                   deadline_s=guard.save_deadline_s)
            return
        wall = time.perf_counter() - t0
        if self.flight is not None:
            self.flight.record("preempt_save_done",
                               step=self.global_steps,
                               wall_ms=round(wall * 1000.0, 1))
        logger.warning(
            f"resilience: emergency checkpoint committed to {save_dir} "
            f"in {wall:.2f}s; engine.preempted=True — stop training and "
            "exit")

    def _dispatch_train_step(self, batches):
        lr_over = jnp.asarray(
            self._lr_override if self._lr_override is not None
            else float("nan"), jnp.float32)
        if self._onebit:
            self.params, self._onebit_state, metrics = self._jit_onebit(
                self.params, self._onebit_state, batches, lr_over)
            self.step_count = self._onebit_state.step
        elif self._zeropp:
            self.params, self._zeropp_state, metrics = self._jit_zeropp(
                self.params, self._zeropp_state, batches, lr_over)
            self.step_count = self._zeropp_state.step
        elif self._offload is not None:
            scale = (self.loss_scale_state.scale if self.config.fp16.enabled
                     else jnp.asarray(1.0, jnp.float32))
            grads, loss = self._jit_grad_step(self.params, batches, scale)
            metrics = self._offload_apply(grads, loss)
        else:
            (self.params, self.opt_state, self.loss_scale_state,
             self.step_count, metrics) = self._jit_train_step(
                self.params, self.opt_state, self.loss_scale_state,
                self.step_count, batches)
        return metrics

    def forward(self, batch, *args, **kwargs):
        """Micro-step path: compute loss (grads cached for backward)."""
        if self._onebit or self._zeropp:
            raise RuntimeError(
                "1-bit/ZeRO++ quantized optimizers support the fused "
                "train_batch() path only (the compressed collective lives "
                "inside the compiled step); use engine.train_batch(...)")
        self.timers(FORWARD_GLOBAL_TIMER).start()
        batch = self.shard_batch(batch)
        scale = (self.loss_scale_state.scale if self.config.fp16.enabled
                 else jnp.asarray(1.0, jnp.float32))
        with topo.use_mesh(self.mesh):
            loss, grads = self._jit_fwd_bwd(self.params, batch, scale)
        self._pending = (loss, grads)
        self.timers(FORWARD_GLOBAL_TIMER).stop(block=loss)
        return loss

    __call__ = forward

    def backward(self, loss=None, retain_graph: bool = False):
        """Accumulate the cached grads (reference engine.backward
        engine.py:3066)."""
        if self._pending is None:
            raise RuntimeError("backward() called without a prior forward()")
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        _, grads = self._pending
        self._pending = None
        coef = jnp.asarray(1.0 / self.gradient_accumulation_steps, jnp.float32)
        if self._grad_acc is None:
            self._grad_acc = jax.tree.map(lambda g: g * coef, grads)
        else:
            self._grad_acc = self._jit_accumulate(self._grad_acc, grads, coef)
        self.micro_steps += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        """Reference engine.py:3270."""
        return self.micro_steps % self.gradient_accumulation_steps == 0

    def step(self):
        """Apply the update at the GAS boundary (reference engine.py:3241)."""
        if not self.is_gradient_accumulation_boundary():
            return
        if self._grad_acc is None:
            raise RuntimeError("step() called without accumulated gradients")
        self.timers(STEP_GLOBAL_TIMER).start()
        if self._offload is not None:
            grads = self._jit_to_opt_sharding(self._grad_acc)
            metrics = self._offload_apply(grads, None)
        else:
            (self.params, self.opt_state, self.loss_scale_state,
             self.step_count, metrics) = self._jit_apply(
                self.params, self.opt_state, self.loss_scale_state,
                self.step_count, self._grad_acc, jnp.asarray(0.0))
        self._grad_acc = None
        self._after_step(metrics)
        self.timers(STEP_GLOBAL_TIMER).stop()

    def _maybe_build_zenflow(self, params_fp32):
        """Config-driven ZenFlow (reference zenflow_stage_1_and_2.py:47
        enablement via the zero_optimization.zenflow block): replaces the
        blocking host step with top-k on-device updates + an overlapped
        host pass. Multi-host: each process's host optimizer owns its
        devices' shards (per-shard masters in runtime/zenflow.py); device
        selection/updates are plain SPMD jits, so no full leaf is ever
        flattened host-side."""
        zf = self.config.zero_optimization.zenflow
        if zf is None:
            return None
        if self.config.zero_optimization.offload_param is not None and \
                self.config.zero_optimization.offload_param.device != "none":
            logger.warning("zenflow does not compose with offload_param "
                           "streaming; falling back to the blocking "
                           "offload step")
            return None
        from deepspeed_tpu.runtime.zenflow import (ZenFlowConfig,
                                                   ZenFlowOptimizer)

        ocfg = self.config.optimizer
        p = dict((ocfg.params or {}) if ocfg else {})
        cfg = ZenFlowConfig(
            topk_ratio=zf.topk_ratio, update_interval=zf.update_interval,
            select_interval=zf.select_interval,
            overlap_step=zf.overlap_step,
            workers=getattr(zf, "workers", 1),
            betas=tuple(p.get("betas", (0.9, 0.999))),
            eps=p.get("eps", 1e-8),
            weight_decay=p.get("weight_decay", 0.0))
        return ZenFlowOptimizer(params_fp32, cfg,
                                lr=p.get("lr", self._base_lr or 1e-3),
                                param_dtype=self.compute_dtype)

    def _setup_param_host_offload(self) -> None:
        """ZeRO-Infinity param tier (reference offload_config.py:21
        offload_param + partitioned_param_swapper semantics): layer
        params move to pinned host memory and the model's scan streams
        one layer at a time to HBM (models/transformer.py
        param_host_offload path). Requires the host optimizer tier."""
        pcfg = self.config.zero_optimization.offload_param
        self._param_host_offload = bool(
            pcfg is not None and pcfg.device != "none")
        if not self._param_host_offload:
            return
        if pcfg.device == "nvme":
            logger.warning("offload_param.device='nvme': layer params are "
                           "held in pinned host RAM (the NVMe tier applies "
                           "to optimizer state); proceeding with cpu "
                           "placement")
        if self._offload is None:
            # (1-bit/ZeRO++ cannot reach here: their validators/gating
            # already reject or disable themselves under optimizer
            # offload, so _offload is always set when offload_optimizer
            # is configured)
            raise ValueError(
                "offload_param requires offload_optimizer (the ZeRO-"
                "Infinity pairing): add zero_optimization."
                "offload_optimizer.device='cpu'")
        if self.mesh.shape.get("pp", 1) > 1:
            raise ValueError("offload_param does not compose with the "
                             "pipeline-parallel layer path yet")
        mcfg = getattr(self.model, "config", None)
        if getattr(self.model, "host_param_paths", None) is not None:
            # model-agnostic protocol (runtime/param_stream.py): the
            # model declares which top-level stacked subtrees stream
            # (self._host_param_paths, set at init) and consults
            # model.param_host_offload in its apply
            self.model.param_host_offload = True
        elif mcfg is not None and hasattr(mcfg, "param_host_offload"):
            updates = {}
            if not mcfg.param_host_offload:
                updates["param_host_offload"] = True
            if not getattr(mcfg, "remat", True):
                # without remat every fetched layer is saved as a backward
                # residual and the full stack materializes in HBM anyway —
                # force the streaming-compatible mode on
                logger.warning("offload_param requires per-layer remat to "
                               "keep the stack out of HBM; enabling remat")
                updates["remat"] = True
            if updates:
                import dataclasses as _dc

                self.model.config = _dc.replace(mcfg, **updates)
        else:
            raise ValueError(
                "offload_param needs a model that supports streaming: "
                "either config.param_host_offload (TransformerLM family) "
                "or the host_param_paths protocol "
                "(runtime/param_stream.py)")
        self.params = self._place_layer_params_on_host(self.params)
        log_dist("offload_param: layer params pinned to host memory; "
                 "the compiled step streams one layer at a time", ranks=[0])

    def _place_layer_params_on_host(self, params):
        # host copies are staged in FP32: sub-32-bit host->device streaming
        # is not supported by current TPU runtimes, and fp32 is the master
        # precision anyway (the layer body casts to compute dtype right
        # after the fetch, so HBM holds one fp32 layer transiently)
        from deepspeed_tpu.runtime.param_stream import pin_to_host

        paths = getattr(self, "_host_param_paths", ("layers",))
        if not isinstance(params, dict):
            return params
        out = dict(params)
        for key in paths:
            if key in out:
                out[key] = pin_to_host(out[key])
        return out

    def _offload_apply(self, grads, loss):
        """Host-side optimizer step (ZeRO-Offload boundary): device grads
        → native CPU optimizer → resharded device params."""
        lr = (float(self.lr_schedule(self.step_count)) if self.lr_schedule
              else float(self._base_lr or 0.0))
        fp16 = self.config.fp16.enabled
        scale = float(self.loss_scale_state.scale) if fp16 else None
        if self._zenflow is not None:
            import optax

            # one fused coefficient applies unscaling + clipping; gnorm
            # stays a device scalar (no host sync) unless fp16 needs the
            # overflow decision
            gnorm = optax.global_norm(grads)
            if scale and scale != 1.0:
                gnorm = gnorm / scale
            coef = jnp.asarray(1.0 / (scale or 1.0), jnp.float32)
            clip = self.config.gradient_clipping
            if clip and clip > 0:
                coef = coef * jnp.minimum(1.0, clip / (gnorm + 1e-6))
            if (clip and clip > 0) or (scale and scale != 1.0):
                grads = jax.tree.map(lambda g: g * coef.astype(g.dtype),
                                     grads)
            overflow = bool(fp16 and not np.isfinite(float(gnorm)))
            new_tree = (None if overflow
                        else self._zenflow.step(grads, self.params, lr=lr))
        else:
            new_tree, gnorm, overflow = self._offload.step(
                grads, self.params, lr=lr, grad_scale=scale,
                skip_on_nonfinite=fp16)
        if not overflow:
            # reshard targets host memory kind for layers under
            # offload_param (out_shardings in _build_step_fns)
            self.params = self._jit_reshard_to_params(new_tree)
            self.step_count = self.step_count + 1
        if fp16:
            self.loss_scale_state = jax.device_put(
                update_loss_scale(self.loss_scale_state,
                                  jnp.asarray(overflow), self.config.fp16),
                NamedSharding(self.mesh, P()))
        self._last_grad_norm = gnorm
        metrics = {"grad_norm": jnp.asarray(gnorm), "lr": jnp.asarray(lr),
                   "loss_scale": self.loss_scale_state.scale,
                   "overflow": jnp.asarray(overflow)}
        if loss is not None:
            metrics["loss"] = loss
        return metrics

    def eval_batch(self, batch):
        self.synchronize()  # eval boundary: settle the in-flight window
        batch = self.shard_batch(batch)
        with topo.use_mesh(self.mesh):
            loss, _aux = self._jit_eval(self.params, batch)
        return loss

    def set_custom_curriculum_learning_schedule(self, fn):
        """Reference engine API: plug a step→difficulty callable into the
        curriculum scheduler (requires a 'custom' curriculum config)."""
        if self.curriculum_scheduler is None:
            raise RuntimeError(
                "no curriculum scheduler: enable data_efficiency with a "
                "curriculum_metrics block first")
        self.curriculum_scheduler.set_custom_get_difficulty(fn)

    def get_data_difficulty(self) -> Optional[int]:
        """Current curriculum difficulty (None when curriculum is off)."""
        if self.curriculum_scheduler is None:
            return None
        return self.curriculum_scheduler.get_difficulty(self.global_steps)

    def register_post_step_hook(self, fn):
        """``fn(engine)`` runs after every optimizer step (compression
        re-masking, progressive layer drop, custom callbacks)."""
        self._post_step_hooks.append(fn)
        return fn

    def _after_step(self, metrics):
        """Synchronous post-step (micro-step ``step()`` path): dispatch
        bookkeeping plus the host reads in one go."""
        self.global_steps += 1
        self.global_samples += self.train_batch_size
        for hook in self._post_step_hooks:
            hook(self)
        # decoupled checkpoint engine: publish a finished async save at the
        # GAS boundary (reference engine.py:3273)
        self._ckpt_io.maybe_commit()
        self._after_step_host(metrics, self.global_steps,
                              self.global_samples)

    def _after_step_host(self, metrics, step_no, samples, wall_s=None):
        """Per-step host reads. Under dispatch-ahead these run at drain
        time — reading ``overflow`` forces the sync, so deferring them is
        what keeps the host off the critical path; ``step_no``/``samples``
        are the step's own snapshots, not the engine's current counters.
        ``wall_s`` set means the span was measured externally
        (drain-to-drain) instead of by the throughput timer's start/stop
        pair."""
        if bool(metrics.get("overflow", False)):
            self.skipped_steps += 1
        if wall_s is None:
            self.tput_timer.stop(global_step=True)
        else:
            self.tput_timer.record(wall_s)
        if step_no % self.config.steps_per_print == 0:
            loss = metrics.get("loss")
            loss_s = f"loss={float(loss):.4f}, " if loss is not None else ""
            log_dist(
                f"step={step_no}, {loss_s}"
                f"lr={float(metrics['lr']):.3e}, "
                f"grad_norm={float(metrics['grad_norm']):.3f}", ranks=[0])
        if self.monitor is not None and self.monitor.enabled:
            events = [("Train/Samples/train_loss",
                       float(metrics.get("loss", 0.0)), samples),
                      ("Train/Samples/lr", float(metrics["lr"]),
                       samples)]
            self.monitor.write_events(events)
        if self.config.wall_clock_breakdown and \
                step_no % self.config.steps_per_print == 0:
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                             STEP_GLOBAL_TIMER, TRAIN_BATCH_TIMER])
        fp = self.config.flops_profiler
        if fp.enabled and step_no == fp.profile_step \
                and jax.process_index() == 0:
            # rank 0 only: the profile recompiles the step (lowering is
            # process-local, no collectives run) and writes output_file
            from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler

            prof = FlopsProfiler(engine=self)
            prof.start_profile()
            prof.stop_profile()
            prof.print_model_profile(profile_step=fp.profile_step,
                                     module_depth=fp.module_depth,
                                     top_modules=fp.top_modules,
                                     detailed=fp.detailed,
                                     output_file=fp.output_file)
            prof.end_profile()

    def _build_monitor(self):
        try:
            from deepspeed_tpu.monitor.monitor import MonitorMaster

            return MonitorMaster(self.config.monitor)
        except Exception as e:
            logger.debug(f"monitor disabled: {e}")
            return None

    # ------------------------------------------------------------------
    # observability (docs/observability.md)
    # ------------------------------------------------------------------
    def _last_step_wall_ms(self) -> float:
        records = self.timers(TRAIN_BATCH_TIMER).records
        return records[-1] if records else 0.0

    def _on_stall_report(self, report: str) -> None:
        if self.hub is not None:
            self.hub.counter_add("train.stalls")
            self.hub.record_event("stall_report", step=self.global_steps,
                                  report=report)

    def _batch_tokens(self, batches):
        """Trained tokens in one train_batch: gas * B * S with input_ids
        [gas, B, S+1] (next-token objective trains S positions per
        sequence — the same count bench.py divides by)."""
        try:
            ids = batches.get("input_ids") if hasattr(batches, "get") \
                else None
            if ids is None:
                leaves = jax.tree.leaves(batches)
                ids = leaves[0] if leaves else None
            if ids is None or ids.ndim < 2 or ids.shape[-1] < 2:
                return None
            return int(np.prod(ids.shape[:-1])) * (ids.shape[-1] - 1)
        except Exception:
            return None

    def _model_flops_per_token(self):
        if self._flops_per_token is None:
            fn = getattr(self.model, "flops_per_token", None)
            try:
                self._flops_per_token = float(fn()) if callable(fn) else 0.0
            except Exception:
                self._flops_per_token = 0.0
        return self._flops_per_token or None

    def _emit_step_trace(self, step_no, metrics, struct, wall_ms,
                         host_gap_ms=None, samples=None,
                         inflight=0) -> None:
        try:
            from deepspeed_tpu.observability import StepTrace
            from deepspeed_tpu.observability import roofline as _rl
            from deepspeed_tpu.utils.memory import device_memory_stats

            samples = self.global_samples if samples is None else samples
            self._last_batches_struct = struct
            dt = wall_ms / 1000.0
            tokens = self._batch_tokens(struct)
            n_chips = max(1, len(jax.devices()))
            tps = tokens / dt if (tokens and dt > 0) else None
            tps_chip = tps / n_chips if tps else None
            mfu_val = fpt = peak = None
            if tps_chip:
                fpt = self._model_flops_per_token()
                if fpt:
                    try:
                        peak = _rl.detect_peak_tflops(jax.devices()[0])
                        mfu_val = _rl.mfu(tps_chip, fpt, peak)
                    except _rl.UnknownDeviceError:
                        pass  # no chip peak known: MFU is not measured

            def _f(key):
                v = metrics.get(key)
                try:
                    return None if v is None else float(v)
                except Exception:
                    return None

            comm_total, comm_delta = self.hub.comm_deltas()
            compile_d = self.hub.compile_delta()
            # what the model counted in this step (int32 scalars of the
            # step program, read with the loss): on the step's row and,
            # summed, in the hub's counters
            counted = {k: int(v) for k, v in
                       (metrics.get("model_counters") or {}).items()}
            for name, n in counted.items():
                self.hub.counter_add(f"train.{name}", n)
            trace = StepTrace(
                step=step_no, wall_ms=wall_ms, tokens=tokens,
                tokens_per_sec=tps, tokens_per_sec_per_chip=tps_chip,
                n_chips=n_chips, loss=_f("loss"),
                grad_norm=_f("grad_norm"), lr=_f("lr"),
                loss_scale=_f("loss_scale"),
                overflow=bool(metrics.get("overflow", False)),
                skipped_steps=self.skipped_steps,
                mfu=mfu_val, mfu_source="model" if mfu_val else None,
                flops_per_token=fpt, peak_tflops=peak,
                host_gap_ms=host_gap_ms, inflight=inflight,
                compile_events=int(compile_d["events"]),
                compile_secs=compile_d["secs"],
                comm_bytes_total=comm_total or None,
                comm_bytes_delta=comm_delta or None,
                device_mem=device_memory_stats(), extras=counted)
            self.hub.record_step(trace)
            if self.monitor is not None and self.monitor.enabled and \
                    step_no % self.config.steps_per_print == 0:
                events = [("Train/Samples/step_seconds", dt, samples)]
                if tps is not None:
                    events.append(("Train/Samples/tokens_per_sec", tps,
                                   samples))
                if mfu_val is not None:
                    events.append(("Train/Samples/mfu", mfu_val, samples))
                self.monitor.write_events(events)
            if self._roofline_cost is None and step_no >= 2 and (
                    os.environ.get("DSTPU_ROOFLINE", "") == "1"
                    or getattr(self._obs_cfg, "xla_cost_analysis", False)):
                self.roofline()
        except Exception as e:  # observability must never fail the step
            logger.warning(f"step trace emission failed: {e}")

    def roofline(self, step_seconds=None):
        """Classify the compiled train step against the chip roofline.

        Lowers + compiles the active step function once more (XLA's
        ``cost_analysis`` lives on the compiled executable) and caches
        the cost — expensive for big models, hence opt-in via
        ``observability.xla_cost_analysis`` or ``DSTPU_ROOFLINE=1``
        (then it runs once, after step 2). Needs one prior
        ``train_batch`` for the batch shapes."""
        from deepspeed_tpu.observability import roofline as _rl
        from deepspeed_tpu.utils.hlo_bytes import program_costs

        if self._roofline_cost is None:
            if self._last_batches_struct is None:
                raise RuntimeError(
                    "roofline() needs one prior train_batch() (the batch "
                    "shapes come from it)")
            b = self._last_batches_struct
            lr_over = jnp.asarray(float("nan"), jnp.float32)
            with topo.use_mesh(self.mesh):
                if self._onebit:
                    lowered = self._jit_onebit.lower(
                        self.params, self._onebit_state, b, lr_over)
                elif self._zeropp:
                    lowered = self._jit_zeropp.lower(
                        self.params, self._zeropp_state, b, lr_over)
                elif self._offload is not None:
                    lowered = self._jit_grad_step.lower(
                        self.params, b, jnp.asarray(1.0, jnp.float32))
                else:
                    lowered = self._jit_train_step.lower(
                        self.params, self.opt_state, self.loss_scale_state,
                        self.step_count, b)
            self._roofline_cost = program_costs(lowered.compile())
        if step_seconds is None:
            wall = self._last_step_wall_ms()
            step_seconds = wall / 1000.0 if wall > 0 else None
        dev = jax.devices()[0]
        summary = _rl.roofline_summary(
            self._roofline_cost, _rl.detect_peak_tflops(dev),
            _rl.detect_hbm_gbps(dev), step_seconds=step_seconds)
        if self.hub is not None:
            self.hub.record_event("roofline", step=self.global_steps,
                                  **summary)
            self.hub.gauge("train.arithmetic_intensity",
                           summary["arithmetic_intensity"])
            if "hw_flops_utilization" in summary:
                self.hub.gauge("train.hw_flops_utilization",
                               summary["hw_flops_utilization"])
        return summary

    # ------------------------------------------------------------------
    # optimizer view + state accessors
    # ------------------------------------------------------------------
    @property
    def optimizer(self):
        return _OptimizerView(self)

    def get_lr(self):
        if self.lr_schedule is not None:
            return [float(self.lr_schedule(self.step_count))]
        return [self._base_lr or 0.0]

    def set_lr(self, lr: float) -> None:
        """Client lr override (the reference-common
        ``optimizer.param_groups[0]['lr'] = x`` pattern). The compiled
        step bakes the lr closure at trace time, so this rebuilds the
        step functions — recompilation happens on the next call (cheap
        relative to how rarely clients poke lr mid-run)."""
        if self._zeropp or getattr(self, "_onebit", False):
            # the ZeRO++ and 1-bit steps take lr as a runtime operand
            # (NaN = use the traced schedule), so no rebuild is needed
            self._lr_override = float(lr)
            self._base_lr = float(lr)
            if self.lr_schedule is not None:
                logger.warning("set_lr override disables the configured "
                               "lr schedule for the runtime-lr step")
                self.lr_schedule = None
            return
        if self._client_optimizer_present:
            raise NotImplementedError(
                "set_lr: the engine cannot re-point a client-supplied "
                "optax transform's lr; rebuild the transform and engine")
        self._base_lr = float(lr)
        if self.lr_schedule is not None:
            logger.warning("set_lr/param_groups override disables the "
                           "configured lr schedule")
            self.lr_schedule = None
        if self.config.optimizer is None:
            # the engine was built with the default transform — pin the
            # implied optimizer into the config so the rebuild below
            # carries the new lr (a skipped rebuild would silently keep
            # the old lr in the compiled step)
            from deepspeed_tpu.config.config import OptimizerConfig

            self.config.optimizer = OptimizerConfig(
                type="adamw", params={"lr": float(lr)})
        self.config.optimizer.params = dict(
            self.config.optimizer.params or {}, lr=float(lr))
        # rebuild the optax transform: the old tx closed over the
        # previous lr (state layout is unchanged — same optimizer)
        self.tx, _ = get_base_optimizer(self.config.optimizer, None)
        self._build_step_fns()

    # ------------------------------------------------------------------
    # state offload between phases (reference engine.offload_states
    # engine.py:5573 / reload_states — frees HBM for e.g. RLHF
    # generation with another model copy)
    # ------------------------------------------------------------------
    def offload_states(self, include=None, device: str = "cpu",
                       pin_memory: bool = True, non_blocking: bool = False):
        """Move engine-held device state to pinned host memory.

        ``include`` limits the set: any of {"lp_params", "optim_states"}
        (reference OffloadStateTypeEnum names accepted; grads have no
        persistent buffer here — they live inside the compiled step).
        """
        if device != "cpu":
            raise ValueError("offload_states supports device='cpu' only")
        self.synchronize()
        include = set(include or ("lp_params", "optim_states"))
        known = {"lp_params", "hp_params", "optim_states", "lp_grads",
                 "contiguous_grad_buffer"}
        unknown = include - known
        if unknown:
            raise ValueError(f"unknown offload_states entries {unknown}")

        def to_host(tree):
            return jax.tree.map(
                lambda a: jax.device_put(
                    a, memspace.with_memory_kind(a.sharding, "pinned_host"))
                if isinstance(a, jax.Array)
                and memspace.memories_supported()
                and a.sharding.memory_kind != "pinned_host" else a, tree)

        if include & {"lp_params", "hp_params"}:
            self.params = to_host(self.params)
        if "optim_states" in include and self.opt_state is not None:
            self.opt_state = to_host(self.opt_state)
        self._states_offloaded = True
        if self.flight is not None:
            self.flight.record("offload_states", step=self.global_steps,
                               include=sorted(include))

    def reload_states(self, non_blocking: bool = False):
        """Inverse of offload_states: device placement restored."""
        if not getattr(self, "_states_offloaded", False):
            return
        if self.flight is not None:
            self.flight.record("reload_states", step=self.global_steps)

        def to_device(tree):
            return jax.tree.map(
                lambda a: jax.device_put(
                    a, memspace.with_memory_kind(a.sharding, "device"))
                if isinstance(a, jax.Array)
                and memspace.memory_kind_of(a) == "pinned_host"
                else a, tree)

        if getattr(self, "_param_host_offload", False):
            # streamed params live on host by design; restore the rest
            paths = getattr(self, "_host_param_paths", ("layers",))
            kept = {k: self.params[k] for k in paths
                    if isinstance(self.params, dict) and k in self.params}
            self.params = to_device(self.params)
            if kept:
                self.params = dict(self.params)
                self.params.update(kept)
        else:
            self.params = to_device(self.params)
        if self.opt_state is not None:
            self.opt_state = to_device(self.opt_state)
        self._states_offloaded = False

    def get_global_grad_norm(self):
        """The global gradient norm of the last step whose results the
        host has read: the last *drained* ``train_batch`` step (the step
        program computes it; no device read is added), or the last host
        optimizer step under offload. None before the first."""
        return self._last_grad_norm

    # -- reference-parity engine API ------------------------------------
    def no_sync(self):
        """Context manager suppressing DP grad sync during accumulation
        (reference engine.no_sync engine.py:2897). On TPU the micro-step
        path accumulates grads that XLA has already reduced — sum and
        reduce commute, so the math (and the comm volume per GAS window
        under reduce-scatter) matches the reference's deferred sync; the
        context exists for API compatibility."""
        import contextlib

        return contextlib.nullcontext()

    def compile(self, backend=None, compile_kwargs=None):
        """Reference engine.compile (engine.py:5472). Everything here is
        already traced+compiled by XLA on first use; this warms the train
        step's compile cache eagerly instead."""
        del backend, compile_kwargs
        self._compiled = True
        return self

    def train(self, mode: bool = True):
        """Mode toggles are meaningless for pure functions; kept for the
        reference's nn.Module-style call sites."""
        del mode
        return self

    def eval(self):
        return self.train(False)

    def module_state_dict(self):
        """Host copy of the model parameters (reference
        module_state_dict engine.py:3693): path → np.ndarray."""
        self.synchronize()
        flat, _ = jax.tree_util.tree_flatten_with_path(self.params)
        out = {}
        for path, leaf in flat:
            key = ".".join(getattr(p, "key", str(getattr(p, "idx", p)))
                           for p in path)
            out[key] = np.asarray(leaf)
        return out

    def load_module_state_dict(self, state_dict, strict: bool = True):
        """Inverse of module_state_dict: place host arrays back with the
        engine's shardings. strict=True raises on missing AND unexpected
        keys (torch/DeepSpeed strict-load semantics)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.params)
        leaves = []
        missing = []
        seen = set()
        for path, leaf in flat:
            key = ".".join(getattr(p, "key", str(getattr(p, "idx", p)))
                           for p in path)
            seen.add(key)
            if key in state_dict:
                leaves.append(jax.device_put(
                    np.asarray(state_dict[key], dtype=leaf.dtype),
                    leaf.sharding))
            else:
                missing.append(key)
                leaves.append(leaf)
        unexpected = sorted(set(state_dict) - seen)
        if strict and (missing or unexpected):
            raise KeyError(
                f"missing keys: {missing[:5]}"
                f"{'...' if len(missing) > 5 else ''}; unexpected keys: "
                f"{unexpected[:5]}{'...' if len(unexpected) > 5 else ''}")
        self.params = jax.tree_util.tree_unflatten(treedef, leaves)

    @property
    def loss_scale(self) -> float:
        return float(self.loss_scale_state.scale)

    def zero_grad(self):
        self._grad_acc = None

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:4557,4079)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest: bool = True):
        # drain in-flight steps first: the saved counters (global_steps,
        # skipped_steps) and state must reflect every dispatched step
        self.synchronize()
        self._last_save_dir = save_dir  # emergency-save fallback target
        if self.flight is not None:
            self.flight.record("checkpoint_save", step=self.global_steps,
                               tag=str(tag), phase="begin")
        out = self._ckpt_io.save(save_dir, tag=tag,
                                 client_state=client_state,
                                 save_latest=save_latest)
        if self.flight is not None:
            self.flight.record("checkpoint_save", step=self.global_steps,
                               tag=str(tag), phase="end")
        return out

    def load_checkpoint(self, load_dir, tag=None,
                        load_module_strict: bool = True,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True):
        self.synchronize()  # in-flight steps must not outlive old state
        if self.flight is not None:
            self.flight.record("checkpoint_load", tag=str(tag),
                               phase="begin")
        out = self._ckpt_io.load(load_dir, tag=tag,
                                 load_optimizer_states=load_optimizer_states)
        if self.flight is not None:
            self.flight.record("checkpoint_load", tag=str(tag),
                               phase="end")
        if getattr(self, "_param_host_offload", False):
            # restored leaves come back in device memory; re-pin layers
            self.params = self._place_layer_params_on_host(self.params)
        return out

    def resume_data_iter(self, data_iter, source=None):
        """Position ``data_iter`` at the first microbatch the checkpoint
        never consumed, using the manifest's data cursor from the last
        ``load_checkpoint`` (no-op on a fresh run). Call BEFORE the first
        ``train_batch`` so the prefetcher only ever sees the positioned
        stream; ``source`` optionally names the loader object (e.g. a
        ``RepeatingLoader``) whose ``load_state_dict`` restores
        epoch/rng state. See docs/resilience.md."""
        from deepspeed_tpu.resilience.resume import resume_data_iter

        return resume_data_iter(data_iter, self.loaded_data_cursor,
                                source=source)


class _LRGroup(dict):
    """One live param group: reading 'lr' reflects the engine; writing
    'lr' re-points the compiled step (reference clients mutate
    ``param_groups[0]['lr']`` and expect it to take effect)."""

    def __init__(self, engine: "Engine"):
        super().__init__()
        self._engine = engine
        self._refresh()

    def _refresh(self):
        # keep the plain-dict view (get()/items()/copy()) in sync with
        # the engine so every read path reports the live lr
        dict.__setitem__(self, "lr", self._engine.get_lr()[0])

    def __getitem__(self, key):
        if key == "lr":
            self._refresh()
        return super().__getitem__(key)

    def get(self, key, default=None):
        if key == "lr":
            self._refresh()
        return super().get(key, default)

    def items(self):
        self._refresh()
        return super().items()

    def __setitem__(self, key, value):
        if key == "lr":
            self._engine.set_lr(float(value))  # raises before storing
        super().__setitem__(key, value)


class _OptimizerView:
    """Duck-types the bits of a torch optimizer users poke (param_groups
    lr); returned as the 2nd element of initialize()'s tuple."""

    def __init__(self, engine: Engine):
        self._engine = engine
        self._groups = [_LRGroup(engine)]

    @property
    def param_groups(self):
        return self._groups

    @property
    def state(self):
        return self._engine.opt_state


def _constrain_tree(tree, shardings):
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, shardings)


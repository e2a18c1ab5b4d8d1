"""The framework config tree.

TPU-native analog of the reference's ``DeepSpeedConfig``
(reference: deepspeed/runtime/config.py:676) and its nested sub-configs.
A single JSON file / dict configures the whole engine. Key parity points:

  - batch-size triple solver: ``train_batch_size`` =
    ``train_micro_batch_size_per_chip`` × ``gradient_accumulation_steps`` ×
    data-parallel world size (reference ``_configure_train_batch_size``
    runtime/config.py:971);
  - ``"auto"`` values resolved by the engine or autotuner;
  - deprecated-key aliasing (e.g. ``train_micro_batch_size_per_gpu``).

TPU-first deltas: fp16 loss-scaling exists for parity but bf16 is the
default compute dtype; ZeRO stages map to sharding declarations instead of
runtime partitioning (see runtime/zero.py); parallel topology (dp/fsdp/
tp/sp/ep/pp) is part of the config because on TPU it compiles into the
program rather than being wired at runtime.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from deepspeed_tpu.config.config_utils import (
    AUTO,
    ConfigModel,
    is_auto,
    register_config_model,
)
from deepspeed_tpu.utils.logging import logger


# ---------------------------------------------------------------------------
# sub-configs
# ---------------------------------------------------------------------------


@register_config_model
@dataclass
class OptimizerConfig(ConfigModel):
    """Reference: ``optimizer`` block (runtime/config.py:90-127)."""

    type: str = "adamw"
    params: Dict[str, Any] = field(default_factory=dict)


@register_config_model
@dataclass
class SchedulerConfig(ConfigModel):
    """Reference: ``scheduler`` block → runtime/lr_schedules.py."""

    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)


@register_config_model
@dataclass
class BF16Config(ConfigModel):
    """Reference: ``bf16`` block (runtime/config.py:157). Default on TPU."""

    enabled: bool = True


@register_config_model
@dataclass
class FP16Config(ConfigModel):
    """Reference: ``fp16`` block with dynamic loss scaling
    (runtime/fp16/loss_scaler.py:187). Rarely wanted on TPU (bf16-native),
    kept for API parity and for accelerators without bf16."""

    enabled: bool = False
    loss_scale: float = 0.0  # 0 = dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    min_loss_scale: float = 1.0


@register_config_model
@dataclass
class OffloadParamConfig(ConfigModel):
    """Reference: DeepSpeedZeroOffloadParamConfig (runtime/zero/offload_config.py:21)."""

    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    pin_memory: bool = False


@register_config_model
@dataclass
class OffloadOptimizerConfig(ConfigModel):
    """Reference: DeepSpeedZeroOffloadOptimizerConfig (runtime/zero/offload_config.py:52)."""

    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = 4
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    # device->host gradient transfer dtype: "fp32" (exact) or "bf16"
    # (halves transfer volume; native bf16-grad optimizer kernels)
    grad_transfer_dtype: str = "fp32"
    ratio: float = 1.0

    def validate(self) -> None:
        if self.device not in ("none", "cpu", "nvme"):
            raise ValueError(
                f"offload_optimizer.device must be none|cpu|nvme, "
                f"got {self.device!r}")
        if self.device == "nvme" and not self.nvme_path:
            raise ValueError(
                "offload_optimizer.device='nvme' requires nvme_path "
                "(otherwise state would silently stay in host RAM)")
        if self.grad_transfer_dtype not in ("fp32", "bf16"):
            raise ValueError(
                f"offload_optimizer.grad_transfer_dtype must be fp32|bf16, "
                f"got {self.grad_transfer_dtype!r}")


@register_config_model
@dataclass
class ZenFlowBlockConfig(ConfigModel):
    """Reference: ZenFlowConfig (runtime/zenflow/zenflow_config.py) —
    importance-split offloaded optimization: top-k coordinates update on
    device every step, the rest in an overlapped host pass."""

    topk_ratio: float = 0.01
    update_interval: int = 4
    select_interval: int = 16
    overlap_step: bool = True
    # host-optimizer worker parallelism (reference SuperOffload runs a
    # CPU optimizer worker process, superoffload_utils.py:165; threads
    # suffice here — the native optimizer releases the GIL)
    workers: int = 1

    def validate(self) -> None:
        if self.workers < 1:
            raise ValueError(f"zenflow.workers must be >= 1, got "
                             f"{self.workers}")
        if not 0.0 < self.topk_ratio <= 1.0:
            raise ValueError(
                f"zenflow.topk_ratio must be in (0, 1], got "
                f"{self.topk_ratio}")
        if self.update_interval < 1 or self.select_interval < 1:
            raise ValueError("zenflow intervals must be >= 1")


@register_config_model
@dataclass
class ZeroConfig(ConfigModel):
    """Reference: DeepSpeedZeroConfig (runtime/zero/config.py:90).

    On TPU the stages are declarative sharding choices (runtime/zero.py):
      0: replicate params/grads/opt-state over dp;
      1: shard optimizer state over dp;
      2: + reduce-scatter grads (grads land sharded);
      3: + shard parameters over dp (XLA all-gathers on use).
    """

    stage: int = 0
    # bucket knobs kept for parity; on TPU XLA handles bucketing, but they
    # bound host-side flattening in the offload path.
    reduce_bucket_size: int = 500_000_000
    allgather_bucket_size: int = 500_000_000
    overlap_comm: bool = True
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    round_robin_gradients: bool = False
    offload_param: Optional[OffloadParamConfig] = None
    offload_optimizer: Optional[OffloadOptimizerConfig] = None
    # ZenFlow (stall-free offload): requires offload_optimizer.device=cpu
    zenflow: Optional[ZenFlowBlockConfig] = None
    sub_group_size: int = 1_000_000_000
    # ZeRO++ (reference docs/_tutorials/zeropp.md): hierarchical partitioning
    # and quantized collectives.
    zero_hpz_partition_size: int = 1  # 1 = off; >1 = shard within ICI slice
    zero_quantized_weights: bool = False  # qwZ: int8 all-gather of params
    zero_quantized_gradients: bool = False  # qgZ: quantized grad reduce
    # qar: EQuARX-style quantized all-reduce of gradients (quantize →
    # int8 reduce-scatter with fp32 accumulation → int8 all-gather →
    # dequant; ops/pallas/quantization.py quantized_all_reduce). Replaces
    # the stage-1/2 gradient reduce; mutually exclusive with qgZ (both
    # own the same wire).
    zero_quantized_allreduce: bool = False
    # MiCS (runtime/zero/mics.py): sub-world shard groups.
    mics_shard_size: int = -1
    mics_hierarchical_params_gather: bool = False
    log_trace_cache_warnings: bool = False
    model_persistence_threshold: int = 0  # params below stay replicated
    param_persistence_threshold: int = 0

    def validate(self) -> None:
        if self.stage not in (0, 1, 2, 3):
            raise ValueError(f"zero_optimization.stage must be 0-3, got {self.stage}")
        if self.zero_hpz_partition_size < 1:
            raise ValueError("zero_hpz_partition_size must be >= 1")
        if self.zero_quantized_allreduce and self.zero_quantized_gradients:
            raise ValueError(
                "zero_quantized_allreduce (qar) and "
                "zero_quantized_gradients (qgZ) both own the gradient "
                "wire — enable at most one")


@register_config_model
@dataclass
class TensorParallelConfig(ConfigModel):
    """Reference: ``tensor_parallel`` block (runtime/tensor_parallel/config.py,
    autotp_size engine.py:624)."""

    autotp_size: int = 1
    tp_grain_size: int = 1

    @property
    def size(self) -> int:
        return max(1, self.autotp_size)


@register_config_model
@dataclass
class SequenceParallelConfig(ConfigModel):
    """Ulysses-style sequence parallelism (reference: deepspeed/sequence/layer.py:351,
    runtime/sequence_parallel/ulysses_sp.py). ``mode='ring'`` adds the
    ring-attention option the reference lacks (SURVEY §5: head-count < chips)."""

    size: int = 1
    mode: str = "ulysses"  # ulysses | ring
    tiled_mlp: bool = False
    tiled_logits: bool = False
    tile_size: int = 0  # 0 = auto
    # unified long-context planner (parallel/auto_sp.py
    # plan_sequence_parallel): when the mesh has an sp axis the engine
    # composes strategy/chunking/host-KV-spill onto the model config at
    # init — conservatively, never overriding explicit model settings.
    # False opts out.
    auto_plan: bool = True
    # per-chip activation HBM budget (GiB) the planner sizes chunking
    # and host-KV spill against; None plans without spill pressure.
    hbm_budget_gb: Optional[float] = None


@register_config_model
@dataclass
class MoEConfig(ConfigModel):
    """Expert parallelism defaults used by our model zoo (reference MoE layer
    args: deepspeed/moe/layer.py:17)."""

    enabled: bool = False
    ep_size: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    top_k: int = 2
    drop_tokens: bool = True
    use_rts: bool = False
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.0
    # expert execution engine: "auto" | "grouped" (dropless grouped-GEMM,
    # reference GroupedExperts moe/ep_experts.py:136) | "einsum" (capacity)
    impl: str = "auto"


@register_config_model
@dataclass
class PipelineConfig(ConfigModel):
    """Reference: PipelineModule/PipelineEngine (runtime/pipe/). On TPU the
    1F1B interpreter becomes a collective-permute microbatch pipeline
    (parallel/pipeline.py)."""

    stages: int = 1
    partition_method: str = "uniform"  # uniform | parameters
    activation_checkpoint_interval: int = 0
    # microbatches per pipeline pass (default 2*stages; more amortizes
    # the bubble) and the 1F1B-depth window: microbatches are run in
    # waves of `window` (default 2*stages) with per-wave remat, so live
    # stage-boundary activations stay O(window) no matter how large
    # `microbatches` grows (the role of TrainSchedule's bounded
    # in-flight depth, reference pipe/schedule.py:189)
    microbatches: int = 0  # 0 = auto
    window: int = 0  # 0 = auto (2*stages)
    # "waves": waves of `window` microbatches with per-wave remat —
    #   activation memory O(window + stages) however large `microbatches`
    #   grows, at the cost of one extra forward per wave (the reference
    #   1F1B TrainSchedule's bounded depth, pipe/schedule.py:189).
    # "save_boundaries": one un-rematted pass — the scan saves exactly
    #   the per-step stage-boundary activations (O(microbatches+stages)
    #   of them), no wave recompute: pipeline flops match the no-pp
    #   model within the bubble. Scale batch via gradient accumulation
    #   instead of microbatches in this mode.
    schedule: str = "waves"


@register_config_model
@dataclass
class ActivationCheckpointingConfig(ConfigModel):
    """Reference: runtime/activation_checkpointing/checkpointing.py:1029.
    On TPU this selects the jax.checkpoint (remat) policy applied to the
    scanned layer stack."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native knob: which remat policy to use for the layer scan.
    # nothing_saveable | dots_saveable | dots_with_no_batch_dims_saveable
    # | offload_dots_host | none
    policy: str = "nothing_saveable"


@register_config_model
@dataclass
class CommsLoggerConfig(ConfigModel):
    """Reference: comms_logger block (utils/comms_logging.py:67)."""

    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: list = field(default_factory=list)


@register_config_model
@dataclass
class MonitorBackendConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"
    team: Optional[str] = None
    project: Optional[str] = None
    group: Optional[str] = None


@register_config_model
@dataclass
class MonitorConfig(ConfigModel):
    """Reference: deepspeed/monitor/config.py; MonitorMaster (monitor/monitor.py:30)."""

    tensorboard: MonitorBackendConfig = field(default_factory=MonitorBackendConfig)
    csv_monitor: MonitorBackendConfig = field(default_factory=MonitorBackendConfig)
    wandb: MonitorBackendConfig = field(default_factory=MonitorBackendConfig)
    comet: MonitorBackendConfig = field(default_factory=MonitorBackendConfig)
    jsonl: MonitorBackendConfig = field(default_factory=MonitorBackendConfig)


@register_config_model
@dataclass
class FlopsProfilerConfig(ConfigModel):
    """Reference: deepspeed/profiling/config.py. On TPU we read XLA's
    ``Compiled.cost_analysis()`` instead of monkey-patching ops."""

    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@register_config_model
@dataclass
class WatchdogConfig(ConfigModel):
    """Stall watchdog (observability/watchdog.py): a step exceeding
    ``max(factor * rolling_mean_step_time, min_seconds)`` triggers a
    report with Python stacks and device memory stats. Env overrides:
    DSTPU_WATCHDOG=0, DSTPU_WATCHDOG_FACTOR, DSTPU_WATCHDOG_MIN_S."""

    enabled: bool = True
    factor: float = 8.0
    min_seconds: float = 30.0


@register_config_model
@dataclass
class RequestTraceConfig(ConfigModel):
    """Per-request serving traces (observability/request_trace.py;
    docs/serving.md "Request tracing & SLO attribution").

    Every request the serving engine touches records a typed span
    timeline; at FINISH a tail-based sampler keeps every SLO violator
    (TTFT > ``slo_deadline_ms``) plus a ``sample_rate`` random slice of
    the healthy rest in a ``ring_size``-bounded ring. ``slo_deadline_ms``
    null means no deadline: only the random slice is kept. Env
    overrides: DSTPU_REQUEST_TRACE=0 (disable),
    DSTPU_REQ_TRACE_SAMPLE, DSTPU_REQ_TRACE_RING,
    DSTPU_REQ_TRACE_SLO_MS."""

    enabled: bool = True
    sample_rate: float = 0.05
    ring_size: int = 4096
    slo_deadline_ms: Optional[float] = None

    def validate(self) -> None:
        if not 0.0 <= self.sample_rate <= 1.0:
            raise ValueError(
                f"observability.request_trace.sample_rate must be in "
                f"[0, 1], got {self.sample_rate}")
        if self.ring_size < 1:
            raise ValueError(
                f"observability.request_trace.ring_size must be >= 1, "
                f"got {self.ring_size}")
        if self.slo_deadline_ms is not None and self.slo_deadline_ms <= 0:
            raise ValueError(
                f"observability.request_trace.slo_deadline_ms must be "
                f"> 0 (or null), got {self.slo_deadline_ms}")


@register_config_model
@dataclass
class ClockSyncConfig(ConfigModel):
    """Per-channel fleet clock sync (observability/clocksync.py;
    docs/observability.md "Fleet tracing & clock sync").

    The supervisor attaches one NTP-style offset estimator to every
    worker channel: ``rounds`` ping/pong exchanges at spawn, a re-ping
    whenever the newest sample is older than ``resync_seconds``. The
    estimate is the median offset of the ``k`` lowest-RTT samples in a
    ``window``-bounded history; ``min_samples`` round trips gate
    ``synced`` (before that — and always with ``enabled=false`` — every
    consumer passes raw timestamps through, bit-exact with the
    pre-clocksync localhost behavior)."""

    enabled: bool = True
    rounds: int = 8
    resync_seconds: float = 5.0
    k: int = 5
    window: int = 32
    min_samples: int = 3

    def validate(self) -> None:
        if self.rounds < 1:
            raise ValueError(
                f"observability.clock_sync.rounds must be >= 1, got "
                f"{self.rounds}")
        if self.resync_seconds <= 0:
            raise ValueError(
                f"observability.clock_sync.resync_seconds must be > 0, "
                f"got {self.resync_seconds}")
        if not 1 <= self.k <= self.window:
            raise ValueError(
                f"observability.clock_sync needs 1 <= k <= window, got "
                f"k={self.k} window={self.window}")
        if self.min_samples < 1:
            raise ValueError(
                f"observability.clock_sync.min_samples must be >= 1, "
                f"got {self.min_samples}")


@register_config_model
@dataclass
class BurnRateConfig(ConfigModel):
    """SLO burn-rate alerting (observability/burn_rate.py;
    docs/observability.md "Burn-rate alerts").

    The SRE multi-window shape: with ``slo_target`` 0.999 the error
    budget is 0.1%, and the alert fires when BOTH the fast window
    (``fast_window_seconds`` at >= ``fast_burn`` x budget-neutral
    spend) and the slow window agree — fast catches the cliff, slow
    suppresses self-healing blips. ``deadline_ms`` is the per-request
    SLO deadline on ``objective`` (``ttft`` or ``e2e``); null leaves
    alerting off even when enabled. A firing alert clears after
    ``clear_checks`` consecutive clean evaluations; ``min_events``
    observations must sit in the fast window before the first fire."""

    enabled: bool = False
    deadline_ms: Optional[float] = None
    slo_target: float = 0.999
    fast_window_seconds: float = 60.0
    fast_burn: float = 14.4
    slow_window_seconds: float = 600.0
    slow_burn: float = 6.0
    clear_checks: int = 3
    min_events: int = 10
    objective: str = "ttft"

    def validate(self) -> None:
        if not 0.0 < self.slo_target < 1.0:
            raise ValueError(
                f"burn_rate.slo_target must be in (0, 1), got "
                f"{self.slo_target}")
        if self.deadline_ms is not None and self.deadline_ms <= 0:
            raise ValueError(
                f"burn_rate.deadline_ms must be > 0 (or null), got "
                f"{self.deadline_ms}")
        if not 0 < self.fast_window_seconds <= self.slow_window_seconds:
            raise ValueError(
                f"burn_rate needs 0 < fast_window_seconds <= "
                f"slow_window_seconds, got ({self.fast_window_seconds}, "
                f"{self.slow_window_seconds})")
        if self.fast_burn <= 0 or self.slow_burn <= 0:
            raise ValueError(
                f"burn_rate burn thresholds must be > 0, got "
                f"({self.fast_burn}, {self.slow_burn})")
        if self.clear_checks < 1 or self.min_events < 1:
            raise ValueError(
                f"burn_rate.clear_checks and min_events must be >= 1, "
                f"got ({self.clear_checks}, {self.min_events})")
        if self.objective not in ("ttft", "e2e"):
            raise ValueError(
                f"burn_rate.objective must be ttft|e2e, got "
                f"{self.objective!r}")


@register_config_model
@dataclass
class PerformanceConfig(ConfigModel):
    """Pipelined training loop (docs/performance.md).

    ``pipeline_depth`` is the number of dispatched-but-unresolved
    ``train_batch`` steps the engine may keep in flight before blocking
    (dispatch-ahead): 0 = fully synchronous — the debugging default,
    where every per-step host read happens inside its own step. The
    ``DSTPU_DISPATCH_AHEAD`` env var overrides it. ``prefetch_depth``
    bounds the background input-prefetch buffer
    (runtime/prefetch.py PrefetchingIterator); 0 disables prefetch, and
    multi-process runs fall back to synchronous input assembly
    regardless.

    ``param_prefetch_depth`` sets the depth of the ZeRO-Infinity layer
    prefetch ring (runtime/param_stream.py streamed_layers_prefetch):
    K layers of host→device fetches ride in flight ahead of the compute
    when ``offload_param`` streams the layer stack. None (default)
    keeps the model's own default (2, or the DSTPU_PREFETCH_DEPTH env);
    1 reproduces plain double-buffering bit-for-bit. HBM cost is K
    fp32 layers.

    ``fp8_mlp`` routes the MLP-block matmuls through fp8 (e4m3 operands,
    fp32 accumulation, straight-through gradients — ops/fp_quantizer.py
    fp8_matmul_ste). Opt-in: off by default for exact parity; on v5p+
    the MXU runs fp8 at 2x the bf16 rate.

    ``overlap_depth`` arms the per-layer overlap engine
    (runtime/param_stream.py pin_stage): the K newest in-flight
    transfers — h2d layer fetches on the ZeRO-Infinity path, fsdp
    all-gathers on the stage-3 resident path, plus the backward grad
    streams — are barrier-pinned into the issuing layer's scheduling
    stage, so each transfer provably overlaps that layer's compute.
    0 disables (today's program, bit-for-bit); None keeps the model/env
    default (DSTPU_OVERLAP_DEPTH). Identity on values at any depth."""

    pipeline_depth: int = 0
    prefetch_depth: int = 2
    param_prefetch_depth: Optional[int] = None
    fp8_mlp: bool = False
    overlap_depth: Optional[int] = None

    def validate(self) -> None:
        if self.pipeline_depth < 0:
            raise ValueError(
                f"performance.pipeline_depth must be >= 0, got "
                f"{self.pipeline_depth}")
        if self.prefetch_depth < 0:
            raise ValueError(
                f"performance.prefetch_depth must be >= 0, got "
                f"{self.prefetch_depth}")
        if self.param_prefetch_depth is not None \
                and self.param_prefetch_depth < 1:
            raise ValueError(
                f"performance.param_prefetch_depth must be >= 1, got "
                f"{self.param_prefetch_depth}")
        if self.overlap_depth is not None and self.overlap_depth < 0:
            raise ValueError(
                f"performance.overlap_depth must be >= 0, got "
                f"{self.overlap_depth}")


@register_config_model
@dataclass
class JournalConfig(ConfigModel):
    """Fleet black-box journal (observability/journal.py): append-only
    CRC-framed capture of admissions, routing/preemption/failover
    decisions with their inputs, chaos injections, and per-request
    emitted-token checksum chains — enough to re-drive the run
    bit-identically with tools/replay.py. Off by default: the journal
    is a forensic artifact, not ambient telemetry. ``dir`` is where
    ``<run>.journal`` files land (gitignored, like ``dstpu_flight/``);
    ``max_mb`` caps one journal file — past it records are dropped
    (counted, plus one TRUNCATED marker) rather than failing the
    run."""

    enabled: bool = False
    dir: str = "dstpu_journal"
    max_mb: float = 64.0

    def validate(self) -> None:
        if self.max_mb <= 0:
            raise ValueError(
                f"observability.journal.max_mb must be > 0, got "
                f"{self.max_mb}")
        if not self.dir:
            raise ValueError("observability.journal.dir must be set")


@register_config_model
@dataclass
class ObservabilityConfig(ConfigModel):
    """Unified observability hub (observability/hub.py). Per-step
    StepTrace rows (wall time, loss, tokens/s, MFU, comm deltas,
    compile events) flow to the in-process hub always; ``jsonl_path`` /
    ``prometheus_path`` additionally stream them to disk
    (DSTPU_METRICS_JSONL / DSTPU_METRICS_PROM env override).
    ``xla_cost_analysis`` opts into the lazily-computed roofline from
    the compiled step's cost analysis (env: DSTPU_ROOFLINE=1) — it
    costs one extra lower+compile, so it is off by default.

    Fleet layer (observability/fleet.py): ``run_dir`` (env override
    DSTPU_RUN_DIR — the launcher sets it for multi-process runs) points
    every rank at one shared directory where it publishes heartbeat +
    step-summary shards every ``publish_every_steps`` steps; a rank
    whose heartbeat is older than ``stale_after_seconds`` is reported
    dead by the aggregator. No run dir → no shard I/O. The crash flight
    recorder keeps a ring of ``flight_events`` structured events
    (0 disables) dumped on crash/SIGTERM/watchdog fire.
    ``request_trace`` configures the per-request serving flight paths
    (tail-sampled span timelines + SLO attribution; see
    RequestTraceConfig). ``quant_stats`` opts into the ZeRO++
    quantization-error telemetry (observability/quant_stats.py):
    ``quant.*`` hub metrics — per-region SNR dB, max relative error,
    wire-vs-logical bytes — sampled at engine init when qwZ/qgZ run
    (env override DSTPU_QUANT_STATS=1); off by default because the
    init-time sample quantizes a capped slice of the real params."""

    enabled: bool = True
    quant_stats: bool = False
    jsonl_path: Optional[str] = None
    prometheus_path: Optional[str] = None
    prometheus_every_steps: int = 10
    step_history: int = 512
    xla_cost_analysis: bool = False
    run_dir: Optional[str] = None
    publish_every_steps: int = 1
    stale_after_seconds: float = 30.0
    flight_events: int = 4096
    watchdog: WatchdogConfig = field(default_factory=WatchdogConfig)
    request_trace: RequestTraceConfig = field(
        default_factory=RequestTraceConfig)
    clock_sync: ClockSyncConfig = field(default_factory=ClockSyncConfig)
    journal: JournalConfig = field(default_factory=JournalConfig)

    def validate(self) -> None:
        self.request_trace.validate()
        self.clock_sync.validate()
        self.journal.validate()
        if self.flight_events < 0:
            raise ValueError(
                f"observability.flight_events must be >= 0, got "
                f"{self.flight_events}")
        if self.publish_every_steps < 1:
            raise ValueError(
                f"observability.publish_every_steps must be >= 1, got "
                f"{self.publish_every_steps}")


@register_config_model
@dataclass
class SparseAttentionConfig(ConfigModel):
    """Reference: ``sparse_attention`` block (runtime/config.py:250-410):
    dense | fixed | variable | bigbird | bslongformer modes. Maps onto the
    Pallas block-sparse layouts (ops/pallas/blocksparse_attention.py)."""

    mode: str = "fixed"
    block: int = 128
    num_local_blocks: int = 4
    num_global_blocks: int = 1
    num_random_blocks: int = 1
    num_sliding_window_blocks: int = 3
    local_window_blocks: Any = field(default_factory=lambda: [4])
    global_block_indices: Any = field(default_factory=lambda: [0])
    attention: str = "unidirectional"  # unidirectional (causal) | bidirectional

    def validate(self) -> None:
        if self.mode not in ("dense", "fixed", "variable", "bigbird",
                             "bslongformer"):
            raise ValueError(
                f"sparse_attention.mode must be dense|fixed|variable|"
                f"bigbird|bslongformer, got {self.mode!r}")
        if self.attention not in ("unidirectional", "bidirectional"):
            raise ValueError(
                f"sparse_attention.attention must be unidirectional|"
                f"bidirectional, got {self.attention!r}")


@register_config_model
@dataclass
class CheckpointConfig(ConfigModel):
    """Reference: checkpoint block (runtime/config.py:439-471)."""

    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False
    async_save: bool = False


@register_config_model
@dataclass
class ResilienceConfig(ConfigModel):
    """Fault tolerance (deepspeed_tpu/resilience/, docs/resilience.md).

    Preemption: ``preemption_guard`` installs a SIGTERM listener on the
    engine (first signal drains in-flight steps and forces an emergency
    checkpoint at the next GAS boundary within
    ``preemption_save_deadline_s``; a second signal escalates to
    immediate shutdown). The emergency save lands in
    ``emergency_save_dir``, defaulting to the directory of the last
    explicit ``save_checkpoint`` call.

    Checkpoint manifests: ``manifest`` writes an atomic per-tag manifest
    (topology, per-file checksums, data cursor) at publish and validates
    it at load, falling back to the previous good tag on corruption;
    ``manifest_checksums`` controls the (streaming crc32) content
    verification at load — size/presence checks always run.

    Collective health: ``init_timeout_s`` bounds ``init_distributed``;
    ``collective_timeout_s`` bounds the process-level control-plane ops
    (barrier, cross-process asserts, heartbeat I/O). ``None`` (default)
    leaves an op unbounded — zero behavior change until the block opts
    in. On deadline, ops retry up to ``max_retries`` times with
    exponential backoff (``backoff_base_s`` doubling to
    ``backoff_max_s``, ±``jitter``) and then raise ``CommTimeoutError``
    (worker exit code 75) carrying the flight-ring tail."""

    enabled: bool = True
    preemption_guard: bool = True
    preemption_save_deadline_s: float = 60.0
    emergency_save_dir: Optional[str] = None
    manifest: bool = True
    manifest_checksums: bool = True
    init_timeout_s: Optional[float] = None
    collective_timeout_s: Optional[float] = None
    max_retries: int = 2
    backoff_base_s: float = 1.0
    backoff_max_s: float = 30.0
    jitter: float = 0.25

    def validate(self) -> None:
        if self.preemption_save_deadline_s <= 0:
            raise ValueError(
                f"resilience.preemption_save_deadline_s must be > 0, got "
                f"{self.preemption_save_deadline_s}")
        if self.max_retries < 0:
            raise ValueError(
                f"resilience.max_retries must be >= 0, got "
                f"{self.max_retries}")
        for name in ("backoff_base_s", "backoff_max_s", "jitter"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"resilience.{name} must be >= 0, got "
                    f"{getattr(self, name)}")
        for name in ("init_timeout_s", "collective_timeout_s"):
            val = getattr(self, name)
            if val is not None and val <= 0:
                raise ValueError(
                    f"resilience.{name} must be > 0 (or null for "
                    f"unbounded), got {val}")


@register_config_model
@dataclass
class RouterConfig(ConfigModel):
    """Serving-fleet router knobs (serving/router.py, docs/serving.md
    "Multi-replica fleet").

    ``replicas`` sizes the in-process fleet the harness builds; ``mode``
    is ``"unified"`` (every replica prefills and decodes) or
    ``"disagg"`` (``prefill_replicas`` of the fleet prefill only, the
    rest decode only, with the KV-block handoff in between).
    ``affinity_blocks`` is the prefix-hash session-affinity window in KV
    blocks (0 disables affinity routing); ``stale_after_seconds`` is the
    heartbeat staleness that declares a replica dead and triggers
    failover. The ``autoscale_*``/``queue_*``/``slo_miss_high``/
    ``hysteresis_rounds`` knobs parameterize the desired-replica-count
    signal (serving/autoscale.py) — metrics only in-process; the
    cross-process supervisor (serving/supervisor.py) is the controller
    that acts on it.

    ``routing`` picks the placement policy behind the affinity check:
    ``least_loaded`` (live load report) or ``predictive`` (lowest
    predicted TTFT from the queue-depth x service-EWMA + prefill-rate
    model). ``transport`` selects how a process fleet connects its
    replicas — ``inproc`` (threads, no processes), ``socket``
    (localhost TCP, the primary), or ``file`` (spool-dir frames, the
    socketless fallback; docs/serving.md degraded-mode matrix) — with
    ``max_frame_mb`` bounding the frame size. The dial-with-backoff
    schedule is a resilience ``RetryPolicy`` built by
    :meth:`connect_retry_policy` from ``connect_retries`` /
    ``connect_backoff_seconds`` / ``connect_backoff_max_seconds``
    (the first two predate the policy and stay as aliases).

    Health state machine (docs/serving.md "Replica health"):
    ``health_mode`` is ``state_machine`` (healthy → suspect → dead with
    hysteresis) or ``legacy`` (the single stale-heartbeat flip,
    bit-exact pre-PR-15 routing). ``suspect_after_seconds`` is the
    heartbeat age that demotes to suspect (0 = half of
    ``stale_after_seconds``); ``transport_error_dead`` consecutive
    channel errors declare dead; ``health_recover_checks`` consecutive
    clean checks promote suspect back to healthy.

    Hedged requests: after ``hedge_ttft_factor`` x the predicted TTFT
    (floored at ``hedge_min_seconds``) with no first token, the router
    resubmits to a second replica and keeps whichever stream emits
    first — greedy decode makes the winner bit-identical either way.

    Crash-loop containment (serving/supervisor.py): a lineage crashing
    more than ``max_restarts_per_window`` times inside
    ``restart_window_seconds`` is quarantined instead of restarted;
    ``min_healthy`` is the floor below which drains are refused.

    Live session migration (docs/serving.md "Zero-downtime
    operations"): with ``migrate_sessions`` (the default), drains,
    rolling weight swaps, and migration-backed scale-down move every
    in-flight decode session off the leaving replica *warm* — KV
    blocks + generated tokens + spec EWMA over the quantized wire,
    zero re-prefill — degrading to host-tier page-out then legacy
    fold-and-recompute, never an error. ``migrate_hedges`` extends
    migrate-first to hedge promotion (off keeps the duplicate-stream
    hedge race bit-exact); ``migrate_wire`` overrides the session wire
    codec (empty = the engine's ``handoff_wire``; else raw/int8/int4/
    fp8)."""

    replicas: int = 2
    mode: str = "unified"
    prefill_replicas: int = 1
    affinity_blocks: int = 2
    stale_after_seconds: float = 5.0
    autoscale_min: int = 1
    autoscale_max: int = 8
    queue_high: float = 4.0
    queue_low: float = 0.5
    slo_miss_high: float = 0.1
    hysteresis_rounds: int = 3
    routing: str = "least_loaded"
    transport: str = "inproc"
    max_frame_mb: int = 64
    connect_retries: int = 40
    connect_backoff_seconds: float = 0.05
    connect_backoff_max_seconds: float = 1.0
    health_mode: str = "state_machine"
    suspect_after_seconds: float = 0.0  # 0 => stale_after_seconds / 2
    transport_error_dead: int = 3
    health_recover_checks: int = 2
    hedge_enabled: bool = False
    hedge_ttft_factor: float = 3.0
    hedge_min_seconds: float = 0.25
    max_restarts_per_window: int = 3
    restart_window_seconds: float = 30.0
    min_healthy: int = 1
    migrate_sessions: bool = True
    migrate_hedges: bool = False
    migrate_wire: str = ""  # "" => the engine's handoff_wire
    burn_rate: BurnRateConfig = field(default_factory=BurnRateConfig)

    def connect_retry_policy(self):
        """The transport dial schedule as a resilience
        :class:`RetryPolicy` — jitter 0 so reconnect timing stays
        deterministic under the chaos gates."""
        from deepspeed_tpu.resilience.policy import RetryPolicy

        return RetryPolicy(
            max_retries=max(0, self.connect_retries - 1),
            backoff_base_s=self.connect_backoff_seconds,
            backoff_max_s=self.connect_backoff_max_seconds,
            jitter=0.0)

    def validate(self) -> None:
        if self.mode not in ("unified", "disagg"):
            raise ValueError(
                f"serving.router.mode must be 'unified' or 'disagg', "
                f"got {self.mode!r}")
        if self.replicas < 1:
            raise ValueError(
                f"serving.router.replicas must be >= 1, got "
                f"{self.replicas}")
        if self.mode == "disagg" and not (
                1 <= self.prefill_replicas < self.replicas):
            raise ValueError(
                f"serving.router.prefill_replicas must leave at least "
                f"one decode replica (1 <= prefill_replicas < replicas),"
                f" got {self.prefill_replicas} of {self.replicas}")
        if self.affinity_blocks < 0:
            raise ValueError(
                f"serving.router.affinity_blocks must be >= 0, got "
                f"{self.affinity_blocks}")
        if self.stale_after_seconds <= 0:
            raise ValueError(
                f"serving.router.stale_after_seconds must be > 0, got "
                f"{self.stale_after_seconds}")
        if not 1 <= self.autoscale_min <= self.autoscale_max:
            raise ValueError(
                f"serving.router needs 1 <= autoscale_min <= "
                f"autoscale_max, got ({self.autoscale_min}, "
                f"{self.autoscale_max})")
        if self.hysteresis_rounds < 1:
            raise ValueError(
                f"serving.router.hysteresis_rounds must be >= 1, got "
                f"{self.hysteresis_rounds}")
        if self.routing not in ("least_loaded", "predictive"):
            raise ValueError(
                f"serving.router.routing must be least_loaded|"
                f"predictive, got {self.routing!r}")
        if self.transport not in ("inproc", "socket", "file"):
            raise ValueError(
                f"serving.router.transport must be inproc|socket|file, "
                f"got {self.transport!r}")
        if self.max_frame_mb < 1:
            raise ValueError(
                f"serving.router.max_frame_mb must be >= 1, got "
                f"{self.max_frame_mb}")
        if self.connect_retries < 1 or self.connect_backoff_seconds <= 0:
            raise ValueError(
                f"serving.router needs connect_retries >= 1 and "
                f"connect_backoff_seconds > 0, got "
                f"({self.connect_retries}, "
                f"{self.connect_backoff_seconds})")
        if self.connect_backoff_max_seconds < self.connect_backoff_seconds:
            raise ValueError(
                f"serving.router.connect_backoff_max_seconds must be >= "
                f"connect_backoff_seconds, got "
                f"{self.connect_backoff_max_seconds}")
        if self.health_mode not in ("state_machine", "legacy"):
            raise ValueError(
                f"serving.router.health_mode must be state_machine|"
                f"legacy, got {self.health_mode!r}")
        if self.suspect_after_seconds < 0:
            raise ValueError(
                f"serving.router.suspect_after_seconds must be >= 0 "
                f"(0 = stale_after_seconds/2), got "
                f"{self.suspect_after_seconds}")
        if self.transport_error_dead < 1 or self.health_recover_checks < 1:
            raise ValueError(
                f"serving.router needs transport_error_dead >= 1 and "
                f"health_recover_checks >= 1, got "
                f"({self.transport_error_dead}, "
                f"{self.health_recover_checks})")
        if self.hedge_ttft_factor <= 0 or self.hedge_min_seconds < 0:
            raise ValueError(
                f"serving.router needs hedge_ttft_factor > 0 and "
                f"hedge_min_seconds >= 0, got "
                f"({self.hedge_ttft_factor}, {self.hedge_min_seconds})")
        if self.max_restarts_per_window < 1 \
                or self.restart_window_seconds <= 0:
            raise ValueError(
                f"serving.router needs max_restarts_per_window >= 1 and "
                f"restart_window_seconds > 0, got "
                f"({self.max_restarts_per_window}, "
                f"{self.restart_window_seconds})")
        if self.min_healthy < 1:
            raise ValueError(
                f"serving.router.min_healthy must be >= 1, got "
                f"{self.min_healthy}")
        if self.migrate_wire not in ("", "auto", "raw", "int8", "int4",
                                     "fp8"):
            raise ValueError(
                f"serving.router.migrate_wire must be empty (engine "
                f"default) or one of auto/raw/int8/int4/fp8, got "
                f"{self.migrate_wire!r}")
        self.burn_rate.validate()


@register_config_model
@dataclass
class ServingConfig(ConfigModel):
    """Serving-engine knobs (inference/engine_v2.py, docs/serving.md).

    Admission: since PR 8, ``InferenceEngineV2.put()`` NEVER raises on a
    full KV pool — the pre-PR-8 contract (put() raised ``RuntimeError``
    when ``can_schedule`` failed) is retired. Requests wait in a FIFO
    queue and admit as blocks free up; ``max_queue_depth`` (default
    unbounded) restores fail-fast backpressure for callers that want an
    error instead of queueing. ``can_schedule()`` remains as an advisory
    capacity probe.

    ``prefix_cache`` shares full KV blocks across requests whose prompt
    prefixes match by content hash (repeated system prompts prefill
    once); ``spec_decode`` enables model-free prompt-lookup speculative
    decoding — ``spec_k`` drafted tokens per sequence verified in one
    ragged forward, n-gram match length up to ``spec_ngram``. Greedy
    output is token-identical with speculation on or off.
    ``decode_steps`` is the steady-state multi-token decode burst length
    (1 restores strict per-token SplitFuse admission).

    ``kv_quant_bits`` stores KV-cache blocks as quantized payloads with
    one fp32 scale per head_dim vector: 8 keeps int8 storage, 4 packs
    two nibbles per byte (~1.9x more sessions at head_dim 128; decode
    SNR gated in ``make serve-quant``), "fp8" stores e4m3 floats (same
    2x footprint as int8 with format-native dynamic range). None keeps
    today's bf16 pool bit-exactly — the quantized pytree never enters
    the traced program. ``handoff_wire`` picks the disaggregated-prefill
    KV handoff codec: "auto" ships the pool's native format, "raw"
    forces full precision, "int8"/"int4"/"fp8" quantize bf16 pools for
    the wire (int4 packs two values per byte, fp8 ships native e4m3
    payloads + per-vector scales with no bf16 round-trip; both
    converted pool-native on install).

    ``host_kv_tier`` attaches a ``host_tier_mb``-byte host-memory tier
    below the HBM pool (ragged/kv_tier.py): KV pressure PAGES cold
    prefix chains and preempted sessions out in pool-native format
    instead of discarding them, and returning sessions warm-resume
    decode without re-prefill. Off keeps the HBM-only engine
    bit-exactly. ``spec_adaptive_k`` makes the speculative draft length
    per-request adaptive (acceptance-EWMA x batch-occupancy controller,
    ``spec_accept_alpha`` smoothing); off is the fixed-``spec_k``
    legacy path, and greedy output stays token-identical either way."""

    max_queue_depth: Optional[int] = None
    prefix_cache: bool = True
    spec_decode: bool = False
    spec_k: int = 4
    spec_ngram: int = 3
    decode_steps: int = 8
    kv_quant_bits: Optional[Any] = None
    handoff_wire: str = "auto"
    host_kv_tier: bool = False
    host_tier_mb: int = 256
    spec_adaptive_k: bool = False
    spec_accept_alpha: float = 0.25
    router: RouterConfig = field(default_factory=RouterConfig)

    def validate(self) -> None:
        if self.max_queue_depth is not None and self.max_queue_depth < 1:
            raise ValueError(
                f"serving.max_queue_depth must be >= 1 (or null for "
                f"unbounded), got {self.max_queue_depth}")
        for name, lo in (("spec_k", 1), ("spec_ngram", 1),
                         ("decode_steps", 1), ("host_tier_mb", 1)):
            if getattr(self, name) < lo:
                raise ValueError(
                    f"serving.{name} must be >= {lo}, got "
                    f"{getattr(self, name)}")
        if self.kv_quant_bits not in (None, 4, 8, "fp8"):
            raise ValueError(
                f"serving.kv_quant_bits must be null, 4, 8 or \"fp8\", "
                f"got {self.kv_quant_bits}")
        if self.handoff_wire not in ("auto", "raw", "int8", "int4",
                                     "fp8"):
            raise ValueError(
                f"serving.handoff_wire must be one of auto/raw/int8/"
                f"int4/fp8, got {self.handoff_wire!r}")
        if not (0.0 < self.spec_accept_alpha <= 1.0):
            raise ValueError(
                f"serving.spec_accept_alpha must be in (0, 1], got "
                f"{self.spec_accept_alpha}")
        self.router.validate()


@register_config_model
@dataclass
class CompileConfig(ConfigModel):
    """Reference: deepspeed/compile/config.py. On TPU everything is compiled;
    these knobs tune donation/remat instead."""

    enabled: bool = True
    donate_params: bool = True
    scan_layers: bool = True


@register_config_model
@dataclass
class KernelsConfig(ConfigModel):
    """Pallas kernel geometry (docs/kernels.md).

    Block sizes were hardcoded in the kernels; they are config knobs
    and autotuner axes now (kernel-geometry axis family — candidates
    are shape-legal divisors only, ``autotuning/autotuner.py``). 0
    means "auto": the kernel's seq-derived default for flash, the
    grouped product's own tiles (an upper bound on them when set), the
    decode kernel's own
    block for paged attention (``ops/pallas/paged_attention.py``
    sizes it from the pool's shapes). Which attention kernel runs is not
    set here: ``ops/attention.py`` decides from the backend and the
    sequence length, or ``attn_impl`` names one."""

    flash_block_q: int = 0  # 0 = auto (1024 at seq>=8k else min(512, S))
    flash_block_k: int = 0
    # 0 = the paged decode kernel chooses its block from the pool's shapes
    # (the prefill kernel folds one page a step); > 0 sets it, for tests
    pages_per_compute_block: int = 0
    # 0 = the grouped product chooses its tiles from the shapes
    # (grouped_matmul.choose_tiles); > 0 is an upper bound on that choice
    gmm_block_m: int = 0
    gmm_block_n: int = 0
    gmm_block_k: int = 0
    blocksparse_block: int = 0  # 0 = follow sparse_attention.block

    def validate(self) -> None:
        for name in ("flash_block_q", "flash_block_k", "gmm_block_m",
                     "gmm_block_n", "gmm_block_k", "blocksparse_block"):
            v = getattr(self, name)
            if v < 0 or (v and v & (v - 1)):
                raise ValueError(
                    f"kernels.{name} must be 0 (auto) or a power of "
                    f"two, got {v}")
        if self.pages_per_compute_block < 0:
            raise ValueError(
                f"kernels.pages_per_compute_block must be 0 (the kernel "
                f"chooses) or positive, got {self.pages_per_compute_block}")


@register_config_model
@dataclass
class DataEfficiencyConfig(ConfigModel):
    """Reference: runtime/data_pipeline/config.py (curriculum etc.)."""

    enabled: bool = False
    seed: int = 1234
    curriculum_metrics: Dict[str, Any] = field(default_factory=dict)


# ---------------------------------------------------------------------------
# top-level config
# ---------------------------------------------------------------------------

_TOP_LEVEL_DEPRECATED = {
    "train_micro_batch_size_per_gpu": "train_micro_batch_size_per_chip",
}


@register_config_model
@dataclass
class Config(ConfigModel):
    """Top-level typed config (reference: DeepSpeedConfig runtime/config.py:676).

    Build with :func:`load_config` / ``Config.from_dict``; the batch triple is
    solved against the data-parallel world size by :meth:`resolve_batch_size`.
    """

    _deprecated_keys = _TOP_LEVEL_DEPRECATED

    # batch triple (any subset; solver fills the rest)
    train_batch_size: Any = None
    train_micro_batch_size_per_chip: Any = None
    gradient_accumulation_steps: Any = None

    steps_per_print: int = 10
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    dump_state: bool = False
    gradient_clipping: float = 0.0
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    communication_data_type: Optional[str] = None
    seed: int = 42

    # dtype blocks
    bf16: BF16Config = field(default_factory=BF16Config)
    fp16: FP16Config = field(default_factory=FP16Config)

    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None
    zero_optimization: ZeroConfig = field(default_factory=ZeroConfig)
    tensor_parallel: TensorParallelConfig = field(default_factory=TensorParallelConfig)
    sequence_parallel: SequenceParallelConfig = field(default_factory=SequenceParallelConfig)
    moe: MoEConfig = field(default_factory=MoEConfig)
    pipeline: PipelineConfig = field(default_factory=PipelineConfig)
    activation_checkpointing: ActivationCheckpointingConfig = field(
        default_factory=ActivationCheckpointingConfig
    )
    comms_logger: CommsLoggerConfig = field(default_factory=CommsLoggerConfig)
    monitor: MonitorConfig = field(default_factory=MonitorConfig)
    flops_profiler: FlopsProfilerConfig = field(default_factory=FlopsProfilerConfig)
    observability: ObservabilityConfig = field(default_factory=ObservabilityConfig)
    performance: PerformanceConfig = field(default_factory=PerformanceConfig)
    sparse_attention: Optional[SparseAttentionConfig] = None
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    compile: CompileConfig = field(default_factory=CompileConfig)
    # raw elasticity block: consumed by deepspeed_tpu/elasticity/ (the
    # launcher and compute_elastic_config take the dict form); kept
    # unparsed here so it survives into checkpoint metadata, where the
    # resharded-restore path re-checks the batch math for the new world
    elasticity: Optional[Dict[str, Any]] = None
    data_efficiency: DataEfficiencyConfig = field(default_factory=DataEfficiencyConfig)
    kernels: KernelsConfig = field(default_factory=KernelsConfig)

    # monitor blocks may also appear top-level in reference configs
    tensorboard: Optional[MonitorBackendConfig] = None
    csv_monitor: Optional[MonitorBackendConfig] = None
    wandb: Optional[MonitorBackendConfig] = None
    comet: Optional[MonitorBackendConfig] = None

    def __post_init__(self):
        # a JSON null for a block means "defaults", not "no block"
        defaultable = {
            "bf16": BF16Config, "fp16": FP16Config, "zero_optimization": ZeroConfig,
            "tensor_parallel": TensorParallelConfig,
            "sequence_parallel": SequenceParallelConfig, "moe": MoEConfig,
            "pipeline": PipelineConfig, "monitor": MonitorConfig,
            "activation_checkpointing": ActivationCheckpointingConfig,
            "comms_logger": CommsLoggerConfig, "flops_profiler": FlopsProfilerConfig,
            "observability": ObservabilityConfig,
            "performance": PerformanceConfig,
            "checkpoint": CheckpointConfig, "serving": ServingConfig,
            "resilience": ResilienceConfig, "compile": CompileConfig,
            "data_efficiency": DataEfficiencyConfig,
            "kernels": KernelsConfig,
        }
        # sparse_attention stays None unless configured (Optional block:
        # "not present" must be distinguishable from "defaults")
        for name, klass in defaultable.items():
            if getattr(self, name) is None:
                setattr(self, name, klass())
        # hoist top-level monitor blocks into .monitor (reference accepts both)
        for name in ("tensorboard", "csv_monitor", "wandb", "comet"):
            blk = getattr(self, name)
            if blk is not None:
                setattr(self.monitor, name, blk)

    # -- dtypes ------------------------------------------------------------
    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        if self.fp16.enabled:
            return jnp.float16
        if self.bf16.enabled:
            return jnp.bfloat16
        return jnp.float32

    @property
    def loss_scaling_enabled(self) -> bool:
        return self.fp16.enabled

    def validate(self) -> None:
        if self.fp16.enabled and self.bf16 is not None and self.bf16.enabled:
            # reference errors on both; bf16 defaults on, so fp16 wins if
            # explicitly requested.
            self.bf16.enabled = False
        if self.gradient_clipping < 0:
            raise ValueError("gradient_clipping must be >= 0")

    # -- batch triple solver ----------------------------------------------
    def resolve_batch_size(self, dp_world_size: int) -> None:
        """Solve train_batch = micro × GAS × dp (reference
        runtime/config.py:971 ``_configure_train_batch_size``)."""
        tb = None if is_auto(self.train_batch_size) else self.train_batch_size
        mb = (
            None
            if is_auto(self.train_micro_batch_size_per_chip)
            else self.train_micro_batch_size_per_chip
        )
        gas = (
            None
            if is_auto(self.gradient_accumulation_steps)
            else self.gradient_accumulation_steps
        )

        if tb is not None and mb is not None and gas is not None:
            if tb != mb * gas * dp_world_size:
                raise ValueError(
                    f"Inconsistent batch config: train_batch_size={tb} != "
                    f"micro({mb}) * gas({gas}) * dp({dp_world_size})"
                )
        elif tb is not None and mb is not None:
            if tb % (mb * dp_world_size) != 0:
                raise ValueError(
                    f"train_batch_size={tb} not divisible by micro*dp="
                    f"{mb * dp_world_size}"
                )
            gas = tb // (mb * dp_world_size)
        elif tb is not None and gas is not None:
            if tb % (gas * dp_world_size) != 0:
                raise ValueError(
                    f"train_batch_size={tb} not divisible by gas*dp="
                    f"{gas * dp_world_size}"
                )
            mb = tb // (gas * dp_world_size)
        elif mb is not None:
            gas = gas if gas is not None else 1
            tb = mb * gas * dp_world_size
        elif tb is not None:
            mb = max(1, tb // dp_world_size)
            gas = tb // (mb * dp_world_size)
            if tb != mb * gas * dp_world_size:
                raise ValueError(
                    f"train_batch_size={tb} not divisible by dp={dp_world_size}"
                )
        elif gas is not None:
            mb = 1
            tb = mb * gas * dp_world_size
        else:
            mb, gas = 1, 1
            tb = dp_world_size

        self.train_batch_size = int(tb)
        self.train_micro_batch_size_per_chip = int(mb)
        self.gradient_accumulation_steps = int(gas)
        if self.gradient_accumulation_steps < 1:
            raise ValueError("gradient_accumulation_steps must be >= 1")


def load_config(config: str | Dict[str, Any] | Config | None) -> Config:
    """Accept a path to JSON, a dict, an existing Config, or None."""
    if config is None:
        return Config.from_dict({})
    if isinstance(config, Config):
        return config
    if isinstance(config, str):
        if not os.path.exists(config):
            raise FileNotFoundError(f"config file not found: {config}")
        with open(config) as f:
            config = json.load(f)
    if not isinstance(config, dict):
        raise TypeError(f"config must be a path, dict, or Config; got {type(config)}")
    return Config.from_dict(config)

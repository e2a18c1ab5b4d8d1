"""FastGen-style ragged continuous-batching engine.

Reference: ``InferenceEngineV2`` (inference/v2/engine_v2.py:30) — ``put``
(:107) runs a ragged forward over new tokens of many sequences and returns
next-token logits; ``query``/``can_schedule`` (:184) let a scheduler probe
admission; KV pages come from a blocked allocator.

TPU re-design: host-side state (StateManager/BlockedAllocator) assembles
dense int metadata per step (ragged_batch.py); ONE jitted program per
(max_tokens, max_seqs) bucket executes scatter-append KV + paged attention
(model_runner.ragged_forward). The SplitFuse scheduler keeps steps at a
near-constant token budget, so in steady state a single compiled program
serves the whole workload.
"""

from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from functools import partial
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.inference import model_runner
from deepspeed_tpu.inference.ragged import (
    BlockedAllocator, BlockedKVCache, PrefixCache, StateManager)
from deepspeed_tpu.inference.ragged.ragged_batch import build_ragged_batch
from deepspeed_tpu.inference.scheduler import SplitFuseScheduler
from deepspeed_tpu.inference.spec_decode import PromptLookupDrafter
from deepspeed_tpu.models.transformer import TransformerLM
from deepspeed_tpu.observability.clocksync import wall_time
from deepspeed_tpu.observability.journal import get_journal
from deepspeed_tpu.utils.annotate import named, span
from deepspeed_tpu.utils.logging import log_dist


@dataclasses.dataclass
class _QueuedRequest:
    """A request waiting for KV admission (FIFO). Requeued preemption
    victims carry their already-generated tokens inside ``tokens`` (for
    prefix recompute) and count them via ``prior_generated``."""
    uid: int
    tokens: np.ndarray
    max_new_tokens: int
    enqueue_time: float
    prior_generated: int = 0
    # original put() time while TTFT is still unmeasured; None once the
    # request has emitted its first token (pre-preemption)
    admit_time: Optional[float] = None
    # queue re-entry after a preemption (vs a fresh put()) — the trace
    # records the round trip's requeue wait on re-admission
    requeued: bool = False
    # the victim's KV is parked in the host tier (ragged/kv_tier.py):
    # admission restores blocks and resumes decode instead of
    # re-prefilling; ``tokens`` carries the folded history anyway as
    # the fallback if the tier spills the session before readmission
    paged: bool = False


@dataclasses.dataclass
class _BurstInFlight:
    """A decode burst the device has been handed and the host has not read
    yet: what ``_issue_burst`` leaves for ``_collect_burst``."""
    live: List[Any]                 # its sequences, in slot order
    steps: int                      # K
    toks: Any                       # [K, S] on the device
    last: Any                       # [S], row K - 1: the next call's ids
    pools: Any                      # what the call returned (its counters)
    counts: Dict[str, int]          # the call's counters, for ``stats``
    eos_token_id: Optional[int]     # of the step that issued it


# Process-level jit cache shared by every engine instance. A fleet of
# replicas (serving/) builds N engines over the SAME model config, and
# per-instance ``jax.jit(partial(...))`` wrappers would compile the
# identical step programs N times — key the wrapped callables by
# (config identity, kernel mesh) so replica N+1 reuses replica 0's
# executables. Entries hold a strong ref to the config, so an id()
# key can never alias a collected object.
_JIT_CACHE: Dict[Any, Tuple[Any, Dict[str, Any]]] = {}


def runner_for(cfg):
    """The runner of a configuration: the module that has its four step
    programs and says what they need of the engine (``store_specs``,
    ``serving_params``, ``COUNTERS`` ..., the same names in each). A hybrid
    stack (models/hybrid.py: recurrent layers, block-sparse or latent
    attention) has its own (inference/hybrid_runner.py)."""
    from deepspeed_tpu.models.hybrid import HybridConfig

    if isinstance(cfg, HybridConfig):
        from deepspeed_tpu.inference import hybrid_runner

        return hybrid_runner
    return model_runner


def _shared_step_fns(cfg, kernel_mesh):
    key = (id(cfg), kernel_mesh)
    hit = _JIT_CACHE.get(key)
    if hit is not None and hit[0] is cfg:
        return hit[1]
    runner = runner_for(cfg)
    # each program under a stable name: the device trace's module line
    # says jit_dstpu_serve_gather, ... ("step" is the gather program).
    # Every program donates the KV pool (argument 1): the pool is one
    # buffer for the life of the engine, updated in place, and the handle
    # a caller passed in is dead once the call returns.
    def program(fn, name, **kw):
        return jax.jit(named(fn, name), donate_argnums=(1,), **kw)

    fns = {
        "step": program(partial(runner.ragged_forward, cfg),
                        "dstpu_serve_gather"),
        "decode": program(partial(runner.ragged_decode_forward, cfg,
                                  mesh=kernel_mesh), "dstpu_serve_decode"),
        "prefill": program(partial(runner.ragged_prefill_forward, cfg,
                                   mesh=kernel_mesh), "dstpu_serve_prefill"),
        "multi_decode": program(
            partial(runner.ragged_multi_decode, cfg, mesh=kernel_mesh),
            "dstpu_serve_multi_decode", static_argnames=("steps",)),
    }
    _JIT_CACHE[key] = (cfg, fns)
    return fns


# device-side token picks are config-independent — one compiled copy
# per process, not per engine
@jax.jit
def dstpu_pick_greedy(lg, idx):
    return jnp.argmax(lg.reshape(-1, lg.shape[-1])[idx].astype(jnp.float32),
                      axis=-1).astype(jnp.int32)


@jax.jit
def dstpu_take_rows(lg, idx):
    return lg.reshape(-1, lg.shape[-1])[idx]


@jax.jit
def dstpu_merge_rows(into, rows, at):
    """``into[at[j]] = rows[j]`` (an ``at`` past the end drops the row)."""
    return into.at[at].set(rows, mode="drop")


@jax.jit
def dstpu_pick_greedy_all(lg):
    return jnp.argmax(lg.reshape(-1, lg.shape[-1]).astype(jnp.float32),
                      axis=-1).astype(jnp.int32)


# the stats key that counts the tokens a step of each program emitted
_TOKENS_OF = {"gather": "tokens_gather", "prefill": "tokens_prefill_kernel",
              "decode": "tokens_decode",
              "multi_decode": "tokens_multi_decode"}
# what a ``dstpu/dispatch`` span can name as its ``program``: the four step
# programs, and ``spec`` (a speculative round's verification, which runs
# the gather program and is counted apart from a step's own gather calls)
PROGRAMS = ("gather", "prefill", "decode", "multi_decode", "spec")

# the phases of a step: every ``dstpu/<phase>`` span that lies inside a
# ``dstpu/serve_step`` (InferenceEngineV2._phase opens them all)
PHASES = ("admit", "schedule", "build_batch", "dispatch", "fetch",
          "bookkeep", "journal")
# what a step can be, by the program calls whose results it read
STEP_KINDS = ("mixed", "prefill", "lone", "burst", "empty")
_CHUNK_PROGRAMS = ("prefill", "gather")
# why ``_plan_decode_burst`` planned no burst (``burst_refused_<reason>``)
# and why it issued no call ahead of the one in flight
# (``ahead_refused_<reason>``): one counter a return
BURST_REFUSALS = ("prefill_pending", "budget", "seq_cap", "pool")
AHEAD_REFUSALS = ("drafter", "queue", "free_slot", "batch_changed",
                  "budget", "seq_cap", "pool")


def step_kind(calls) -> str:
    """The kind of a step, one of STEP_KINDS, from the ``(program,
    token_steps)`` of the program calls whose results it read: those it
    issued, but for a burst it left in flight (``ahead=1`` on its dispatch
    span), which belongs to the step that collects it. ``mixed``: a chunk
    call (``prefill`` or ``gather``) and token rows in one step, the step
    whose one token waits for a prompt beside it; ``prefill``: chunk calls
    alone (a ``gather`` call does not say whether it carried token rows
    too, and counts as chunks); ``burst``: a call that makes several token
    steps (``multi_decode``); ``lone``: one token step (``decode``, or a
    speculative round's ``spec``); ``empty``: no call. The engine's counters
    and the benchmark's readers of a profile both come through here."""
    chunks = rows = burst = False
    for program, token_steps in calls:
        if program in _CHUNK_PROGRAMS:
            chunks = True
        else:
            rows = True
            burst = burst or token_steps > 1
    if chunks:
        return "mixed" if rows else "prefill"
    if burst:
        return "burst"
    return "lone" if rows else "empty"


class _StepRecord:
    """What a step books of itself: its wall clock by phase, the calls whose
    results it read, the tokens it returns. One an engine, the context
    manager of its ``dstpu/serve_step`` span and of each phase's span
    (phases are siblings: one is open at a time), folded into ``stats`` and
    the flight recorder when the step closes (_close_step). Calls and tokens
    read outside a step (_drain) wait here for the step that delivers them;
    a phase outside a step books no time."""

    __slots__ = ("engine", "t0", "phases", "calls", "rows", "tokens",
                 "_step_span", "_open", "_phase_span", "_phase_t0")

    def __init__(self, engine):
        self.engine = engine
        self.t0: Optional[float] = None        # None: no step is open
        self.phases = dict.fromkeys(PHASES, 0.0)
        self._open: Optional[str] = None       # the phase that is open
        self.clear()

    def clear(self) -> None:
        for name in PHASES:
            self.phases[name] = 0.0
        self.calls: List[Tuple[str, int]] = []
        self.rows = self.tokens = 0

    def step(self, step_span) -> "_StepRecord":
        self._step_span = step_span
        return self

    def phase(self, name: str, phase_span) -> "_StepRecord":
        if self._open is not None:
            raise RuntimeError(f"phase {name!r} inside phase {self._open!r}: "
                               "the phases of a step do not nest")
        self._open, self._phase_span = name, phase_span
        return self

    def __enter__(self):
        if self._open is None:
            self._step_span.__enter__()
            self.t0 = time.perf_counter()
        else:
            self._phase_span.__enter__()
            self._phase_t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        now = time.perf_counter()
        if self._open is None:
            self.engine._close_step(now - self.t0)
            self.t0 = None
            return self._step_span.__exit__(*exc)
        if self.t0 is not None:
            self.phases[self._open] += now - self._phase_t0
        self._open = None
        return self._phase_span.__exit__(*exc)


_PICK_GREEDY = dstpu_pick_greedy
_TAKE_ROWS = dstpu_take_rows
_PICK_GREEDY_ALL = dstpu_pick_greedy_all


class InferenceEngineV2:
    def __init__(self, model: TransformerLM, mesh: Optional[Mesh] = None,
                 params: Optional[Dict[str, Any]] = None,
                 kv_blocks: int = 256, kv_block_size: int = 16,
                 max_tokens_per_step: int = 128, max_seqs_per_step: int = 16,
                 max_blocks_per_seq: int = 32, dtype=jnp.bfloat16, seed: int = 0,
                 quantize_weights: Optional[str] = None,
                 decode_steps: int = 8,
                 prefix_cache: bool = True,
                 spec_decode: bool = False, spec_k: int = 4,
                 spec_ngram: int = 3, drafter: Optional[Any] = None,
                 max_queue_depth: Optional[int] = None,
                 kv_quant_bits: Optional[Any] = None,
                 handoff_wire: str = "auto",
                 host_kv_tier: bool = False, host_tier_mb: int = 256,
                 spec_adaptive_k: bool = False,
                 spec_accept_alpha: float = 0.25,
                 serving: Optional[Any] = None,
                 state_slots: Optional[int] = None,
                 donate_params: bool = False,
                 request_trace: Optional[Any] = None,
                 metric_labels: Optional[Dict[str, str]] = None):
        from deepspeed_tpu.inference.engine import InferenceEngine

        if serving is not None:
            # a config.ServingConfig block supplies the serving knobs;
            # explicit kwargs above keep their call-site values only when
            # the caller passed no block (the block is the source of
            # truth for config-driven deployments)
            prefix_cache = serving.prefix_cache
            spec_decode = serving.spec_decode
            spec_k = serving.spec_k
            spec_ngram = serving.spec_ngram
            decode_steps = serving.decode_steps
            max_queue_depth = serving.max_queue_depth
            kv_quant_bits = getattr(serving, "kv_quant_bits", None)
            handoff_wire = getattr(serving, "handoff_wire", "auto")
            host_kv_tier = getattr(serving, "host_kv_tier", False)
            host_tier_mb = getattr(serving, "host_tier_mb", 256)
            spec_adaptive_k = getattr(serving, "spec_adaptive_k", False)
            spec_accept_alpha = getattr(serving, "spec_accept_alpha", 0.25)

        # reuse v1's TP placement logic for params/mesh
        self._v1 = InferenceEngine(model, mesh=mesh, params=params,
                                   dtype=dtype, seed=seed,
                                   quantize_weights=quantize_weights)
        self.model, self.cfg = model, model.config
        self.mesh, self.params = self._v1.mesh, self._v1.params
        # what the model keeps per sequence and what its programs take is
        # the runner's to say; the cache holds it (ragged/store.py)
        self._runner = runner_for(self.cfg)
        # ``donate_params``: the caller gives the stacked tree up, and the
        # engine deletes each stacked leaf it cuts (hybrid.serving_params):
        # the arrays handed in, here and to every later reload_params, are
        # gone afterwards
        self._donate_params = bool(donate_params)
        # smallest chunk bucket of the prefill program (powers of two from
        # here: _plan_prefill_segments)
        self._min_segment = self._runner.min_segment(self.cfg)
        # whether a step may use the gather program (_split_by_program)
        self._has_gather = self._runner.has_gather(self.cfg)
        # how often a token step runs the stack over each of its rows (the
        # passes of a looped stack; 1 for every other)
        self._ut_steps = int(self._runner.passes_per_token(self.cfg))
        # kept for reload_params: a hot-swap routes replacement weights
        # through the same v1 placement/quantization path as boot
        self._param_dtype = dtype
        self._quantize_weights = quantize_weights

        paged, beside = self._runner.store_specs(
            self.cfg, kv_blocks=kv_blocks, kv_block_size=kv_block_size,
            max_seqs=max_seqs_per_step, state_slots=state_slots,
            dtype=dtype, quant_bits=kv_quant_bits)
        # the tree is cut before the pools are made: a chip that the cut
        # tree and the pools nearly fill cannot hold the stacked one too
        self._keep_serving_params()
        self.kv_cache = BlockedKVCache(
            paged, mesh=self.mesh, stores=[spec.build() for spec in beside])
        if spec_decode or drafter is not None:
            self.kv_cache.require(
                "speculation", "speculative decoding (spec_decode / drafter)")
        if host_kv_tier:
            self.kv_cache.require("host_tier",
                                  "the host KV tier (host_kv_tier)")
        if prefix_cache and not self.kv_cache.supports("prefix_cache"):
            log_dist(
                "InferenceEngineV2: a store of this model has no snapshot a "
                "skipped prefix could start from: prefix cache OFF (no hit "
                "is taken)", ranks=[0])
            prefix_cache = False
        # disagg handoff wire codec mode ("auto"/"raw"/"int8"/"int4");
        # consumed by serving/disagg.py serialize_prefix
        self._handoff_wire = handoff_wire
        # the last block is the padding-token scratch target
        # (model_runner.ragged_forward routes padded writes there): shrink
        # the allocator so it is never handed out
        self.kv_cache.allocator = BlockedAllocator(kv_blocks - 1)
        self._scratch_block = kv_blocks - 1
        # shared-prefix KV reuse: full blocks whose content-hash chain
        # matches a cached prefix are shared by reference and skip
        # prefill (ragged/prefix_cache.py; docs/serving.md)
        # per-replica metric labels: a fleet of engines in one process
        # (serving/) tags every serve.* series with its replica id so
        # aggregation never collapses replicas into one series
        self._metric_labels = dict(metric_labels) if metric_labels else None
        if prefix_cache:
            self.kv_cache.prefix_cache = PrefixCache(
                kv_block_size, metric_labels=self._metric_labels)
        # host-memory KV tier (ragged/kv_tier.py): KV pressure PAGES
        # blocks out (through the pool's own compact storage format)
        # instead of evicting them — cold prefix chains and preempted
        # sessions come back without re-prefill
        if host_kv_tier:
            from deepspeed_tpu.inference.ragged.kv_tier import HostKVTier

            self.kv_cache.host_tier = HostKVTier(
                capacity_bytes=int(host_tier_mb) << 20,
                metric_labels=self._metric_labels)

        self.state = StateManager(self.kv_cache,
                                  max_tracked_sequences=4 * max_seqs_per_step,
                                  max_blocks_per_seq=max_blocks_per_seq)
        self.scheduler = SplitFuseScheduler(
            self.state, max_tokens_per_step, max_seqs_per_step)
        self.max_tokens = max_tokens_per_step
        self.max_seqs = max_seqs_per_step
        self.max_blocks_per_seq = max_blocks_per_seq
        # decode-only steps use the Pallas paged-attention kernel (no
        # per-token context gather). On any multi-device mesh the kernel
        # runs inside a shard_map — manual over tp (q heads / KV heads
        # co-sharded; needs tp | kv_heads for the GQA grouping), other
        # axes replicated. Pallas can't run under plain GSPMD, so a bare
        # multi-chip mesh without the wrap is NOT a kernel-path config.
        axes = {} if self.mesh is None else dict(self.mesh.shape)
        self._tp = axes.get("tp", 1)
        single = self.mesh is None or all(v == 1 for v in axes.values())
        # v1's constructor (run above) already raised unless tp divides
        # both head counts, which is exactly the GQA co-sharding the
        # shard_map wrap needs — every constructible config runs the
        # kernel path. The flag stays as a manual escape hatch (tests
        # flip it to compare against the gather path).
        self._use_paged_kernel = True
        # serve-path telemetry (VERDICT r2: the gather fallback is a perf
        # cliff users can't see — count it; reference analog: the comms
        # logger's op counts, utils/comms_logging.py)
        self.stats = {"decode_kernel_steps": 0, "prefill_kernel_steps": 0,
                      "prefill_gather_fallbacks": 0,
                      "fallback_reasons": {"vmem": 0, "padding": 0},
                      "queued": 0, "admitted": 0, "preempted": 0,
                      "preempt_reasons": {},
                      "requeued": 0, "truncated": 0,
                      "prefix_hit_tokens": 0,
                      "spec_steps": 0, "spec_proposed": 0,
                      "spec_accepted": 0, "spec_backoff_rounds": 0,
                      "paged_out": 0, "paged_in": 0,
                      "warm_resume_tokens": 0,
                      # live-migration ladder (serving/disagg.py
                      # serialize_session/install_session): warm resume /
                      # parked-in-tier / folded-recompute install rungs,
                      # plus the source-side captures
                      "migrated_out": 0, "migrated_in": 0,
                      "migrate_paged": 0, "migrate_recompute": 0,
                      "migrate_resume_tokens": 0,
                      # where the work happens, as flat counters a
                      # caller can difference over a window: tokens
                      # emitted by steps of each program (they sum to
                      # the tokens emitted; speculative rounds run the
                      # gather program), (request, step) pairs that
                      # advanced a prompt chunk, and the sums behind the
                      # admission-wait and TTFT histograms
                      "tokens_gather": 0, "tokens_prefill_kernel": 0,
                      "tokens_decode": 0, "tokens_multi_decode": 0,
                      "prefill_chunks": 0, "admission_wait_s": 0.0,
                      "ttft_s": 0.0, "first_tokens": 0,
                      # the calls of the prefill program made for the
                      # chunks of a step split by program
                      # (_split_by_program)
                      "prefill_chunk_calls": 0, "burst_steps": 0,
                      # decode bursts dispatched while the one before was
                      # still unread (_burst_step), and the rows of such a
                      # call thrown away because their sequence had ended
                      # in the call before it
                      "calls_issued_ahead": 0, "ahead_rows_discarded": 0,
                      # what each program was given, counted where a call
                      # is issued (_dispatch): engine steps that made a
                      # call at all, and below a set a program; a
                      # request's wait for its first token in calls: all
                      # those issued from its put() to that token, and
                      # those of them that carried it
                      "steps_dispatched": 0, "first_token_calls": 0,
                      "first_token_own_calls": 0,
                      # what _plan_decode_burst decided, one counter a
                      # return (the refusals are added below), and the
                      # token steps the planned bursts stood under
                      # ``decode_steps``
                      "bursts_planned": 0, "burst_steps_clamped": 0,
                      # the blocks the planned bursts took from the prefix
                      # cache's idle entries, and the plans that took any
                      "burst_blocks_reclaimed": 0, "bursts_reclaiming": 0}
        self.stats.update(
            dict.fromkeys(["burst_refused_" + r for r in BURST_REFUSALS]
                          + ["ahead_refused_" + r for r in AHEAD_REFUSALS], 0))
        # a step's own record, folded by its kind when it closes
        # (_close_step): steps, their wall seconds, the seconds of them the
        # host blocked on the device (the ``fetch`` phases) and the tokens
        # they returned
        for kind in STEP_KINDS:
            self.stats.update({f"steps_{kind}": 0, f"step_s_{kind}": 0.0,
                               f"step_wait_s_{kind}": 0.0,
                               f"step_tokens_{kind}": 0})
        self._rec = _StepRecord(self)
        for program in PROGRAMS:
            # calls, the token rows they really carried, the rows they
            # computed (the program's padded layout), the passes over
            # the layers' weights they made one after another, and the
            # rows times the passes of the stack the program ran over each
            # (``rows`` for a stack run once a token)
            self.stats.update({f"{k}_{program}": 0 for k in (
                "calls", "rows", "padded_rows", "token_steps", "ut_passes")})
        # the paged pool's layer slots: a layer's K/V, once a pass of a
        # looped stack
        self.stats["kv_slots"] = paged.num_layers
        # what the runner's programs count on the device (their ``counters``
        # vector, fetched with the step's tokens and summed over calls:
        # hybrid_runner.COUNTERS), those of them kept for the two decode
        # programs alone (``<name>_decode``), and the stores' occupancy
        self._counters = self._runner.COUNTERS
        self.stats.update(
            dict.fromkeys(self._counters + self._runner.OCCUPANCY, 0),
            **{name + "_decode": 0 for name in self._runner.DECODE_COUNTERS})
        self.stats.update(self.kv_cache.occupancy())
        # engine steps so far: the ``step_id`` of each ``dstpu/serve_step``
        # span, of the spans nested in it, and of the request tracer's
        # PREFILL / DECODE_EMIT spans of that step
        self._step_id = 0
        # program calls: those of the open step so far (a dispatch span's
        # ``call``) and those of the engine's life, against which a request
        # is stamped at put(); uid -> [that stamp, the calls since that
        # carried it] while its first token is out (kept through a requeue,
        # as ``_admit_time`` is)
        self._step_calls = 0
        self._calls_issued = 0
        self._calls_at_put: Dict[int, List[int]] = {}
        self._closed = False
        # the decode burst in flight (issued by the last ``serve_step``,
        # read by the next), and tokens a drain read outside a step, which
        # the next ``serve_step`` returns with its own (_drain)
        self._inflight: Optional[_BurstInFlight] = None
        self._undelivered: Dict[int, List[int]] = {}
        # where a burst's ``token_ids`` live: what a step program's own
        # outputs carry, so that the ids built on the host and the last row
        # of the call before are one kind of argument to one compiled
        # program
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        # admission queue: put() never raises on a full KV pool — requests
        # wait FIFO here and admit as blocks free up; preemption victims
        # requeue at the FRONT with their generated tokens preserved
        self._queue: Deque[_QueuedRequest] = deque()
        self._max_queue_depth = max_queue_depth
        # speculative decoding: model-free prompt-lookup drafts verified
        # through the ragged step (spec_decode.py; greedy acceptance is
        # token-identical to non-speculative greedy)
        self.spec_k = max(1, int(spec_k))
        self._drafter = drafter if drafter is not None else (
            PromptLookupDrafter(max_ngram=spec_ngram) if spec_decode
            else None)
        # adaptive draft length (ISSUE 17): per-request k chosen each
        # spec round from the measured acceptance EWMA and batch
        # occupancy — speculate hard when decode is memory-bound and the
        # batch is idle, back off toward k=0 under load. Off (the
        # default) is the bit-exact legacy fixed-k path; on changes only
        # HOW MANY drafts verify, never the accepted greedy chain.
        self._spec_adaptive = bool(spec_adaptive_k)
        self._spec_alpha = float(spec_accept_alpha)
        self._spec_accept_ewma: Optional[float] = None    # global
        self._seq_accept_ewma: Dict[int, float] = {}      # per request
        self._spec_wasted_verify_tokens = 0
        # backoff curve: the j-th draft's expected yield is a^j; draft
        # while a^j >= cut, where cut scales with batch occupancy (at
        # full occupancy verify rows crowd out real decode tokens)
        self._spec_cut_base = 0.25
        self._spec_load_gain = 3.0
        # request-latency observability (docs/observability.md): TTFT is
        # put()->first emitted token; decode latency is the gap between
        # consecutive emitted tokens of one sequence (a burst spreads its
        # round-trip evenly over the tokens it produced). Histograms live
        # in the process-wide hub so serving percentiles land on the same
        # Prometheus page as training metrics.
        from deepspeed_tpu.observability import get_hub
        from deepspeed_tpu.observability.flight_recorder import (
            get_flight_recorder, install_crash_handlers)

        self._hub = get_hub()
        lbl = self._metric_labels
        self._ttft_hist = self._hub.histogram("serve.ttft_seconds",
                                              labels=lbl)
        self._decode_hist = self._hub.histogram("serve.decode_token_seconds",
                                                labels=lbl)
        self._step_hist = self._hub.histogram("serve.step_seconds",
                                              labels=lbl)
        self._admission_hist = self._hub.histogram(
            "serve.admission_wait_seconds", labels=lbl)
        self._spec_hist = self._hub.histogram("serve.spec_accepted_len",
                                              labels=lbl)
        # the gauges are read, not pushed: the hub asks when somebody looks
        self._hub.add_provider(self._serve_gauges, labels=lbl)
        # serving shares the crash flight recorder: a wedged serve step
        # dumps the last admits/steps the same way a training hang does
        self._flight = get_flight_recorder()
        install_crash_handlers()
        # per-request flight paths (observability/request_trace.py):
        # every request gets a typed span timeline, tail-sampled at
        # FINISH (SLO violators always kept); the tracer registers the
        # in-flight request state as crash-dump context. ``request_trace``
        # takes the observability.request_trace config block (or a
        # dict); env: DSTPU_REQUEST_TRACE=0, DSTPU_REQ_TRACE_SAMPLE/
        # _RING/_SLO_MS.
        from deepspeed_tpu.observability.request_trace import RequestTracer

        self.tracer = RequestTracer.from_config(
            request_trace, hub=self._hub, flight=self._flight)
        self.scheduler.tracer = self.tracer
        self._admit_time: Dict[int, float] = {}
        self._last_emit_time: Dict[int, float] = {}
        self._burst_tokens = 0
        self._burst_capacity = 0
        kernel_mesh = None if single else self.mesh
        # all four step programs come from the process-level cache
        # (_shared_step_fns) so a fleet of same-config replicas compiles
        # each program once, not once per engine
        _fns = _shared_step_fns(self.cfg, kernel_mesh)
        self._step_fn = _fns["step"]
        self._decode_fn = _fns["decode"]
        self._prefill_fn = _fns["prefill"]
        # the rows a call of the gather program computes: the flat budget,
        # or what the runner lays it out as inside the program
        self._gather_rows = int(self._runner.gather_rows_computed(
            self.max_seqs, self.max_tokens))
        # device-side token pick: the step fetches only sampled ids (or
        # the consumed rows when temperature > 0), never the full [T, V]
        # logits buffer (see step())
        self._pick_greedy = _PICK_GREEDY
        self._take_rows = _TAKE_ROWS
        # speculative verification consumes the greedy id of EVERY chunk
        # row (draft j is accepted iff it equals row j-1's argmax), so
        # fetch all T ids in one device round trip — still 4 bytes/row,
        # never the [T, V] logits
        self._pick_greedy_all = _PICK_GREEDY_ALL
        # multi-step greedy decode: one device program per `decode_steps`
        # tokens when every live sequence is in steady decode
        # (model_runner.ragged_multi_decode; decode_steps=1 restores
        # strict per-token SplitFuse admission)
        self.decode_steps = max(1, int(decode_steps))
        self._multi_decode_fn = _fns["multi_decode"]
        log_dist(
            f"InferenceEngineV2: kv_blocks={kv_blocks}x{kv_block_size} "
            f"budget={max_tokens_per_step}tok/{max_seqs_per_step}seq",
            ranks=[0])

    # -- admission (reference engine_v2.py:184 query/can_schedule) --------

    def can_schedule(self, prompt_len: int) -> bool:
        """Capacity probe: would a prompt of this length admit RIGHT NOW?
        KV blocks allocate lazily (the scheduler's ensure_capacity), so
        the free list alone over-admits — count the blocks already
        COMMITTED to live sequences: each sequence's private claim at its
        current length, plus every cache-shared block once. Idle
        prefix-cached blocks stay admissible (reclaimed on demand).
        Since the admission queue landed this is advisory only: put()
        enqueues regardless and admission happens as blocks free up.
        Admission also stops at ``max_seqs_per_step`` live sequences:
        the scheduler can't run more per step, and the fast multi-step
        decode/spec paths require every live sequence to fit one batch —
        over-admitting past the slots would silently degrade them to
        per-token steps for zero scheduling benefit."""
        blocks = self.kv_cache.blocks_needed(prompt_len + 1)
        if (blocks > self.max_blocks_per_seq
                or len(self.state.seqs) >= self.max_seqs
                or len(self.state.seqs)
                >= self.state.max_tracked_sequences
                or not self.kv_cache.admissible()):
            return False
        committed = 0
        for s in self.state.seqs.values():
            need = self.kv_cache.blocks_needed(s.total_tokens + 1)
            committed += max(need, len(s.kv_blocks)) - len(s.prefix_keys)
        cache = self.kv_cache.prefix_cache
        if cache is not None:
            committed += cache.referenced_blocks
        return blocks + committed <= self.kv_cache.allocator.total_blocks

    # -- core step (reference engine_v2.py:107 put) -----------------------

    @property
    def _journal_owner(self) -> str:
        """This engine's ingress-claim identity for the fleet journal
        (stable per instance; see FleetJournal.claim_ingress)."""
        return f"engine:{id(self)}"

    def put(self, uids: List[int], tokens_list: List[np.ndarray],
            max_new_tokens: int = 64) -> None:
        """Submit new sequences (uid -> prompt tokens). Requests enter a
        FIFO waiting queue and admit as KV blocks free up — a full pool
        means backpressure (``serve.queue_wait_depth``), never an error.
        (The pre-PR-8 contract — put() raised RuntimeError when the pool
        was full — is retired; see docs/serving.md.) Raises ValueError
        only for a prompt that can NEVER fit (per-seq block cap / total
        pool), and RuntimeError when ``max_queue_depth`` is configured
        and the queue is full (opt-in fail-fast backpressure)."""
        with span("put", uid=int(uids[0]) if len(uids) else -1,
                  requests=len(uids)):
            self._put(uids, tokens_list, max_new_tokens)

    def _put(self, uids, tokens_list, max_new_tokens: int) -> None:
        now = time.perf_counter()
        jr = get_journal()
        # a router-fronted engine defers ADMIT/EMIT journaling to the
        # router (which owns request identity); a standalone engine is
        # its own ingress and records admissions here
        journal_ingress = (jr is not None and jr.claim_ingress(
            self._journal_owner) == self._journal_owner)
        for uid, toks in zip(uids, tokens_list):
            toks = np.asarray(toks, np.int32).ravel()
            blocks = self.kv_cache.blocks_needed(len(toks) + 1)
            if (blocks > self.max_blocks_per_seq
                    or blocks > self.kv_cache.allocator.total_blocks):
                raise ValueError(
                    f"uid={uid}: prompt of {len(toks)} tokens needs "
                    f"{blocks} KV blocks and can never be scheduled "
                    f"(max_blocks_per_seq={self.max_blocks_per_seq}, "
                    f"pool={self.kv_cache.allocator.total_blocks})")
            if (self._max_queue_depth is not None
                    and len(self._queue) >= self._max_queue_depth):
                raise RuntimeError(
                    f"uid={uid}: admission queue full "
                    f"(max_queue_depth={self._max_queue_depth})")
            self._queue.append(_QueuedRequest(
                uid=uid, tokens=toks, max_new_tokens=max_new_tokens,
                enqueue_time=now, admit_time=now))
            self._calls_at_put[uid] = [self._calls_issued, 0]
            if journal_ingress:
                jr.admit(uid, toks.tolist(), int(max_new_tokens))
            self.stats["queued"] += 1
            self._hub.counter_add("serve.requests", labels=self._metric_labels)
            self.tracer.on_enqueue(uid, len(toks),
                                   queue_depth=len(self._queue))
        self._admit_from_queue()

    def _admit_from_queue(self) -> None:
        """Admit waiting requests strictly FIFO while capacity lasts.
        Strict head-of-line order keeps big prompts from starving behind
        a stream of small ones; the rotation fairness lives in the
        scheduler's prefill scan instead."""
        now = time.perf_counter()
        while self._queue and self.can_schedule(len(self._queue[0].tokens)):
            req = self._queue.popleft()
            if req.paged:
                outcome = self._try_page_in(req, now)
                if outcome == "stall":
                    # the session's blocks don't fit RIGHT NOW (live
                    # pressure): keep FIFO order and retry next round
                    self._queue.appendleft(req)
                    break
                if outcome == "resumed":
                    continue
                # tier spilled the session: fall through — ``tokens``
                # carries the folded history for prefix recompute
            seq = self.state.get_or_create(req.uid, req.tokens,
                                           req.max_new_tokens)
            seq.prior_generated = req.prior_generated
            self.tracer.on_admit(req.uid, wait_s=now - req.enqueue_time,
                                 requeued=req.requeued)
            skipped = self.state.attach_prefix(seq)
            if skipped:
                self.stats["prefix_hit_tokens"] += skipped
                self._hub.counter_add("serve.prefix_hit_tokens", skipped,
                                       labels=self._metric_labels)
                self.tracer.on_prefix_hit(req.uid, skipped)
            if req.admit_time is not None:
                self._admit_time[req.uid] = req.admit_time
            self._admission_hist.observe(now - req.enqueue_time)
            self.stats["admission_wait_s"] += now - req.enqueue_time
            self.stats["admitted"] += 1

    def _release_seq(self, uid: int, requeue: bool = False
                     ) -> Optional[float]:
        """The ONE sequence-teardown path: frees state + KV and pops the
        latency maps (both the finished and the preempted path route
        here, so neither leaks ``_admit_time``/``_last_emit_time`` under
        sustained overload). Returns the pending admit time, if TTFT was
        still unmeasured, for requeue to carry forward; with ``requeue``
        the request's stamp of calls stays too (``_calls_at_put``)."""
        self.state.release(uid)
        admit = self._admit_time.pop(uid, None)
        if not requeue:
            self._calls_at_put.pop(uid, None)
        self._last_emit_time.pop(uid, None)
        self._seq_accept_ewma.pop(uid, None)
        return admit

    def _requeue(self, seq, reason: str = "pool_exhausted") -> None:
        """Preempt-and-requeue: park the victim back at the FRONT of the
        admission queue with its generated-so-far tokens folded into the
        prompt, so readmission recomputes the prefix (often straight
        from the prefix cache) and the request continues where it
        stopped — no work is discarded and nothing is dropped.
        ``reason`` tags the preemption (today only pool_exhausted; the
        disaggregated-router follow-ups add more) on the counter, the
        stats dict, and the victim's trace."""
        if (self.kv_cache.blocks_needed(seq.total_tokens + 1)
                > self.max_blocks_per_seq):
            # grown to the per-seq block cap: readmission could never
            # fit, so end it (the pre-existing cap-truncation contract)
            # instead of queueing it forever
            seq.done = True
            seq.truncated = True
            self.stats["truncated"] += 1
            self.tracer.on_finish(seq.uid, "truncated")
            self._release_seq(seq.uid)
            log_dist(f"uid={seq.uid} at per-seq KV cap on preemption: "
                     "truncated", ranks=[0])
            return
        self.tracer.on_preempt(seq.uid, reason=reason,
                               generated=len(seq.generated))
        jr = get_journal()
        if jr is not None:
            jr.decision("PREEMPT", uid=seq.uid, reason=reason,
                        generated=len(seq.generated),
                        free_blocks=self.kv_cache.free_blocks,
                        queue_depth=len(self._queue))
        self._to_queue_front(seq, reason)

    def _to_queue_front(self, seq, reason: str, paged: bool = False) -> None:
        """Release a preempted ``seq`` and queue it at the FRONT, its history
        folded into the prompt (what readmission recomputes; the fallback of
        a ``paged`` one whose session the tier spills before then)."""
        tokens = np.concatenate(
            [np.asarray(seq.input_tokens, np.int32),
             np.asarray(seq.generated, np.int32)])
        prior = seq.prior_generated + len(seq.generated)
        admit = self._release_seq(seq.uid, requeue=True)
        self._queue.appendleft(_QueuedRequest(
            uid=seq.uid, tokens=tokens, max_new_tokens=seq.max_new_tokens,
            enqueue_time=time.perf_counter(), prior_generated=prior,
            admit_time=admit, requeued=True, paged=paged))
        self.stats["preempted"] += 1
        self.stats["preempt_reasons"][reason] = \
            self.stats["preempt_reasons"].get(reason, 0) + 1
        self.stats["paged_out" if paged else "requeued"] += 1
        self._hub.counter_add("serve.preempted", labels=self._metric_labels)
        self._hub.counter_add(f"serve.preempted_reason.{reason}",
                              labels=self._metric_labels)

    def _page_out(self, seq, reason: str = "paged_out") -> bool:
        """Preempt ``seq`` by PAGING its KV to the host tier instead of
        discarding it: block contents copy out in pool-native format (a
        pure byte copy — bit-exact round trip by construction) together
        with the descriptor state, and the request requeues at the queue
        front flagged ``paged``. Readmission restores the blocks and
        resumes *decode* — zero re-prefill FLOPs, token stream identical
        to a never-paged run. False when paging doesn't apply (no tier,
        mid-prefill, at the per-seq cap, or session oversize for the
        tier) — the caller falls back to ``_requeue`` recompute."""
        tier = getattr(self.kv_cache, "host_tier", None)
        if tier is None or seq.pending_prefill or seq.seen_tokens <= 0:
            return False
        if (self.kv_cache.blocks_needed(seq.total_tokens + 1)
                > self.max_blocks_per_seq):
            return False  # could never regrow: _requeue owns truncation
        # trim to the blocks holding real KV: rejected speculative
        # drafts may have grown the block list past the accepted
        # frontier, and those trailing blocks hold only draft garbage
        keep = self.kv_cache.blocks_needed(seq.seen_tokens)
        if keep <= 0 or keep > len(seq.kv_blocks):
            return False
        from deepspeed_tpu.inference.ragged.kv_tier import PagedSession

        payload, scales = self.kv_cache.read_blocks_host(
            np.asarray(seq.kv_blocks[:keep], np.int64))
        sess = PagedSession(
            uid=seq.uid,
            input_tokens=np.asarray(seq.input_tokens, np.int32),
            generated=list(seq.generated),
            seen_tokens=seq.seen_tokens,
            max_new_tokens=seq.max_new_tokens,
            prior_generated=seq.prior_generated,
            payload=payload, scales=scales,
            admit_time=self._admit_time.get(seq.uid),
            spec_accept_ewma=self._seq_accept_ewma.get(seq.uid))
        if not tier.put_session(sess):
            return False
        self.tracer.on_preempt(seq.uid, reason=reason,
                               generated=len(seq.generated))
        jr = get_journal()
        if jr is not None:
            jr.decision("PAGE_OUT", uid=seq.uid, reason=reason,
                        seen_tokens=int(seq.seen_tokens),
                        n_blocks=int(keep),
                        free_blocks=self.kv_cache.free_blocks,
                        queue_depth=len(self._queue))
        self._to_queue_front(seq, reason, paged=True)
        return True

    def _try_page_in(self, req: _QueuedRequest, now: float) -> str:
        """Warm-resume a ``paged`` queued request from the host tier.
        Returns ``"resumed"`` (decode continues, zero prefill),
        ``"stall"`` (session present but HBM can't take its blocks this
        round — keep queue order, retry later), or ``"recompute"`` (the
        tier spilled the session; the folded tokens re-prefill)."""
        tier = getattr(self.kv_cache, "host_tier", None)
        sess = tier.peek_session(req.uid) if tier is not None else None
        if sess is None:
            return "recompute"
        keep = sess.n_blocks
        if keep > self.kv_cache.free_blocks:
            self.kv_cache.reclaim(keep - self.kv_cache.free_blocks)
        if keep > self.kv_cache.free_blocks:
            return "stall"
        self._restore(tier.pop_session(req.uid), keep)
        self.stats["paged_in"] += 1
        self.stats["admitted"] += 1
        self.stats["warm_resume_tokens"] += sess.seen_tokens
        self._hub.counter_add("serve.warm_resume_tokens", sess.seen_tokens,
                              labels=self._metric_labels)
        self.tracer.on_admit(req.uid, wait_s=now - req.enqueue_time,
                             requeued=True)
        if sess.admit_time is not None:
            self._admit_time[req.uid] = sess.admit_time
        elif req.admit_time is not None:
            self._admit_time[req.uid] = req.admit_time
        self._admission_hist.observe(now - req.enqueue_time)
        self.stats["admission_wait_s"] += now - req.enqueue_time
        return "resumed"

    def _restore(self, sess, n: int) -> None:
        """A parked or migrated session as a live sequence again: its ``n``
        blocks (pool-native) written into blocks of this pool, its
        descriptor as it stood. The caller has seen to the room."""
        seq = self.state.get_or_create(
            int(sess.uid), np.asarray(sess.input_tokens, np.int32),
            sess.max_new_tokens)
        seq.generated = list(sess.generated)
        seq.prior_generated = int(sess.prior_generated)
        seq.seen_tokens = int(sess.seen_tokens)
        blocks = self.kv_cache.allocator.allocate(n)
        seq.kv_blocks = np.asarray(blocks, np.int64)
        self.kv_cache.write_blocks(blocks, sess.payload, sess.scales)
        seq.resumed_from_tier = n
        if sess.spec_accept_ewma is not None:
            self._seq_accept_ewma[seq.uid] = float(sess.spec_accept_ewma)

    def page_out(self, uid: int) -> bool:
        """Explicitly park a live sequence's KV in the host tier (e.g. a
        session going idle between turns). The request re-enters the
        admission queue flagged ``paged`` and warm-resumes when capacity
        allows. False when paging doesn't apply — the sequence stays
        live."""
        self.kv_cache.require("host_tier", "host-tier parking (page_out)")
        self._drain()
        seq = self.state.seqs.get(uid)
        if seq is None or seq.done:
            return False
        return self._page_out(seq, reason="explicit_page_out")

    # -- live session migration (serving/disagg.py owns the wire codec) --

    def migrate_out_session(self, uid: int) -> Optional[Dict[str, Any]]:
        """Destructively capture a mid-stream session for live migration:
        the committed KV blocks (partial tail block included, pool-native
        format), the descriptor state that rebuilds the sequence on the
        target, and the per-request spec-acceptance EWMA. The sequence is
        RELEASED here — the caller owns shipping the capture (or falling
        back to recompute on the target if the wire fails).

        A session already parked in the host tier migrates warm straight
        from host memory. Returns None when there is nothing warm to
        capture (unknown uid, mid-prefill, queued-but-never-admitted):
        the caller degrades to the legacy fold-and-resubmit path."""
        self.kv_cache.require("migration",
                              "session migration (migrate_out_session)")
        self._drain()
        tier = getattr(self.kv_cache, "host_tier", None)
        seq = self.state.seqs.get(uid)
        if seq is None or seq.done:
            src = tier.pop_session(uid) if tier is not None else None
            if src is None:
                return None
            # drop the paged queue entry: ownership moves with the bytes
            if any(r.uid == uid for r in self._queue):
                self._queue = deque(r for r in self._queue
                                    if r.uid != uid)
            self._seq_accept_ewma.pop(uid, None)
            payload, scales, ewma = (src.payload, src.scales,
                                     src.spec_accept_ewma)
        else:
            if seq.pending_prefill or seq.seen_tokens <= 0:
                return None
            # trim to the blocks holding real KV (same rule as _page_out):
            # rejected speculative drafts leave garbage past the frontier
            keep = self.kv_cache.blocks_needed(seq.seen_tokens)
            if keep <= 0 or keep > len(seq.kv_blocks):
                return None
            payload, scales = self.kv_cache.read_blocks_host(
                np.asarray(seq.kv_blocks[:keep], np.int64))
            src, ewma = seq, self._seq_accept_ewma.get(uid)
        cap = {"uid": int(uid),
               "input_tokens": np.asarray(src.input_tokens, np.int32),
               "generated": list(src.generated),
               "seen_tokens": int(src.seen_tokens),
               "max_new_tokens": int(src.max_new_tokens),
               "prior_generated": int(src.prior_generated),
               "payload": payload, "scales": scales,
               "spec_accept_ewma": ewma}
        self.tracer.on_finish(uid, "migrated")
        if src is seq:
            self._release_seq(uid)
        self.stats["migrated_out"] += 1
        self._hub.counter_add("serve.migrated_out",
                              labels=self._metric_labels)
        return cap

    def install_migrated_session(self, sess) -> str:
        """Install a migrated session whose ``payload`` is already in
        THIS pool's native storage format (serving/disagg.py
        install_session owns the wire→pool conversion). Walks the
        degradation ladder and NEVER raises:

        * ``"resumed"``    — blocks written, decode continues warm with
          zero re-prefill FLOPs;
        * ``"paged"``      — no HBM room right now: parked in the host
          tier + queued ``paged`` (still warm — readmission restores the
          blocks via the ordinary ``_try_page_in`` path);
        * ``"recompute"``  — no payload / no tier room: the folded token
          history queues for ordinary prefix-recompute admission;
        * ``"duplicate"``  — uid already live or queued here (a raced
          failover already owns it): installed nothing;
        * ``"truncated"``  — the folded history can never fit this
          engine (per-seq cap): counted and closed, mirroring
          ``_requeue``'s cap-truncation contract.
        """
        self.kv_cache.require("migration",
                              "session migration (install_migrated_session)")
        self._drain()
        uid = int(sess.uid)
        if uid in self.state.seqs or any(r.uid == uid for r in self._queue):
            return "duplicate"
        tier = getattr(self.kv_cache, "host_tier", None)
        n = 0 if sess.payload is None else sess.n_blocks
        fold = np.concatenate(
            [np.asarray(sess.input_tokens, np.int32),
             np.asarray(sess.generated, np.int32)])
        prior = int(sess.prior_generated) + len(sess.generated)
        now = time.perf_counter()
        if (n > 0 and n <= self.max_blocks_per_seq
                and len(self.state.seqs) < self.max_seqs
                and len(self.state.seqs) < self.state.max_tracked_sequences):
            if n > self.kv_cache.free_blocks:
                self.kv_cache.reclaim(n - self.kv_cache.free_blocks)
            if n <= self.kv_cache.free_blocks:
                self._restore(sess, n)
                self.tracer.on_enqueue(uid, len(fold),
                                       queue_depth=len(self._queue))
                self.tracer.on_admit(uid, wait_s=0.0, requeued=True)
                self.stats["migrated_in"] += 1
                self.stats["admitted"] += 1
                self.stats["migrate_resume_tokens"] += int(
                    sess.seen_tokens)
                self._hub.counter_add("serve.migrated_in",
                                      labels=self._metric_labels)
                self._hub.counter_add("serve.warm_resume_tokens",
                                      int(sess.seen_tokens),
                                      labels=self._metric_labels)
                return "resumed"
        # target HBM is full RIGHT NOW: park the warm bytes in the host
        # tier — readmission warm-resumes with zero re-prefill
        paged = bool(n > 0 and tier is not None
                     and n <= self.max_blocks_per_seq
                     and tier.put_session(sess))
        blocks_needed = self.kv_cache.blocks_needed(len(fold) + 1)
        if not paged and (
                blocks_needed > self.max_blocks_per_seq
                or blocks_needed > self.kv_cache.allocator.total_blocks):
            # can never fit this engine: close it loudly (the same
            # contract as _requeue's per-seq-cap truncation) instead of
            # wedging the admission queue head forever
            self.stats["truncated"] += 1
            self.tracer.on_finish(uid, "truncated")
            return "truncated"
        rung = "paged" if paged else "recompute"
        self._queue.append(_QueuedRequest(
            uid=uid, tokens=fold, max_new_tokens=int(sess.max_new_tokens),
            enqueue_time=now, prior_generated=prior, requeued=True,
            paged=paged))
        self.tracer.on_enqueue(uid, len(fold),
                               queue_depth=len(self._queue))
        self.stats["migrate_" + rung] += 1
        self.stats["queued"] += 1
        self._hub.counter_add("serve.migrate_" + rung,
                              labels=self._metric_labels)
        self._admit_from_queue()
        return rung

    def reload_params(self, params: Optional[Dict[str, Any]] = None,
                      seed: Optional[int] = None) -> None:
        """Hot-swap the serving weights in place. Replacement params
        route through the same v1 placement/quantization path as boot
        (``params=None`` re-derives them from ``model.init(seed)``).
        Every compiled step program takes params as an ARGUMENT, not a
        capture, so the swap costs zero recompilation and the next step
        serves the new weights — live KV blocks stay valid only if the
        caller quiesced the engine first (supervisor.rolling_swap drains
        and migrates sessions out before calling this). An engine built
        with ``donate_params`` consumes the replacement tree as it consumed
        the first: the stacked leaves it cuts are deleted, and the caller
        must not read ``params`` afterwards."""
        from deepspeed_tpu.inference.engine import InferenceEngine

        self._drain()       # the call in flight reads the weights it was given
        if params is None:
            params = self.model.init(
                jax.random.PRNGKey(int(seed or 0)))
        self._v1 = InferenceEngine(
            self.model, mesh=self.mesh, params=params,
            dtype=self._param_dtype,
            quantize_weights=self._quantize_weights)
        self.params = self._v1.params
        self._keep_serving_params()

    def _keep_serving_params(self) -> None:
        """Keep of the tree handed in what the runner's programs read."""
        self.params = self._runner.serving_params(
            self.cfg, self.params, donate=self._donate_params)
        self._v1.params = self.params     # drop the stacked tree's last ref

    def _fetch_counters(self, calls) -> None:
        """Add what a step's program calls counted to ``stats``, each under
        its own program: ``calls`` holds, a call, the pools it returned (for
        their ``counters``: that call's own vector, which no later call
        takes) and whether it was of a decode program. Once a step, inside
        the ``fetch`` span, after the step's tokens: the programs are done,
        and the host has waited for none of them between two calls."""
        if not self._counters:
            return
        for pools, decode in calls:
            counted = dict(zip(self._counters, (
                int(v) for v in np.asarray(pools["counters"]))))
            for name, n in counted.items():
                self.stats[name] += n
            if decode:
                for name in self._runner.DECODE_COUNTERS:
                    self.stats[name + "_decode"] += counted[name]
        self.stats.update(self.kv_cache.occupancy())

    def holds_prefix_blocks(self, tokens) -> int:
        """How many full prefix blocks of ``tokens`` this engine can
        serve without prefill, counting BOTH the HBM prefix cache and
        the host tier behind it — the fleet router's session-affinity
        signal (serving/router.py prefers the replica already holding a
        returning session's blocks)."""
        cache = self.kv_cache.prefix_cache
        if cache is None:
            return 0
        toks = np.asarray(tokens, np.int32).ravel()
        tier = getattr(self.kv_cache, "host_tier", None)
        if tier is not None:
            return tier.holds_chain_prefix(cache, toks)
        keys, _ = cache.lookup(toks, max_tokens=max(0, len(toks) - 1))
        return len(keys)

    def _open_step(self):
        """The next engine step's host span: ``dstpu/serve_step`` with its
        ``step_id``. The phases nest inside it (admit, schedule,
        build_batch, dispatch, fetch, bookkeep, journal), and the request
        tracer's spans of the step carry the same id, so a request's
        timeline joins to its steps and through them to the device
        programs dispatched under them."""
        self._step_id += 1
        self._step_calls = 0
        return self._rec.step(span("serve_step", step_id=self._step_id))

    def _phase(self, name: str, **ids):
        """The one place a phase of a step opens: the ``dstpu/<name>``
        profiler span with its ``ids``, and the phase's seconds on the
        step's record (one of PHASES; they are siblings, none inside
        another)."""
        return self._rec.phase(name, span(name, **ids))

    def _close_step(self, wall: float) -> None:
        """Fold the closing step's record into ``stats`` under the step's
        kind (step_kind), once a step: ``steps_<kind>``, ``step_s_<kind>``
        (``wall``), ``step_wait_s_<kind>`` (its ``fetch`` phases: the host
        blocked on the device) and ``step_tokens_<kind>``; the hub's token
        counter once a step; and, for a step that read a call, its row in
        the flight recorder: the operator's per-step ledger."""
        rec = self._rec
        kind = step_kind(rec.calls)
        wait = rec.phases["fetch"]
        self.stats["steps_" + kind] += 1
        self.stats["step_s_" + kind] += wall
        self.stats["step_wait_s_" + kind] += wait
        self.stats["step_tokens_" + kind] += rec.tokens
        if rec.tokens:
            self._hub.counter_add("serve.tokens_emitted", rec.tokens,
                                  labels=self._metric_labels)
        if rec.calls and self._flight.enabled:
            row = {name + "_ms": round(1e3 * s, 3)
                   for name, s in rec.phases.items()}
            if any(program == "spec" for program, _ in rec.calls):
                row["spec"] = True
            # ``step_kind``: an event's own ``kind`` is "serve_step"
            self._flight.record("serve_step", step_kind=kind, tokens=rec.rows,
                                emitted=rec.tokens,
                                wall_ms=round(1e3 * wall, 3),
                                wait_ms=round(1e3 * wait, 3), **row)
        rec.clear()

    def _dispatch(self, program: str, seqs, tokens: int,
                  token_steps: int = 1, chunks: int = 0,
                  counts: Optional[Dict[str, int]] = None, **shape):
        """The one place a program call is described: the
        ``dstpu/dispatch`` span to issue it under (the jitted call goes
        inside the ``with``), after counting it. ``program`` is one of
        PROGRAMS; ``seqs`` the sequences the call carries; ``tokens`` the
        token rows that are really theirs; ``token_steps`` the passes over
        the model's layers it makes one after another (K for a burst);
        ``chunks`` the prompt chunks in it; ``shape`` the prefill
        program's padded layout, ``S`` and ``tq``. The span carries all of
        it with the passes of the stack a token step makes (``ut_steps``),
        the ``step_id`` of the enclosing ``serve_step``, the
        call's place among the step's calls (``call``, from 0) and the
        rows the program computes whatever it carries (``padded_rows``);
        the counters (``calls_<program>`` ...) take the same numbers, and
        each carried sequence whose first token is still out counts the
        call as its own. ``counts``: a dict to take the call's counters in
        place of ``stats``, for a burst, whose counters move with its
        tokens (_collect_burst); a call dispatched while the one before it
        is unread says ``ahead=1`` in ``shape``. The step's record takes the
        call where its counters move: here, or where a burst is collected."""
        if program == "prefill":
            padded_rows = shape["S"] * shape["tq"]
        elif program in ("decode", "multi_decode"):
            padded_rows = token_steps * self.max_seqs
        else:       # the gather program: its runner's layout of the budget
            padded_rows = self._gather_rows
        call = self._step_calls
        self._step_calls += 1
        self._calls_issued += 1
        self.stats["steps_dispatched"] += call == 0
        mine = {"calls_" + program: 1, "rows_" + program: tokens,
                "padded_rows_" + program: padded_rows,
                "token_steps_" + program: token_steps,
                "ut_passes_" + program: tokens * self._ut_steps}
        if program == "prefill":
            mine["prefill_chunk_calls"] = 1
        elif program in ("decode", "multi_decode"):
            mine["decode_kernel_steps"] = token_steps
            mine["burst_steps"] = int(program == "multi_decode")
        if counts is None:
            self._count(mine)
            self._rec.calls.append((program, token_steps))
            self._rec.rows += tokens
        else:
            counts.update(mine)
        if self._calls_at_put:
            for seq in seqs:
                waiting = self._calls_at_put.get(seq.uid)
                if waiting is not None:
                    waiting[1] += 1
        return self._phase("dispatch", program=program,
                           step_id=self._step_id, call=call, seqs=len(seqs),
                           tokens=tokens, padded_rows=padded_rows,
                           token_steps=token_steps, ut_steps=self._ut_steps,
                           chunks=chunks, **shape)

    def _count(self, counts: Dict[str, int]) -> None:
        for name, n in counts.items():
            self.stats[name] += n

    def step(self, temperature: float = 0.0, seed: int = 0,
             eos_token_id: Optional[int] = None) -> Dict[int, int]:
        """Run one SplitFuse step. Returns {uid: new_token} for sequences
        that produced a token this step. (A burst still in flight from a
        ``serve_step`` is read first; the next ``serve_step`` returns its
        tokens.)"""
        self._drain()
        with self._open_step():
            with self._phase("admit"):
                self._admit_from_queue()
            return self._splitfuse_step(temperature, seed, eos_token_id)

    def _preempt_starved(self) -> None:
        """All live sequences starved for KV (pool exhausted mid-decode):
        preempt the last-admitted sequence so the others can progress —
        without this the engine deadlocks and leaks the pool. The victim
        requeues at the queue front with its generated tokens kept for
        prefix recompute; it is never silently dropped."""
        live = [s for s in self.state.seqs.values() if not s.done]
        if len(live) > 1 or (live and self._queue):
            victim = live[-1]
            # page to the host tier when one is attached (decode
            # resumes without re-prefill); recompute-requeue is the
            # fallback when paging doesn't apply
            if self._page_out(victim):
                log_dist(
                    f"KV pool exhausted: paged uid={victim.uid} to "
                    f"the host tier ({len(victim.generated)} tokens "
                    "generated) — warm resume on readmission",
                    ranks=[0])
            else:
                log_dist(
                    f"KV pool exhausted: preempting uid={victim.uid} "
                    f"({len(victim.generated)} tokens generated) — "
                    "requeued for readmission", ranks=[0])
                self._requeue(victim)
        elif live:
            # a lone sequence the pool cannot grow for: requeueing
            # would just readmit it into the same wall, so end it
            # (the only remaining truncation path)
            victim = live[0]
            log_dist(
                f"KV pool exhausted by lone uid={victim.uid}: "
                "truncated (pool smaller than one request)", ranks=[0])
            victim.done = True
            victim.truncated = True
            self.stats["truncated"] += 1
            self.tracer.on_finish(victim.uid, "truncated")
            self._release_seq(victim.uid)

    def _splitfuse_step(self, temperature: float, seed: int,
                        eos_token_id: Optional[int]) -> Dict[int, int]:
        with self._phase("schedule"):
            scheduled = self.scheduler.schedule()
            self._release_finished()
            if not scheduled:
                self._preempt_starved()
                return {}
        t0 = time.perf_counter()
        # one program a part (_split_by_program); the pools pass from one
        # to the next
        runs, counted = [], []
        call_of = {}        # index into ``scheduled`` -> its call of the step
        for part in self._split_by_program(scheduled):
            mine = [scheduled[i] for i in part]
            call_of.update(dict.fromkeys(part, self._step_calls))
            with self.mesh:
                with self._phase("build_batch"):
                    fn, program, args, pools, batch = \
                        self._build_step_call(mine)
                    shape = (dict(zip(("S", "tq"), args[0].shape))
                             if program == "prefill" else {})
                with self._dispatch(
                        program, [seq for seq, _, _ in mine],
                        int(batch.num_tokens),
                        chunks=sum(sp < len(seq.input_tokens)
                                   for seq, _, sp in mine), **shape):
                    logits, new_kv = fn(self.params, self.kv_cache.kv_state,
                                        *args, **pools)
            # the program consumed (donated) the handle it was given
            self.kv_cache.set_kv_state(new_kv)
            counted.append((new_kv, program == "decode"))
            runs.append((part, program, logits, batch))
        if any(program == "prefill" for _, program, _, _ in runs):
            # the step's chunks went through the prefill program. No step
            # of the kernel path is left to the gather program any more:
            # the share of prompt steps that were reads 0, and goes with
            # ``prefill_gather_fallbacks`` and that program (ROADMAP.md, D12)
            self.stats["prefill_kernel_steps"] += 1

        # Sample ON DEVICE and fetch only token ids (greedy) or just the
        # consumed rows (stochastic). Materializing the full [T, V]
        # logits host-side is 131 MB/step at a 256-token budget x 128k
        # vocab; the ids are 4 bytes/sequence.
        with self._phase("bookkeep"):
            consumers, picks = [], []
            for part, program, logits, batch in runs:
                stride = logits.shape[1] if logits.ndim == 3 else 1
                flat_idx = np.zeros(self.max_seqs, np.int32)
                for slot, i in enumerate(part):
                    seq, new_tokens, start_pos = scheduled[i]
                    n = len(new_tokens)
                    seq.seen_tokens = start_pos + n
                    # prompt blocks the step just completed become shareable
                    self.state.register_prefix_blocks(seq)
                    if start_pos < len(seq.input_tokens):
                        self.stats["prefill_chunks"] += 1
                    if seq.seen_tokens < len(seq.input_tokens):
                        continue  # mid-prefill: no logits consumed
                    if program == "prefill":
                        flat_idx[slot] = slot * stride + (n - 1)
                    elif program == "decode":
                        flat_idx[slot] = slot
                    else:
                        flat_idx[slot] = batch.last_token_index[slot]
                    consumers.append((i, seq, program))
                picks.append(flat_idx)

        emitted: Dict[int, int] = {}
        last_program = runs[-1][1]
        if consumers:
            with self._phase("fetch"), self.mesh:
                # the host blocks on the device here: the picked ids (or
                # rows) are the step's only result it reads. Several
                # programs: each one's sampled rows, put in the order of
                # the schedule, so that the pick is one call whose row i
                # belongs to the i-th scheduled sequence as ever. (A
                # chunk call's rows are taken this way even where it is the
                # step's only call, and the decode program's logits are its
                # slots' rows as they stand: so no shape of the take is one
                # that only a step with token rows beside chunks compiles.)
                if len(runs) == 1 and last_program != "prefill":
                    logits, idx_dev = runs[0][2], jnp.asarray(picks[0])
                else:
                    logits = None
                    for (part, program, lg, _), idx in zip(runs, picks):
                        rows = lg if program == "decode" else \
                            self._take_rows(lg, jnp.asarray(idx))
                        at = np.full(self.max_seqs, self.max_seqs, np.int32)
                        at[:len(part)] = part
                        logits = dstpu_merge_rows(
                            rows if logits is None else logits, rows,
                            jnp.asarray(at))
                    idx_dev = jnp.asarray(np.arange(self.max_seqs,
                                                    dtype=np.int32))
                if temperature == 0.0:
                    toks_np = np.asarray(self._pick_greedy(logits, idx_dev))
                else:
                    rows_np = np.asarray(self._take_rows(logits, idx_dev))
                self._fetch_counters(counted)
        elif self._counters:
            with self._phase("fetch"):  # no token to read: the counters alone
                self._fetch_counters(counted)
        with self._phase("bookkeep"):
            for slot, seq, program in consumers:
                if temperature == 0.0:
                    tok = int(toks_np[slot])
                else:
                    tok = int(_sample_np(rows_np[slot], temperature,
                                         seed + slot + seq.seen_tokens))
                seq.generated.append(tok)
                emitted[seq.uid] = tok
                self.stats[_TOKENS_OF[program]] += 1
                if eos_token_id is not None and tok == eos_token_id:
                    seq.done = True
                if seq.gen_budget_left <= 0:
                    seq.done = True
            now = time.perf_counter()
            self._step_hist.observe(now - t0)
            self._rec.tokens += len(emitted)
            if self.tracer.enabled:
                # one PREFILL span per prompt chunk this step advanced;
                # the span start backdates by the step wall so prefill
                # lanes line up with the step that computed them
                wall_ms = (now - t0) * 1e3
                # same clock domain as every other span (skew-aware wall
                # time): a stamp from the raw clock would rebase acausally
                t_start = wall_time() - (now - t0)
                for i, (seq, new_tokens, start_pos) in enumerate(scheduled):
                    if start_pos < len(seq.input_tokens):
                        self.tracer.on_prefill(seq.uid, t_start, wall_ms,
                                               tokens=len(new_tokens),
                                               start_pos=start_pos,
                                               step_id=self._step_id,
                                               call=call_of[i])
            for uid in emitted:
                self._note_emitted(uid, 1, now)
            self._release_finished()
        return emitted

    @property
    def _splits_steps(self) -> bool:
        """Whether a step is split by program: wherever the kernel path is
        on, whatever the runner, and always for a model with block-sparse
        or latent attention (no gather program is built for it). Not where
        the gather program is the path (``_use_paged_kernel`` off)."""
        return not self._has_gather or self._use_paged_kernel

    def _split_by_program(self, scheduled):
        """The step's work as the lists (of indices into ``scheduled``) one
        program call each takes: all of it, but where steps are split
        (_splits_steps) the sequences that advance one token go through the
        decode program, one call, and the chunks through the prefill
        program over their own sequences' pages. Chunks share a call, in
        the order of the schedule, while its padded layout stays within
        twice the step's budget (``S x tq <= 2 x max_tokens``, chunk rows
        only: a call is bound by one read of the weights, so several short
        chunks cost what one does); one sequence a call for a model with
        block-sparse or latent attention."""
        chunks = [i for i, s in enumerate(scheduled) if len(s[1]) > 1]
        if not self._splits_steps or not chunks:
            return [list(range(len(scheduled)))]
        calls = []
        for i in chunks:
            if calls and self._has_gather and self._pads_within_budget(
                    [len(scheduled[j][1]) for j in (*calls[-1], i)]):
                calls[-1].append(i)
            else:
                calls.append([i])
        single = [i for i, s in enumerate(scheduled) if len(s[1]) == 1]
        return ([single] if single else []) + calls

    def _segment_shape(self, lens):
        """``(S, tq)`` of the prefill program's padded layout for chunks
        of these lengths, each bucketed to a power of two so jit compiles
        a handful of programs."""
        tq = self._min_segment
        while tq < max(lens):
            tq *= 2
        S = 1  # segment-count bucket: slots are ordered, so the forward
        while S < len(lens):  # runs on the leading S rows only
            S *= 2
        return min(S, self.max_seqs), tq

    def _pads_within_budget(self, lens) -> bool:
        """The padded layout materializes S*tq token rows (incl. [S,tq,V]
        fp32 logits): whether that blowup stays within twice the flat
        token budget."""
        S, tq = self._segment_shape(lens)
        return S * tq <= 2 * self.max_tokens

    def _build_step_call(self, scheduled):
        """Pick the program for this part of a step and assemble its host
        arrays: ``(jitted fn, program name, arguments after params and
        KV, the stores' keyword arguments (``kv_cache.step_args``), the
        ragged batch)``. On the kernel path ``decode`` when every
        sequence advances one token (tokens line up with slots, so the
        compact paged-kernel path applies), else ``prefill`` (the part is
        chunks: _split_by_program); off it the flat ``gather`` program."""
        batch = build_ragged_batch(scheduled, self.max_tokens,
                                   self.max_seqs, self.max_blocks_per_seq)
        pools = self.kv_cache.step_args([seq for seq, _, _ in scheduled],
                                        self.max_seqs)
        if not self._use_paged_kernel:
            return self._step_fn, "gather", (
                jnp.asarray(batch.token_ids), jnp.asarray(batch.token_seq),
                jnp.asarray(batch.token_pos), jnp.asarray(batch.block_table),
                jnp.asarray(batch.num_tokens, jnp.int32)), pools, batch
        if all(len(nt) == 1 for _, nt, _ in scheduled):
            # compact per-slot arrays: token i belongs to slot i; pad
            # out to max_seqs (token budget may be smaller than the
            # slot budget)
            n = batch.num_tokens
            d_tok = np.zeros(self.max_seqs, np.int32)
            d_pos = np.zeros(self.max_seqs, np.int32)
            d_tok[:n] = batch.token_ids[:n]
            d_pos[:n] = batch.token_pos[:n]
            return self._decode_fn, "decode", (
                jnp.asarray(d_tok), jnp.asarray(d_pos),
                jnp.asarray(batch.block_table),
                jnp.asarray(batch.ctx_lens)), pools, batch
        seg_plan = self._plan_prefill_segments(scheduled)
        n_segs = seg_plan[0].shape[0]
        return self._prefill_fn, "prefill", (
            *seg_plan, jnp.asarray(batch.block_table[:n_segs])), pools, batch

    def _plan_prefill_segments(self, scheduled):
        """Per-slot padded chunk layout for the prefill program
        (_segment_shape): the chunks of one call (_split_by_program)."""
        S, tq = self._segment_shape([len(nt) for _, nt, _ in scheduled])
        toks = np.zeros((S, tq), np.int32)
        pos0 = np.zeros(S, np.int32)
        nreal = np.zeros(S, np.int32)
        for slot, (seq, nt, sp) in enumerate(scheduled):
            toks[slot, :len(nt)] = nt
            pos0[slot] = sp
            nreal[slot] = len(nt)
        return jnp.asarray(toks), jnp.asarray(pos0), jnp.asarray(nreal)

    def _release_finished(self) -> None:
        for seq in [s for s in self.state.seqs.values() if s.done]:
            self.tracer.on_finish(
                seq.uid, "truncated" if seq.truncated else "finished")
            self._release_seq(seq.uid)

    def _note_emitted(self, uid: int, n_tokens: int, now: float,
                      spec_overhead_ms: float = 0.0) -> None:
        """Fold ``n_tokens`` just-emitted tokens of ``uid`` into the
        latency histograms: the first token of a request is its TTFT;
        later tokens record the gap since the previous emission (a burst
        spreads one device round trip evenly over its tokens).
        ``spec_overhead_ms`` is this request's share of a speculative
        round's rejected-draft compute, attached to its DECODE_EMIT
        span for the phase decomposition."""
        self.tracer.on_emit(uid, n_tokens,
                            spec_overhead_ms=spec_overhead_ms,
                            step_id=self._step_id)
        admit = self._admit_time.pop(uid, None)
        last = self._last_emit_time.get(uid)
        if admit is not None:
            self._ttft_hist.observe(now - admit)
            self.stats["ttft_s"] += now - admit
            self.stats["first_tokens"] += 1
            stamp, own = self._calls_at_put.pop(uid, (self._calls_issued, 0))
            self.stats["first_token_calls"] += self._calls_issued - stamp
            self.stats["first_token_own_calls"] += own
            n_tokens -= 1
            last = now
        if last is not None and n_tokens > 0:
            per_tok = (now - last) / n_tokens
            for _ in range(n_tokens):
                self._decode_hist.observe(per_tok)
        self._last_emit_time[uid] = now

    def _serve_gauges(self) -> Dict[str, float]:
        """The engine's gauges as they read now: the hub's provider
        (``MetricsHub.add_provider``), called on the thread of whoever
        reads the hub and never by a step. ``serve.paged_fallback_ratio``
        appears with the first step whose chunks went through the kernel
        path or fell back (no step of it is left to the gather program any
        more: it reads 0 and goes with ``prefill_gather_fallbacks`` and
        that program, ROADMAP.md D12), the burst's two with the first burst
        read."""
        st = self.stats
        live = [s for s in list(self.state.seqs.values()) if not s.done]
        out = {"serve.queue_depth": len(live),
               "serve.queue_wait_depth": len(self._queue),
               "serve.pending_prefill_tokens":
                   sum(s.pending_prefill for s in live),
               "serve.kv_free_blocks": self.kv_cache.free_blocks,
               "serve.batch_seq_occupancy":
                   self.scheduler.last_scheduled_seqs / max(1, self.max_seqs),
               "serve.batch_token_occupancy":
                   self.scheduler.last_scheduled_tokens
                   / max(1, self.max_tokens)}
        if self.kv_cache.prefix_cache is not None:
            out["serve.prefix_cached_blocks"] = \
                self.kv_cache.prefix_cache.cached_blocks
        tried = st["prefill_gather_fallbacks"] + st["prefill_kernel_steps"]
        if tried:
            out["serve.paged_fallback_ratio"] = \
                st["prefill_gather_fallbacks"] / tried
        if self._burst_capacity > 0:
            out["serve.burst_efficiency"] = \
                self._burst_tokens / self._burst_capacity
            out["serve.issued_ahead_share"] = \
                st["calls_issued_ahead"] / max(1, st["calls_multi_decode"])
        if self._spec_accept_ewma is not None:
            out["serve.spec_accept_ewma"] = self._spec_accept_ewma
        return out

    def _burst_step(self, eos_token_id: Optional[int]
                    ) -> Optional[Dict[int, List[int]]]:
        """Hand out ``decode_steps`` greedy tokens a sequence from one
        device program, and keep the device one call ahead where that costs
        nobody anything.

        A burst applies only in steady state: every live sequence
        mid-decode, no prefill pending, and KV capacity for the whole burst
        among the pool's free blocks and the prefix cache's idle entries,
        taken at the plan (the block tables are frozen for the burst's
        duration). It has two halves:
        *issue* (plan, build, dispatch: _issue_burst) and *collect* (fetch,
        accept, bookkeeping: _collect_burst). This step collects the call
        the last step left in flight, or where there is none issues one and
        collects it; before it collects, it issues the call after that if
        the rule of _plan_decode_burst allows. The follow-on needs nothing
        the host learns from the call before it: its ids are that call's
        last row, on the device already. So the host's work between two
        calls (fetch, bookkeeping, the caller's own, the next plan and
        build) runs under a call and not between two. Returns None when a
        single SplitFuse step should run instead."""
        t0 = time.perf_counter()
        flight, self._inflight = self._inflight, None
        if flight is None:
            flight = self._issue_burst(eos_token_id)
            if flight is None:
                return None
        self._inflight = self._issue_burst(eos_token_id, after=flight)
        return self._collect_burst(flight, t0)

    def _issue_burst(self, eos_token_id: Optional[int],
                     after: Optional[_BurstInFlight] = None
                     ) -> Optional[_BurstInFlight]:
        """Plan, build and dispatch one burst; None where none applies.
        ``after``: the call in flight that this one follows. Its sequences
        stand ``after.steps`` tokens past what the host has accounted, and
        its ``last`` row is this call's ``token_ids``."""
        with self._phase("schedule"):
            K = self._plan_decode_burst(after)
        if K is None:
            return None
        if after is None:
            live, unread = [s for s in self.state.seqs.values()
                            if not s.done], 0
        else:
            live, unread = after.live, after.steps
        with self.mesh:
            with self._phase("build_batch"):
                S = self.max_seqs
                d_pos = np.zeros(S, np.int32)
                ctx = np.zeros(S, np.int32)
                bt = np.zeros((S, self.max_blocks_per_seq), np.int32)
                for i, s in enumerate(live):
                    d_pos[i] = s.seen_tokens + unread
                    ctx[i] = s.seen_tokens + unread + 1
                    bt[i, :len(s.kv_blocks)] = s.kv_blocks
                if after is None:
                    d_tok = np.zeros(S, np.int32)
                    for i, s in enumerate(live):
                        d_tok[i] = (s.generated[-1] if s.generated
                                    else int(s.input_tokens[-1]))
                    ids = jax.device_put(d_tok, self._replicated)
                else:
                    ids = after.last
                args = (ids, jnp.asarray(d_pos), jnp.asarray(bt),
                        jnp.asarray(ctx))
                pools = self.kv_cache.step_args(live, self.max_seqs)
            counts: Dict[str, int] = {}
            ahead = {} if after is None else {"ahead": 1}
            with self._dispatch("multi_decode", live, K * len(live),
                                token_steps=K, counts=counts, **ahead):
                toks, new_kv, last = self._multi_decode_fn(
                    self.params, self.kv_cache.kv_state, *args, steps=K,
                    **pools)
            # at once: the handle the cache still holds is consumed
            self.kv_cache.set_kv_state(new_kv)
        self.stats["calls_issued_ahead"] += after is not None
        return _BurstInFlight(live, K, toks, last, new_kv, counts,
                              eos_token_id)

    def _collect_burst(self, flight: _BurstInFlight, t0: float
                       ) -> Dict[int, List[int]]:
        """Read a burst's tokens and account for them: the call's counters
        move here, with the tokens the step returns. A sequence that ended
        in the call before this one (an end-of-sequence token, found when
        this call was already issued) takes none of its rows; it is
        released once no call that writes its pages or its state is in
        flight."""
        K = flight.steps
        with self._phase("fetch"):
            toks_np = np.asarray(flight.toks)  # [K, S]: one fetch per K tokens
            self._fetch_counters([(flight.pools, True)])
        with self._phase("bookkeep"):
            self._count(flight.counts)
            self._rec.calls.append(("multi_decode", K))
            self._rec.rows += K * len(flight.live)
            eos_token_id = flight.eos_token_id
            emitted: Dict[int, List[int]] = {}
            for i, s in enumerate(flight.live):
                if s.done:
                    self.stats["ahead_rows_discarded"] += K
                    continue
                accepted = []
                budget_left = s.gen_budget_left
                for k in range(K):
                    tok = int(toks_np[k, i])
                    accepted.append(tok)
                    if eos_token_id is not None and tok == eos_token_id:
                        s.done = True
                        break
                    if len(accepted) >= budget_left:
                        s.done = True
                        break
                s.generated.extend(accepted)
                s.seen_tokens += len(accepted)
                emitted[s.uid] = accepted
            now = time.perf_counter()
            self._step_hist.observe(now - t0)
            # burst efficiency: accepted tokens vs the K*len(live) the
            # device program computed (early-EOS/max-token exits waste
            # the tail)
            n_emitted = sum(len(v) for v in emitted.values())
            self.stats["tokens_multi_decode"] += n_emitted
            self._rec.tokens += n_emitted
            self._burst_tokens += n_emitted
            self._burst_capacity += K * len(flight.live)
            for uid, toks in emitted.items():
                if toks:
                    self._note_emitted(uid, len(toks), now)
            if self._inflight is None:
                self._release_finished()
        return emitted

    def _drain(self) -> None:
        """Read the burst in flight, if any, outside the step that would
        have: whatever reads or moves engine state from outside a step does
        this first. The tokens wait in ``_undelivered`` for the next
        ``serve_step`` (or ``take_undelivered``)."""
        flight, self._inflight = self._inflight, None
        if flight is not None:
            for uid, toks in self._collect_burst(
                    flight, time.perf_counter()).items():
                self._undelivered.setdefault(uid, []).extend(toks)

    def take_undelivered(self) -> Dict[int, List[int]]:
        """Tokens read while no step was open (_drain), handed over once:
        for a caller that moves a session elsewhere and may not step this
        engine again before the tokens are due."""
        out, self._undelivered = self._undelivered, {}
        return out

    def _plan_decode_burst(self, after: Optional[_BurstInFlight] = None
                           ) -> Optional[int]:
        """The burst length K this round can run, with KV capacity for
        the whole burst allocated, or None; and the one counter that says
        which: ``bursts_planned`` (with ``burst_steps_clamped``, the token
        steps K stood under ``decode_steps``), or the reason it was refused
        (_burst_verdict): ``burst_refused_<reason>``, and with ``after``
        ``ahead_refused_<reason>``.

        What the free list lacks of the burst's blocks is reclaimed from
        the prefix cache's idle entries at once, before any sequence grows
        (``burst_blocks_reclaimed``, ``bursts_reclaiming``). With a call in
        flight that is safe: an idle entry is a block no live sequence
        holds, so no program in flight reads it, and the call that writes
        it takes the pool's handle from the call in flight and so runs
        behind it."""
        live = [s for s in self.state.seqs.values() if not s.done]
        K, refused, short = self._burst_verdict(live, after)
        if refused is not None:
            self.stats[("burst_refused_" if after is None
                        else "ahead_refused_") + refused] += 1
        elif K is not None:
            self.stats["bursts_planned"] += 1
            self.stats["burst_steps_clamped"] += self.decode_steps - K
            if short > 0:
                self.stats["burst_blocks_reclaimed"] += \
                    self.kv_cache.reclaim(short)
                self.stats["bursts_reclaiming"] += 1
            unread = 0 if after is None else after.steps
            for s in live:
                ok = self.state.ensure_capacity(s, s.seen_tokens + unread + K)
                assert ok, "capacity probe said yes but allocation failed"
        return K

    def _burst_verdict(self, live, after: Optional[_BurstInFlight]
                       ) -> Tuple[Optional[int], Optional[str], int]:
        """For the ``live`` sequences: ``(K, None, short)``, ``(None, why
        not, 0)`` with one of BURST_REFUSALS (AHEAD_REFUSALS with
        ``after``), or ``(None, None, 0)`` where the question does not arise
        (bursts off, nothing live). ``short``: the blocks of the burst that
        the free list lacks and the prefix cache's idle entries have. No
        side effect.

        Capacity is what the pool can give (``kv_cache.available_blocks``:
        the free list and every idle prefix entry, which ``reclaim`` hands
        over), as for a speculative round: a finished request's prompt
        blocks stay in the prefix cache until something asks for them, so
        the free list alone stands near empty in steady state. ``pool``
        says that the pool, with everything idle counted, cannot hold the
        burst. Under a host tier ``reclaim`` pages a block out by a read of
        the pool, which waits for the call in flight: there the plan ahead
        of a call keeps to the free list.

        With ``after``, the call in flight: whether the engine runs a call
        ahead, from what it can see. The batch is full (an arrival could
        not be admitted before a slot frees, so a second queued call keeps
        nobody from a first token; with a free slot the engine stays one
        call deep), nothing waits in the admission queue, no drafter (a
        speculative round is planned from tokens the host has not read),
        the sequences are the call's own with none ended, none reaches its
        budget inside the call in flight (a finish is an admission: the
        blocking path's), and there is capacity for both calls."""
        if self.decode_steps <= 1 or not live:
            return None, None, 0
        unread = 0
        if after is not None:
            unread = after.steps
            if self._drafter is not None:
                return None, "drafter", 0
            if self._queue:
                return None, "queue", 0
            if len(live) != self.max_seqs:
                return None, "free_slot", 0
            if (len(after.live) != len(live)
                    or any(a is not b for a, b in zip(after.live, live))):
                return None, "batch_changed", 0
        if (len(live) > self.max_seqs
                or any((not s.in_decode) or s.pending_prefill for s in live)):
            # a prompt still has a chunk to run (with a call in flight: a
            # sequence that call does not know)
            return None, ("prefill_pending" if after is None
                          else "batch_changed"), 0
        # clamp the burst to the shortest remaining budget: probing
        # capacity K tokens past a sequence that only needs 1 more would
        # trip ensure_capacity's per-seq-cap kill and truncate output
        # that per-token stepping would have finished
        K = min(self.decode_steps,
                max(1, min(s.gen_budget_left for s in live) - unread))
        if K <= 1:
            return None, "budget", 0
        # side-effect-free capacity probe first: per-seq cap, then total
        # pool demand (a partial speculative grab would strand blocks
        # and push the fallback step into victim preemption)
        need_total = 0
        for s in live:
            blocks = self.kv_cache.blocks_needed(s.seen_tokens + unread + K)
            if (self.state.max_blocks_per_seq is not None
                    and blocks > self.state.max_blocks_per_seq):
                # near the per-seq cap: per-token tail
                return None, "seq_cap", 0
            need_total += max(0, blocks - len(s.kv_blocks))
        free = self.kv_cache.free_blocks
        ahead_of_a_page_out = (after is not None
                               and self.kv_cache.host_tier is not None)
        if need_total > (free if ahead_of_a_page_out
                         else self.kv_cache.available_blocks):
            return None, "pool", 0
        return K, None, max(0, need_total - free)

    def _spec_round_k(self, seq, occ: float) -> int:
        """Draft length for ``seq`` this spec round. Fixed ``spec_k``
        unless adaptive speculation is on; then the controller models
        the j-th draft's expected yield as a^j (a = the request's
        measured acceptance EWMA, global EWMA as cold-start fallback)
        and drafts while a^j >= cut, where the cutoff rises with batch
        occupancy: an idle batch speculates hard (verify rows ride a
        memory-bound step for ~free), a full batch backs off toward k=0
        (verify rows crowd out real decode tokens). Only draft COUNT
        changes — accepted tokens are always the model's own greedy
        argmax chain, so bit-identity to fixed-k greedy holds."""
        if not self._spec_adaptive:
            return self.spec_k
        cut = min(0.95, self._spec_cut_base
                  * (1.0 + self._spec_load_gain * occ))
        a = self._seq_accept_ewma.get(seq.uid, self._spec_accept_ewma)
        if a is None:
            return self.spec_k  # no signal yet: speculate optimistically
        a = min(max(a, 0.0), 0.99)
        if a <= cut:
            return 0
        return max(0, min(self.spec_k,
                          int(math.log(cut) / math.log(a))))

    def _try_spec_step(self, eos_token_id: Optional[int]
                       ) -> Optional[Dict[int, List[int]]]:
        """One speculative greedy decode round: the drafter proposes up
        to ``spec_k`` tokens per sequence and ONE ragged forward verifies
        them (the SplitFuse chunk machinery doubles as the verifier —
        each chunk is [last real token, draft 1..k] and row j's argmax is
        the greedy token after prefix+drafts[:j]). The longest matching
        draft prefix is accepted plus one bonus token, so every emitted
        token is the model's own argmax chain — token-identical to
        non-speculative greedy. Returns None when a plain step should
        run instead (prefill pending, no drafts, or KV-starved)."""
        if self._drafter is None:
            return None
        with self._phase("schedule"):
            sched = self._plan_spec_round()
        if sched is None:
            return None
        t_start = time.perf_counter()
        with self.mesh:
            with self._phase("build_batch"):
                batch = build_ragged_batch(sched, self.max_tokens,
                                           self.max_seqs,
                                           self.max_blocks_per_seq)
                args = (jnp.asarray(batch.token_ids),
                        jnp.asarray(batch.token_seq),
                        jnp.asarray(batch.token_pos),
                        jnp.asarray(batch.block_table),
                        jnp.asarray(batch.num_tokens, jnp.int32))
            with self._dispatch("spec", [seq for seq, _, _ in sched],
                                int(batch.num_tokens)):
                logits, new_kv = self._step_fn(
                    self.params, self.kv_cache.kv_state, *args)
            self.kv_cache.set_kv_state(new_kv)
            with self._phase("fetch"):
                greedy = np.asarray(self._pick_greedy_all(logits))
        with self._phase("bookkeep"):
            return self._accept_spec_round(sched, batch, greedy, t_start,
                                           eos_token_id)

    def _plan_spec_round(self):
        """Pass 1 of a speculative round: propose drafts and probe KV
        capacity without side effects, then allocate. Returns the
        round's ``[(seq, chunk, start_pos)]`` or None."""
        live = [s for s in self.state.seqs.values() if not s.done]
        if (self._drafter is None or not live or len(live) > self.max_seqs
                or len(live) > self.max_tokens
                or any((not s.in_decode) or s.pending_prefill for s in live)):
            return None
        # pass 1 — side-effect-free: propose drafts and probe capacity.
        # KV writes land for every chunk token (rejected drafts leave
        # garbage PAST the accepted frontier that the next real token
        # overwrites in place), so capacity must cover 1 + k per seq —
        # shrink a proposal rather than trip the per-seq-cap kill, and
        # bail to the plain step (which owns preemption) when the pool
        # cannot cover even the plain decode tokens.
        chunks: List[np.ndarray] = []
        total = 0
        need_total = 0
        n_drafted = 0
        occ = len(live) / max(1, self.max_seqs)
        adaptive_k_sum = 0
        for s in live:
            k_round = self._spec_round_k(s, occ)
            adaptive_k_sum += k_round
            k = min(k_round, s.gen_budget_left - 1,
                    self.max_tokens - total - 1)
            drafts: List[int] = []
            if k > 0:
                drafts = list(self._drafter.propose(
                    s.input_tokens.tolist() + s.generated, k))[:k]
            while drafts and (self.kv_cache.blocks_needed(
                    s.seen_tokens + 1 + len(drafts))
                    > self.max_blocks_per_seq):
                drafts.pop()
            blocks = self.kv_cache.blocks_needed(
                s.seen_tokens + 1 + len(drafts))
            if blocks > self.max_blocks_per_seq:
                return None  # at the per-seq cap: plain step decides
            need_total += max(0, blocks - len(s.kv_blocks))
            if drafts:
                n_drafted += 1
            t0 = (s.generated[-1] if s.generated
                  else int(s.input_tokens[-1]))
            chunks.append(np.asarray([t0] + drafts, np.int32))
            total += 1 + len(drafts)
        if n_drafted == 0:
            if self._spec_adaptive and adaptive_k_sum == 0:
                # the controller chose k=0 across the batch (load high
                # or acceptance low): deliberate backoff, not a drafter
                # miss — the burst path serves this round
                self.stats["spec_backoff_rounds"] += 1
            return None  # nothing proposed: the burst path is faster
        if need_total > self.kv_cache.available_blocks:
            return None
        sched: List[Tuple[Any, np.ndarray, int]] = []
        for s, chunk in zip(live, chunks):
            ok = self.state.ensure_capacity(s, s.seen_tokens + len(chunk))
            assert ok, "spec capacity probe said yes but allocation failed"
            sched.append((s, chunk, s.seen_tokens))
        return sched

    def _accept_spec_round(self, sched, batch, greedy, t_start: float,
                           eos_token_id: Optional[int]
                           ) -> Dict[int, List[int]]:
        """Pass 2: accept each sequence's longest draft prefix that
        matches the greedy chain, plus one bonus token."""
        emitted: Dict[int, List[int]] = {}
        wasted_rows: Dict[int, int] = {}
        cursor = 0
        for s, chunk, start_pos in sched:
            n = len(chunk)
            rows = greedy[cursor:cursor + n]
            cursor += n
            emit = [int(rows[0])]
            for j in range(1, n):
                if int(chunk[j]) != emit[-1]:
                    break  # draft j diverged from the greedy chain
                emit.append(int(rows[j]))
            self.stats["spec_proposed"] += n - 1
            self.stats["spec_accepted"] += len(emit) - 1
            # drafted/accepted COUNTERS (not just the accepted-len
            # histogram) so the acceptance *rate* is derivable on the
            # Prometheus page: accepted_tokens / drafted_tokens
            self._hub.counter_add("serve.spec_drafted_tokens", n - 1,
                                  labels=self._metric_labels)
            self._hub.counter_add("serve.spec_accepted_tokens",
                                  len(emit) - 1,
                                  labels=self._metric_labels)
            self.tracer.on_spec(s.uid, drafted=n - 1,
                                accepted=len(emit) - 1)
            self._spec_hist.observe(len(emit) - 1)
            if n > 1:
                # measured acceptance feeds the adaptive-k controller
                # (per-request EWMA, global EWMA as the cold-start
                # fallback) and the drafter's own counters
                rate = (len(emit) - 1) / (n - 1)
                a = self._spec_alpha
                prev = self._seq_accept_ewma.get(s.uid)
                self._seq_accept_ewma[s.uid] = (
                    rate if prev is None else a * rate + (1 - a) * prev)
                prev_g = self._spec_accept_ewma
                self._spec_accept_ewma = (
                    rate if prev_g is None else a * rate + (1 - a) * prev_g)
                note = getattr(self._drafter, "note_result", None)
                if note is not None:
                    note(n - 1, len(emit) - 1)
            # rows computed past the accepted frontier: the verify
            # round's wasted work (what adaptive-k minimizes under load)
            wasted = n - len(emit)
            if wasted:
                self._spec_wasted_verify_tokens += wasted
                self._hub.counter_add("serve.spec_wasted_verify_tokens",
                                      wasted, labels=self._metric_labels)
            budget_left = s.gen_budget_left
            final: List[int] = []
            for tok in emit:
                final.append(tok)
                if eos_token_id is not None and tok == eos_token_id:
                    s.done = True
                    break
                if len(final) >= budget_left:
                    s.done = True
                    break
            s.generated.extend(final)
            s.seen_tokens = start_pos + len(final)
            emitted[s.uid] = final
            wasted_rows[s.uid] = n - len(final)
        self.stats["spec_steps"] += 1
        now = time.perf_counter()
        self._step_hist.observe(now - t_start)
        round_wall_ms = (now - t_start) * 1e3
        # a speculative round runs the gather program
        n_emitted = sum(len(v) for v in emitted.values())
        self.stats["tokens_gather"] += n_emitted
        self._rec.tokens += n_emitted
        for uid, toks in emitted.items():
            if toks:
                # this request's share of the verify round spent on
                # rows past its accepted frontier — the spec_overhead
                # carve-out of its decode phase
                self._note_emitted(
                    uid, len(toks), now,
                    spec_overhead_ms=round_wall_ms * wasted_rows[uid]
                    / max(1, batch.num_tokens))
        self._release_finished()
        return emitted

    def serve_step(self, temperature: float = 0.0, seed: int = 0,
                   eos_token_id: Optional[int] = None
                   ) -> Dict[int, List[int]]:
        """One serving round: admit from the waiting queue, then run the
        best step for the current mix — speculative decode (drafts
        available), multi-token burst (steady greedy decode), or a plain
        SplitFuse step. Returns {uid: tokens emitted this round}. The
        open-loop SLO harness (tools/serve_bench.py) drives this.

        With every sequence slot taken and nothing queued, a burst step
        returns the tokens of the call the step before dispatched and
        leaves the next call running (_burst_step): the caller reads call
        n's tokens while call n+1 runs. The tokens, their order and each
        sequence's end are those of an engine that reads every call before
        it issues the next."""
        with self._open_step():
            with self._phase("admit"):
                self._admit_from_queue()
            out: Optional[Dict[int, List[int]]] = None
            if temperature == 0.0:
                if self._inflight is None:
                    out = self._try_spec_step(eos_token_id)
                if out is None:
                    out = self._burst_step(eos_token_id)
            else:
                self._drain()
            if out is None:
                emitted = self._splitfuse_step(temperature, seed,
                                               eos_token_id)
                out = {uid: [tok] for uid, tok in emitted.items()}
            if self._undelivered:
                # what a drain read between two steps, before this step's
                for uid, toks in out.items():
                    self._undelivered.setdefault(uid, []).extend(toks)
                out = self.take_undelivered()
            with self._phase("journal"):
                jr = get_journal()
                if jr is not None and out and jr.claim_ingress(
                        self._journal_owner) == self._journal_owner:
                    for uid, toks in out.items():
                        if toks:
                            jr.emit(uid, toks)
        return out

    def generate_all(self, temperature: float = 0.0, seed: int = 0,
                     eos_token_id: Optional[int] = None,
                     max_steps: int = 100_000) -> Dict[int, List[int]]:
        """Drive serve_step until every submitted sequence finishes
        (including requests still waiting in the admission queue);
        returns {uid: generated tokens}. In steady greedy decode, bursts
        ``decode_steps`` tokens (or verified speculative drafts) per
        device round trip."""
        results: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            if not (self.state.seqs or self._queue or self._undelivered):
                break
            # every round makes progress: emits tokens, advances a
            # prefill, admits from the queue, or preempts a starved
            # sequence — so this loop terminates
            for uid, toks in self.serve_step(
                    temperature, seed, eos_token_id).items():
                results.setdefault(uid, []).extend(toks)
        return results

    def flush(self, uids: List[int]) -> None:
        """Drop sequences + free KV (reference engine_v2.py flush);
        covers queued-but-unadmitted requests too."""
        self._drain()
        tier = getattr(self.kv_cache, "host_tier", None)
        for uid in uids:
            self._undelivered.pop(uid, None)
            self.tracer.on_finish(uid, "flushed")
            self._release_seq(uid)
            if tier is not None and tier.has_session(uid):
                tier.pop_session(uid)  # flushed sessions never resume
        drop = set(uids)
        if any(r.uid in drop for r in self._queue):
            self._queue = deque(r for r in self._queue if r.uid not in drop)

    def close(self) -> None:
        """Stop what this engine's observability started: the request
        tracer's crash-dump context leaves the flight recorder and the
        hub's Prometheus page is written once more. Idempotent. Device
        state (weights, KV pool) goes with the object; the process-wide
        hub, flight recorder and crash handlers stay for other engines. A
        burst in flight is read first (``take_undelivered`` has its
        tokens)."""
        if self._closed:
            return
        self._drain()
        if self._rec.tokens:    # read by that drain: no step delivers them
            self._hub.counter_add("serve.tokens_emitted", self._rec.tokens,
                                  labels=self._metric_labels)
            self._rec.tokens = 0
        self._closed = True
        self.tracer.detach_flight()
        self._hub.write_prometheus()

    def log_summary(self) -> Dict[str, Any]:
        """Serve-path telemetry (the comms-logger log_summary analog):
        the calls and rows of each program, kernel-path step counts
        (``prefill_kernel_steps``: steps whose chunks the prefill program
        took) and the gather-fallback counts, which no step raises since
        every runner splits its steps by program."""
        s = dict(self.stats)
        s["fallback_reasons"] = dict(self.stats["fallback_reasons"])
        s["preempt_reasons"] = dict(self.stats["preempt_reasons"])
        log_dist(f"InferenceEngineV2 summary: {s}", ranks=[0])
        return s

    def request_traces(self, last: int = 0):
        """Finished (tail-sampled) request traces — the input to
        ``slo_attribution`` and the per-request chrome-trace lanes."""
        return self.tracer.finished(last=last)

    def snapshot(self) -> Dict[str, Any]:
        """Serving observability snapshot: request-latency percentiles
        (TTFT + per-decode-token, p50/p95/p99), queue/occupancy gauges
        and the kernel/fallback counters. The same histograms render on
        the hub's Prometheus page (docs/observability.md). A burst in
        flight is read first, so the counters and the tokens agree."""
        self._drain()
        gauges = self._serve_gauges()
        out: Dict[str, Any] = {
            "ttft": self._ttft_hist.snapshot(),
            "decode_token_latency": self._decode_hist.snapshot(),
            "step_latency": self._step_hist.snapshot(),
            "admission_wait": self._admission_hist.snapshot(),
            "kv_quant_bits": self.kv_cache.quant_bits,
            "handoff_wire": self._handoff_wire,
            "scheduler": dict(self.scheduler.stats),
            "stats": dict(self.stats,
                          fallback_reasons=dict(
                              self.stats["fallback_reasons"]),
                          preempt_reasons=dict(
                              self.stats["preempt_reasons"])),
            "request_trace": self.tracer.snapshot(),
        }
        # the gauges the hub is given, under their bare names
        for name in ("queue_depth", "queue_wait_depth",
                     "pending_prefill_tokens", "kv_free_blocks",
                     "batch_seq_occupancy", "batch_token_occupancy",
                     "burst_efficiency"):
            if "serve." + name in gauges:
                out[name] = gauges["serve." + name]
        if self.kv_cache.prefix_cache is not None:
            out["prefix_cache"] = self.kv_cache.prefix_cache.snapshot()
        if self.stats["spec_proposed"] > 0:
            # acceptance RATE next to the raw drafted/accepted counters
            # (the counters alone make it derivable across processes;
            # the line here makes it readable in one snapshot)
            out["spec_drafted_tokens"] = self.stats["spec_proposed"]
            out["spec_accepted_tokens"] = self.stats["spec_accepted"]
            out["spec_acceptance_rate"] = (self.stats["spec_accepted"]
                                           / self.stats["spec_proposed"])
            out["spec_accepted_len"] = self._spec_hist.snapshot()
        if self._spec_accept_ewma is not None:
            out["spec_accept_ewma"] = self._spec_accept_ewma
        if self._spec_wasted_verify_tokens:
            out["spec_wasted_verify_tokens"] = self._spec_wasted_verify_tokens
        tier = getattr(self.kv_cache, "host_tier", None)
        if tier is not None:
            out["host_tier"] = tier.snapshot()
        if self._drafter is not None and hasattr(self._drafter, "stats"):
            out["drafter"] = dict(self._drafter.stats)
        return out


def _sample_np(logits_row: np.ndarray, temperature: float, seed: int) -> int:
    if temperature <= 0.0:
        return int(np.argmax(logits_row))
    rng = np.random.default_rng(seed)
    z = logits_row / temperature
    z = z - z.max()
    p = np.exp(z)
    p /= p.sum()
    return int(rng.choice(len(p), p=p))

"""Process-wide metrics hub.

Every signal the runtime already produces — step timing, loss/grad-norm,
traced collective volume (utils/comms_logging), capability-fallback
counters (utils/telemetry), serving latencies (inference/engine_v2) —
flows through one registry with three export paths:

* ``record_step`` keeps a bounded in-memory history of ``StepTrace``
  rows and mirrors the headline numbers into gauges;
* a JSON-lines sink streams every row to disk as it happens;
* a Prometheus text snapshot is rewritten (atomically) on a cadence for
  textfile-collector scraping.

The hub is a singleton (``get_hub``): training engine, serving engine
and user code in one process share the registry, so one Prometheus page
shows the whole picture. Sinks attach via :meth:`configure` (config
block or ``DSTPU_METRICS_JSONL`` / ``DSTPU_METRICS_PROM`` env vars).

Compile/retrace visibility: jax.monitoring event listeners (registered
once) count XLA compilations
and their wall time; ``StepTrace.compile_events`` > 0 on a mid-run step
is the classic silent-retrace regression signature.

Gauges come two ways: pushed (``gauge``), and *provided*: an engine
registers one method (``add_provider``) that says what its gauges read,
and the hub calls it when somebody reads (``snapshot``, ``to_prometheus``,
the Prometheus sink, ``gauges``), not when the engine steps.
"""

from __future__ import annotations

import os
import threading
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from deepspeed_tpu.observability.histogram import Histogram
from deepspeed_tpu.observability.sinks import (JSONLSink, PrometheusTextSink,
                                               labeled_name,
                                               render_prometheus)
from deepspeed_tpu.observability.step_trace import StepTrace
from deepspeed_tpu.utils.logging import logger

# process-global compile accounting: jax.monitoring listeners cannot be
# unregistered, so they feed module state rather than a hub instance
# (reset_hub() would otherwise leak dead hubs into the listener)
_COMPILE_LOCK = threading.Lock()
_COMPILE_EVENTS = 0
_COMPILE_SECS = 0.0
_LISTENERS_REGISTERED = False
# the one event a compilation raises once: tracing, lowering and the
# persistent cache's lookups raise others with "compile" in their names
# (and ``compile_time_saved_sec`` is time that was not spent)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile_duration(event: str, duration: float, **kw) -> None:
    global _COMPILE_EVENTS, _COMPILE_SECS
    if event != _COMPILE_EVENT:
        return
    with _COMPILE_LOCK:
        _COMPILE_EVENTS += 1
        _COMPILE_SECS += float(duration)


def _register_compile_listeners() -> None:
    global _LISTENERS_REGISTERED
    if _LISTENERS_REGISTERED:
        return
    _LISTENERS_REGISTERED = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_compile_duration)


def compile_stats() -> Dict[str, float]:
    with _COMPILE_LOCK:
        return {"events": _COMPILE_EVENTS, "secs": _COMPILE_SECS}


class MetricsHub:
    def __init__(self, step_history: int = 512):
        self._lock = threading.Lock()
        self._gauges: Dict[str, float] = {}      # pushed (``gauge``)
        # (weak reference to a bound method, its labels): see add_provider
        self._providers: List[Tuple[Any, Optional[Dict[str, str]]]] = []
        self.counters: Dict[str, float] = {}
        self.histograms: Dict[str, Histogram] = {}
        self.step_history: deque = deque(maxlen=step_history)
        self._jsonl: Optional[JSONLSink] = None
        self._prom: Optional[PrometheusTextSink] = None
        self._prom_every = 10  # steps between Prometheus snapshot rewrites
        self._fleet = None  # FleetPublisher when a run dir is configured
        self._last_comm_totals: Dict[str, float] = {}
        self._last_fallbacks: Dict[str, float] = {}
        self._last_compile = compile_stats()
        _register_compile_listeners()

    # -- configuration -------------------------------------------------
    def configure(self, obs_config=None, rank=None) -> None:
        """Attach sinks from the config block and/or env vars. Safe to
        call more than once (a second engine in the process reuses the
        already-attached sinks). With a run dir configured
        (``observability.run_dir`` / ``DSTPU_RUN_DIR``) a
        ``FleetPublisher`` additionally shards every step row into it
        (docs/observability.md "Fleet view"); no run dir → no publisher,
        no shard I/O."""
        jsonl = os.environ.get("DSTPU_METRICS_JSONL") or getattr(
            obs_config, "jsonl_path", None)
        prom = os.environ.get("DSTPU_METRICS_PROM") or getattr(
            obs_config, "prometheus_path", None)
        hist = int(getattr(obs_config, "step_history", 0) or 0)
        every = int(getattr(obs_config, "prometheus_every_steps", 0) or 0)
        with self._lock:
            if jsonl and (self._jsonl is None or self._jsonl.path != jsonl):
                self._jsonl = JSONLSink(jsonl)
            if prom and (self._prom is None or self._prom.path != prom):
                self._prom = PrometheusTextSink(prom)
            if every > 0:
                self._prom_every = every
            if hist > 0 and hist != self.step_history.maxlen:
                self.step_history = deque(self.step_history, maxlen=hist)
        try:
            from deepspeed_tpu.observability.fleet import (FleetPublisher,
                                                           resolve_run_dir)

            run_dir = resolve_run_dir(obs_config)
            if run_dir and (self._fleet is None
                            or self._fleet.run_dir != run_dir):
                self._fleet = FleetPublisher(
                    run_dir, rank=rank,
                    publish_every_steps=getattr(
                        obs_config, "publish_every_steps", 1))
        except Exception as e:  # the fleet layer must never block startup
            logger.warning(f"fleet publisher unavailable: {e}")

    # -- primitive metrics ---------------------------------------------
    # ``labels`` composes a distinct series per label set
    # (``serve.queue_depth{replica="r0"}``) — fleet serving metrics use
    # it so aggregation never collapses N replicas into one series; the
    # Prometheus renderer understands the composed keys (sinks.py)
    def gauge(self, name: str, value: float,
              labels: Optional[Dict[str, str]] = None) -> None:
        if labels:
            name = labeled_name(name, labels)
        with self._lock:
            self._gauges[name] = float(value)

    def add_provider(self, method: Callable[[], Dict[str, float]],
                     labels: Optional[Dict[str, str]] = None) -> None:
        """Gauges computed when somebody reads them: ``method`` (a bound
        method of the object the gauges describe) returns ``{name: value}``
        as it stands now, and every read of the hub's gauges calls it and
        files the values under ``labels``. The object is held weakly: a
        provider whose object is gone is dropped at the next read, and its
        series with it. It runs on the reader's thread, so it reads state a
        step may be changing and must not iterate over it in place."""
        with self._lock:
            self._providers.append((weakref.WeakMethod(method), labels))

    def _provided(self) -> Dict[str, float]:
        """What the providers read now, by labeled name. Outside the lock:
        a provider may ask the hub for something itself."""
        with self._lock:
            providers = list(self._providers)
        out: Dict[str, float] = {}
        dead = False
        for ref, labels in providers:
            method = ref()
            if method is None:
                dead = True
                continue
            try:
                values = method()
            except Exception:     # a reader's snapshot must not fail for it
                logger.warning("metrics hub: a gauge provider failed",
                               exc_info=True)
                continue
            for name, value in values.items():
                out[labeled_name(name, labels)] = float(value)
        if dead:
            with self._lock:
                self._providers = [e for e in self._providers
                                   if e[0]() is not None]
        return out

    @property
    def gauges(self) -> Dict[str, float]:
        """Every gauge as it reads now, pushed and provided (a copy)."""
        provided = self._provided()
        with self._lock:
            return {**self._gauges, **provided}

    def counter_add(self, name: str, n: float = 1.0,
                    labels: Optional[Dict[str, str]] = None) -> None:
        if labels:
            name = labeled_name(name, labels)
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + n

    def histogram(self, name: str,
                  labels: Optional[Dict[str, str]] = None,
                  **kw) -> Histogram:
        if labels:
            name = labeled_name(name, labels)
        with self._lock:
            h = self.histograms.get(name)
            if h is None:
                h = self.histograms[name] = Histogram(name, **kw)
            return h

    # -- step traces -----------------------------------------------------
    def comm_deltas(self) -> (dict, dict):
        """(cumulative, delta-since-last-call) traced collective bytes
        by op — empty when the comms logger is disabled."""
        try:
            from deepspeed_tpu.utils.comms_logging import get_comms_logger

            totals = get_comms_logger().totals()
        except Exception:
            totals = {}
        delta = {k: v - self._last_comm_totals.get(k, 0.0)
                 for k, v in totals.items()
                 if v != self._last_comm_totals.get(k, 0.0)}
        self._last_comm_totals = dict(totals)
        return totals, delta

    def compile_delta(self) -> Dict[str, float]:
        now = compile_stats()
        delta = {"events": now["events"] - self._last_compile["events"],
                 "secs": now["secs"] - self._last_compile["secs"]}
        self._last_compile = now
        return delta

    def fallback_delta(self) -> Dict[str, float]:
        """Capability-fallback counters (utils/telemetry) that moved
        since the last call — empty in the steady state, so exporting
        the delta costs nothing per step."""
        try:
            from deepspeed_tpu.utils import telemetry

            now = telemetry.snapshot()
        except Exception:
            return {}
        delta = {k: v - self._last_fallbacks.get(k, 0)
                 for k, v in now.items()
                 if v != self._last_fallbacks.get(k, 0)}
        self._last_fallbacks = {k: float(v) for k, v in now.items()}
        return delta

    def record_step(self, trace: StepTrace) -> None:
        with self._lock:
            self.step_history.append(trace)
            self._gauges["train.step"] = trace.step
            self._gauges["train.step_seconds"] = trace.wall_ms / 1000.0
            for name, val in (("train.loss", trace.loss),
                              ("train.grad_norm", trace.grad_norm),
                              ("train.lr", trace.lr),
                              ("train.tokens_per_sec", trace.tokens_per_sec),
                              ("train.tokens_per_sec_per_chip",
                               trace.tokens_per_sec_per_chip),
                              ("train.mfu", trace.mfu),
                              ("train.host_gap_ms", trace.host_gap_ms)):
                if val is not None:
                    self._gauges[name] = float(val)
            self.counters["train.steps"] = \
                self.counters.get("train.steps", 0.0) + 1.0
            if trace.tokens:
                self.counters["train.tokens"] = \
                    self.counters.get("train.tokens", 0.0) + trace.tokens
            if trace.overflow:
                self.counters["train.overflow_steps"] = \
                    self.counters.get("train.overflow_steps", 0.0) + 1.0
            if trace.compile_events:
                self.counters["jit.compile_events"] = \
                    self.counters.get("jit.compile_events", 0.0) \
                    + trace.compile_events
        self.histogram("train.step_seconds").observe(trace.wall_ms / 1000.0)
        # capability downgrades land on the same dashboard as throughput:
        # moved telemetry counters mirror into hub counters (-> Prometheus
        # as dstpu_fallback_*_total) and emit one JSONL event per change
        fb = self.fallback_delta()
        for name, d in fb.items():
            self.counter_add(f"fallback.{name}", d)
        if fb:
            self.record_event("capability_fallback", step=trace.step,
                              delta=fb)
        if self._jsonl is not None:
            self._jsonl.write(trace.to_dict())
        if self._fleet is not None:
            self._fleet.publish_step(trace)
        if self._prom is not None and \
                trace.step % max(1, self._prom_every) == 0:
            self.write_prometheus()

    def record_event(self, kind: str, **fields) -> None:
        """Free-form JSONL row (watchdog reports, trace markers, ...)."""
        if self._jsonl is not None:
            self._jsonl.write(dict(fields, kind=kind))

    # -- export ----------------------------------------------------------
    def mean_mfu(self, last_n: int = 0) -> Optional[float]:
        """Mean MFU over the most recent ``last_n`` traced steps (all
        history when 0); None when no step carried an MFU."""
        with self._lock:
            rows = list(self.step_history)
        if last_n > 0:
            rows = rows[-last_n:]
        vals = [t.mfu for t in rows if t.mfu is not None]
        if not vals:
            return None
        return sum(vals) / len(vals)

    def window_mfu(self, last_n: int = 0) -> Optional[float]:
        """MFU of the most recent ``last_n`` traced steps computed the
        way bench.py computes its window: total tokens over total wall
        time (token-weighted — a mean of per-step rates would overweight
        fast steps). None when the window carries no MFU inputs."""
        with self._lock:
            rows = list(self.step_history)
        if last_n > 0:
            rows = rows[-last_n:]
        rows = [t for t in rows
                if t.mfu is not None and t.wall_ms > 0 and t.tokens]
        if not rows:
            return None
        total_tokens = sum(t.tokens for t in rows)
        total_s = sum(t.wall_ms for t in rows) / 1000.0
        last = rows[-1]
        from deepspeed_tpu.observability.roofline import mfu as _mfu

        return _mfu(total_tokens / total_s / max(1, last.n_chips),
                    last.flops_per_token, last.peak_tflops)

    def window_host_gap_ms(self, last_n: int = 0) -> Optional[float]:
        """Mean host-side gap per step over the most recent ``last_n``
        traced steps (all history when 0) — the per-window aggregate
        bench.py reports next to tokens/s/chip so host-overhead
        regressions are visible in every BENCH artifact. None when no
        step in the window carried the measurement."""
        with self._lock:
            rows = list(self.step_history)
        if last_n > 0:
            rows = rows[-last_n:]
        vals = [t.host_gap_ms for t in rows if t.host_gap_ms is not None]
        if not vals:
            return None
        return sum(vals) / len(vals)

    def snapshot(self) -> Dict[str, Any]:
        from deepspeed_tpu.utils import telemetry

        gauges = self.gauges
        with self._lock:
            out: Dict[str, Any] = {
                "gauges": gauges,
                "counters": dict(self.counters),
                "histograms": {n: h.snapshot()
                               for n, h in self.histograms.items()},
                "fallbacks": telemetry.snapshot(),
            }
            last = self.step_history[-1] if self.step_history else None
        if last is not None:
            out["last_step"] = last.to_dict()
        return out

    def to_prometheus(self) -> str:
        from deepspeed_tpu.utils import telemetry

        gauges = self.gauges
        with self._lock:
            counters = dict(self.counters)
            hists = dict(self.histograms)
        return render_prometheus(
            gauges, counters, hists,
            labeled_counters={"capability_fallback":
                              {k: float(v)
                               for k, v in telemetry.snapshot().items()}})

    def write_prometheus(self) -> None:
        if self._prom is not None:
            self._prom.write_text(self.to_prometheus())

    def close(self) -> None:
        if self._jsonl is not None:
            self._jsonl.close()
        if self._fleet is not None:
            self._fleet.close()
        self.write_prometheus()


_HUB: Optional[MetricsHub] = None
_HUB_LOCK = threading.Lock()


def get_hub() -> MetricsHub:
    global _HUB
    with _HUB_LOCK:
        if _HUB is None:
            _HUB = MetricsHub()
        return _HUB


def peek_hub() -> Optional[MetricsHub]:
    """The singleton if one exists, without creating it — for report
    paths (watchdog, crash dumps) that must not allocate mid-failure."""
    return _HUB


def reset_hub() -> None:
    """Drop the singleton (tests). Sinks on the old hub are closed."""
    global _HUB
    with _HUB_LOCK:
        if _HUB is not None:
            try:
                if _HUB._jsonl is not None:
                    _HUB._jsonl.close()
                if _HUB._fleet is not None:
                    _HUB._fleet.close()
            except Exception:
                pass
        _HUB = None

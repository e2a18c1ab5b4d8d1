"""Process supervisor: real replica processes behind the same router.

:class:`RemoteReplica` is the router-side stub for a replica living in
another process — it satisfies the exact surface the in-process
:class:`ServingReplica` exposes (``submit``/``load_report``/
``load_score``/``alive``/``serialize_handoff``/``engine.tracer``), so
:class:`FleetRouter` routes, hands off, and fails over without knowing
which side of a socket each replica is on. What changes is *where*
things run: emissions arrive on the supervisor's per-replica receive
threads instead of pump threads, and the KV-serialize step of a
disaggregated handoff becomes an async request/reply (the continuation
passed to ``serialize_handoff`` fires when the payload message lands).

:class:`ReplicaSupervisor` owns the process lifecycle:

* **spawn** — write a worker spec, fork ``python -m
  deepspeed_tpu.serving.proc_worker``, wait for the ready file, connect
  (with backoff — the connect races worker startup), start the receive
  thread, and hand the ``RemoteReplica`` to the router;
* **restart** — a worker that exits without being asked to is a crash:
  the stub is marked failed (so the router's next health check declares
  it dead and resubmits its in-flight requests — the zero-drop failover
  path, unchanged), and a replacement spawns under a *new* replica id.
  Replacements inherit the crashed worker's *lineage*: repeat restarts
  back off exponentially (``restart_policy``, a resilience RetryPolicy),
  and a lineage crashing more than ``max_restarts_per_window`` times
  inside ``restart_window_s`` trips the circuit breaker — it is
  **quarantined** (recorded in the decision history, never respawned;
  replacing its capacity becomes the autoscale signal's job) instead of
  being restarted unboundedly. ``drain`` refuses to shrink the fleet
  below ``min_healthy`` live workers (``drain_refused`` in the act log);
* **autoscale acts** — the PR 10 signal stops being metrics-only: when
  ``desired`` exceeds the live count the supervisor spins up, when it
  drops below it picks a victim, stops new admissions
  (``router.remove_replica``), and sends ``drain`` — the worker
  finishes its in-flight work and exits 0. Every act is recorded into
  the autoscale decision history next to the desires that caused it.

Every worker publishes its load report both over the channel (routing)
and through ``ReplicaPublisher`` into ``<run_dir>/replicas/`` —
:meth:`write_fleet_snapshot` merges channel-side state into
``<run_dir>/fleet_snapshot.json`` for ``serve_top --fleet``.

Clock note: worker-side wall timestamps (load-report ``ts``, trace
spans) are rebased into the supervisor's clock domain via the
per-channel NTP-style offset estimator
(observability/clocksync.ClockSyncEstimator, attached to each channel
at spawn, re-synced by :meth:`ReplicaSupervisor.maintain`). With
``clock_sync=False`` — or before an estimator has its minimum sample
count — the raw timestamps pass through untouched, bit-exact with the
pre-clocksync behavior that assumed localhost's shared ``time.time()``.
Liveness never depends on wall clocks either way: heartbeat ages use
``time.monotonic()`` on the supervisor side only.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.observability.clocksync import wall_time
from deepspeed_tpu.observability.journal import get_journal
from deepspeed_tpu.serving.replica import Submission
from deepspeed_tpu.serving.transport import (ChannelError, FileChannel,
                                             connect_with_backoff,
                                             decode_handoff, decode_session,
                                             encode_handoff, encode_session)


_WARNED_LEGACY_CONNECT = False


def _atomic_write_json(path: str, doc: Dict[str, Any]) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class _KVConfigView:
    def __init__(self, block_size: int):
        self.block_size = int(block_size)


class _KVAllocatorView:
    def __init__(self, total_blocks: int):
        self.total_blocks = int(total_blocks)


class _KVCacheView:
    """Just enough KV-cache geometry for the router's admission math
    (``_check_fits``/``_affinity_key``) — numbers from the worker's
    first report, never the blocks themselves."""

    def __init__(self, block_size: int, total_blocks: int):
        self.config = _KVConfigView(block_size)
        self.allocator = _KVAllocatorView(total_blocks)

    def blocks_needed(self, n_tokens: int) -> int:
        bs = self.config.block_size
        return (int(n_tokens) + bs - 1) // bs


class RemoteEngineView:
    """The router touches ``replica.engine`` for exactly two things:
    KV geometry and the tracer. This view provides both — the tracer is
    a real :class:`RequestTracer` fed from the worker's shipped trace
    dicts, so fleet SLO attribution and Perfetto export work unchanged
    across the process boundary."""

    def __init__(self, block_size: int, total_blocks: int,
                 max_blocks_per_seq: int):
        from deepspeed_tpu.observability.request_trace import RequestTracer

        self.kv_cache = _KVCacheView(block_size, total_blocks)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.tracer = RequestTracer(enabled=True, sample_rate=1.0)
        # per-channel ClockSyncEstimator + domain label, set by the
        # owning RemoteReplica; None means ingest raw (bit-exact)
        self.clock = None
        self.clock_domain: Optional[str] = None

    def update_geometry(self, geo: Dict[str, Any]) -> None:
        self.kv_cache.config.block_size = int(geo["block_size"])
        self.kv_cache.allocator.total_blocks = int(geo["total_blocks"])
        self.max_blocks_per_seq = int(geo["max_blocks_per_seq"])

    def ingest_traces(self, docs: List[Dict[str, Any]]) -> None:
        from deepspeed_tpu.observability.request_trace import RequestTrace

        clk = self.clock
        rebase = clk is not None and clk.synced
        if rebase:
            # one estimate per batch: spans from one emit must land in
            # one coherent shift, not straddle a mid-batch re-sync
            off, unc = clk.offset_s, clk.uncertainty_s
        t = self.tracer
        with t._lock:
            for d in docs:
                tr = RequestTrace.from_dict(d)
                if rebase:
                    tr.rebase(off, unc, domain=self.clock_domain)
                t._ring.append(tr)
                t.stats["finished"] += 1
                t.stats["kept"] += 1
                if t.alerter is not None:
                    t.alerter.observe_trace(tr)


def _empty_report(replica_id: int, role: str) -> Dict[str, Any]:
    return {"replica": replica_id, "role": role, "ts": 0.0, "steps": 0,
            "queue_wait_depth": 0, "live_seqs": 0, "inflight": 0,
            "kv_free_blocks": 0, "kv_free_frac": 1.0,
            "goodput_tokens_per_s": 0.0, "killed": False,
            "kv_quant_bits": None, "handoff_wire": "auto",
            "handoff_wire_bytes": 0, "handoff_logical_bytes": 0,
            "kv_wire_snr_db": None}


class RemoteReplica:
    """Router-side stub for one worker process."""

    def __init__(self, replica_id: int, role: str, channel,
                 block_size: int, total_blocks: int,
                 max_blocks_per_seq: int,
                 handoff_timeout_s: float = 15.0):
        self.replica_id = int(replica_id)
        self.name = f"r{self.replica_id}"
        self.role = role
        self.channel = channel
        self.engine = RemoteEngineView(block_size, total_blocks,
                                       max_blocks_per_seq)
        # the channel's ClockSyncEstimator (attached by the supervisor
        # before construction when clock_sync is on; the channel layer
        # defaults it to None) drives trace/report rebasing
        self.engine.clock = getattr(channel, "clock", None)
        self.engine.clock_domain = self.name
        # FleetMetricsPlane fed by the metrics the worker piggybacks on
        # heartbeats (set by the supervisor; None drops them)
        self.metrics_plane = None
        self.emit_callback: Optional[Callable] = None
        self.killed = False
        self.draining = False
        self.exited = False  # worker announced a clean drain-exit
        self._send_failed = False
        # consecutive channel errors; reset by any successful inbound
        # message — the router's health state machine reads this
        self.transport_errors = 0
        self._report = _empty_report(self.replica_id, role)
        self._report_ts = time.time()  # display only (report ts)
        self._report_mono = time.monotonic()  # liveness decisions
        self._sent_submits = 0  # vs the report's received_submits
        self._lock = threading.Lock()
        self._handoff_timeout_s = float(handoff_timeout_s)
        self._handoff_cbs: Dict[int, Tuple[Callable, float]] = {}
        # live-migration + hot-swap RPCs share the handoff timeout/
        # expiry discipline: an orphaned continuation fires with None
        self._migrate_cbs: Dict[int, Tuple[Callable, float]] = {}
        self._reload_cbs: Dict[int, Tuple[Callable, float]] = {}
        self._next_req = 0

    # -- the ServingReplica surface ------------------------------------
    def heartbeat_age(self, now: Optional[float] = None) -> float:
        """Seconds since the last inbound report, on the *monotonic*
        clock — a stepped wall clock must never fail a healthy worker
        over. ``now``, when given, is a ``time.monotonic()`` stamp."""
        now = time.monotonic() if now is None else now
        return now - self._report_mono

    def alive(self, now: Optional[float] = None,
              stale_after: float = 5.0) -> bool:
        """Liveness = recent heartbeat over a working channel. A dead
        worker stops reporting; a broken channel flips ``_send_failed``
        immediately — either way the router's health check fails the
        replica over without waiting on process state. ``now`` is
        monotonic (see heartbeat_age)."""
        if self._send_failed:
            return False
        return self.heartbeat_age(now) < stale_after

    def _unacked(self, r: Dict[str, Any]) -> int:
        """Submissions on the wire the worker's report can't see yet.
        Monotone counters on both sides (sent here, received in the
        report) — a report generated *before* a submission landed
        cannot erase the pending window the way a reset-on-report
        scheme would. Caller holds the lock."""
        return max(0, self._sent_submits
                   - int(r.get("received_submits", 0)))

    def load_report(self, now: Optional[float] = None) -> Dict[str, Any]:
        """Last heartbeat report, with ``inflight`` bumped by the
        unacked sends — the worker can't see them yet, but the router's
        TTFT predictor must, or every submit inside one heartbeat
        window reads the same stale depth and piles onto a single
        worker."""
        with self._lock:
            r = dict(self._report)
            r["inflight"] = int(r.get("inflight", 0)) + self._unacked(r)
            return r

    def load_score(self) -> float:
        """Same cost shape as the local replica, plus the unacked
        in-flight window."""
        with self._lock:
            r = self._report
            return (r["queue_wait_depth"] + r["live_seqs"]
                    + self._unacked(r) + (1.0 - r["kv_free_frac"]))

    def submit(self, sub: Submission) -> None:
        if sub.session is not None:
            # live migration install: the SessionHandoff rides its own
            # message type; tokens carry the recompute fallback the
            # worker degrades to if the payload can't land
            msg = {"type": "install_session", "uid": int(sub.uid),
                   "tokens": np.asarray(sub.tokens, np.int32),
                   "max_new_tokens": int(sub.max_new_tokens),
                   "span_notes": [[k, dict(f)]
                                  for k, f in sub.span_notes],
                   "session": encode_session(sub.session)}
        else:
            msg = {"type": "submit", "uid": int(sub.uid),
                   "tokens": np.asarray(sub.tokens, np.int32),
                   "max_new_tokens": int(sub.max_new_tokens),
                   "span_notes": [[k, dict(f)]
                                  for k, f in sub.span_notes],
                   "handoff": encode_handoff(sub.handoff)}
        try:
            self.channel.send(msg)
        except ChannelError:
            # the stale-heartbeat path will resubmit this request
            # elsewhere; losing the send is exactly a replica crash
            self.transport_errors += 1
            self._send_failed = True
            return
        with self._lock:
            self._sent_submits += 1

    def serialize_handoff(self, tokens: np.ndarray,
                          cb: Callable[[Optional[Any]], None]) -> None:
        """Async serialize RPC: the reply (``handoff_payload``) invokes
        ``cb`` on the receive thread; a dead channel or an expired wait
        degrades to ``cb(None)`` — the install side's recompute path."""
        with self._lock:
            req = self._next_req
            self._next_req += 1
            self._handoff_cbs[req] = (
                cb, time.monotonic() + self._handoff_timeout_s)
        try:
            self.channel.send({"type": "serialize", "req": req,
                               "tokens": np.asarray(tokens, np.int32)})
        except ChannelError:
            self.transport_errors += 1
            self._send_failed = True
            with self._lock:
                self._handoff_cbs.pop(req, None)
            cb(None)

    def migrate_out(self, uid: int,
                    cb: Callable[[Optional[Any]], None],
                    wire: Optional[str] = None) -> None:
        """Async live-migration capture RPC: the worker captures and
        releases session ``uid``'s full decode state; the reply
        (``session_payload``) invokes ``cb`` on the receive thread. A
        dead channel or an expired wait degrades to ``cb(None)`` — the
        router's fold-and-resubmit recompute path. Channel FIFO
        guarantees every emission the session produced arrives before
        the capture, so the caller's folded tokens are complete."""
        with self._lock:
            req = self._next_req
            self._next_req += 1
            self._migrate_cbs[req] = (
                cb, time.monotonic() + self._handoff_timeout_s)
        try:
            self.channel.send({"type": "migrate_out", "req": req,
                               "uid": int(uid), "wire": wire})
        except ChannelError:
            self.transport_errors += 1
            self._send_failed = True
            with self._lock:
                self._migrate_cbs.pop(req, None)
            cb(None)

    def reload(self, cb: Callable[[Optional[Dict[str, Any]]], None],
               ckpt_dir: Optional[str] = None,
               seed: Optional[int] = None,
               timeout_s: Optional[float] = None) -> None:
        """Async weight hot-swap RPC: the worker validates the
        checkpoint manifest, reloads params, runs the canary prompt
        set, and replies ``reload_done`` (which invokes ``cb`` with the
        reply dict). ``cb(None)`` = channel death or timeout — the
        rolling-swap driver treats it like a failed parity gate."""
        with self._lock:
            req = self._next_req
            self._next_req += 1
            self._reload_cbs[req] = (
                cb, time.monotonic()
                + float(timeout_s or self._handoff_timeout_s))
        try:
            self.channel.send({"type": "reload", "req": req,
                               "ckpt_dir": ckpt_dir, "seed": seed})
        except ChannelError:
            self.transport_errors += 1
            self._send_failed = True
            with self._lock:
                self._reload_cbs.pop(req, None)
            cb(None)

    def transport_bytes(self) -> Tuple[int, int]:
        return (int(self.channel.bytes_sent),
                int(self.channel.bytes_received))

    def clock_info(self) -> Optional[Dict[str, Any]]:
        """The channel clock estimate (None with clock sync off)."""
        clk = getattr(self.channel, "clock", None)
        return clk.to_dict() if clk is not None else None

    def kill(self) -> None:
        self.killed = True

    def pump(self, eos_token_id=None) -> Dict[int, List[int]]:
        return {}  # the worker pumps itself

    def start(self, **kw) -> None:
        pass

    def stop(self) -> None:
        pass

    # -- receive path (supervisor rx thread) ---------------------------
    def handle_message(self, msg: Dict[str, Any]) -> None:
        kind = msg.get("type")
        if kind == "emit":
            rep = dict(msg.get("report") or self._report)
            clk = getattr(self.channel, "clock", None)
            if clk is not None and clk.synced and rep.get("ts"):
                # worker wall time -> supervisor wall time; the raw
                # stamp survives as ts_worker for cross-checks. With
                # clock sync off/unsynced the dict is untouched.
                rep["ts_worker"] = rep["ts"]
                rep["ts"] = clk.rebase(rep["ts"])
            with self._lock:
                self._report = rep
                self._report_ts = time.time()
                self._report_mono = time.monotonic()
            self.transport_errors = 0  # channel demonstrably works
            metrics = msg.get("metrics")
            if metrics and self.metrics_plane is not None:
                self.metrics_plane.ingest(self.name, metrics)
            geo = msg.get("geometry")
            if geo:
                self.engine.update_geometry(geo)
            traces = msg.get("traces")
            if traces:
                self.engine.ingest_traces(traces)
            emitted = {int(u): [int(t) for t in toks]
                       for u, toks in (msg.get("emitted") or {}).items()}
            if emitted and self.emit_callback is not None:
                self.emit_callback(self, emitted)
        elif kind == "handoff_payload":
            with self._lock:
                entry = self._handoff_cbs.pop(int(msg["req"]), None)
            if entry is not None:
                entry[0](decode_handoff(msg.get("handoff")))
        elif kind == "session_payload":
            with self._lock:
                entry = self._migrate_cbs.pop(int(msg["req"]), None)
            if entry is not None:
                entry[0](decode_session(msg.get("session")))
        elif kind == "reload_done":
            with self._lock:
                entry = self._reload_cbs.pop(int(msg["req"]), None)
            if entry is not None:
                entry[0](msg)
        elif kind == "exiting":
            self.exited = True

    def expire_handoffs(self, now: Optional[float] = None) -> int:
        """Time out serialize/migrate/reload RPCs whose worker died
        mid-reply: each orphaned continuation fires with None (the
        caller's documented degraded path — recompute for handoffs and
        migrations, swap-abort for reloads). ``now`` is monotonic.
        Returns how many expired."""
        now = time.monotonic() if now is None else now
        expired = []
        with self._lock:
            for cbs in (self._handoff_cbs, self._migrate_cbs,
                        self._reload_cbs):
                for req, (cb, deadline) in list(cbs.items()):
                    if now >= deadline:
                        expired.append(cb)
                        del cbs[req]
        for cb in expired:
            cb(None)
        return len(expired)


class ReplicaSupervisor:
    """Spawns, connects, restarts, and scales worker processes.

    Construction fixes the fleet-wide spec (model, engine keywords,
    channel kind, seed); :meth:`spawn` instantiates workers from it.
    Attach the router after building it from the spawned stubs —
    :meth:`maintain` needs it for add/remove and the autoscale signal.
    """

    def __init__(self, run_dir: str,
                 model: Optional[Dict[str, Any]] = None,
                 engine: Optional[Dict[str, Any]] = None,
                 channel: str = "socket",
                 seed: int = 0,
                 eos_token_id: Optional[int] = None,
                 heartbeat_s: float = 0.05,
                 max_frame_mb: int = 64,
                 connect_retries: int = 40,
                 connect_backoff_s: float = 0.05,
                 spawn_timeout_s: float = 60.0,
                 default_role: str = "unified",
                 python: Optional[str] = None,
                 connect_policy=None,
                 restart_policy=None,
                 max_restarts_per_window: int = 3,
                 restart_window_s: float = 30.0,
                 min_healthy: int = 1,
                 clock_sync: bool = True,
                 clock_sync_rounds: int = 8,
                 clock_resync_s: float = 5.0,
                 *, jax_platform: str,
                 tpu_chips: Sequence[int] = ()):
        """``jax_platform`` is the backend every worker must come up on
        ("cpu" | "tpu"); the caller names it — there is no default to
        fall back to. With "tpu", ``tpu_chips`` lists the host's chip
        indices this fleet may use: each live worker owns exactly one
        of them (the only chip its process can see), and this process
        itself stays off JAX, since a parent that holds the chips
        starves its children."""
        if jax_platform not in ("cpu", "tpu"):
            raise ValueError(
                f"jax_platform must be cpu|tpu, got {jax_platform!r}")
        if jax_platform == "tpu" and not tpu_chips:
            raise ValueError("jax_platform='tpu' needs tpu_chips: the "
                             "chip indices the workers may own")
        if channel not in ("socket", "file"):
            raise ValueError(
                f"channel must be socket|file, got {channel!r}")
        self.run_dir = run_dir
        self.model = dict(model or {"name": "tiny"})
        self.engine = dict(engine or {})
        self.channel_kind = channel
        self.seed = int(seed)
        self.eos_token_id = eos_token_id
        self.heartbeat_s = float(heartbeat_s)
        self.max_frame_mb = int(max_frame_mb)
        self.connect_retries = int(connect_retries)
        self.connect_backoff_s = float(connect_backoff_s)
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.default_role = default_role
        self.jax_platform = jax_platform
        self._free_chips: List[int] = [int(c) for c in tpu_chips]
        self._chip_of: Dict[int, int] = {}  # live tpu worker -> chip
        self.python = python or sys.executable
        self.router = None  # attach after building FleetRouter
        self.replicas: Dict[int, RemoteReplica] = {}
        self._procs: Dict[int, subprocess.Popen] = {}
        self._rx_threads: Dict[int, threading.Thread] = {}
        self._rx_stop: Dict[int, threading.Event] = {}
        self._next_id = 0
        # (ts, action, replica_id) —
        # spawn | restart | drain | quarantine | drain_refused
        self.actions: List[Tuple[float, str, int]] = []
        # crash-loop containment (see class docstring)
        from deepspeed_tpu.resilience.policy import RetryPolicy
        self.connect_policy = connect_policy
        if connect_policy is None and (
                int(connect_retries) != 40
                or float(connect_backoff_s) != 0.05):
            global _WARNED_LEGACY_CONNECT
            if not _WARNED_LEGACY_CONNECT:
                _WARNED_LEGACY_CONNECT = True
                import warnings
                warnings.warn(
                    "connect_retries/connect_backoff_s are legacy "
                    "aliases; pass connect_policy= (a resilience "
                    "RetryPolicy, e.g. RouterConfig."
                    "connect_retry_policy()) instead",
                    DeprecationWarning, stacklevel=2)
        # jitter=0: restart timing must be deterministic for the chaos
        # gates (and drift does nothing useful on a single host)
        self.restart_policy = restart_policy or RetryPolicy(
            max_retries=max(1, int(max_restarts_per_window)),
            backoff_base_s=0.25, backoff_max_s=5.0, jitter=0.0)
        self.max_restarts_per_window = int(max_restarts_per_window)
        self.restart_window_s = float(restart_window_s)
        self.min_healthy = max(1, int(min_healthy))
        # rid -> lineage id (the first spawn's rid, carried through
        # restarts so the breaker sees one crash-looping identity)
        self._lineage: Dict[int, int] = {}
        self._lineage_crashes: Dict[int, List[float]] = {}  # monotonic
        self.quarantined: set = set()  # lineage ids
        self._pending_restarts: List[Dict[str, Any]] = []
        # spawn-time knobs remembered so restarts reproduce the worker
        # (env carries e.g. the DSTPU_CHAOS spec of a chaos drill)
        self._env_extra: Dict[int, Dict[str, str]] = {}
        self._step_delay: Dict[int, float] = {}
        # fleet observability: per-channel clock sync + the transport-
        # borne metrics plane (no shared filesystem required)
        self.clock_sync = bool(clock_sync)
        self.clock_sync_rounds = max(1, int(clock_sync_rounds))
        self.clock_resync_s = float(clock_resync_s)
        from deepspeed_tpu.observability.fleet_metrics import \
            FleetMetricsPlane
        self.metrics_plane = FleetMetricsPlane(
            stale_after_s=max(1.0, 20.0 * self.heartbeat_s))
        for sub in ("specs", "ready", "logs", "spool", "replicas"):
            os.makedirs(os.path.join(run_dir, sub), exist_ok=True)

    # -- geometry defaults (valid before the first worker report) ------
    def _engine_geometry(self) -> Tuple[int, int, int]:
        block_size = int(self.engine.get("kv_block_size", 16))
        total = int(self.engine.get("kv_blocks", 256))
        max_per_seq = int(self.engine.get("max_blocks_per_seq",
                                          total))
        return block_size, total, max_per_seq

    # -- act log + black box -------------------------------------------
    def _act(self, action: str, replica_id: int,
             now: Optional[float] = None, **fields: Any) -> None:
        """One supervisor act: appended to the in-memory decision
        history (the fleet snapshot's ``supervisor.actions``) and, when
        the black box is recording, journaled as a SUPERVISOR decision
        with the state that triggered it."""
        now = wall_time() if now is None else now
        self.actions.append((now, action, replica_id))
        jr = get_journal()
        if jr is not None:
            jr.decision("SUPERVISOR", ts=now, action=action,
                        replica=replica_id, **fields)

    def _chip_env(self, rid: int) -> Dict[str, str]:
        """A tpu worker's view of the host: exactly one chip. libtpu
        reads these at start-up; each worker is a one-chip "slice" of
        its own with its own controller port, so workers neither see
        nor wait for one another."""
        if self.jax_platform != "tpu":
            return {}
        self._reap_chips()
        if not self._free_chips:
            raise RuntimeError(
                f"no free chip for replica {rid}: every one of this "
                "fleet's tpu_chips is owned by a live worker")
        chip = self._chip_of[rid] = self._free_chips.pop(0)
        port = 8476 + chip
        return {"TPU_VISIBLE_CHIPS": str(chip),
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1",
                "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
                "TPU_MESH_CONTROLLER_PORT": str(port)}

    def _reap_chips(self) -> None:
        """Return the chips of workers whose process has exited."""
        for rid, chip in list(self._chip_of.items()):
            proc = self._procs.get(rid)
            if proc is None or proc.poll() is not None:
                del self._chip_of[rid]
                self._free_chips.append(chip)

    # -- spawn ---------------------------------------------------------
    def spawn(self, role: Optional[str] = None,
              replica_id: Optional[int] = None,
              step_delay_ms: float = 0.0,
              env_extra: Optional[Dict[str, str]] = None,
              action: str = "spawn",
              lineage: Optional[int] = None) -> RemoteReplica:
        rid = self._next_id if replica_id is None else int(replica_id)
        self._next_id = max(self._next_id, rid + 1)
        role = role or self.default_role
        self._lineage[rid] = rid if lineage is None else int(lineage)
        self._env_extra[rid] = dict(env_extra or {})
        self._step_delay[rid] = float(step_delay_ms)
        spool = os.path.join(self.run_dir, "spool", f"replica_{rid}")
        ready = os.path.join(self.run_dir, "ready",
                             f"replica_{rid}.json")
        if os.path.exists(ready):
            os.unlink(ready)
        spec = {
            "replica_id": rid, "role": role, "run_dir": self.run_dir,
            "ready_path": ready, "channel": self.channel_kind,
            "spool_dir": spool, "max_frame_mb": self.max_frame_mb,
            "model": self.model, "engine": self.engine,
            "seed": self.seed, "eos_token_id": self.eos_token_id,
            "step_delay_ms": float(step_delay_ms),
            "heartbeat_s": self.heartbeat_s,
            "jax_platform": self.jax_platform,
        }
        spec_path = os.path.join(self.run_dir, "specs",
                                 f"replica_{rid}.json")
        _atomic_write_json(spec_path, spec)
        env = dict(os.environ)
        env.update(env_extra or {})
        env.update(self._chip_env(rid))
        log_path = os.path.join(self.run_dir, "logs",
                                f"replica_{rid}.log")
        log = open(log_path, "ab")
        proc = subprocess.Popen(
            [self.python, "-m", "deepspeed_tpu.serving.proc_worker",
             spec_path],
            stdout=log, stderr=subprocess.STDOUT, env=env)
        log.close()
        try:
            chan = self._connect(proc, ready, spool, rid)
        except Exception:
            proc.kill()
            raise
        if self.clock_sync:
            from deepspeed_tpu.observability.clocksync import \
                ClockSyncEstimator
            chan.clock = ClockSyncEstimator()
        bs, total, mps = self._engine_geometry()
        remote = RemoteReplica(rid, role, chan, bs, total, mps)
        remote.metrics_plane = self.metrics_plane
        self.replicas[rid] = remote
        self._procs[rid] = proc
        self._start_rx(remote)
        if self.clock_sync:
            # initial burst: the estimator is synced (min_samples) well
            # before the first routed request; pongs land on the rx
            # thread just started above
            for _ in range(self.clock_sync_rounds):
                try:
                    chan.ping_clock()
                except ChannelError:
                    break
        self._act(action, rid, role=role,
                  lineage=self._lineage.get(rid, rid))
        return remote

    def _connect(self, proc: subprocess.Popen, ready_path: str,
                 spool: str, rid: int):
        deadline = time.monotonic() + self.spawn_timeout_s
        while not os.path.exists(ready_path):
            if proc.poll() is not None:
                raise ChannelError(
                    f"worker exited with {proc.returncode} before "
                    f"publishing its ready file (see logs/)")
            if time.monotonic() >= deadline:
                raise ChannelError(
                    f"worker not ready within {self.spawn_timeout_s}s")
            time.sleep(0.01)
        with open(ready_path) as f:
            ready = json.load(f)
        max_frame = self.max_frame_mb << 20
        if ready.get("channel") == "socket":
            return connect_with_backoff(
                "127.0.0.1", int(ready["port"]),
                retries=self.connect_retries,
                backoff_s=self.connect_backoff_s,
                max_frame_bytes=max_frame,
                policy=self.connect_policy, peer_id=rid)
        return FileChannel(spool, side="a", max_frame_bytes=max_frame,
                           peer_id=rid)

    def _start_rx(self, remote: RemoteReplica) -> None:
        stop = threading.Event()

        def _loop():
            while not stop.is_set():
                try:
                    msg = remote.channel.recv(timeout=0.1)
                except ChannelError:
                    remote.transport_errors += 1
                    remote._send_failed = True
                    return
                if msg is not None:
                    remote.handle_message(msg)

        t = threading.Thread(target=_loop, daemon=True,
                             name=f"rx-{remote.name}")
        t.start()
        self._rx_threads[remote.replica_id] = t
        self._rx_stop[remote.replica_id] = stop

    # -- lifecycle -----------------------------------------------------
    def _live_ids(self) -> List[int]:
        return [rid for rid, r in self.replicas.items()
                if not r.draining and not r.exited
                and self._procs[rid].poll() is None]

    def maintain(self, now: Optional[float] = None) -> Dict[str, int]:
        """One supervision round: contain crashes (failover now,
        restart after backoff, quarantine a crash-looper), act on the
        autoscale signal, expire orphaned handoff RPCs, refresh the
        merged fleet snapshot. Call it from the serving loop at
        health-check cadence. ``now`` (wall clock) stamps the decision
        history only — scheduling runs on the monotonic clock. Returns
        counts of the actions taken."""
        now = wall_time() if now is None else now
        mono = time.monotonic()
        acted = {"restarted": 0, "spawned": 0, "drained": 0,
                 "quarantined": 0, "handoffs_expired": 0}
        autoscale = getattr(self.router, "autoscale", None) \
            if self.router is not None else None

        if self.clock_sync:
            # periodic re-sync: drift and NTP steps on the worker side
            # show up within one resync period, not at the next spawn
            for remote in self.replicas.values():
                clk = getattr(remote.channel, "clock", None)
                if (clk is None or remote._send_failed
                        or remote.draining or remote.exited):
                    continue
                if mono - clk.last_sync_mono >= self.clock_resync_s:
                    try:
                        remote.channel.ping_clock()
                    except ChannelError:
                        remote.transport_errors += 1
                        remote._send_failed = True

        for rid in list(self.replicas):
            remote = self.replicas[rid]
            proc = self._procs[rid]
            if proc.poll() is None:
                acted["handoffs_expired"] += remote.expire_handoffs(mono)
                continue
            if remote.draining or remote.exited:
                continue  # asked to leave; clean exit, nothing to heal
            # crash: fail the stub now (fast failover) — the dead id
            # stays dead, its in-flight work is the router's resubmit
            # problem, not the replacement's
            remote._send_failed = True
            remote.draining = True
            if self.router is not None:
                self.router.check_health()  # declares rid dead
            lineage = self._lineage.get(rid, rid)
            crashes = self._lineage_crashes.setdefault(lineage, [])
            crashes.append(mono)
            crashes[:] = [t for t in crashes
                          if mono - t <= self.restart_window_s]
            attempt = len(crashes)
            if attempt > self.max_restarts_per_window:
                # circuit breaker: this lineage crashes faster than it
                # serves — stop feeding it restarts; the autoscale
                # desired-vs-live path owns replacing its capacity
                if lineage not in self.quarantined:
                    self.quarantined.add(lineage)
                    self._act("quarantine", rid, now, lineage=lineage,
                              crashes_in_window=attempt,
                              window_s=self.restart_window_s)
                    if autoscale is not None:
                        autoscale.record_action("quarantine", rid, now)
                    acted["quarantined"] += 1
                continue
            # first crash restarts immediately (the pre-breaker
            # behavior); repeats back off exponentially
            delay = (0.0 if attempt <= 1
                     else self.restart_policy.backoff_s(attempt - 1))
            self._pending_restarts.append({
                "due_mono": mono + delay, "role": remote.role,
                "lineage": lineage,
                "env": self._env_extra.get(rid) or None,
                "step_delay_ms": self._step_delay.get(rid, 0.0)})

        still_pending = []
        for plan in self._pending_restarts:
            if plan["due_mono"] > time.monotonic():
                still_pending.append(plan)
                continue
            replacement = self.spawn(
                role=plan["role"], action="restart",
                env_extra=plan["env"],
                step_delay_ms=plan["step_delay_ms"],
                lineage=plan["lineage"])
            if self.router is not None:
                self.router.add_replica(replacement)
            if autoscale is not None:
                autoscale.record_action("restart",
                                        replacement.replica_id, now)
            acted["restarted"] += 1
        self._pending_restarts = still_pending

        if autoscale is not None and autoscale.desired is not None:
            live = self._live_ids()
            if autoscale.desired > len(live):
                replacement = self.spawn(action="spawn")
                self.router.add_replica(replacement)
                autoscale.record_action("spawn",
                                        replacement.replica_id, now,
                                        live=len(live) + 1,
                                        direction="up")
                acted["spawned"] += 1
            elif autoscale.desired < len(live) and len(live) > 1:
                victim = self.replicas[max(live)]
                # migration-backed scale-down: the victim's live
                # sessions move warm before the worker drains
                if self.drain(victim.replica_id, reason="scale_down"):
                    st = getattr(self.router, "stats", {})
                    autoscale.record_action(
                        "drain", victim.replica_id, now,
                        live=len(live) - 1, direction="down",
                        migrations=int(st.get("migrations", 0)))
                    acted["drained"] += 1
        self.write_fleet_snapshot()
        return acted

    def drain(self, replica_id: int, migrate: bool = True,
              reason: str = "drain") -> bool:
        """Graceful scale-down: no new admissions, live sessions
        migrate out warm (when the router supports it), the worker
        finishes whatever could not move and exits 0. Refuses (returns
        False, with a ``drain_refused`` act recorded) when draining
        would leave the fleet below its ``min_healthy`` floor.

        Ordering is what makes this zero-drop: remove_replica stops new
        admissions first, migrate_sessions then sends the capture RPCs,
        and the ``drain`` flag goes on the SAME channel afterwards —
        FIFO means the worker processes every capture while still
        serving, and any session the migration ladder left behind is
        simply finished in place before the clean exit."""
        live = len(self._live_ids())
        if live - 1 < self.min_healthy:
            self._act("drain_refused", replica_id, live=live,
                      min_healthy=self.min_healthy)
            return False
        remote = self.replicas[replica_id]
        remote.draining = True
        migrated: Dict[str, int] = {}
        if self.router is not None:
            self.router.remove_replica(replica_id)
            if migrate and hasattr(self.router, "migrate_sessions"):
                migrated = self.router.migrate_sessions(
                    replica_id, reason=reason)
        try:
            remote.channel.send({"type": "drain"})
        except ChannelError:
            remote.transport_errors += 1
            remote._send_failed = True
        self._act("drain", replica_id, **(
            {"migrate": migrated} if migrated else {}))
        return True

    def kill(self, replica_id: int,
             sig: int = signal.SIGKILL) -> None:
        """Hard-kill a worker (chaos drills / tests)."""
        proc = self._procs.get(replica_id)
        if proc is not None and proc.poll() is None:
            proc.send_signal(sig)

    def run_until_drained(self, timeout_s: float = 120.0,
                          poll_s: float = 0.02) -> None:
        """Drive the attached router to completion with supervision:
        the process-fleet analog of ``FleetRouter.drain``."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            self.maintain()
            self.router.check_health()
            if self.router.pending() == 0:
                return
            time.sleep(poll_s)
        raise TimeoutError(
            f"process fleet did not drain in {timeout_s}s "
            f"({self.router.pending()} requests pending)")

    # -- rolling weight hot-swap (ISSUE 20) ----------------------------
    def compute_canary_chains(self, prompts: List[List[int]],
                              gen: int = 8,
                              seed: Optional[int] = None
                              ) -> Dict[str, List[int]]:
        """Expected A/B-parity chains for a canary prompt set: build a
        throwaway replica from the SAME model+engine spec the workers
        use (engine config affects numerics, so it must match), decode
        the canaries greedily, and checksum-chain the streams. The
        publisher bakes these into weights.json; each swapped worker
        must reproduce them before it rejoins."""
        import numpy as np  # noqa: F811 (module-level alias)

        from deepspeed_tpu.observability.journal import chain_tokens
        from deepspeed_tpu.serving.proc_worker import build_replica

        if self.jax_platform == "tpu":
            # building a replica here would take the chips from the
            # workers this process supervises
            raise RuntimeError(
                "the supervisor of tpu workers stays off JAX: pass "
                "canary_chains (decoded by a worker or an earlier "
                "release) instead of computing them in this process")
        rep = build_replica({"replica_id": 9_999, "role": "unified",
                             "model": self.model, "engine": self.engine,
                             "seed": int(self.seed if seed is None
                                         else seed)})
        eng = rep.engine
        uids = [3_000_000 + i for i in range(len(prompts))]
        eng.put(uids, [np.asarray(p, np.int32) for p in prompts],
                max_new_tokens=int(gen))
        out = eng.generate_all(eos_token_id=self.eos_token_id)
        return {str(i): chain_tokens(out.get(uid, []))
                for i, uid in enumerate(uids)}

    def publish_weights(self, tag: str,
                        seed: Optional[int] = None,
                        canary_prompts: Optional[List[List[int]]] = None,
                        canary_gen: int = 8,
                        canary_chains: Optional[Dict[str, List[int]]]
                        = None) -> str:
        """Publish a weight release the fleet can roll onto:
        ``<run_dir>/weights/<tag>/weights.json`` (seed + canary prompt
        set + expected token chains) sealed by a checksum manifest
        (resilience/manifest.py — a torn or tampered release fails
        validation before any worker touches it). ``canary_chains``
        overrides the computed expectation — tests use it to publish a
        release whose parity gate MUST fail. Returns the release dir."""
        ckpt_dir = os.path.join(self.run_dir, "weights", str(tag))
        os.makedirs(ckpt_dir, exist_ok=True)
        seed = int(self.seed if seed is None else seed)
        canary: Dict[str, Any] = {}
        if canary_prompts:
            if canary_chains is None:
                canary_chains = self.compute_canary_chains(
                    canary_prompts, gen=canary_gen, seed=seed)
            canary = {"prompts": [[int(t) for t in p]
                                  for p in canary_prompts],
                      "gen": int(canary_gen),
                      "chains": {str(k): [int(c) for c in v]
                                 for k, v in canary_chains.items()}}
        _atomic_write_json(os.path.join(ckpt_dir, "weights.json"),
                           {"tag": str(tag), "seed": seed,
                            "canary": canary})
        from deepspeed_tpu.resilience.manifest import write_manifest

        write_manifest(ckpt_dir, str(tag))
        self._act("publish", -1, tag=str(tag), seed=seed,
                  canaries=len(canary_prompts or []))
        return ckpt_dir

    def _reload_sync(self, remote: RemoteReplica,
                     ckpt_dir: Optional[str], seed: Optional[int],
                     timeout_s: float) -> Optional[Dict[str, Any]]:
        """Blocking wrapper over the async reload RPC (None = channel
        death or timeout)."""
        box: Dict[str, Any] = {}
        ev = threading.Event()

        def _cb(reply):
            box["reply"] = reply
            ev.set()

        remote.reload(_cb, ckpt_dir=ckpt_dir, seed=seed,
                      timeout_s=timeout_s)
        ev.wait(timeout_s + 5.0)
        return box.get("reply")

    def _quiesce(self, remote: RemoteReplica, timeout_s: float) -> bool:
        """Wait for a router-removed replica to go empty (live sessions
        migrated or finished, queue drained)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            r = remote.load_report()
            if int(r.get("inflight", 0)) == 0:
                return True
            if remote._send_failed:
                return False
            time.sleep(0.01)
        return False

    def rolling_swap(self, tag: str,
                     timeout_s: float = 60.0) -> Dict[str, Any]:
        """Zero-downtime weight rollout, replica by replica: quiesce
        (admissions off + live sessions migrate out warm) -> reload the
        manifest-validated release -> A/B token-parity gate on the
        published canary chains -> rejoin. A parity failure (or reload
        error / timeout) ABORTS the rollout: the failing replica rolls
        back to the running weights and rejoins, and no further replica
        is touched. The ``min_healthy`` floor is respected throughout —
        at most one replica is ever out of the fleet.

        Only after EVERY replica swaps does ``self.seed`` advance, so
        crash restarts spawn with the new weights; an aborted rollout
        leaves restarts on the old ones — the fleet stays coherent
        either way."""
        from deepspeed_tpu.resilience.manifest import (
            CheckpointCorruptError, validate_manifest)

        jr = get_journal()
        result: Dict[str, Any] = {"tag": str(tag), "swapped": 0,
                                  "rolled_back": 0, "refused": 0,
                                  "aborted": False, "parity_ok": True,
                                  "error": None}

        def _swap_rec(stage: str, rid: int, **fields: Any) -> None:
            if jr is not None:
                jr.decision("SWAP", ts=wall_time(), tag=str(tag),
                            replica=rid, stage=stage, **fields)

        ckpt_dir = os.path.join(self.run_dir, "weights", str(tag))
        try:
            # supervisor-side gate: a torn/tampered release aborts the
            # rollout before any replica is touched
            validate_manifest(ckpt_dir)
            with open(os.path.join(ckpt_dir, "weights.json")) as f:
                wdoc = json.load(f)
        except (CheckpointCorruptError, OSError, ValueError) as exc:
            result["aborted"] = True
            result["error"] = f"{type(exc).__name__}: {exc}"
            _swap_rec("manifest", -1, ok=False, error=result["error"])
            self._act("swap_abort", -1, tag=str(tag),
                      error=result["error"])
            return result
        expected = {str(k): [int(c) for c in v] for k, v in
                    ((wdoc.get("canary") or {}).get("chains")
                     or {}).items()}
        new_seed = int(wdoc.get("seed", self.seed))
        _swap_rec("manifest", -1, ok=True, seed=new_seed,
                  canaries=len(expected))

        for rid in sorted(self._live_ids()):
            remote = self.replicas.get(rid)
            if remote is None or remote.draining or remote.exited:
                continue
            live = len(self._live_ids())
            if live - 1 < self.min_healthy:
                result["refused"] += 1
                result["aborted"] = True
                self._act("swap_refused", rid, live=live,
                          min_healthy=self.min_healthy)
                _swap_rec("quiesce", rid, ok=False,
                          reason="min_healthy")
                break
            # quiesce: admissions off, live sessions migrate out warm
            self._act("swap_quiesce", rid, tag=str(tag))
            migrated: Dict[str, int] = {}
            if self.router is not None:
                self.router.remove_replica(rid)
                if hasattr(self.router, "migrate_sessions"):
                    migrated = self.router.migrate_sessions(
                        rid, reason="swap")
            quiet = self._quiesce(remote, timeout_s)
            _swap_rec("quiesce", rid, ok=quiet, migrate=migrated)
            reply = self._reload_sync(remote, ckpt_dir, None, timeout_s)
            if reply is None or not reply.get("ok"):
                # reload failed (corrupt release seen worker-side,
                # channel death, timeout): abort + roll this replica
                # back to the running weights before it rejoins
                err = None if reply is None else reply.get("error")
                _swap_rec("reload", rid, ok=False, error=err)
                result["aborted"] = True
                result["error"] = err or "reload timeout"
                if reply is not None:
                    rb = self._reload_sync(remote, None, self.seed,
                                           timeout_s)
                    if rb is not None and rb.get("ok"):
                        result["rolled_back"] += 1
                        if self.router is not None:
                            self.router.add_replica(remote)
                        self._act("swap_rollback", rid, tag=str(tag))
                else:
                    remote._send_failed = True  # crash containment
                break
            measured = {str(k): [int(c) for c in v] for k, v in
                        (reply.get("canary_chains") or {}).items()}
            parity = measured == expected
            divergent = sorted(k for k in expected
                               if measured.get(k) != expected[k])
            _swap_rec("parity", rid, ok=parity,
                      canaries=len(expected),
                      divergent=divergent[:8])
            if not parity:
                # THE gate: the new weights do not reproduce the
                # published canary streams on this replica — abort the
                # rollout and put the old weights back before rejoin
                result["aborted"] = True
                result["parity_ok"] = False
                result["error"] = (f"canary parity failed on r{rid}: "
                                   f"canaries {divergent[:8]} diverged")
                rb = self._reload_sync(remote, None, self.seed,
                                       timeout_s)
                if rb is not None and rb.get("ok"):
                    result["rolled_back"] += 1
                    if self.router is not None:
                        self.router.add_replica(remote)
                    self._act("swap_rollback", rid, tag=str(tag),
                              divergent=divergent[:8])
                else:
                    remote._send_failed = True
                break
            if self.router is not None:
                self.router.add_replica(remote)
            result["swapped"] += 1
            self._act("swap", rid, tag=str(tag))
            _swap_rec("done", rid, ok=True)

        if not result["aborted"] and result["swapped"] > 0:
            self.seed = new_seed  # restarts now reproduce the release
        _swap_rec("rollout", -1, ok=not result["aborted"],
                  swapped=result["swapped"],
                  rolled_back=result["rolled_back"])
        self._act("swap_done" if not result["aborted"]
                  else "swap_abort", -1, tag=str(tag),
                  swapped=result["swapped"],
                  rolled_back=result["rolled_back"])
        return result

    def shutdown(self, timeout_s: float = 10.0) -> None:
        """SIGTERM everyone, wait, SIGKILL stragglers, stop rx threads."""
        for rid, proc in self._procs.items():
            if proc.poll() is None:
                proc.terminate()
        deadline = time.time() + timeout_s
        for proc in self._procs.values():
            left = max(deadline - time.time(), 0.1)
            try:
                proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5.0)
        for stop in self._rx_stop.values():
            stop.set()
        for t in self._rx_threads.values():
            t.join(timeout=2.0)
        for r in self.replicas.values():
            try:
                r.channel.close()
            except Exception:
                pass

    # -- fleet snapshot (serve_top --fleet) ----------------------------
    def write_fleet_snapshot(self) -> str:
        """Merge channel-side fleet state into one document the
        cross-process ``serve_top --fleet`` can read without importing
        jax or joining any socket."""
        path = os.path.join(self.run_dir, "fleet_snapshot.json")
        if self.router is not None:
            snap = self.router.fleet_snapshot()
        else:
            snap = {"schema": "serving_fleet/v3", "ts": wall_time(),
                    "replicas": [r.load_report()
                                 for r in self.replicas.values()]}
            jr = get_journal()
            if jr is not None:
                snap["journal"] = jr.snapshot()
        snap["supervisor"] = {
            "actions": [{"ts": ts, "action": act, "replica": rid}
                        for ts, act, rid in self.actions[-64:]],
            "restarts": sum(1 for _, act, _r in self.actions
                            if act == "restart"),
            "quarantined": sorted(self.quarantined),
            "pending_restarts": len(self._pending_restarts),
            "min_healthy": self.min_healthy,
            "procs": {str(rid): {
                "pid": p.pid,
                "running": p.poll() is None,
                "returncode": p.poll(),
            } for rid, p in self._procs.items()},
            "transport": {str(rid): {
                "tx_bytes": r.channel.bytes_sent,
                "rx_bytes": r.channel.bytes_received,
                "transport_errors": r.transport_errors,
                "dup_frames": getattr(r.channel, "dup_frames", 0),
            } for rid, r in self.replicas.items()},
        }
        if self.clock_sync:
            snap["clock"] = {
                str(rid): info for rid, r in self.replicas.items()
                if (info := r.clock_info()) is not None}
        if self.metrics_plane.ingested:
            # the transport-borne metrics plane: per-worker hub values
            # merged with no shared run dir (workers may be remote)
            snap["fleet_metrics"] = self.metrics_plane.merged()
        _atomic_write_json(path, snap)
        return path

"""Serving replica: one engine_v2 instance wrapped for fleet duty.

A replica owns exactly one :class:`InferenceEngineV2` and adds what the
router needs to treat N of them as a fleet:

* a **role** — ``unified`` (prefill + decode), ``prefill``, or
  ``decode`` (the disaggregated pools, serving/disagg.py);
* an **inbox** of submissions, so every engine mutation happens on the
  replica's own pump thread (the engine is single-threaded by design;
  the inbox is the concurrency boundary);
* a **heartbeat** updated on every pump and a **load report** (queue
  depth, KV-pool pressure, in-flight sequences, goodput EWMA) — the
  router's routing and stale-heartbeat failover inputs, optionally
  published through the PR 3 fleet machinery
  (``observability/fleet.py`` ``ReplicaPublisher``) for external
  ``serve_top --fleet`` consumers;
* ``kill()`` — a simulated crash for failover tests and drills: the
  pump stops mid-flight *without* draining, the heartbeat goes stale,
  and the router's health check must recover the in-flight requests.

The engine is constructed with ``metric_labels={"replica": "rN"}`` so
every ``serve.*`` hub series carries the replica id — fleet dashboards
aggregate across labels instead of collapsing N replicas into one line.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from deepspeed_tpu.observability.clocksync import wall_time

ROLES = ("unified", "prefill", "decode")


@dataclasses.dataclass
class Submission:
    """One routed request on its way into a replica's engine. Applied
    on the pump thread: install the handoff payload (if any), ``put``,
    then record the routing span notes on the replica's tracer."""

    uid: int
    tokens: np.ndarray
    max_new_tokens: int
    span_notes: List[Tuple[str, Dict[str, Any]]] = \
        dataclasses.field(default_factory=list)
    handoff: Optional[Any] = None  # disagg.KVHandoff
    # disagg.SessionHandoff — a live-migrated mid-stream session. When
    # set, install replaces put(): the migrated KV blocks, generated
    # tokens, and spec EWMA land through install_session and decode
    # resumes warm (zero re-prefill). ``tokens``/``max_new_tokens``
    # then describe the RECOMPUTE fallback the installer degrades to
    # if the payload can't land (pool full, geometry mismatch, ...).
    session: Optional[Any] = None


@dataclasses.dataclass
class _MigrateOut:
    """Inbox marker: capture+release session ``uid`` on the pump thread
    (the only thread allowed to touch the engine) and hand the
    SessionHandoff — or None if the session is gone — to ``cb``."""

    uid: int
    cb: Callable[[Optional[Any]], None]
    wire: Optional[str] = None


class ServingReplica:
    def __init__(self, engine, replica_id: int, role: str = "unified",
                 publisher=None, goodput_alpha: float = 0.25):
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        self.engine = engine
        self.replica_id = int(replica_id)
        self.name = f"r{self.replica_id}"
        self.role = role
        self.publisher = publisher
        self.inbox: "queue.Queue[Submission]" = queue.Queue()
        # router wires this to its emission handler; called on the pump
        # thread with (replica, {uid: [tokens]}) after each serve round
        self.emit_callback: Optional[Callable] = None
        # load_report ts: this process's wall clock (skew-aware, so a
        # cross-process supervisor can rebase it like any other stamp)
        self.last_heartbeat = wall_time()
        self.last_heartbeat_mono = time.monotonic()  # liveness decisions
        self.transport_errors = 0  # in-process replicas have no wire
        self.killed = False
        self.steps = 0
        self.goodput_ewma = 0.0
        self._alpha = float(goodput_alpha)
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    @classmethod
    def create(cls, model, replica_id: int, role: str = "unified",
               run_dir: Optional[str] = None, device=None, **engine_kw
               ) -> "ServingReplica":
        """Build the replica AND its engine, injecting the per-replica
        metric labels and (when a run dir is given) the fleet-layer
        load-report publisher. ``device`` is the one device this replica
        owns (its engine runs on a one-device mesh over it); a replica
        that spans devices passes ``mesh`` in ``engine_kw`` instead.
        With neither, the engine's own default applies — a mesh over
        every device of the process — which is right only for a process
        that hosts a single replica."""
        from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

        if device is not None:
            if engine_kw.get("mesh") is not None:
                raise ValueError("pass a replica its device or its mesh, "
                                 "not both")
            from deepspeed_tpu.parallel.topology import (TopologyConfig,
                                                         build_mesh)

            engine_kw["mesh"] = build_mesh(TopologyConfig(),
                                           devices=[device])
        engine_kw.setdefault("metric_labels",
                             {"replica": f"r{int(replica_id)}"})
        engine = InferenceEngineV2(model, **engine_kw)
        publisher = None
        if run_dir:
            from deepspeed_tpu.observability.fleet import ReplicaPublisher

            publisher = ReplicaPublisher(run_dir, replica_id)
        return cls(engine, replica_id, role=role, publisher=publisher)

    # -- liveness ------------------------------------------------------
    def heartbeat_age(self, now: Optional[float] = None) -> float:
        """Seconds since the last pump, on the *monotonic* clock — a
        stepped wall clock (NTP slew, manual reset) must never make a
        healthy replica look dead. ``now``, when given, is a
        ``time.monotonic()`` timestamp."""
        now = time.monotonic() if now is None else now
        return now - self.last_heartbeat_mono

    def alive(self, now: Optional[float] = None,
              stale_after: float = 5.0) -> bool:
        """Stale-heartbeat liveness — the same contract as the fleet
        aggregator's dead-rank detection: a killed replica is not dead
        until its heartbeat *ages out*, which is exactly what a real
        crashed process looks like to a router that can only observe
        published state. ``now`` is monotonic (see heartbeat_age)."""
        return self.heartbeat_age(now) < stale_after

    def kill(self) -> None:
        """Simulated crash: stop pumping (and heartbeating) immediately,
        leaving the inbox and the engine's in-flight sequences wedged —
        recovery is entirely the router's failover problem."""
        self.killed = True
        self._stop.set()

    # -- the serve round ----------------------------------------------
    def pump(self, eos_token_id: Optional[int] = None
             ) -> Dict[int, List[int]]:
        """One serve round: drain the inbox into the engine, run one
        ``serve_step``, heartbeat, and hand emissions to the router.
        The ONLY code path that touches the engine — callers on other
        threads go through :meth:`submit`."""
        if self.killed:
            return {}
        t0 = time.perf_counter()
        while True:
            try:
                sub = self.inbox.get_nowait()
            except queue.Empty:
                break
            if isinstance(sub, _MigrateOut):
                self._migrate_out(sub)
            else:
                self._apply(sub)
        busy = bool(self.engine.state.seqs) or bool(self.engine._queue)
        emitted = self.engine.serve_step(eos_token_id=eos_token_id) \
            if busy else self.engine.take_undelivered()
        self.steps += 1
        now = wall_time()
        self.last_heartbeat = now
        self.last_heartbeat_mono = time.monotonic()
        dt = max(time.perf_counter() - t0, 1e-9)
        rate = sum(len(v) for v in emitted.values()) / dt
        self.goodput_ewma = (self._alpha * rate
                             + (1.0 - self._alpha) * self.goodput_ewma)
        if self.publisher is not None:
            self.publisher.publish(self.load_report(now))
        if emitted and self.emit_callback is not None:
            self.emit_callback(self, emitted)
        return emitted

    def _apply(self, sub: Submission) -> None:
        if sub.session is not None:
            # live migration install: the payload carries the session's
            # KV blocks + descriptor state, so install replaces put()
            # entirely — install_session enqueues/admits internally and
            # degrades (paged / recompute) on its own when the warm
            # path can't land, using the folded tokens in the payload.
            from deepspeed_tpu.serving.disagg import install_session

            rung = install_session(self.engine, sub.session)
            sub.span_notes.append(("MIGRATE", {
                "stage": "install", "rung": rung,
                "blocks": sub.session.n_blocks
                if sub.session.block_data is not None else 0}))
            for kind, fields in sub.span_notes:
                fields.setdefault("replica_id", self.replica_id)
                self.engine.tracer.note(sub.uid, kind, **fields)
            return
        if sub.handoff is not None:
            from deepspeed_tpu.serving.disagg import install_prefix

            blocks, tokens = install_prefix(self.engine, sub.handoff)
            # tokens>0 with blocks==0 means the chain was already
            # installed here by an earlier handoff — still the KV path
            sub.span_notes.append(("HANDOFF", {
                "blocks": blocks, "tokens": tokens,
                "mode": "kv_blocks" if tokens else "recompute"}))
        self.engine.put([sub.uid], [sub.tokens],
                        max_new_tokens=sub.max_new_tokens)
        for kind, fields in sub.span_notes:
            # stamp which replica actually applied the span: routers and
            # supervisors attach notes from their own process, and the
            # cross-process trace merge needs the executing replica id
            fields.setdefault("replica_id", self.replica_id)
            self.engine.tracer.note(sub.uid, kind, **fields)

    def submit(self, sub: Submission) -> None:
        self.inbox.put(sub)

    def serialize_handoff(self, tokens: np.ndarray,
                          cb: Callable[[Optional[Any]], None]) -> None:
        """Serialize this replica's KV prefix for ``tokens`` and hand
        the payload to ``cb`` (None = degrade to recompute). Local
        replicas run it synchronously — _handoff is called on THIS
        replica's pump thread, so reading its KV pool here is race-free,
        the pre-transport semantics. RemoteReplica overrides this with a
        serialize RPC whose reply invokes ``cb`` later."""
        from deepspeed_tpu.serving.disagg import serialize_prefix

        cb(serialize_prefix(self.engine, tokens))

    def migrate_out(self, uid: int,
                    cb: Callable[[Optional[Any]], None],
                    wire: Optional[str] = None) -> None:
        """Capture session ``uid``'s full decode state (committed KV
        blocks, partial tail block, generated tokens, spec EWMA) as a
        SessionHandoff, release it here, and hand the payload to ``cb``
        (None = session gone or un-capturable; the caller degrades to
        fold-and-resubmit recompute). The capture is enqueued as an
        inbox marker so it runs on the pump thread — the engine is
        single-threaded, and migrate-out both reads the KV pool and
        mutates sequence state. A killed replica never pumps, so its
        callbacks never fire; callers must pair this with the same
        stale-heartbeat failover that covers ordinary requests.
        RemoteReplica overrides with a migrate RPC (deadline-expired)."""
        self.inbox.put(_MigrateOut(uid=int(uid), cb=cb, wire=wire))

    def _migrate_out(self, mo: "_MigrateOut") -> None:
        """Pump-thread half of migrate_out."""
        from deepspeed_tpu.serving.disagg import serialize_session

        try:
            sess = serialize_session(self.engine, mo.uid, wire=mo.wire)
        except Exception:
            sess = None  # degrade, never wedge the pump
        # the capture read the engine's burst in flight first: its tokens
        # reach the router before the session changes hands (a later
        # emission of this replica's would be dropped as stale)
        unread = self.engine.take_undelivered()
        if unread and self.emit_callback is not None:
            self.emit_callback(self, unread)
        mo.cb(sess)

    # -- load report ---------------------------------------------------
    def load_report(self, now: Optional[float] = None) -> Dict[str, Any]:
        e = self.engine
        live = [s for s in e.state.seqs.values() if not s.done]
        total = e.kv_cache.allocator.total_blocks
        free = e.kv_cache.free_blocks
        tier = getattr(e.kv_cache, "host_tier", None)
        return {
            "replica": self.replica_id,
            "role": self.role,
            "ts": self.last_heartbeat if now is None else now,
            "steps": self.steps,
            "queue_wait_depth": len(e._queue),
            "live_seqs": len(live),
            "inflight": len(live) + len(e._queue) + self.inbox.qsize(),
            "kv_free_blocks": free,
            "kv_free_frac": free / max(1, total),
            "goodput_tokens_per_s": round(self.goodput_ewma, 3),
            "killed": self.killed,
            # serving-quant data plane (ISSUE 12): pool storage mode,
            # handoff codec, cumulative wire-vs-logical handoff bytes,
            # and the last measured wire SNR (None until a quantized
            # handoff leaves/enters this replica)
            "kv_quant_bits": getattr(e.kv_cache, "quant_bits", None),
            "handoff_wire": getattr(e, "_handoff_wire", "auto"),
            "handoff_wire_bytes": getattr(e, "_handoff_wire_bytes", 0),
            "handoff_logical_bytes": getattr(
                e, "_handoff_logical_bytes", 0),
            "kv_wire_snr_db": getattr(e, "_last_kv_wire_snr_db", None),
            # adaptive speculation + host KV tier (ISSUE 17): measured
            # acceptance EWMA + rejected-verify-row count drive the
            # per-request draft-length controller; the host-tier gauges
            # show how much session state lives below HBM (and
            # paged_out/paged_in how often decode warm-resumes)
            "spec_accept_ewma": getattr(e, "_spec_accept_ewma", None),
            "spec_wasted_verify_tokens": getattr(
                e, "_spec_wasted_verify_tokens", 0),
            "host_tier_bytes": (0 if tier is None else tier.used_bytes),
            "host_tier_blocks": (0 if tier is None else tier.total_blocks),
            "host_tier_sessions": (0 if tier is None
                                   else tier.session_count),
            "paged_out": e.stats.get("paged_out", 0),
            "paged_in": e.stats.get("paged_in", 0),
            # live migration (ISSUE 20): warm sessions shipped out/in
            # plus the degradation-ladder counters (host-tier page-out,
            # legacy recompute) — the drill's "zero cold resumes" gate
            # reads these across the fleet
            "migrated_out": e.stats.get("migrated_out", 0),
            "migrated_in": e.stats.get("migrated_in", 0),
            "migrate_paged": e.stats.get("migrate_paged", 0),
            "migrate_recompute": e.stats.get("migrate_recompute", 0),
        }

    def holds_prefix(self, tokens) -> int:
        """Full prefix blocks of ``tokens`` this replica can serve
        without prefill (HBM prefix cache + host tier) — the router's
        session-affinity probe. RemoteReplica proxies don't implement
        this; the router getattr-guards the call."""
        fn = getattr(self.engine, "holds_prefix_blocks", None)
        return 0 if fn is None else fn(tokens)

    def load_score(self) -> float:
        """Routing cost: queued + live work, plus KV-pool pressure as a
        tiebreaker (two idle replicas: prefer the emptier pool, where a
        new prompt is least likely to trigger evictions)."""
        r = self.load_report()
        return (r["queue_wait_depth"] + r["live_seqs"]
                + self.inbox.qsize() + (1.0 - r["kv_free_frac"]))

    # -- threaded mode -------------------------------------------------
    def start(self, eos_token_id: Optional[int] = None,
              idle_sleep_s: float = 0.001) -> None:
        """Run the pump on a dedicated thread (the bench's in-process
        fleet). Synchronous callers (tests) skip this and drive
        :meth:`pump` directly."""
        if self._thread is not None:
            return
        self._stop.clear()

        def _loop():
            while not self._stop.is_set():
                emitted = self.pump(eos_token_id=eos_token_id)
                if not emitted and self.inbox.empty():
                    time.sleep(idle_sleep_s)

        self._thread = threading.Thread(
            target=_loop, name=f"replica-{self.name}", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

"""Fleet router: the single front door over N serving replicas.

The router owns three decisions and one promise:

* **Routing** — prefix-hash session affinity first (requests sharing a
  prompt prefix land where those KV blocks are already cached, the
  MII-replica-router / vLLM-prefix-aware-routing idea), then one of two
  policies: ``least_loaded`` by live load report, or ``predictive`` —
  route by predicted TTFT per replica from the five-phase model's
  decomposition (queue-wait estimate = reported queue depth x the
  observed per-request service-time EWMA, plus a prefill estimate from
  prompt length over the replica's observed prefill token rate). The
  predictive policy is what lets a degraded replica shed load *before*
  its queue builds: its service EWMA rises, so its predicted TTFT does
  too.
* **Disaggregation** — with ``prefill``/``decode``-role replicas, a new
  request goes to a prefill replica with a one-token budget; when its
  first token lands, the prompt's KV blocks are serialized from the
  prefill replica and installed into a decode replica
  (serving/disagg.py), and the remainder of the budget decodes there.
  Decode p99 never waits behind another request's prompt.
* **Failover** — a per-replica health state machine (``healthy →
  suspect → dead``) driven by *monotonic* heartbeat age and consecutive
  transport-error counts. A ``suspect`` replica (heartbeat past
  ``suspect_after_s`` or any transport error) stops receiving new
  routes but keeps its in-flight streams; it recovers to ``healthy``
  only after ``health_recover_checks`` consecutive clean checks
  (hysteresis — a flapping link doesn't flap the fleet). A ``dead``
  replica (heartbeat past ``stale_after_s``, a failed send, or
  ``transport_error_dead`` consecutive transport errors) has every one
  of its in-flight requests resubmitted elsewhere with the tokens
  generated so far folded into the prompt — PR 8's zero-drop contract
  (preempt-and-requeue) extended across replica death. Greedy decoding
  makes the continuation bit-identical to the uninterrupted stream;
  tokens already handed out are never re-emitted.
  ``health_mode="legacy"`` restores the single stale-threshold flip
  bit-exactly.
* **Hedged requests** — with ``hedge_enabled``, a routed request whose
  predicted TTFT has been exceeded by ``hedge_ttft_factor`` with no
  first token is resubmitted to a second replica; whichever stream
  emits first owns the request (greedy decoding makes both streams
  bit-identical, so the loser is dropped by the existing stale-emission
  uid guard). Hedges are HEDGE spans on the request trace plus
  ``serve.hedged``/``serve.hedge_wins`` counters.
* **Live migration** — ``migrate_sessions`` moves every in-flight
  decode session off a replica *warm*: committed KV blocks, the
  partial tail block, generated tokens, and the per-request
  spec-acceptance EWMA ship over the quantized handoff wire and
  resume on the target with zero re-prefill. Drains, rolling weight
  swaps, and migration-backed scale-down all ride it; a capture that
  can't happen degrades down the documented ladder (host-tier page-in
  on the target -> fold-and-recompute -> finish in place), each rung
  counted, never an error. ``migrate_hedges`` extends the same
  machinery to hedge promotion (off by default — legacy duplicate-
  stream hedging stays bit-exact).
* **The promise** — every accepted request completes with its full
  token budget, through overload, handoff, and replica death alike.

Every decision lands in the observability stack: ``ROUTE``/``HANDOFF``/
``FAILOVER`` spans on the per-request traces, fleet-level SLO
attribution aggregated over all replicas' tracers, per-replica Perfetto
lanes, and ``serve.fleet.*`` gauges (including the autoscaler's
desired-replica signal, serving/autoscale.py).

Threading: the router never touches an engine directly — it enqueues
:class:`Submission` objects into replica inboxes and receives emissions
via callbacks that run on the replica pump threads. Router state is
lock-protected, so the same code drives both the synchronous test mode
(``step()``/``run_until_complete()``) and the threaded bench mode
(``start()``/``drain()``).

Process fleets (serving/supervisor.py) reuse this router unchanged: a
``RemoteReplica`` satisfies the same surface (``submit``,
``load_report``, ``alive``, ``serialize_handoff``), emissions arrive on
the supervisor's receive threads instead of pump threads, and
``add_replica``/``remove_replica`` let the supervisor act on the
autoscale signal with real spin-up and drain.
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from deepspeed_tpu.observability.clocksync import wall_time
from deepspeed_tpu.observability.journal import get_journal
from deepspeed_tpu.serving.replica import ServingReplica, Submission


def build_fleet(model, router_cfg=None, engine_kw=None,
                run_dir: Optional[str] = None,
                eos_token_id: Optional[int] = None,
                devices=None) -> "FleetRouter":
    """Construct replicas + router from a ``serving.router`` config
    block (config.RouterConfig or any object with its fields; None uses
    the defaults). ``engine_kw`` is forwarded to every replica's
    engine constructor — pass shared ``params`` so the fleet serves one
    model, not N random inits.

    Each in-process replica owns ONE device: replica ``i`` runs on
    ``devices[i % len(devices)]`` (default: this process's local
    devices), so four replicas on a four-chip host are four one-chip
    engines, not four engines each spread over all four chips. A fleet
    of multi-device replicas passes ``mesh`` in ``engine_kw`` instead
    (every replica then runs on that mesh)."""
    from deepspeed_tpu.config.config import RouterConfig
    from deepspeed_tpu.serving.autoscale import AutoscaleSignal

    cfg = router_cfg if router_cfg is not None else RouterConfig()
    engine_kw = dict(engine_kw or {})
    if engine_kw.get("mesh") is not None:
        if devices is not None:
            raise ValueError("pass build_fleet devices or a shared "
                             "engine mesh, not both")
    elif devices is None:
        import jax

        devices = jax.local_devices()
    n = int(cfg.replicas)
    n_prefill = int(cfg.prefill_replicas) if cfg.mode == "disagg" else 0
    replicas = []
    for i in range(n):
        role = "unified" if cfg.mode == "unified" else (
            "prefill" if i < n_prefill else "decode")
        replicas.append(ServingReplica.create(
            model, i, role=role, run_dir=run_dir,
            device=devices[i % len(devices)] if devices else None,
            **engine_kw))
    from deepspeed_tpu.observability.hub import get_hub

    autoscale = AutoscaleSignal(
        min_replicas=cfg.autoscale_min, max_replicas=cfg.autoscale_max,
        queue_high=cfg.queue_high, queue_low=cfg.queue_low,
        slo_miss_high=cfg.slo_miss_high,
        hysteresis_rounds=cfg.hysteresis_rounds, hub=get_hub())
    return FleetRouter(replicas, affinity_blocks=cfg.affinity_blocks,
                       stale_after_s=cfg.stale_after_seconds,
                       autoscale=autoscale, eos_token_id=eos_token_id,
                       routing=getattr(cfg, "routing", "least_loaded"),
                       health_mode=getattr(cfg, "health_mode",
                                           "state_machine"),
                       suspect_after_s=getattr(cfg, "suspect_after_seconds",
                                               None),
                       transport_error_dead=getattr(
                           cfg, "transport_error_dead", 3),
                       health_recover_checks=getattr(
                           cfg, "health_recover_checks", 2),
                       hedge_enabled=getattr(cfg, "hedge_enabled", False),
                       hedge_ttft_factor=getattr(
                           cfg, "hedge_ttft_factor", 3.0),
                       hedge_min_s=getattr(cfg, "hedge_min_seconds", 0.25),
                       migrate_enabled=getattr(cfg, "migrate_sessions",
                                               True),
                       migrate_hedges=getattr(cfg, "migrate_hedges",
                                              False),
                       migrate_wire=(getattr(cfg, "migrate_wire", None)
                                     or None),
                       alerter=_build_alerter(
                           getattr(cfg, "burn_rate", None)))


def _build_alerter(burn_cfg):
    """BurnRateAlerter from a RouterConfig.burn_rate block (None when
    disabled — the default keeps the router alert-free, bit-exact with
    pre-alerting behavior)."""
    if burn_cfg is None:
        return None
    from deepspeed_tpu.observability.burn_rate import BurnRateAlerter
    from deepspeed_tpu.observability.hub import get_hub

    return BurnRateAlerter.from_config(burn_cfg, hub=get_hub())


class _RequestRecord:
    __slots__ = ("uid", "tokens", "max_new_tokens", "replica_id", "phase",
                 "emitted", "done", "failovers", "affinity_key",
                 "submitted_ts", "first_emit_ts", "last_emit_ts",
                 "submitted_mono", "hedge_replica_id", "hedge_at_mono",
                 "stale_rids")

    def __init__(self, uid, tokens, max_new_tokens, replica_id, phase,
                 affinity_key):
        self.uid = uid
        self.tokens = tokens
        self.max_new_tokens = max_new_tokens
        self.replica_id = replica_id
        self.phase = phase  # "prefill" (awaiting handoff) or "decode"
        self.emitted: List[int] = []
        self.done = False
        self.failovers = 0
        self.affinity_key = affinity_key
        # wall_time(), not time.time(): _on_emissions derives TTFT from
        # this stamp on the same clock domain as spans and the journal
        self.submitted_ts = wall_time()
        self.submitted_mono = time.monotonic()
        self.first_emit_ts = 0.0
        self.last_emit_ts = 0.0
        self.hedge_replica_id: Optional[int] = None
        self.hedge_at_mono: Optional[float] = None
        # replicas that may STILL be streaming this uid (a hedge that
        # lost the race, a primary abandoned by a hedge win): their
        # late emissions are dropped by the ownership guard, but they
        # must never be picked as a failover target for this request —
        # the engine would hold two live streams of one uid
        self.stale_rids: set = set()


ROUTING_POLICIES = ("least_loaded", "predictive")
HEALTH_MODES = ("state_machine", "legacy")
_HEALTH_ORDER = {"healthy": 0, "suspect": 1, "dead": 2}


class FleetRouter:
    def __init__(self, replicas: List[ServingReplica],
                 affinity_blocks: int = 2,
                 stale_after_s: float = 5.0,
                 autoscale=None,
                 eos_token_id: Optional[int] = None,
                 routing: str = "least_loaded",
                 service_ewma_alpha: float = 0.3,
                 health_mode: str = "state_machine",
                 suspect_after_s: Optional[float] = None,
                 transport_error_dead: int = 3,
                 health_recover_checks: int = 2,
                 hedge_enabled: bool = False,
                 hedge_ttft_factor: float = 3.0,
                 hedge_min_s: float = 0.25,
                 migrate_enabled: bool = True,
                 migrate_hedges: bool = False,
                 migrate_wire: Optional[str] = None,
                 alerter=None):
        if not replicas:
            raise ValueError("FleetRouter needs at least one replica")
        if routing not in ROUTING_POLICIES:
            raise ValueError(f"routing must be one of {ROUTING_POLICIES},"
                             f" got {routing!r}")
        if health_mode not in HEALTH_MODES:
            raise ValueError(f"health_mode must be one of {HEALTH_MODES},"
                             f" got {health_mode!r}")
        self.replicas = {r.replica_id: r for r in replicas}
        self.prefill_pool = [r.replica_id for r in replicas
                             if r.role == "prefill"]
        self.decode_pool = [r.replica_id for r in replicas
                            if r.role in ("decode", "unified")]
        self.disagg = bool(self.prefill_pool)
        if self.disagg and not self.decode_pool:
            raise ValueError("disaggregated fleet needs decode replicas")
        self.affinity_blocks = max(0, int(affinity_blocks))
        self.stale_after_s = float(stale_after_s)
        self.autoscale = autoscale
        self.eos_token_id = eos_token_id
        self.routing = routing
        self.health_mode = health_mode
        # suspect at half the dead threshold unless configured — early
        # enough to stop routing onto a silent replica well before the
        # failover fires
        self.suspect_after_s = (float(suspect_after_s)
                                if suspect_after_s
                                else self.stale_after_s / 2.0)
        self.transport_error_dead = max(1, int(transport_error_dead))
        self.health_recover_checks = max(1, int(health_recover_checks))
        self.hedge_enabled = bool(hedge_enabled)
        self.hedge_ttft_factor = float(hedge_ttft_factor)
        self.hedge_min_s = float(hedge_min_s)
        # live session migration (ISSUE 20): drains and scale-downs
        # move mid-stream decode state warm instead of recompute-
        # requeueing. migrate_hedges extends migrate-first to hedge
        # promotion — OFF by default so legacy hedge behavior (race a
        # duplicate stream) stays bit-exact. migrate_wire picks the
        # session wire codec (None = the engine's handoff_wire).
        self.migrate_enabled = bool(migrate_enabled)
        self.migrate_hedges = bool(migrate_hedges)
        self.migrate_wire = migrate_wire
        # rid -> {"state", "since" (monotonic), "ok_checks",
        # "transitions"} — the per-replica health state machine
        self._health: Dict[int, Dict[str, Any]] = {}
        self._lock = threading.RLock()
        self._requests: Dict[int, _RequestRecord] = {}
        # (pool, prefix-hash) -> replica id that holds those KV blocks
        self._affinity: Dict[Any, int] = {}
        self.dead: set = set()
        self.draining: set = set()
        self._last_policy = "least_loaded"
        self._last_predicted_ms: Optional[float] = None
        # per-candidate forensics for the fleet journal's ROUTE records
        # — populated by _pick only while a journal is installed, so
        # the disabled path stays allocation-free
        self._last_candidates: Optional[List[Dict[str, Any]]] = None
        # per-replica observations feeding the predictive policy:
        # service EWMA in seconds per completed request, and the
        # observed prefill token rate from first-token latencies
        self._svc_ewma: Dict[int, float] = {}
        self._prefill_rate: Dict[int, float] = {}
        # decode seconds-per-token from emission gaps: learned within a
        # couple of rounds of a replica's FIRST request, long before
        # any completion feeds _svc_ewma — the predictor's cold-start
        # service estimate (spt x typical budget)
        self._spt_ewma: Dict[int, float] = {}
        self._avg_budget = 0.0
        self._ewma_alpha = float(service_ewma_alpha)
        # a fresh replica's FIRST request pays the one-time JIT compile
        # (seconds, vs milliseconds steady-state); folding that sample
        # into the EWMAs would make a fast replica look 100x slower for
        # the first dozen requests, so each signal discards its first
        # per-replica observation as the compile-warming round
        self._prefill_seen: Dict[int, int] = {}
        self._svc_seen: Dict[int, int] = {}
        self.stats = {"submitted": 0, "completed": 0, "handoffs": 0,
                      "handoff_recompute": 0, "failovers": 0,
                      "failed_over_requests": 0, "affinity_hits": 0,
                      "tier_affinity_hits": 0,
                      "hedged": 0, "hedge_wins": 0, "stranded": 0,
                      # the migration ladder, router view: sessions
                      # moved warm / degraded to fold-and-recompute /
                      # left in place (no eligible target)
                      "migrations": 0, "migrate_recompute": 0,
                      "migrate_skipped": 0,
                      # bytes actually shipped for warm migrations —
                      # the deploy drill certifies bytes/session stays
                      # near the quantized-wire budget, not bf16
                      "migrate_wire_bytes": 0}
        # one BurnRateAlerter for the FLEET (observability/burn_rate.py):
        # every replica's finished traces feed it through the tracer
        # hook, and check_health runs its fire/clear state machine —
        # the burn rate is a fleet property, not a per-replica one
        self.alerter = alerter
        for r in replicas:
            r.emit_callback = self._on_emissions
            if alerter is not None:
                r.engine.tracer.alerter = alerter
        from deepspeed_tpu.observability.hub import get_hub

        self._hub = get_hub()
        jr = get_journal()
        if jr is not None:
            # the router owns request identity, so it owns ADMIT/EMIT
            # journaling; engines sharing this process defer to it
            jr.claim_ingress("router")

    # -- fleet membership (supervisor spin-up / drain) -----------------
    def add_replica(self, replica: ServingReplica) -> None:
        """Wire a freshly spun-up replica into the pools (supervisor
        scale-up / crash-restart path), or READMIT one that was
        quiesced with ``remove_replica`` — the rolling-swap rejoin:
        same id, same channel, it just starts receiving work again."""
        with self._lock:
            rid = replica.replica_id
            if (rid in self.replicas and rid not in self.dead
                    and rid not in self.draining):
                raise ValueError(f"replica id {rid} already in the fleet")
            self.replicas[rid] = replica
            self.dead.discard(rid)
            self.draining.discard(rid)
            if replica.role == "prefill":
                if rid not in self.prefill_pool:
                    self.prefill_pool.append(rid)
            elif rid not in self.decode_pool:
                self.decode_pool.append(rid)
            replica.emit_callback = self._on_emissions
            if self.alerter is not None:
                replica.engine.tracer.alerter = self.alerter

    def remove_replica(self, replica_id: int) -> None:
        """Stop routing NEW work to a replica (supervisor drain). The
        replica stays in ``self.replicas`` so its in-flight requests
        finish through the normal emission path — drain means 'no new
        admissions', never 'drop what you hold'."""
        with self._lock:
            self.draining.add(replica_id)
            if replica_id in self.prefill_pool:
                self.prefill_pool.remove(replica_id)
            if replica_id in self.decode_pool:
                self.decode_pool.remove(replica_id)

    # -- admission + routing -------------------------------------------
    def submit(self, uid: int, tokens, max_new_tokens: int = 64) -> int:
        """Route one request. Returns the chosen replica id. Raises
        ValueError (before accepting) for a prompt no replica could
        ever schedule — the fleet-wide analog of ``put()``'s never-fit
        contract; once accepted, completion is guaranteed."""
        toks = np.asarray(tokens, np.int32).ravel()
        jr = get_journal()
        if jr is not None:
            # a journal installed after __init__ still belongs to the
            # router: claim before any engine sees the request
            jr.claim_ingress("router")
        with self._lock:
            if uid in self._requests:
                raise ValueError(f"uid={uid} already in flight")
            key = self._affinity_key(toks)
            if self.disagg:
                target = self._pick(self.prefill_pool, key, len(toks),
                                    tokens=toks)
                phase, budget = "prefill", 1
            else:
                target = self._pick(self.decode_pool, key, len(toks),
                                    tokens=toks)
                phase, budget = "decode", int(max_new_tokens)
            self._check_fits(target, toks, max_new_tokens)
            rec = _RequestRecord(uid, toks, int(max_new_tokens),
                                 target.replica_id, phase, key)
            if self.hedge_enabled and phase == "decode":
                pred = self.predict_ttft(target, len(toks))
                rec.hedge_at_mono = rec.submitted_mono + max(
                    self.hedge_min_s, self.hedge_ttft_factor * pred)
            self._requests[uid] = rec
            self.stats["submitted"] += 1
            self._avg_budget = float(max_new_tokens) \
                if self._avg_budget <= 0.0 else (
                    self._ewma_alpha * float(max_new_tokens)
                    + (1.0 - self._ewma_alpha) * self._avg_budget)
            route = self._route_fields(target, self._last_policy,
                                       self._last_predicted_ms, uid=uid)
            if jr is not None:
                jr.admit(uid, toks.tolist(), int(max_new_tokens))
                jr.decision(
                    "ROUTE", uid=uid, replica=target.replica_id,
                    phase=phase, policy=self._last_policy,
                    predicted_ttft_ms=self._last_predicted_ms,
                    candidates=self._last_candidates)
        target.submit(Submission(
            uid=uid, tokens=toks, max_new_tokens=budget,
            span_notes=[("ROUTE", route)]))
        return target.replica_id

    def _route_fields(self, target: ServingReplica, policy: str,
                      predicted_ms: Optional[float] = None,
                      uid: Optional[int] = None) -> Dict[str, Any]:
        """ROUTE span fields: placement decision + the transport byte
        counters at decision time, so cross-process lanes show what each
        hop had already paid on the wire (replica_id itself is stamped
        by the replica applying the submission — in ITS process).

        With ``uid`` the fields double as Dapper-style trace context:
        the router-side trace id and clock-domain label travel inside
        the ROUTE span note, land in the worker's trace via
        ``tracer.note``, and ship back with the trace dicts — the merge
        side joins both processes' spans on ``fleet_trace_id`` without
        any wire-protocol change."""
        fields: Dict[str, Any] = {"replica": target.replica_id,
                                  "role": target.role, "policy": policy}
        if uid is not None:
            fields["fleet_trace_id"] = f"fleet-{int(uid)}"
            fields["parent_domain"] = "router"
        tx = getattr(target, "transport_bytes", None)
        if tx is not None:
            sent, received = tx()
            fields["wire_tx_bytes"] = int(sent)
            fields["wire_rx_bytes"] = int(received)
        if predicted_ms is not None:
            fields["predicted_ttft_ms"] = round(predicted_ms, 3)
        return fields

    def _affinity_key(self, toks: np.ndarray) -> Optional[str]:
        if self.affinity_blocks <= 0:
            return None
        any_r = next(iter(self.replicas.values()))
        span = self.affinity_blocks * \
            any_r.engine.kv_cache.config.block_size
        if len(toks) < span:
            return None
        return hashlib.sha1(
            np.ascontiguousarray(toks[:span], np.int32).tobytes()
        ).hexdigest()

    def _instant_health(self, r: ServingReplica, now: float) -> str:
        """Stateless health read from the replica's observables at
        monotonic ``now`` (the state machine adds hysteresis on top)."""
        if getattr(r, "killed", False) or getattr(r, "_send_failed",
                                                  False):
            return "dead"
        age = r.heartbeat_age(now)
        terr = getattr(r, "transport_errors", 0)
        if age >= self.stale_after_s or terr >= self.transport_error_dead:
            return "dead"
        if age >= self.suspect_after_s or terr > 0:
            return "suspect"
        return "healthy"

    def _route_state(self, rid: int, now: float) -> str:
        """Health as routing sees it: the worse of the instantaneous
        read and the stored state — a suspect mid-recovery stays
        suspect until the hysteresis clears it."""
        inst = self._instant_health(self.replicas[rid], now)
        stored = self._health.get(rid, {}).get("state", "healthy")
        return (inst if _HEALTH_ORDER[inst] >= _HEALTH_ORDER[stored]
                else stored)

    def _alive(self, pool: List[int]) -> List[ServingReplica]:
        now = time.monotonic()
        if self.health_mode == "legacy":
            out = [self.replicas[rid] for rid in pool
                   if rid not in self.dead
                   and self.replicas[rid].alive(now, self.stale_after_s)]
        else:
            cands = [rid for rid in pool if rid not in self.dead]
            states = {rid: self._route_state(rid, now) for rid in cands}
            # healthy replicas take new routes; suspects only when
            # nothing healthy is left (they keep in-flight streams
            # either way — emissions don't pass through here)
            out = [self.replicas[rid] for rid in cands
                   if states[rid] == "healthy"]
            if not out:
                out = [self.replicas[rid] for rid in cands
                       if states[rid] == "suspect"]
        if not out:  # last resort: any replica not yet declared dead
            out = [r for rid, r in self.replicas.items()
                   if rid not in self.dead]
        if not out:
            raise RuntimeError("no live replicas left in the fleet")
        return out

    def _pick(self, pool: List[int], key: Optional[str],
              n_tokens: int = 0,
              exclude: Optional[set] = None,
              tokens: Optional[np.ndarray] = None) -> ServingReplica:
        """Affinity if the remembered replica is still live, else the
        host-KV-tier probe (the replica already HOLDING a returning
        session's paged-out blocks warm-resumes it without re-prefill —
        worth more than a marginally lower load score), else the
        configured policy (least-loaded or predicted-TTFT). Caller
        holds the lock. ``exclude`` removes replicas that may still
        hold a live stream of the request being placed (hedge losers);
        an all-excluded pool raises like a dead one, which parks the
        failover until fresh capacity arrives."""
        alive = self._alive(pool)
        if exclude:
            alive = [r for r in alive if r.replica_id not in exclude]
            if not alive:
                raise RuntimeError(
                    "no live replicas without a stale stream of this "
                    "request")
        pool_tag = id(pool)
        self._last_predicted_ms = None
        if get_journal() is not None:
            # decision forensics: every candidate's health / load /
            # predicted-TTFT at decision time, not just the winner —
            # computed only while the black box is recording
            mono = time.monotonic()
            self._last_candidates = [
                {"replica": r.replica_id,
                 "health": self._route_state(r.replica_id, mono),
                 "load_score": round(float(r.load_score()), 4),
                 "predicted_ttft_ms": round(
                     self.predict_ttft(r, n_tokens) * 1e3, 3)}
                for r in alive]
        else:
            self._last_candidates = None
        if key is not None:
            rid = self._affinity.get((pool_tag, key))
            if rid is not None and any(r.replica_id == rid for r in alive):
                self.stats["affinity_hits"] += 1
                self._last_policy = "affinity"
                return self.replicas[rid]
        if tokens is not None:
            # tiered-KV placement: probe only replicas WITH a host tier
            # (in-process handles expose holds_prefix; RemoteReplica
            # proxies don't and are skipped — they compete on load).
            # Probing every submit is an O(prefix blocks) hash walk per
            # tiered replica, host-side only.
            best, best_hits = None, 0
            for r in alive:
                eng = getattr(r, "engine", None)
                if getattr(getattr(eng, "kv_cache", None),
                           "host_tier", None) is None:
                    continue
                hits = r.holds_prefix(tokens)
                if hits > best_hits or (hits == best_hits and hits > 0
                                        and r.load_score()
                                        < best.load_score()):
                    best, best_hits = r, hits
            if best is not None and best_hits > 0:
                self.stats["tier_affinity_hits"] += 1
                self._last_policy = "tier_affinity"
                if key is not None:
                    self._affinity[(pool_tag, key)] = best.replica_id
                return best
        if self.routing == "predictive":
            # ties (no observations yet) fall back to load score, so a
            # cold fleet degrades to exactly the least-loaded policy
            best = min(alive, key=lambda r: (
                self.predict_ttft(r, n_tokens), r.load_score()))
            self._last_policy = "predictive"
            self._last_predicted_ms = \
                self.predict_ttft(best, n_tokens) * 1e3
        else:
            best = min(alive, key=lambda r: r.load_score())
            self._last_policy = "least_loaded"
        if key is not None:
            self._affinity[(pool_tag, key)] = best.replica_id
        return best

    def predict_ttft(self, replica: ServingReplica,
                     n_tokens: int = 0) -> float:
        """Predicted TTFT in seconds for a new ``n_tokens`` prompt on
        ``replica`` — the five-phase model's first two phases estimated
        from fleet observables: queue_wait ~= (everything already
        queued or running there) x the replica's observed per-request
        service EWMA, prefill ~= prompt length over its observed
        prefill token rate. Both EWMAs are router-side observations, so
        the estimate works identically for thread and process replicas."""
        rid = replica.replica_id
        rep = replica.load_report()
        depth = rep.get("inflight",
                        rep.get("queue_wait_depth", 0)
                        + rep.get("live_seqs", 0))
        svc = self._svc_ewma.get(rid, 0.0)
        if svc <= 0.0:
            # no completion observed yet: estimate service time from
            # the replica's decode cadence x the typical budget (learned
            # within rounds, not requests), else borrow the fleet's
            # observed service time, else a 1s prior — a zero here
            # would erase the queue term entirely and leave the ranking
            # to prefill-rate noise
            spt = self._spt_ewma.get(rid, 0.0)
            if spt > 0.0 and self._avg_budget > 0.0:
                svc = spt * self._avg_budget
            else:
                known = [v for v in self._svc_ewma.values() if v > 0.0]
                svc = (sum(known) / len(known)) if known else 1.0
        queue_wait = float(depth) * svc
        rate = self._prefill_rate.get(rid, 0.0)
        prefill = (float(n_tokens) / rate) if rate > 0.0 else 0.0
        return queue_wait + prefill

    @staticmethod
    def _check_fits(replica: ServingReplica, toks: np.ndarray,
                    max_new: int) -> None:
        e = replica.engine
        blocks = e.kv_cache.blocks_needed(len(toks) + 1)
        if (blocks > e.max_blocks_per_seq
                or blocks > e.kv_cache.allocator.total_blocks):
            raise ValueError(
                f"prompt of {len(toks)} tokens needs {blocks} KV blocks "
                f"and can never be scheduled on replica "
                f"{replica.replica_id}")

    # -- emissions (runs on replica pump threads) ----------------------
    def _on_emissions(self, replica: ServingReplica,
                      emitted: Dict[int, List[int]]) -> None:
        handoffs = []
        now = wall_time()  # same clock domain as spans + journal
        jr = get_journal()
        with self._lock:
            for uid, toks in emitted.items():
                rec = self._requests.get(uid)
                if rec is None or rec.done:
                    continue
                if rec.replica_id != replica.replica_id:
                    if (rec.hedge_replica_id == replica.replica_id
                            and not rec.emitted and toks):
                        # hedge wins: the secondary produced the first
                        # token first — adopt its stream; the primary's
                        # later emissions become the stale ones (and it
                        # still streams this uid: taint it)
                        rec.stale_rids.add(rec.replica_id)
                        rec.replica_id = replica.replica_id
                        rec.hedge_replica_id = None
                        self.stats["hedge_wins"] += 1
                        self._hub.counter_add("serve.hedge_wins")
                    else:
                        # stale emission from a failed-over replica or
                        # a hedge that lost the race
                        continue
                if (rec.hedge_replica_id is not None and toks
                        and not rec.emitted):
                    # first token came from the primary: the hedge lost,
                    # but its replica still streams this uid to the end
                    # of the budget — taint it for failover picks
                    rec.stale_rids.add(rec.hedge_replica_id)
                    rec.hedge_replica_id = None
                if not rec.emitted and toks:
                    self._observe_first_token(replica.replica_id, rec, now)
                elif toks and rec.last_emit_ts > 0.0:
                    # decode cadence: gap since the last batch over the
                    # tokens it produced -> seconds-per-token EWMA
                    spt = max(now - rec.last_emit_ts, 1e-6) / len(toks)
                    prev = self._spt_ewma.get(replica.replica_id)
                    self._spt_ewma[replica.replica_id] = \
                        spt if prev is None else (
                            self._ewma_alpha * spt
                            + (1.0 - self._ewma_alpha) * prev)
                if toks:
                    rec.last_emit_ts = now
                    if jr is not None:
                        # under the lock, after the ownership guards:
                        # the checksum chain records exactly the tokens
                        # the request adopted, in adoption order
                        jr.emit(uid, toks)
                rec.emitted.extend(int(t) for t in toks)
                if rec.phase == "prefill":
                    handoffs.append(rec)  # budget-1 stage just finished
                elif len(rec.emitted) >= rec.max_new_tokens:
                    rec.done = True
                    self.stats["completed"] += 1
                    self._observe_completion(replica.replica_id, rec, now)
        for rec in handoffs:
            self._handoff(rec, replica)

    def _observe_first_token(self, rid: int, rec: _RequestRecord,
                             now: float) -> None:
        """Feed the predictive policy's prefill-rate EWMA: prompt
        tokens over observed first-token latency (queue wait included —
        an *effective* rate, which is the one a new arrival will see).
        Caller holds the lock."""
        rec.first_emit_ts = now
        seen = self._prefill_seen.get(rid, 0)
        self._prefill_seen[rid] = seen + 1
        if seen == 0:
            return  # compile-warming round (see __init__)
        ttft = max(now - rec.submitted_ts, 1e-6)
        rate = len(rec.tokens) / ttft
        prev = self._prefill_rate.get(rid)
        self._prefill_rate[rid] = rate if prev is None else (
            self._ewma_alpha * rate + (1.0 - self._ewma_alpha) * prev)

    def _observe_completion(self, rid: int, rec: _RequestRecord,
                            now: float) -> None:
        """Feed the per-request service-time EWMA (first token -> full
        budget, queue wait excluded: the ``depth x svc`` queue term of
        predict_ttft models waiting separately, and folding a backlog
        into svc would make a busy-but-fast replica look slower than a
        genuinely slow one). Caller holds the lock."""
        seen = self._svc_seen.get(rid, 0)
        self._svc_seen[rid] = seen + 1
        if seen == 0:
            return  # compile-warming round (see __init__)
        svc = max(now - (rec.first_emit_ts or rec.submitted_ts), 1e-6)
        prev = self._svc_ewma.get(rid)
        self._svc_ewma[rid] = svc if prev is None else (
            self._ewma_alpha * svc + (1.0 - self._ewma_alpha) * prev)

    def _handoff(self, rec: _RequestRecord,
                 prefill_replica: ServingReplica) -> None:
        """Move a prefill-complete request to a decode replica. The
        prefill replica serializes its own KV pool — on its pump thread
        for local replicas, in its own process for remote ones — and
        the completion callback submits to the decode target (local
        replicas invoke it synchronously; remote ones when the payload
        message arrives). The install then runs on the decode replica's
        own thread (Submission.handoff)."""
        with self._lock:
            remaining = rec.max_new_tokens - len(rec.emitted)
            if remaining <= 0:
                rec.done = True
                self.stats["completed"] += 1
                return
            target = self._pick(self.decode_pool, rec.affinity_key,
                                len(rec.tokens))
            rec.phase = "decode"
            rec.replica_id = target.replica_id
            self.stats["handoffs"] += 1
            tokens = np.concatenate(
                [rec.tokens, np.asarray(rec.emitted, np.int32)])

        def _complete(payload) -> None:
            if payload is None:
                with self._lock:
                    self.stats["handoff_recompute"] += 1
            route = self._route_fields(target, "disagg_handoff",
                                       uid=rec.uid)
            target.submit(Submission(
                uid=rec.uid, tokens=tokens, max_new_tokens=remaining,
                handoff=payload, span_notes=[("ROUTE", route)]))

        prefill_replica.serialize_handoff(rec.tokens, _complete)

    # -- failover ------------------------------------------------------
    def check_health(self, now: Optional[float] = None) -> List[int]:
        """Advance the per-replica health state machine (or, in legacy
        mode, the single stale flip), declare dead replicas and
        re-route their in-flight requests, fire due hedges, and feed
        the autoscaler + fleet gauges. ``now`` is a monotonic
        timestamp. Returns replica ids newly declared dead."""
        now = time.monotonic() if now is None else now
        newly_dead = []
        if self.health_mode == "legacy":
            for rid, r in self.replicas.items():
                if rid not in self.dead \
                        and not r.alive(now, self.stale_after_s):
                    newly_dead.append(rid)
        else:
            with self._lock:
                for rid, r in self.replicas.items():
                    if rid in self.dead:
                        continue
                    if self._observe_health(rid, r, now) == "dead":
                        newly_dead.append(rid)
        for rid in newly_dead:
            self._failover(rid)
        # victims parked during a total outage (every replica dead in
        # one window) retry every round: once the supervisor restores
        # capacity they fail over like any other victim
        with self._lock:
            parked = sorted({rec.replica_id
                             for rec in self._requests.values()
                             if not rec.done
                             and rec.replica_id in self.dead
                             and rec.replica_id not in newly_dead})
        for rid in parked:
            self._failover(rid)
        with self._lock:
            self.stats["stranded"] = sum(
                1 for rec in self._requests.values()
                if not rec.done and rec.replica_id in self.dead)
        if self.hedge_enabled:
            self._check_hedges(now)
        self._update_fleet_gauges()
        if self.alerter is not None:
            self.alerter.evaluate()
        return newly_dead

    def _observe_health(self, rid: int, r: ServingReplica,
                        now: float) -> str:
        """One state-machine tick for one replica. Demotion is
        immediate; promotion back to healthy requires
        ``health_recover_checks`` consecutive clean reads (hysteresis).
        Caller holds the lock."""
        h = self._health.get(rid)
        if h is None:
            h = self._health[rid] = {"state": "healthy", "since": now,
                                     "ok_checks": 0, "transitions": 0}
        target = self._instant_health(r, now)
        state = h["state"]
        if target == "dead":
            new = "dead"
        elif state == "suspect":
            if target == "healthy":
                h["ok_checks"] += 1
                new = ("healthy"
                       if h["ok_checks"] >= self.health_recover_checks
                       else "suspect")
            else:
                h["ok_checks"] = 0
                new = "suspect"
        else:
            new = target
        if new != state:
            h["state"] = new
            h["since"] = now
            h["transitions"] += 1
            h["ok_checks"] = 0
        return new

    def _check_hedges(self, now: float) -> None:
        """Resubmit requests whose predicted TTFT has been exceeded by
        ``hedge_ttft_factor`` with no first token. Plans are built
        under the lock, submits happen outside it (the failover
        discipline). Greedy decoding makes both streams bit-identical,
        so whichever emits first wins and the loser is dropped by the
        stale-emission guard in _on_emissions."""
        if self.disagg:
            return  # prefill handoffs have their own recompute path
        plans = []
        migrate_plans = []
        with self._lock:
            for rec in self._requests.values():
                if (rec.done or rec.emitted or rec.phase != "decode"
                        or rec.hedge_replica_id is not None
                        or rec.hedge_at_mono is None
                        or now < rec.hedge_at_mono):
                    continue
                try:
                    alive = [r for r in self._alive(self.decode_pool)
                             if r.replica_id != rec.replica_id
                             and r.replica_id not in rec.stale_rids]
                except RuntimeError:
                    continue
                if not alive:
                    continue
                if self.routing == "predictive":
                    target = min(alive, key=lambda r: (
                        self.predict_ttft(r, len(rec.tokens)),
                        r.load_score()))
                else:
                    target = min(alive, key=lambda r: r.load_score())
                rec.hedge_replica_id = target.replica_id
                self.stats["hedged"] += 1
                waited_ms = (now - rec.submitted_mono) * 1e3
                jr = get_journal()
                if jr is not None:
                    jr.decision(
                        "HEDGE", uid=rec.uid,
                        from_replica=rec.replica_id,
                        to_replica=target.replica_id,
                        waited_ms=round(waited_ms, 3),
                        migrate=self.migrate_hedges,
                        hedge_ttft_factor=self.hedge_ttft_factor)
                if self.migrate_hedges and self.migrate_enabled:
                    # migrate-first hedge promotion: MOVE the stuck
                    # request instead of racing a duplicate stream —
                    # one stream, no loser to drop, and a mid-decode
                    # victim carries its KV state along. Pre-first-
                    # token captures degrade to recompute on the
                    # target (the same outcome a hedge win delivers).
                    src = self.replicas[rec.replica_id]
                    migrate_plans.append(
                        (rec, src,
                         self._plan_migration(rec, src, target,
                                              "hedge")))
                    continue
                plans.append((rec, target,
                              self._route_fields(target, "hedge",
                                                 uid=rec.uid),
                              waited_ms))
        for rec, src, cb in migrate_plans:
            src.migrate_out(rec.uid, cb, wire=self.migrate_wire)
            self._hub.counter_add("serve.hedged")
        for rec, target, route, waited_ms in plans:
            target.submit(Submission(
                uid=rec.uid, tokens=rec.tokens,
                max_new_tokens=rec.max_new_tokens,
                span_notes=[
                    ("HEDGE", {"from_replica": rec.replica_id,
                               "to_replica": target.replica_id,
                               "waited_ms": round(waited_ms, 3)}),
                    ("ROUTE", route)]))
            self._hub.counter_add("serve.hedged")

    def _failover(self, dead_rid: int) -> None:
        with self._lock:
            if dead_rid not in self.dead:
                self.dead.add(dead_rid)
                if dead_rid in self._health:
                    self._health[dead_rid]["state"] = "dead"
                self.stats["failovers"] += 1
            victims = [rec for rec in self._requests.values()
                       if rec.replica_id == dead_rid and not rec.done]
            for rec in self._requests.values():
                # a dead hedge target just stops being a hedge
                if rec.hedge_replica_id == dead_rid:
                    rec.hedge_replica_id = None
            plans = []
            for rec in victims:
                remaining = rec.max_new_tokens - len(rec.emitted)
                if remaining <= 0:
                    rec.done = True
                    self.stats["completed"] += 1
                    continue
                if (rec.hedge_replica_id is not None
                        and rec.hedge_replica_id not in self.dead
                        and not rec.emitted):
                    # a live hedge already holds this request verbatim —
                    # promote it instead of resubmitting a third copy
                    rec.replica_id = rec.hedge_replica_id
                    rec.hedge_replica_id = None
                    continue
                rec.hedge_replica_id = None
                try:
                    if rec.phase == "prefill":
                        pool = self.prefill_pool
                        alive = [r for r in self._alive(pool)
                                 if r.replica_id != dead_rid]
                        if not alive:  # prefill pool gone: decode e2e
                            rec.phase = "decode"
                            pool = self.decode_pool
                        budget = 1 if rec.phase == "prefill" \
                            else remaining
                    else:
                        pool, budget = self.decode_pool, remaining
                    rec.stale_rids.add(dead_rid)
                    target = self._pick(pool, rec.affinity_key,
                                        len(rec.tokens),
                                        exclude=rec.stale_rids)
                except RuntimeError:
                    # transient total outage: every candidate died in
                    # the same health window. Park the victim on its
                    # dead replica id — check_health retries it once
                    # the supervisor restores capacity; raising here
                    # would turn a survivable outage into a crashed
                    # router (new submits still fail loud).
                    continue
                old = rec.replica_id
                rec.replica_id = target.replica_id
                rec.failovers += 1
                self.stats["failed_over_requests"] += 1
                jr = get_journal()
                if jr is not None:
                    jr.decision(
                        "FAILOVER", uid=rec.uid, from_replica=old,
                        to_replica=target.replica_id,
                        dead_replica=dead_rid,
                        recovered_tokens=len(rec.emitted),
                        failovers=rec.failovers)
                tokens = np.concatenate(
                    [rec.tokens, np.asarray(rec.emitted, np.int32)]) \
                    if rec.emitted else rec.tokens
                plans.append((rec.uid, tokens, budget, old, target,
                              len(rec.emitted),
                              self._route_fields(target, "failover",
                                                 uid=rec.uid)))
        for uid, tokens, budget, old, target, recovered, route in plans:
            target.submit(Submission(
                uid=uid, tokens=tokens, max_new_tokens=budget,
                span_notes=[
                    ("FAILOVER", {"from_replica": old,
                                  "to_replica": target.replica_id,
                                  "recovered_tokens": recovered}),
                    ("ROUTE", route)]))
            self._hub.counter_add("serve.fleet.failed_over_requests")

    # -- live session migration (ISSUE 20) -----------------------------
    def migrate_sessions(self, src_rid: int,
                         reason: str = "drain") -> Dict[str, int]:
        """Move every in-flight decode session off ``src_rid`` warm:
        each session's committed KV blocks + partial tail block +
        generated tokens + spec-acceptance EWMA are captured on the
        source (releasing it there), shipped over the quantized wire,
        and installed on a picked target — decode resumes with zero
        re-prefill. The graceful degradation ladder, never an error:

        1. **warm** — capture lands, install resumes from the wire
           blocks (or parks in the target's host KV tier until HBM
           frees up: same zero-recompute outcome, deferred);
        2. **recompute** — capture returned None (session mid-prefill,
           already finished, transport death): fold emitted tokens into
           the prompt and resubmit — PR 8's legacy path, bit-identical
           output under greedy decoding;
        3. **skip** — no eligible target (pool of one, all candidates
           tainted): the session stays put and finishes on the source
           (a draining worker finishes what it holds before exiting).

        Call with the source already removed from the pools
        (``remove_replica``) so no new work lands behind the captures.
        Plans are built under the lock, capture RPCs sent outside it;
        installs happen in the capture callbacks (receive/pump
        threads). Returns plan counts — the rung each migration
        actually landed on accumulates in ``stats`` as callbacks
        fire."""
        if not self.migrate_enabled:
            return {"requested": 0, "skipped": 0}
        plans = []
        counts = {"requested": 0, "skipped": 0}
        with self._lock:
            src = self.replicas.get(src_rid)
            if src is None:
                return counts
            for rec in self._requests.values():
                if (rec.done or rec.replica_id != src_rid
                        or rec.phase != "decode"):
                    continue  # prefill-phase recs have the handoff path
                try:
                    target = self._pick(
                        self.decode_pool, rec.affinity_key,
                        len(rec.tokens),
                        exclude={src_rid} | rec.stale_rids)
                except RuntimeError:
                    self.stats["migrate_skipped"] += 1
                    counts["skipped"] += 1
                    continue
                plans.append((rec, target,
                              self._plan_migration(rec, src, target,
                                                   reason)))
                counts["requested"] += 1
        for rec, target, cb in plans:
            src.migrate_out(rec.uid, cb, wire=self.migrate_wire)
        return counts

    def _plan_migration(self, rec: _RequestRecord, src, target,
                        reason: str):
        """Build the capture continuation for one migration. The
        callback runs on the source's receive/pump thread when the
        SessionHandoff (or None) lands; it transfers ownership, folds
        the emitted tokens (the recompute fallback AND the guard
        prompt), journals the MIGRATE decision with the inputs that
        drove it, and submits to the target. Caller holds the lock."""
        src_rid = src.replica_id
        src_score = round(float(src.load_score()), 4)
        tgt_score = round(float(target.load_score()), 4)

        def _cb(sess) -> None:
            with self._lock:
                if rec.done or rec.replica_id != src_rid:
                    # finished, or a failover/hedge raced the capture
                    # and already owns the stream elsewhere — drop the
                    # payload (its tokens are folded wherever it went)
                    return
                remaining = rec.max_new_tokens - len(rec.emitted)
                if remaining <= 0:
                    rec.done = True
                    self.stats["completed"] += 1
                    return
                # the source released the session on capture (or still
                # streams it after a None capture): either way it must
                # never be picked again for this request
                rec.stale_rids.add(src_rid)
                rec.replica_id = target.replica_id
                rec.hedge_replica_id = None  # migrate-first hedge done
                tokens = np.concatenate(
                    [rec.tokens, np.asarray(rec.emitted, np.int32)]) \
                    if rec.emitted else rec.tokens
                rung = "warm" if sess is not None else "recompute"
                self.stats["migrations" if sess is not None
                           else "migrate_recompute"] += 1
                fields = {"from_replica": src_rid,
                          "to_replica": target.replica_id,
                          "reason": reason, "rung": rung,
                          "recovered_tokens": len(rec.emitted),
                          "source_score": src_score,
                          "target_score": tgt_score}
                if sess is not None:
                    fields["wire_bytes"] = int(sess.wire_nbytes)
                    fields["n_blocks"] = int(sess.n_blocks)
                    self.stats["migrate_wire_bytes"] += \
                        int(sess.wire_nbytes)
                jr = get_journal()
                if jr is not None:
                    jr.decision("MIGRATE", uid=rec.uid, **fields)
                route = self._route_fields(target, "migrate",
                                           uid=rec.uid)
                notes = [("MIGRATE", dict(fields)), ("ROUTE", route)]
            target.submit(Submission(
                uid=rec.uid, tokens=tokens, max_new_tokens=remaining,
                session=sess, span_notes=notes))
            self._hub.counter_add("serve.fleet.migrations"
                                  if sess is not None
                                  else "serve.fleet.migrate_recompute")

        return _cb

    # -- driving -------------------------------------------------------
    def step(self) -> int:
        """Synchronous mode: pump every live replica once, then health-
        check. Returns the number of requests still pending."""
        for r in self.replicas.values():
            if r.replica_id not in self.dead and not r.killed:
                r.pump(eos_token_id=self.eos_token_id)
        self.check_health()
        return self.pending()

    def run_until_complete(self, max_rounds: int = 100000) -> None:
        for _ in range(max_rounds):
            if self.step() == 0:
                return
        raise RuntimeError(
            f"fleet did not drain in {max_rounds} rounds "
            f"({self.pending()} requests pending)")

    def start(self) -> None:
        for r in self.replicas.values():
            r.start(eos_token_id=self.eos_token_id)

    def stop(self) -> None:
        for r in self.replicas.values():
            r.stop()

    def drain(self, timeout_s: float = 120.0,
              poll_s: float = 0.02) -> None:
        """Threaded mode: wait (health-checking) until every accepted
        request completed."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            self.check_health()
            if self.pending() == 0:
                return
            time.sleep(poll_s)
        raise TimeoutError(
            f"fleet did not drain in {timeout_s}s "
            f"({self.pending()} requests pending)")

    def pending(self) -> int:
        with self._lock:
            return sum(1 for rec in self._requests.values()
                       if not rec.done)

    def results(self) -> Dict[int, List[int]]:
        with self._lock:
            return {uid: list(rec.emitted)
                    for uid, rec in self._requests.items() if rec.done}

    # -- fleet observability -------------------------------------------
    def _update_fleet_gauges(self) -> None:
        reports = [r.load_report() for r in self.replicas.values()
                   if r.replica_id not in self.dead]
        waiting = sum(r["queue_wait_depth"] for r in reports)
        goodput = sum(r["goodput_tokens_per_s"] for r in reports)
        self._hub.gauge("serve.fleet.replicas_alive", len(reports))
        self._hub.gauge("serve.fleet.replicas_dead", len(self.dead))
        self._hub.gauge("serve.fleet.queue_wait_depth", waiting)
        self._hub.gauge("serve.fleet.pending_requests", self.pending())
        self._hub.gauge("serve.fleet.goodput_tokens_per_s", goodput)
        if self.autoscale is not None:
            self.autoscale.update(
                n_replicas=max(1, len(reports)),
                queue_wait_depth=waiting,
                slo_miss_rate=self._slo_miss_rate(),
                goodput_tokens_per_s=goodput)

    def _slo_miss_rate(self, last: int = 128) -> float:
        total = misses = 0
        for r in self.replicas.values():
            tracer = r.engine.tracer
            for t in tracer.finished(last=last):
                total += 1
                if tracer.is_slo_miss(t):
                    misses += 1
        return misses / total if total else 0.0

    def traces_by_replica(self) -> Dict[int, List[Any]]:
        return {rid: r.engine.tracer.finished()
                for rid, r in self.replicas.items()}

    def slo_attribution(self, deadline_s: Optional[float] = None
                        ) -> Dict[str, Any]:
        """Fleet-level "why did p99 miss": one attribution report over
        every replica's finished traces, plus the per-replica counts the
        single-replica report cannot show."""
        from deepspeed_tpu.observability.request_trace import \
            slo_attribution

        by_replica = self.traces_by_replica()
        all_traces = [t for ts in by_replica.values() for t in ts]
        report = slo_attribution(all_traces, deadline_s=deadline_s)
        report["per_replica"] = {
            rid: {"traces": len(ts),
                  "slo_misses": sum(
                      1 for t in ts
                      if self.replicas[rid].engine.tracer.is_slo_miss(t))}
            for rid, ts in by_replica.items()}
        return report

    def export_perfetto(self, path: str) -> str:
        """One Perfetto file, one lane group per replica (shared
        wall-clock base, so handoffs and failovers line up)."""
        from deepspeed_tpu.observability.chrome_trace import \
            export_fleet_request_traces

        return export_fleet_request_traces(path, self.traces_by_replica())

    def fleet_snapshot(self, deadline_s: Optional[float] = None
                       ) -> Dict[str, Any]:
        """The ``serve_top --fleet`` document: load reports, router
        stats, autoscale state, and fleet SLO attribution."""
        with self._lock:
            stats = dict(self.stats)
            dead = sorted(self.dead)
            now = time.monotonic()
            health = {
                str(rid): {
                    "state": ("dead" if rid in self.dead
                              else self._route_state(rid, now)),
                    "transitions": self._health.get(rid, {}).get(
                        "transitions", 0),
                }
                for rid in self.replicas}
        snap = {
            "schema": "serving_fleet/v3",
            "ts": wall_time(),  # fleet clock domain, not raw time.time
            "mode": "disagg" if self.disagg else "unified",
            "replicas": [r.load_report()
                         for r in self.replicas.values()],
            "dead_replicas": dead,
            "health": health,
            "router": stats,
            "slo_attribution": self.slo_attribution(deadline_s),
        }
        if self.autoscale is not None:
            snap["autoscale"] = self.autoscale.snapshot()
        if self.alerter is not None:
            snap["alerts"] = self.alerter.snapshot()
        clock = {
            str(rid): info for rid, r in self.replicas.items()
            if (info := getattr(r, "clock_info", lambda: None)())
            is not None}
        if clock:
            snap["clock"] = clock
        jr = get_journal()
        if jr is not None:
            # v3: the black-box handle — where the journal lives and
            # how much it has captured, so an incident snapshot points
            # straight at its own replay artifact
            snap["journal"] = jr.snapshot()
        return snap

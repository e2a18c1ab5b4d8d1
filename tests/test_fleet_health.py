"""Replica health state machine, hedged requests, crash-loop
containment, and the monotonic-clock liveness contract — jax-light:
every test drives the real FleetRouter/ReplicaSupervisor code over fake
replicas/processes, so the whole file runs in the smoke tier.

The contracts under test (docs/serving.md "Replica health"):
- healthy -> suspect -> dead with hysteresis: demotion is immediate,
  promotion needs ``health_recover_checks`` consecutive clean checks;
- a suspect replica stops receiving NEW routes but keeps its in-flight
  streams (no premature failover);
- consecutive transport errors demote and eventually kill a replica
  even while its heartbeats look fresh;
- hedged requests: a stalled primary is raced by a second replica,
  whichever emits first owns the stream, the loser's emissions are
  dropped (greedy decode makes the winner bit-identical either way);
- liveness runs on the MONOTONIC clock — stepping the wall clock an
  hour forward must not fail anyone over;
- ``health_mode="legacy"`` + hedging off reproduces the pre-state-
  machine routing bit-exactly (the off-switch);
- the supervisor's circuit breaker: restarts back off exponentially,
  a lineage crashing more than ``max_restarts_per_window`` times is
  quarantined exactly once, and drains below ``min_healthy`` are
  refused.
"""

import time
import warnings

import numpy as np
import pytest

from deepspeed_tpu.serving.replica import Submission
from deepspeed_tpu.serving.router import FleetRouter
from deepspeed_tpu.serving.supervisor import (RemoteEngineView,
                                              RemoteReplica,
                                              ReplicaSupervisor)

PROMPT = np.arange(12, dtype=np.int32)


class FakeReplica:
    """The ServingReplica surface with hand-settable observables —
    heartbeat age, transport errors, and a submission log — so tests
    drive the router's health machine deterministically."""

    def __init__(self, rid, role="unified"):
        self.replica_id = rid
        self.name = f"r{rid}"
        self.role = role
        self.engine = RemoteEngineView(8, 64, 64)
        self.emit_callback = None
        self.killed = False
        self._send_failed = False
        self.transport_errors = 0
        self._hb_mono = time.monotonic()
        self.submissions = []

    def heartbeat_age(self, now=None):
        now = time.monotonic() if now is None else now
        return now - self._hb_mono

    def alive(self, now=None, stale_after=5.0):
        return self.heartbeat_age(now) < stale_after

    def load_report(self, now=None):
        return {"replica": self.replica_id, "role": self.role,
                "steps": 0, "queue_wait_depth": len(self.submissions),
                "live_seqs": 0, "inflight": len(self.submissions),
                "kv_free_blocks": 64, "kv_free_frac": 1.0,
                "goodput_tokens_per_s": 0.0, "killed": self.killed}

    def load_score(self):
        return float(len(self.submissions))

    def submit(self, sub):
        self.submissions.append(sub)

    def serialize_handoff(self, tokens, cb):
        cb(None)

    def pump(self, eos_token_id=None):
        return {}

    def start(self, **kw):
        pass

    def stop(self):
        pass


def make_router(n=2, **kw):
    reps = [FakeReplica(i) for i in range(n)]
    kw.setdefault("affinity_blocks", 0)
    kw.setdefault("stale_after_s", 10.0)
    return FleetRouter(reps, **kw), reps


class TestHealthStateMachine:
    def test_demotion_immediate_promotion_hysteretic(self):
        router, reps = make_router(health_recover_checks=2)
        base = time.monotonic()
        reps[1]._hb_mono = base - 6.0  # past suspect (5), under dead (10)
        assert router.check_health(base) == []
        assert router._health[1]["state"] == "suspect"
        assert router._health[1]["transitions"] == 1
        # heartbeat recovers: ONE clean check is not enough
        reps[1]._hb_mono = base
        router.check_health(base + 0.1)
        assert router._health[1]["state"] == "suspect"
        # routing still avoids the mid-recovery suspect
        assert router.submit(100, PROMPT, 4) == 0
        router.check_health(base + 0.2)
        assert router._health[1]["state"] == "healthy"
        assert router._health[1]["transitions"] == 2

    def test_suspect_loses_new_routes_keeps_inflight(self):
        router, reps = make_router()
        reps[0].submissions.extend(["pad"] * 3)  # r1 is least loaded
        assert router.submit(1, PROMPT, 4) == 1
        base = time.monotonic()
        reps[1]._hb_mono = base - 6.0  # suspect, not dead
        assert router.check_health(base) == []
        assert router.stats["failovers"] == 0  # in-flight stream kept
        # new work goes to the healthy replica despite its higher load
        assert router.submit(2, PROMPT, 4) == 0
        # the suspect's stream still completes normally
        router._on_emissions(reps[1], {1: [5, 6, 7, 8]})
        assert router.results()[1] == [5, 6, 7, 8]

    def test_transport_errors_demote_then_kill(self):
        router, reps = make_router(stale_after_s=1000.0,
                                   transport_error_dead=3)
        reps[0].submissions.extend(["pad"] * 3)
        assert router.submit(3, PROMPT, 4) == 1
        reps[1].transport_errors = 1  # heartbeats fresh, channel flaky
        router.check_health()
        assert router._health[1]["state"] == "suspect"
        reps[1].transport_errors = 3
        assert router.check_health() == [1]
        assert 1 in router.dead
        # the in-flight request was resubmitted with a FAILOVER span
        subs = [s for s in reps[0].submissions
                if isinstance(s, Submission) and s.uid == 3]
        assert subs
        assert any(k == "FAILOVER" for k, _ in subs[-1].span_notes)
        assert router.stats["failed_over_requests"] == 1

    def test_stale_heartbeat_still_kills(self):
        router, reps = make_router()
        assert router.submit(4, PROMPT, 4) in (0, 1)
        base = time.monotonic()
        reps[0]._hb_mono = base - 11.0
        reps[1]._hb_mono = base - 11.0
        # both dead would strand the request; one dies, one survives
        reps[1]._hb_mono = base
        assert router.check_health(base) == [0]
        assert 0 in router.dead

    def test_snapshot_is_v3_with_health_block(self):
        router, reps = make_router()
        base = time.monotonic()
        reps[1]._hb_mono = base - 11.0
        router.check_health(base)
        snap = router.fleet_snapshot()
        assert snap["schema"] == "serving_fleet/v3"
        assert snap["health"]["0"]["state"] == "healthy"
        assert snap["health"]["1"]["state"] == "dead"
        assert {"hedged", "hedge_wins"} <= set(snap["router"])


class TestHedgedRequests:
    def _hedged_router(self):
        return make_router(stale_after_s=1000.0, hedge_enabled=True,
                           hedge_ttft_factor=2.0, hedge_min_s=0.01)

    def test_stalled_primary_is_hedged_and_loser_dropped(self):
        router, reps = self._hedged_router()
        assert router.submit(7, PROMPT, max_new_tokens=4) == 0
        time.sleep(0.03)  # primary stalls past the hedge deadline
        router.check_health()
        assert router.stats["hedged"] == 1
        hedge = [s for s in reps[1].submissions if s.uid == 7]
        assert hedge, "no hedge submission reached the second replica"
        assert any(k == "HEDGE" for k, _ in hedge[-1].span_notes)

        # the hedge emits first -> it owns the stream
        stream = [11, 13, 17, 19]
        router._on_emissions(reps[1], {7: stream[:2]})
        assert router.stats["hedge_wins"] == 1
        # the primary finally wakes up; its emissions are stale
        router._on_emissions(reps[0], {7: [99, 98]})
        router._on_emissions(reps[1], {7: stream[2:]})
        # winner-takes-all: the result is exactly the hedge stream —
        # under greedy decode both streams are identical, so this is
        # the bit-identical continuation guarantee
        assert router.results() == {7: stream}

    def test_primary_win_clears_hedge(self):
        router, reps = self._hedged_router()
        router.submit(8, PROMPT, max_new_tokens=2)
        time.sleep(0.03)
        router.check_health()
        assert router.stats["hedged"] == 1
        router._on_emissions(reps[0], {8: [1, 2]})  # primary wins
        assert router.stats["hedge_wins"] == 0
        assert router.results() == {8: [1, 2]}
        # hedge emissions after the primary's first token are stale
        router._on_emissions(reps[1], {8: [1, 2]})
        assert router.results() == {8: [1, 2]}

    def test_dead_primary_promotes_live_hedge(self):
        router, reps = self._hedged_router()
        router.submit(9, PROMPT, max_new_tokens=2)
        time.sleep(0.03)
        router.check_health()
        assert router.stats["hedged"] == 1
        # the primary dies before either stream emitted: the live
        # hedge is promoted instead of resubmitting a third copy
        reps[0]._send_failed = True
        assert router.check_health() == [0]
        assert router.stats["failed_over_requests"] == 0
        router._on_emissions(reps[1], {9: [4, 5]})
        assert router.results() == {9: [4, 5]}

    def test_failover_avoids_hedge_loser(self):
        """After the primary wins the hedge race, the loser still
        streams the uid to the end of its budget — a later failover
        must never resubmit there (two live streams of one uid in one
        engine would interleave)."""
        router, reps = make_router(n=3, stale_after_s=1000.0,
                                   hedge_enabled=True,
                                   hedge_ttft_factor=2.0,
                                   hedge_min_s=0.01)
        reps[1].submissions.append("pad")
        reps[2].submissions.extend(["pad", "pad"])
        assert router.submit(5, PROMPT, max_new_tokens=6) == 0
        time.sleep(0.03)
        router.check_health()
        assert router.stats["hedged"] == 1
        assert any(isinstance(s, Submission) and s.uid == 5
                   for s in reps[1].submissions)  # least-loaded hedge
        router._on_emissions(reps[0], {5: [1, 2]})  # primary wins
        reps[0]._send_failed = True
        assert router.check_health() == [0]
        assert router.stats["failed_over_requests"] == 1
        fo = [s for s in reps[2].submissions
              if isinstance(s, Submission) and s.uid == 5]
        assert fo, "failover skipped the only untainted replica"
        assert any(k == "FAILOVER" for k, _ in fo[-1].span_notes)
        # the loser got exactly its hedge copy, nothing more
        assert sum(1 for s in reps[1].submissions
                   if isinstance(s, Submission) and s.uid == 5) == 1

    def test_failover_parks_when_only_loser_left(self):
        router, reps = self._hedged_router()
        router.submit(6, PROMPT, max_new_tokens=6)
        time.sleep(0.03)
        router.check_health()
        assert router.stats["hedged"] == 1
        router._on_emissions(reps[0], {6: [1, 2]})  # hedge on r1 lost
        reps[0]._send_failed = True
        assert router.check_health() == [0]
        # r1 still streams uid 6: park rather than double-submit
        assert router.stats["failed_over_requests"] == 0
        assert router.stats["stranded"] == 1
        assert sum(1 for s in reps[1].submissions if s.uid == 6) == 1

    def test_hedging_off_never_hedges(self):
        router, reps = make_router(stale_after_s=1000.0)
        router.submit(10, PROMPT, max_new_tokens=2)
        time.sleep(0.03)
        router.check_health()
        assert router.stats["hedged"] == 0
        assert not reps[1].submissions


class TestMonotonicLiveness:
    def test_wall_clock_step_does_not_kill_anyone(self, monkeypatch):
        """Regression: an NTP step (wall clock jumps +1h) must not fail
        healthy replicas over — liveness runs on time.monotonic()."""
        router, reps = make_router()
        remote = RemoteReplica(0, "unified", _FakeChan(), 8, 64, 64)
        remote.handle_message({"type": "emit", "report":
                               reps[0].load_report(), "emitted": {}})
        real = time.time()
        monkeypatch.setattr(time, "time", lambda: real + 3600.0)
        assert remote.alive(stale_after=5.0)
        assert remote.heartbeat_age() < 5.0
        assert router.check_health() == []
        states = [router._health.get(r.replica_id, {}).get(
            "state", "healthy") for r in reps]
        assert states == ["healthy", "healthy"]


class TestLegacyOffSwitch:
    def test_legacy_mode_routes_like_the_old_flip(self):
        """health_mode='legacy' (+ hedging off, chaos off) must
        reproduce the single stale-threshold behavior: a replica inside
        the stale window keeps taking routes no matter how old its
        heartbeat, and death happens only past stale_after_s."""
        legacy, lreps = make_router(health_mode="legacy")
        modern, mreps = make_router()
        # identical healthy fleets route identically
        a = [legacy.submit(i, PROMPT, 4) for i in range(6)]
        b = [modern.submit(i, PROMPT, 4) for i in range(6)]
        assert a == b
        # age one replica into the suspect zone (6s of a 10s window)
        base = time.monotonic()
        for reps in (lreps, mreps):
            reps[0].submissions.extend(["pad"] * 10)
            reps[1]._hb_mono = base - 6.0
        legacy.check_health(base)
        modern.check_health(base)
        # legacy: still routable (the old behavior); modern: shunned
        assert legacy.submit(100, PROMPT, 4) == 1
        assert modern.submit(100, PROMPT, 4) == 0
        # both modes agree on death past the stale threshold
        lreps[1]._hb_mono = base - 11.0
        mreps[1]._hb_mono = base - 11.0
        assert legacy.check_health(base) == [1]
        assert modern.check_health(base) == [1]

    def test_bad_health_mode_rejected(self):
        with pytest.raises(ValueError, match="health_mode"):
            make_router(health_mode="bogus")


# -- supervisor containment (fake processes, real maintain()) ------------


class _FakeChan:
    def __init__(self):
        self.sent = []
        self.bytes_sent = 0
        self.bytes_received = 0
        self.dup_frames = 0

    def send(self, msg):
        self.sent.append(msg)

    def recv(self, timeout=0.0):
        return None

    def close(self):
        pass


class _FakeProc:
    def __init__(self):
        self.rc = None
        self.pid = 4242

    def poll(self):
        return self.rc


def _install(sup, rid, role="unified", lineage=None):
    remote = RemoteReplica(rid, role, _FakeChan(), 8, 64, 64)
    sup.replicas[rid] = remote
    sup._procs[rid] = _FakeProc()
    sup._next_id = max(sup._next_id, rid + 1)
    sup._lineage[rid] = rid if lineage is None else lineage
    sup._env_extra[rid] = {}
    sup._step_delay[rid] = 0.0
    return remote


@pytest.fixture
def faked_supervisor(tmp_path, monkeypatch):
    """A ReplicaSupervisor whose spawn() installs fakes instead of
    forking — maintain()'s containment logic runs unmodified."""
    sup = ReplicaSupervisor(str(tmp_path), jax_platform="cpu",
                            model={"name": "tiny"},
                            max_restarts_per_window=2,
                            restart_window_s=60.0)
    spawned = []

    def fake_spawn(role=None, replica_id=None, step_delay_ms=0.0,
                   env_extra=None, action="spawn", lineage=None):
        rid = sup._next_id
        remote = _install(sup, rid, role or "unified", lineage=lineage)
        sup._env_extra[rid] = dict(env_extra or {})
        sup._step_delay[rid] = float(step_delay_ms)
        sup.actions.append((time.time(), action, rid))
        spawned.append((rid, action, lineage))
        return remote

    monkeypatch.setattr(sup, "spawn", fake_spawn)
    return sup, spawned


class TestCrashLoopContainment:
    def test_backoff_then_quarantine_once(self, faked_supervisor):
        sup, spawned = faked_supervisor
        _install(sup, 0)
        # crash 1: restart is immediate (the pre-breaker behavior)
        sup._procs[0].rc = 1
        acted = sup.maintain()
        assert acted["restarted"] == 1 and acted["quarantined"] == 0
        rid1 = spawned[-1][0]
        assert spawned[-1] == (rid1, "restart", 0)  # lineage carried
        # crash 2: exponential backoff defers the respawn
        sup._procs[rid1].rc = 1
        acted = sup.maintain()
        assert acted["restarted"] == 0
        assert len(sup._pending_restarts) == 1
        assert sup._pending_restarts[0]["due_mono"] > time.monotonic()
        time.sleep(0.3)  # backoff_s(1) = 0.25
        acted = sup.maintain()
        assert acted["restarted"] == 1
        rid2 = spawned[-1][0]
        assert spawned[-1][2] == 0
        # crash 3 in the window: the breaker trips — quarantine, no
        # respawn, exactly one quarantine act (no flapping)
        sup._procs[rid2].rc = 1
        acted = sup.maintain()
        assert acted["quarantined"] == 1 and acted["restarted"] == 0
        assert sup.quarantined == {0}
        acted = sup.maintain()
        assert acted["quarantined"] == 0 and acted["restarted"] == 0
        assert sum(1 for _, a, _r in sup.actions
                   if a == "quarantine") == 1
        snap_restarts = sum(1 for _, a, _r in sup.actions
                            if a == "restart")
        assert snap_restarts == 2  # bounded by the window

    def test_snapshot_carries_containment_state(self, faked_supervisor):
        sup, _ = faked_supervisor
        _install(sup, 0)
        sup._procs[0].rc = 1
        sup.maintain()
        import json
        with open(sup.write_fleet_snapshot()) as f:
            snap = json.load(f)
        s = snap["supervisor"]
        assert s["restarts"] == 1
        assert s["quarantined"] == []
        assert s["min_healthy"] == 1
        assert "transport_errors" in next(iter(s["transport"].values()))


class TestMinHealthyFloor:
    def test_drain_refused_at_the_floor(self, tmp_path):
        sup = ReplicaSupervisor(str(tmp_path), jax_platform="cpu",
                                min_healthy=1)
        _install(sup, 0)
        assert sup.drain(0) is False
        assert sup.actions[-1][1] == "drain_refused"
        assert not sup.replicas[0].draining
        _install(sup, 1)
        assert sup.drain(1) is True
        assert sup.replicas[1].draining
        assert sup.replicas[1].channel.sent[-1] == {"type": "drain"}


class TestConnectPolicyKnobs:
    def test_router_config_builds_retry_policy(self):
        from deepspeed_tpu.config.config import RouterConfig

        cfg = RouterConfig(connect_retries=5,
                           connect_backoff_seconds=0.1,
                           connect_backoff_max_seconds=2.0)
        pol = cfg.connect_retry_policy()
        assert pol.max_retries == 4
        assert pol.backoff_base_s == 0.1
        assert pol.backoff_max_s == 2.0
        assert pol.jitter == 0.0  # deterministic under the chaos gates

    def test_legacy_connect_knobs_warn_once(self, tmp_path, monkeypatch):
        import deepspeed_tpu.serving.supervisor as sup_mod

        monkeypatch.setattr(sup_mod, "_WARNED_LEGACY_CONNECT", False)
        with pytest.warns(DeprecationWarning, match="legacy"):
            ReplicaSupervisor(str(tmp_path / "a"), jax_platform="cpu",
                              connect_retries=10)
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # second time stays silent
            ReplicaSupervisor(str(tmp_path / "b"), jax_platform="cpu",
                              connect_retries=10)

    def test_config_validates_new_knobs(self):
        from deepspeed_tpu.config.config import RouterConfig

        with pytest.raises(ValueError, match="health_mode"):
            RouterConfig(health_mode="bogus").validate()
        with pytest.raises(ValueError, match="min_healthy"):
            RouterConfig(min_healthy=0).validate()
        with pytest.raises(ValueError, match="connect_backoff_max"):
            RouterConfig(connect_backoff_max_seconds=0.01).validate()


class TestSnapshotCompat:
    def test_serve_top_renders_v1_documents(self):
        """The --fleet reader predates the health block; a v1 snapshot
        (old run dirs, old bench artifacts) must still render."""
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        try:
            import serve_top
        finally:
            sys.path.pop(0)
        v1 = {"schema": "serving_fleet/v1", "ts": time.time(),
              "mode": "unified",
              "replicas": [
                  {"replica": 0, "role": "unified", "steps": 3,
                   "queue_wait_depth": 0, "live_seqs": 1, "inflight": 1,
                   "kv_free_frac": 1.0, "goodput_tokens_per_s": 12.5,
                   "killed": False},
                  {"replica": 1, "role": "unified", "steps": 0,
                   "queue_wait_depth": 0, "live_seqs": 0, "inflight": 0,
                   "kv_free_frac": 1.0, "goodput_tokens_per_s": 0.0,
                   "killed": True}],
              "dead_replicas": [1],
              "router": {"submitted": 2, "completed": 1, "handoffs": 0,
                         "failovers": 1}}
        table = serve_top._fleet_table(v1)
        assert "| r0 |" in table and "up" in table
        assert "DEAD" in table  # v1 fallback: the dead set
        assert "submitted=2" in table

"""Zero-downtime fleet operations: live session migration, rolling
weight hot-swap, and migration-backed autoscale (ISSUE 20).

The load-bearing guarantees (docs/serving.md "Zero-downtime
operations"):
- a mid-stream decode session moves between replicas WARM — committed
  KV blocks (any quant rung), the partial tail block, generated tokens
  and the spec-acceptance EWMA ship over the quantized wire, and decode
  resumes on the target with ZERO re-prefill;
- migration degrades gracefully, never errors: warm install -> host-
  tier page-in -> fold-and-recompute -> finish-in-place, each rung
  observable via engine/router counters and MIGRATE journal records;
- a rolling weight swap quiesces one replica at a time (live sessions
  migrate out first), reloads a manifest-validated release, and gates
  every rejoin on A/B canary token parity — a parity failure aborts the
  rollout and rolls the replica back;
- under greedy decoding all of the above is bit-identical to a fleet
  that never migrated, swapped, or scaled.

In-process tests run smoke-tier; the process-level e2e drills (socket
fleets, SIGKILL mid-migration, the full deploy drill) are tiered slow
via tests/slow_tests.txt.
"""

import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.serving import (FleetRouter, ReplicaSupervisor,
                                   ServingReplica, install_session,
                                   serialize_session)

MODEL_SPEC = {"name": "tiny",
              "overrides": {"dtype": "float32", "param_dtype": "float32"}}
ENGINE_DEFAULTS = dict(kv_blocks=64, kv_block_size=8,
                       max_tokens_per_step=32, max_seqs_per_step=4,
                       max_blocks_per_seq=8,
                       request_trace={"sample_rate": 1.0})
ENGINE_SPEC = dict(ENGINE_DEFAULTS, dtype="float32")

PROMPT = ((np.arange(20) * 3 + 1) % 100).astype(np.int32)


@pytest.fixture(scope="module")
def tiny():
    model = get_model("tiny", dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def make_engine(tiny, **kw):
    from deepspeed_tpu.inference import InferenceEngineV2

    model, params = tiny
    for k, v in ENGINE_DEFAULTS.items():
        kw.setdefault(k, v)
    return InferenceEngineV2(model, params=params, dtype=jnp.float32, **kw)


def make_fleet(tiny, n=2, router_kw=None, **engine_kw):
    model, params = tiny
    for k, v in ENGINE_DEFAULTS.items():
        engine_kw.setdefault(k, v)
    replicas = [ServingReplica.create(model, i, role="unified",
                                      params=params, dtype=jnp.float32,
                                      **engine_kw)
                for i in range(n)]
    return FleetRouter(replicas, **(router_kw or {}))


def reference_stream(tiny, prompt, gen, uid=1):
    eng = make_engine(tiny)
    eng.put([uid], [np.asarray(prompt, np.int32)], max_new_tokens=gen)
    return list(eng.generate_all()[uid])


def capture_midstream(tiny, gen=24, steps=2, wire=None, **engine_kw):
    """A source engine with uid 1 provably mid-decode, serialized —
    capture releases the session on the source."""
    fleet = make_fleet(tiny, n=1, **engine_kw)
    fleet.submit(1, PROMPT, max_new_tokens=gen)
    for _ in range(steps):
        fleet.step()
    rec = fleet._requests[1]
    assert 0 < len(rec.emitted) < gen, "session not mid-stream"
    src = fleet.replicas[0].engine
    sess = serialize_session(src, 1, wire=wire)
    assert sess is not None
    return sess, list(rec.emitted)


# -- the session wire ----------------------------------------------------


class TestSessionWire:
    def test_fp8_wire_native_alongside_int8_int4(self, tiny):
        """Satellite: fp8 rides WIRE_MODES natively (e4m3 payload +
        per-vector scales, no bf16 round trip), SNR-measured at
        serialize time like int8/int4."""
        grabs = {w: capture_midstream(tiny, wire=w)[0]
                 for w in ("raw", "int8", "fp8", "int4")}
        raw, i8, f8, i4 = (grabs[w] for w in
                           ("raw", "int8", "fp8", "int4"))
        assert raw.wire_bits is None and raw.wire_snr_db is None
        assert i8.wire_bits == 8 and not i8.packed
        assert f8.wire_bits == "fp8" and not f8.packed
        assert i4.wire_bits == 4 and i4.packed
        # bytes: fp8 is the int8-sized rung (1 byte/elem + scales),
        # int4 packs two to a byte; all quantized rungs beat raw bf16
        assert f8.wire_nbytes == i8.wire_nbytes
        assert f8.wire_nbytes <= 0.6 * raw.wire_nbytes
        assert i4.wire_nbytes < f8.wire_nbytes
        # SNR ladder: every rung measured, int8 (7-bit mantissa-free
        # grid) beats fp8 (3-bit mantissa), and nothing is junk
        for h in (i8, f8, i4):
            assert h.wire_snr_db is not None and h.wire_snr_db > 10.0
        assert i8.wire_snr_db > f8.wire_snr_db

    def test_fp8_wire_installs_and_completes(self, tiny):
        sess, emitted = capture_midstream(tiny, wire="fp8")
        dst = make_engine(tiny)
        assert install_session(dst, sess) == "resumed"
        out = dst.generate_all()
        assert len(emitted) + len(out[1]) == 24
        assert dst.stats["migrated_in"] == 1

    def test_bad_wire_mode_rejected(self, tiny):
        fleet = make_fleet(tiny, n=1)
        fleet.submit(1, PROMPT, max_new_tokens=8)
        fleet.step()
        with pytest.raises(ValueError):
            serialize_session(fleet.replicas[0].engine, 1, wire="int2")


# -- warm migration ------------------------------------------------------


class TestWarmMigration:
    def test_bit_identical_zero_reprefill_ewma_travels(self, tiny):
        """The tentpole contract in one run: a mid-stream session moves
        warm, the target re-prefills NOTHING, the adaptive-speculation
        EWMA survives the move, and the stream is bit-identical to a
        fleet that never migrated."""
        gen = 40
        ref = reference_stream(tiny, PROMPT, gen)
        fleet = make_fleet(tiny, n=2)
        fleet.submit(1, PROMPT, max_new_tokens=gen)
        for _ in range(2):
            fleet.step()
        rec = fleet._requests[1]
        assert 0 < len(rec.emitted) < gen
        src_rid = rec.replica_id
        src = fleet.replicas[src_rid].engine
        src._seq_accept_ewma[1] = 0.7  # the adaptive-k signal
        fleet.remove_replica(src_rid)
        counts = fleet.migrate_sessions(src_rid, reason="drain")
        assert counts == {"requested": 1, "skipped": 0}
        fleet.step()  # pump: capture on src, install on target
        tgt_rid = fleet._requests[1].replica_id
        assert tgt_rid != src_rid
        tgt = fleet.replicas[tgt_rid].engine
        assert tgt.stats["migrated_in"] == 1
        assert tgt.stats["migrate_resume_tokens"] > 0
        # zero re-prefill: the target never ran a prefill for anything
        assert tgt.scheduler.stats.get("prefill_tokens", 0) == 0
        assert tgt._seq_accept_ewma.get(1) == pytest.approx(0.7)
        assert 1 not in src._seq_accept_ewma
        assert src.stats["migrated_out"] == 1
        fleet.run_until_complete()
        res = fleet.results()[1]
        assert list(res) == ref
        assert fleet.stats["migrations"] == 1
        assert fleet.stats["migrate_wire_bytes"] > 0

    def test_a_burst_in_flight_is_read_before_the_session_leaves(self, tiny):
        """One slot a replica, so a lone session is a full batch and its
        replica keeps a decode burst in flight between steps: the capture
        reads it first, those tokens reach the router before the session
        changes hands, and the stream is the unmigrated one."""
        gen = 40
        ref = reference_stream(tiny, PROMPT, gen)
        fleet = make_fleet(tiny, n=2, max_seqs_per_step=1)
        fleet.submit(1, PROMPT, max_new_tokens=gen)
        for _ in range(3):
            fleet.step()
        rec = fleet._requests[1]
        src_rid = rec.replica_id
        src = fleet.replicas[src_rid].engine
        assert src._inflight is not None and src.stats["calls_issued_ahead"]
        had = len(rec.emitted)
        fleet.remove_replica(src_rid)
        assert fleet.migrate_sessions(src_rid)["requested"] == 1
        fleet.step()
        assert src._inflight is None and not src._undelivered
        assert len(rec.emitted) >= had + src.decode_steps
        assert rec.replica_id != src_rid
        fleet.run_until_complete()
        assert list(fleet.results()[1]) == ref
        assert fleet.stats["migrations"] == 1

    def test_transport_death_degrades_to_recompute(self, tiny):
        """A capture that never lands (the RPC path hands the callback
        None) folds emitted tokens and recomputes — bit-identical, the
        recompute counter bumped, never an error."""
        gen = 24
        ref = reference_stream(tiny, PROMPT, gen)
        fleet = make_fleet(tiny, n=2)
        fleet.submit(1, PROMPT, max_new_tokens=gen)
        for _ in range(2):
            fleet.step()
        src_rid = fleet._requests[1].replica_id
        src = fleet.replicas[src_rid]
        src.migrate_out = lambda uid, cb, wire=None: cb(None)
        fleet.remove_replica(src_rid)
        assert fleet.migrate_sessions(src_rid)["requested"] == 1
        fleet.run_until_complete()
        assert list(fleet.results()[1]) == ref
        assert fleet.stats["migrate_recompute"] == 1
        assert fleet.stats["migrations"] == 0

    def test_no_eligible_target_finishes_in_place(self, tiny):
        """Pool of one: the ladder's last rung — the session stays put,
        the skip counter says so, and the draining replica finishes
        what it holds."""
        fleet = make_fleet(tiny, n=1)
        fleet.submit(1, PROMPT, max_new_tokens=16)
        fleet.step()
        fleet.remove_replica(0)
        counts = fleet.migrate_sessions(0)
        assert counts == {"requested": 0, "skipped": 1}
        assert fleet.stats["migrate_skipped"] == 1
        fleet.run_until_complete()
        assert len(fleet.results()[1]) == 16


# -- the degradation matrix (install side) -------------------------------


class TestInstallDegradation:
    def test_no_room_pages_into_host_tier(self, tiny):
        """Target has no slot for the session RIGHT NOW + host tier on:
        the warm bytes park in the tier (paged rung) and resume warm at
        readmission — still zero recompute."""
        sess, emitted = capture_midstream(tiny, gen=24)
        dst = make_engine(tiny, max_seqs_per_step=1, host_kv_tier=True)
        dst.put([9], [PROMPT], max_new_tokens=8)  # occupies the slot
        rung = install_session(dst, sess)
        assert rung == "paged"
        assert dst.stats["migrate_paged"] == 1
        out = dst.generate_all()
        assert len(emitted) + len(out[1]) == 24

    def test_no_room_no_tier_recomputes(self, tiny):
        sess, emitted = capture_midstream(tiny, gen=24)
        dst = make_engine(tiny, max_seqs_per_step=1)
        dst.put([9], [PROMPT], max_new_tokens=8)
        rung = install_session(dst, sess)
        assert rung == "recompute"
        assert dst.stats["migrate_recompute"] == 1
        out = dst.generate_all()
        # recompute re-prefills prompt+generated and finishes the budget
        assert len(emitted) + len(out[1]) == 24

    def test_geometry_mismatch_recomputes(self, tiny):
        sess, emitted = capture_midstream(tiny, gen=24)
        odd = make_engine(tiny, kv_block_size=16, kv_blocks=32,
                          max_blocks_per_seq=4)
        assert install_session(odd, sess) == "recompute"
        out = odd.generate_all()
        assert len(emitted) + len(out[1]) == 24

    def test_unknown_wire_rung_recomputes(self, tiny):
        sess, emitted = capture_midstream(tiny, gen=24)
        sess.wire_bits = 3  # a rung this build does not speak
        dst = make_engine(tiny)
        assert install_session(dst, sess) == "recompute"
        out = dst.generate_all()
        assert len(emitted) + len(out[1]) == 24

    def test_uid_already_live_is_duplicate(self, tiny):
        sess, _ = capture_midstream(tiny, gen=24)
        dst = make_engine(tiny)
        dst.put([1], [PROMPT], max_new_tokens=4)
        assert install_session(dst, sess) == "duplicate"
        dst.flush([1])


# -- journal forensics ---------------------------------------------------


class TestOpsJournal:
    def test_migrate_swap_scale_records_roundtrip_and_render(
            self, tmp_path):
        from deepspeed_tpu.observability.journal import (
            DECISION_KINDS, FleetJournal, load_journal,
            render_incident_log)

        for kind in ("MIGRATE", "SWAP", "SCALE"):
            assert kind in DECISION_KINDS
        path = str(tmp_path / "ops.journal")
        jr = FleetJournal(path)
        jr.write_header({"combined": "test"})
        jr.decision("MIGRATE", uid=5, from_replica=0, to_replica=1,
                    reason="drain", rung="warm", recovered_tokens=9,
                    source_score=2.5, target_score=0.5,
                    wire_bytes=4096, n_blocks=2)
        jr.decision("SWAP", tag="v2", replica=1, stage="parity",
                    ok=True, canaries=2, divergent=[])
        jr.decision("SCALE", action="drain", replica=3, desired=2,
                    live=2, direction="down", migrations=1)
        jr.close()
        recs = load_journal(path)
        kinds = [r.get("kind") for r in recs]
        assert {"MIGRATE", "SWAP", "SCALE"} <= set(kinds)
        text = "\n".join(render_incident_log(recs))
        # decisions render WITH the inputs that drove them
        assert "MIGRATE   uid=5 r0->r1 rung=warm" in text
        assert "source_score=2.5" in text
        assert "SWAP      tag=v2 r1 stage=parity ok=True" in text
        assert "SCALE     drain r3 desired=2 live=2" in text

    def test_router_migration_journals_decision(self, tiny, tmp_path):
        from deepspeed_tpu.observability.journal import (FleetJournal,
                                                         load_journal,
                                                         reset_journal,
                                                         set_journal)

        path = str(tmp_path / "mig.journal")
        jr = FleetJournal(path)
        set_journal(jr)
        try:
            fleet = make_fleet(tiny, n=2)
            fleet.submit(1, PROMPT, max_new_tokens=24)
            for _ in range(2):
                fleet.step()
            src_rid = fleet._requests[1].replica_id
            fleet.remove_replica(src_rid)
            fleet.migrate_sessions(src_rid, reason="scale_down")
            fleet.run_until_complete()
        finally:
            reset_journal()
        migs = [r for r in load_journal(path)
                if r.get("kind") == "MIGRATE"]
        assert len(migs) == 1
        m = migs[0]
        assert m["uid"] == 1 and m["reason"] == "scale_down"
        assert m["rung"] == "warm" and m["wire_bytes"] > 0
        assert m["from_replica"] == src_rid
        # the triggering inputs ride the record
        assert "source_score" in m and "target_score" in m


# -- config surface ------------------------------------------------------


class TestOpsConfig:
    def test_migration_fields_default_and_validate(self):
        from deepspeed_tpu.config.config import (RouterConfig,
                                                 ServingConfig)

        rc = RouterConfig()
        assert rc.migrate_sessions is True
        assert rc.migrate_hedges is False
        assert rc.migrate_wire == ""
        rc.validate()
        RouterConfig(migrate_wire="fp8").validate()
        with pytest.raises(ValueError):
            RouterConfig(migrate_wire="int2").validate()
        ServingConfig(handoff_wire="fp8").validate()

    def test_build_fleet_threads_migration_knobs(self, tiny):
        from deepspeed_tpu.config.config import RouterConfig
        from deepspeed_tpu.serving import build_fleet

        model, params = tiny
        cfg = RouterConfig(replicas=2, migrate_sessions=False,
                           migrate_hedges=True, migrate_wire="int8")
        fleet = build_fleet(model, cfg,
                            engine_kw=dict(ENGINE_DEFAULTS,
                                           params=params,
                                           dtype=jnp.float32))
        assert fleet.migrate_enabled is False
        assert fleet.migrate_hedges is True
        assert fleet.migrate_wire == "int8"
        assert fleet.migrate_sessions(0) == {"requested": 0,
                                             "skipped": 0}


# -- process-level e2e drills (slow tier) --------------------------------


def _proc_fleet(run_dir, n=2, seed=0):
    sup = ReplicaSupervisor(str(run_dir), jax_platform="cpu",
                            model=MODEL_SPEC,
                            engine=dict(ENGINE_SPEC), seed=seed,
                            min_healthy=1)
    remotes = [sup.spawn(role="unified") for _ in range(n)]
    router = FleetRouter(remotes, stale_after_s=2.0, affinity_blocks=0,
                         routing="least_loaded")
    sup.router = router
    return sup, router


def _wait_midstream(sup, router, uid, timeout_s=60.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        sup.maintain()
        router.check_health()
        rec = router._requests.get(uid)
        if rec is not None and not rec.done and len(rec.emitted) >= 2:
            return rec
        time.sleep(0.02)
    raise TimeoutError(f"uid={uid} never got mid-stream")


class TestProcMigration:
    def test_drain_migrates_warm_over_socket(self, tiny, tmp_path):
        """Supervisor drain = migrate-first over the real socket
        transport: the session resumes warm on the survivor and the
        stream is bit-identical to the never-migrated reference."""
        gen = 40
        ref = reference_stream(tiny, PROMPT, gen)
        sup, router = _proc_fleet(tmp_path)
        try:
            router.submit(1, PROMPT, max_new_tokens=gen)
            rec = _wait_midstream(sup, router, 1)
            assert sup.drain(rec.replica_id, reason="drain")
            sup.run_until_drained(timeout_s=120.0)
            assert list(router.results()[1]) == ref
            assert router.stats["migrations"] == 1
            survivor = router.replicas[router._requests[1].replica_id]
            assert survivor.load_report().get("migrated_in", 0) >= 1
            acts = {a[1] for a in sup.actions}
            assert "drain" in acts
        finally:
            sup.shutdown()

    def test_sigkill_mid_migration_never_drops(self, tiny, tmp_path):
        """The worker dies BETWEEN capture request and payload: the
        ladder lands on fold-and-recompute via failover/expiry — zero
        drops, bit-identical, no error."""
        gen = 40
        ref = reference_stream(tiny, PROMPT, gen)
        sup, router = _proc_fleet(tmp_path)
        try:
            router.submit(1, PROMPT, max_new_tokens=gen)
            rec = _wait_midstream(sup, router, 1)
            victim = rec.replica_id
            sup.kill(victim)  # SIGKILL: the capture RPC can never land
            router.remove_replica(victim)
            router.migrate_sessions(victim, reason="drain")
            sup.run_until_drained(timeout_s=120.0)
            assert list(router.results()[1]) == ref
            # recovery rung is environment-timing dependent (failover
            # vs expired-capture recompute) but it is never a drop and
            # never a warm install from a dead worker
            assert (router.stats["failed_over_requests"]
                    + router.stats["migrate_recompute"]) >= 1
        finally:
            sup.shutdown()


class TestRollingSwap:
    def test_same_seed_swap_parity_and_corrupt_abort(self, tiny,
                                                     tmp_path):
        """One fleet, both exits of the parity gate: a same-seed
        release rolls across every replica (canary parity holds), then
        a release with corrupted canary chains ABORTS the rollout,
        rolls the replica back, and the fleet still serves."""
        canaries = [list(map(int, PROMPT[:10])),
                    list(map(int, PROMPT[5:17]))]
        sup, router = _proc_fleet(tmp_path)
        try:
            sup.publish_weights("v2", seed=0, canary_prompts=canaries)
            res = sup.rolling_swap("v2", timeout_s=60.0)
            assert res["swapped"] == 2 and not res["aborted"]
            assert res["parity_ok"] and res["rolled_back"] == 0
            # every replica rejoined the pools
            assert len(router.decode_pool) == 2

            sup.publish_weights("bad", seed=0,
                                canary_prompts=canaries,
                                canary_chains={"0": [12345]})
            bad = sup.rolling_swap("bad", timeout_s=60.0)
            assert bad["aborted"] and bad["parity_ok"] is False
            assert bad["rolled_back"] == 1 and bad["swapped"] == 0
            assert "parity" in (bad["error"] or "")
            acts = [a[1] for a in sup.actions]
            assert "swap_done" in acts and "swap_abort" in acts
            assert "swap_rollback" in acts

            # the fleet is intact and still serving after the abort
            router.submit(7, PROMPT, max_new_tokens=8)
            sup.run_until_drained(timeout_s=90.0)
            assert list(router.results()[7]) == \
                reference_stream(tiny, PROMPT, 8, uid=7)
        finally:
            sup.shutdown()

    def test_torn_release_aborts_before_any_replica(self, tmp_path):
        sup, router = _proc_fleet(tmp_path)
        try:
            ckpt = sup.publish_weights("v3", seed=0)
            with open(os.path.join(ckpt, "weights.json"), "a") as f:
                f.write("  ")  # torn write: manifest checksum breaks
            res = sup.rolling_swap("v3", timeout_s=30.0)
            assert res["aborted"] and res["swapped"] == 0
            assert "Corrupt" in res["error"] or "error" in res
            assert len(router.decode_pool) == 2  # nobody was touched
        finally:
            sup.shutdown()


class TestDeployDrillBench:
    def test_deploy_drill_bench_e2e(self, monkeypatch, tmp_path):
        """The full make deploy-drill gate: quiet reference arm vs the
        kill + rolling swap + autoscale swing + corrupted-canary drill
        arm, zero drops, bit-identical streams, >=1 warm migration."""
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import serve_bench

        # default knobs: shrinking the workload lets the long session
        # finish before the swap's quiesce reaches its replica, and the
        # warm-migration gate would then race instead of certify
        monkeypatch.setenv("DRILL_RUN_DIR", str(tmp_path))
        payload = serve_bench.run_deploy_drill()
        assert payload["ok"], payload["violations"]
        assert payload["drill.zero_drops"] is True
        assert payload["drill.bit_identical"] is True
        assert payload["drill.warm_migrations"] >= 1
        assert payload["swap.parity_ok"] is True
        assert payload["swap.abort_ok"] is True
        assert payload["migrate.wire_bytes_per_session"] > 0
        drill = payload["arms"]["drill"]
        assert drill["restarts"] >= 1  # the SIGKILL was survived
        assert drill["spawns"] >= 1 and drill["drains"] >= 1

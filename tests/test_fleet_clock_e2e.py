"""Fleet clock-sync e2e drill: a two-worker process fleet whose worker
wall clocks are stepped +/-250 ms (``DSTPU_CLOCK_SKEW_S`` injected via
``spawn(env_extra=...)``) must still produce ONE coherent timeline.

The acceptance criteria this file certifies (docs/observability.md
"Fleet tracing & clock sync"):

- each worker channel's NTP-style estimator recovers its replica's
  injected skew within the estimator's own reported uncertainty;
- traces ingested by the supervisor arrive rebased into router time:
  stamps land inside the router's wall-clock window even though the raw
  worker stamps were up to 250 ms acausal (a -250 ms worker "enqueues"
  requests before the router submitted them);
- the merged Perfetto export over those traces is causally ordered with
  per-lane clock metadata, no double-shifting;
- the live metrics plane (heartbeat-piggybacked hub snapshots, no
  shared run dir) merges to exactly the work the fleet did, and the
  fleet snapshot carries both the clock block and the merged metrics.

Spawns jax worker subprocesses (~5s startup each): slow tier
(tests/slow_tests.txt). The estimator math and the transport-level
ping/pong are covered jax-free in the smoke tier by
tests/test_clocksync.py.
"""

import json
import os
import time

import numpy as np
import pytest

from deepspeed_tpu.serving import FleetRouter, ReplicaSupervisor

MODEL_SPEC = {"name": "tiny",
              "overrides": {"dtype": "float32", "param_dtype": "float32"}}
ENGINE_SPEC = dict(kv_blocks=64, kv_block_size=8, max_tokens_per_step=32,
                   max_seqs_per_step=4, max_blocks_per_seq=8,
                   request_trace={"sample_rate": 1.0}, dtype="float32")

SKEW_S = 0.25  # per-worker wall-clock step, opposite signs
N_REQ = 6
GEN = 8


def shared_prompts(n, prefix_len=16, tail=4):
    base = ((np.arange(prefix_len) * 5 + 3) % 97).astype(np.int32)
    return [np.concatenate(
        [base, ((np.arange(tail) * 7 + 11 * i) % 89).astype(np.int32)])
        for i in range(n)]


@pytest.fixture(scope="module")
def skewed_fleet(tmp_path_factory):
    """One +/-250 ms two-worker fleet, driven to drained once; every
    test reads the same aftermath (the drill is the expensive part)."""
    run_dir = tmp_path_factory.mktemp("skewed_fleet")
    sup = ReplicaSupervisor(str(run_dir), jax_platform="cpu",
                            model=MODEL_SPEC,
                            engine=dict(ENGINE_SPEC), seed=0)
    skews = {}
    remotes = []
    for skew in (SKEW_S, -SKEW_S):
        r = sup.spawn(role="unified",
                      env_extra={"DSTPU_CLOCK_SKEW_S": repr(skew)})
        skews[r.replica_id] = skew
        remotes.append(r)
    # affinity off: the shared prompt prefix must not pin every request
    # to one worker — the drill needs both clock domains exercised
    router = FleetRouter(remotes, stale_after_s=5.0,
                         routing="least_loaded", affinity_blocks=0)
    sup.router = router
    deadline = time.time() + 60.0
    while time.time() < deadline:
        if all(r.load_report()["ts"] > 0 for r in remotes):
            break
        time.sleep(0.05)
    else:
        raise TimeoutError("workers never heartbeat")
    t_submit = time.time()
    for i, p in enumerate(shared_prompts(N_REQ)):
        router.submit(i, p, max_new_tokens=GEN)
    sup.run_until_drained(timeout_s=120.0)
    t_done = time.time()
    yield sup, router, skews, str(run_dir), t_submit, t_done
    sup.shutdown()


class TestSkewedFleetOneTimeline:
    def test_estimators_recover_injected_skew(self, skewed_fleet):
        """Each channel's clock estimate lands on its worker's injected
        step, within the estimator's OWN uncertainty bound (+ a small
        scheduling-noise floor) — the honest-bound property, end to end
        through real subprocesses."""
        sup, router, skews, *_ = skewed_fleet
        for rid, r in sup.replicas.items():
            info = r.clock_info()
            assert info is not None and info["synced"], \
                f"r{rid} never converged: {info}"
            off_s = info["offset_ms"] / 1e3
            unc_s = info["uncertainty_ms"] / 1e3
            err = abs(off_s - skews[rid])
            assert err <= unc_s + 5e-3, \
                (f"r{rid}: est {off_s:+.4f}s vs injected "
                 f"{skews[rid]:+.3f}s escapes bound {unc_s:.4f}s")
            assert err < 0.1  # absolute sanity: way under the 250ms step

    def test_ingested_traces_rebased_into_router_window(self, skewed_fleet):
        """Supervisor-ingested traces are already in router time: every
        stamp inside the router's [submit, drained] wall window, the
        recorded per-trace offset matching the replica's skew — while
        the raw worker stamps (stamp + clock_offset_s) were acausal for
        the -250 ms worker."""
        sup, router, skews, _, t_submit, t_done = skewed_fleet
        by_rep = router.traces_by_replica()
        traced = {rid: ts for rid, ts in by_rep.items() if ts}
        assert sum(len(ts) for ts in traced.values()) == N_REQ
        assert len(traced) == 2, \
            f"least_loaded left a worker idle: {sorted(traced)}"
        for rid, traces in traced.items():
            for t in traces:
                assert t.clock_domain is not None, \
                    f"r{rid} uid={t.uid} ingested unrebased"
                assert abs(t.clock_offset_s - skews[rid]) < 0.1
                for ts in (t.enqueue_ts, t.first_token_ts, t.finish_ts):
                    assert t_submit - 0.1 <= ts <= t_done + 0.1, \
                        (f"r{rid} uid={t.uid}: rebased stamp {ts:.3f} "
                         f"outside [{t_submit:.3f}, {t_done:.3f}]")
        # the -250ms worker's RAW stamps really were causally broken:
        # its un-rebased enqueue predates the router's first submit
        behind = [rid for rid, s in skews.items()
                  if s < 0 and rid in traced]
        assert behind
        raw_enq = min(t.enqueue_ts + t.clock_offset_s
                      for t in traced[behind[0]])
        assert raw_enq < t_submit - 0.15

    def test_trace_context_joins_both_domains(self, skewed_fleet):
        """The Dapper join: ROUTE spans shipped back from the skewed
        workers still carry the router-stamped fleet_trace_id and
        parent clock-domain label."""
        sup, router, *_ = skewed_fleet
        routes = [s for ts in router.traces_by_replica().values()
                  for t in ts for s in t.spans if s.kind == "ROUTE"]
        assert len(routes) == N_REQ
        for s in routes:
            assert s.fields["parent_domain"] == "router"
            assert s.fields["fleet_trace_id"].startswith("fleet-")

    def test_merged_perfetto_causally_ordered(self, skewed_fleet):
        """export_fleet_merged_trace over the (already rebased) lanes:
        every event inside the drill's wall window — a raw +/-250 ms
        export would spread an extra half second — and each lane's
        process metadata carries its clock offset/uncertainty."""
        from deepspeed_tpu.observability.chrome_trace import \
            export_fleet_merged_trace

        sup, router, skews, run_dir, t_submit, t_done = skewed_fleet
        lanes = []
        for rid, traces in sorted(router.traces_by_replica().items()):
            info = sup.replicas[rid].clock_info() or {}
            lanes.append({"pid": rid, "name": f"worker r{rid}",
                          "traces": traces,
                          "offset_s": 0.0,  # rebased at ingest: no re-shift
                          "uncertainty_s":
                              (info.get("uncertainty_ms") or 0.0) / 1e3})
        path = export_fleet_merged_trace(
            os.path.join(run_dir, "merged_trace.json"), lanes)
        with open(path) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        meta = {e["pid"]: e["args"] for e in evs
                if e.get("name") == "process_name"}
        assert set(meta) == set(skews)
        for rid, args in meta.items():
            assert args["clock_offset_ms"] == 0.0  # no double shift
            assert args["clock_uncertainty_ms"] >= 0.0
        spans = [e for e in evs if e.get("ph") == "X"]
        assert spans
        ts_us = [e["ts"] for e in spans] + \
                [e["ts"] + e.get("dur", 0) for e in spans]
        assert min(ts_us) >= 0.0
        # merged width fits the real run; unrebased skew would add ~500ms
        assert max(ts_us) - min(ts_us) <= (t_done - t_submit + 0.1) * 1e6

    def test_metrics_plane_merged_without_shared_dir(self, skewed_fleet):
        """The heartbeat-piggybacked metrics plane saw both workers and
        the merged counters equal the work actually done — nothing was
        read off a shared filesystem."""
        sup, router, skews, *_ = skewed_fleet
        merged = sup.metrics_plane.merged()
        assert set(merged["replicas"]) == {f"r{rid}" for rid in skews}
        req = sum(v for k, v in merged["counters"].items()
                  if k.startswith("serve.requests"))
        assert req == N_REQ
        # ttft histograms are labeled per replica; the merged plane
        # keeps the label split — total observations must equal N_REQ
        ttft_n = sum(v["count"] for k, v in merged["histograms"].items()
                     if k.startswith("serve.ttft_seconds"))
        assert ttft_n == N_REQ

    def test_fleet_snapshot_carries_clock_and_metrics(self, skewed_fleet):
        """write_fleet_snapshot: the persisted doc shows the clock block
        (per-replica offsets ~ the injected skews) and the merged
        fleet_metrics, so serve_top --fleet renders the one timeline's
        vitals from the snapshot alone."""
        sup, router, skews, *_ = skewed_fleet
        with open(sup.write_fleet_snapshot()) as f:
            snap = json.load(f)
        clock = snap["clock"]
        for rid, skew in skews.items():
            info = clock[str(rid)]
            assert info["synced"]
            assert abs(info["offset_ms"] / 1e3 - skew) < 0.1
        req = sum(v for k, v in
                  snap["fleet_metrics"]["counters"].items()
                  if k.startswith("serve.requests"))
        assert req == N_REQ

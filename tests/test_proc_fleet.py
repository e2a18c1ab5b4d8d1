"""Cross-process serving fleet tests: real worker subprocesses behind
the unchanged FleetRouter, connected over the socket transport.

The load-bearing guarantees (docs/serving.md "Cross-process fleet"):
- socket-routed requests are bit-identical to the single-replica
  reference — placement, process boundaries, and the framed wire are
  pure plumbing;
- zero drops under a mid-run SIGKILL: the channel breaks, the router
  fails the worker's in-flight requests over, and the supervisor
  restarts a replacement under a fresh id;
- disaggregated prefill->decode handoffs cross the wire through the
  serialize RPC with real socket byte accounting;
- the supervisor acts on the autoscale signal (spawn/drain) and its
  acts land in the autoscale decision history.

These tests spawn jax subprocesses (~5s startup each) and live in the
slow tier (tests/slow_tests.txt); the transport layer itself is
covered jax-free in the smoke tier by tests/test_transport.py.
"""

import os
import signal
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.serving import (AutoscaleSignal, FleetRouter,
                                   ReplicaSupervisor)

MODEL_SPEC = {"name": "tiny",
              "overrides": {"dtype": "float32", "param_dtype": "float32"}}
ENGINE_SPEC = dict(kv_blocks=64, kv_block_size=8, max_tokens_per_step=32,
                   max_seqs_per_step=4, max_blocks_per_seq=8,
                   request_trace={"sample_rate": 1.0}, dtype="float32")


def shared_prompts(n, prefix_len=16, tail=4):
    base = ((np.arange(prefix_len) * 5 + 3) % 97).astype(np.int32)
    return [np.concatenate(
        [base, ((np.arange(tail) * 7 + 11 * i) % 89).astype(np.int32)])
        for i in range(n)]


def reference_outputs(prompts, gen):
    """Single uncontended in-process engine over the same seed-0 params
    the workers derive — the stream every process fleet must match."""
    from deepspeed_tpu.inference import InferenceEngineV2

    model = get_model("tiny", dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    kw = {k: v for k, v in ENGINE_SPEC.items() if k != "dtype"}
    eng = InferenceEngineV2(model, params=params, dtype=jnp.float32, **kw)
    eng.put(list(range(len(prompts))), prompts, max_new_tokens=gen)
    return {u: list(t) for u, t in eng.generate_all().items()}


def make_proc_fleet(run_dir, roles, engine=None, routing="least_loaded",
                    stale_after_s=5.0, affinity_blocks=2, autoscale=None):
    sup = ReplicaSupervisor(str(run_dir), jax_platform="cpu",
                            model=MODEL_SPEC,
                            engine=dict(engine or ENGINE_SPEC), seed=0)
    remotes = [sup.spawn(role=r) for r in roles]
    router = FleetRouter(remotes, stale_after_s=stale_after_s,
                         routing=routing, affinity_blocks=affinity_blocks,
                         autoscale=autoscale)
    sup.router = router
    return sup, router


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """One 2-worker unified fleet shared by the tests that don't
    degrade it; predictive routing so ROUTE spans carry the predictor's
    fields."""
    run_dir = tmp_path_factory.mktemp("proc_fleet")
    sup, router = make_proc_fleet(run_dir, ("unified", "unified"),
                                  routing="predictive")
    yield sup, router, str(run_dir)
    sup.shutdown()


class TestProcFleetE2E:
    def test_socket_fleet_bit_identical(self, fleet):
        sup, router, _ = fleet
        prompts = shared_prompts(6)
        for i, p in enumerate(prompts):
            router.submit(i, p, max_new_tokens=8)
        sup.run_until_drained(timeout_s=90.0)
        ref = reference_outputs(prompts, 8)
        res = router.results()
        assert set(res) == set(ref)
        for uid in ref:
            assert list(res[uid]) == ref[uid], f"uid={uid} diverged"

    def test_route_spans_carry_replica_and_wire_bytes(self, fleet):
        """Satellite: ROUTE spans stamped with the executing replica id
        and the transport byte counters at decision time — the
        cross-process flight path."""
        sup, router, _ = fleet
        spans = [s for ts in router.traces_by_replica().values()
                 for t in ts for s in t.spans if s.kind == "ROUTE"]
        assert spans, "no ROUTE spans shipped back over the channel"
        for s in spans:
            assert "replica_id" in s.fields
            assert s.fields["policy"] in ("predictive", "affinity")
            assert s.fields["wire_tx_bytes"] >= 0
            assert s.fields["wire_rx_bytes"] >= 0
        # heartbeats landed before at least one routing decision
        assert any(s.fields["wire_rx_bytes"] > 0 for s in spans)
        pred = [s for s in spans if s.fields["policy"] == "predictive"]
        assert pred and all("predicted_ttft_ms" in s.fields for s in pred)

    def test_supervisor_acts_on_autoscale_signal(self, fleet):
        """desired>live spawns a worker, desired<live drains one; both
        acts land in the autoscale decision history."""
        sup, router, _ = fleet
        autoscale = AutoscaleSignal(min_replicas=1, max_replicas=4)
        autoscale.desired = 3
        router.autoscale = autoscale
        before = set(sup.replicas)
        sup.maintain()
        new_ids = set(sup.replicas) - before
        assert len(new_ids) == 1, "scale-up did not spawn"
        (new_rid,) = new_ids
        assert new_rid in router.replicas
        assert new_rid in router.decode_pool

        autoscale.desired = 2
        sup.maintain()
        assert len(sup._live_ids()) == 2, "scale-down did not drain"
        acts = [h[2] for h in autoscale.history if len(h) == 3]
        assert f"spawn:r{new_rid}" in acts
        assert any(a.startswith("drain:") for a in acts)
        # the drained worker exits 0 once idle
        deadline = time.time() + 30.0
        drained = [rid for rid, r in sup.replicas.items() if r.draining]
        while time.time() < deadline:
            if all(sup._procs[rid].poll() is not None for rid in drained):
                break
            time.sleep(0.1)
        assert all(sup._procs[rid].poll() == 0 for rid in drained)
        router.autoscale = None  # leave the fleet unscaled for peers

    def test_fleet_snapshot_and_serve_top_run_dir(self, fleet):
        """Satellite: the merged snapshot lands in the run dir and
        serve_top --fleet renders it from the directory alone."""
        sup, router, run_dir = fleet
        path = sup.write_fleet_snapshot()
        assert os.path.basename(path) == "fleet_snapshot.json"
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        try:
            import serve_top
        finally:
            sys.path.pop(0)
        snap = serve_top._load_run_dir_snapshot(run_dir)
        assert snap["schema"] == "serving_fleet/v3"
        assert snap["supervisor"]["actions"]
        table = serve_top._fleet_table(snap)
        assert "worker processes up" in table and "transport:" in table
        # the raw per-worker reports also suffice (mid-run fallback)
        os.rename(path, path + ".bak")
        try:
            fallback = serve_top._load_run_dir_snapshot(run_dir)
            assert fallback["schema"] == "serving_fleet/v3"
            assert fallback["replicas"]
        finally:
            os.rename(path + ".bak", path)


class TestProcFleetDisagg:
    def test_disagg_handoff_over_socket(self, tmp_path):
        """>=1 prefill->decode handoff whose KV payload crossed the
        real socket (byte counters prove it), with the decode stream
        bit-identical to the single-replica reference."""
        engine = dict(ENGINE_SPEC, handoff_wire="int8")
        sup, router = make_proc_fleet(tmp_path, ("prefill", "decode"),
                                      engine=engine)
        try:
            prompts = shared_prompts(4)
            for i, p in enumerate(prompts):
                router.submit(i, p, max_new_tokens=6)
            sup.run_until_drained(timeout_s=90.0)
            assert router.stats["handoffs"] >= 1
            assert router.stats["handoff_recompute"] == 0, \
                "handoffs degraded to recompute — payloads never crossed"
            ref = reference_outputs(prompts, 6)
            res = router.results()
            for uid in ref:
                assert list(res[uid]) == ref[uid], f"uid={uid} diverged"
            # KV bytes moved through the prefill worker's socket: its
            # rx counter (supervisor side) includes the serialize
            # replies, far beyond heartbeat-only traffic
            tx, rx = sup.replicas[0].transport_bytes()
            assert tx > 0 and rx > 0
            reports = [r.load_report() for r in sup.replicas.values()]
            wire = sum(r["handoff_wire_bytes"] for r in reports)
            logical = sum(r["handoff_logical_bytes"] for r in reports)
            assert wire > 0 and logical > 0
            # int8 pool-to-wire: quantized bytes + scales, under raw
            assert wire < logical
        finally:
            sup.shutdown()


class TestProcFleetChaos:
    def test_sigkill_midrun_zero_drops_and_restart(self, tmp_path):
        """SIGKILL one worker mid-run: every accepted request still
        completes its full budget (failover resubmit), and the
        supervisor restarts a replacement under a fresh id."""
        sup, router = make_proc_fleet(
            tmp_path, ("unified", "unified"), affinity_blocks=0,
            stale_after_s=5.0)
        try:
            prompts = shared_prompts(8)
            for i, p in enumerate(prompts):
                router.submit(i, p, max_new_tokens=12)
            time.sleep(0.5)  # let both workers take work
            victim = sup.replicas[0].replica_id
            sup.kill(victim, signal.SIGKILL)
            sup.run_until_drained(timeout_s=120.0)
            res = router.results()
            assert len(res) == len(prompts), "requests dropped"
            assert all(len(t) == 12 for t in res.values()), \
                "token budgets not honored through the kill"
            restarts = [a for a in sup.actions if a[1] == "restart"]
            assert restarts, "supervisor never restarted the victim"
            assert victim in router.dead
            assert router.stats["failed_over_requests"] > 0
            # greedy decoding: the recovered streams are still the
            # reference streams
            ref = reference_outputs(prompts, 12)
            for uid in ref:
                assert list(res[uid]) == ref[uid], f"uid={uid} diverged"
        finally:
            sup.shutdown()


class TestFileChannelFleet:
    def test_file_channel_degraded_mode(self, tmp_path):
        """The socketless fallback serves the same workload over
        spool-dir frames (slower, same contract)."""
        sup = ReplicaSupervisor(str(tmp_path), jax_platform="cpu",
                                model=MODEL_SPEC,
                                engine=dict(ENGINE_SPEC), seed=0,
                                channel="file")
        try:
            remote = sup.spawn(role="unified")
            router = FleetRouter([remote], stale_after_s=8.0)
            sup.router = router
            prompts = shared_prompts(3)
            for i, p in enumerate(prompts):
                router.submit(i, p, max_new_tokens=5)
            sup.run_until_drained(timeout_s=90.0)
            ref = reference_outputs(prompts, 5)
            res = router.results()
            for uid in ref:
                assert list(res[uid]) == ref[uid]
            tx, rx = remote.transport_bytes()
            assert tx > 0 and rx > 0
        finally:
            sup.shutdown()

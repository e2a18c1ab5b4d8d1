"""Subprocess replica entrypoint: ``python -m deepspeed_tpu.serving.proc_worker spec.json``.

One OS process = one :class:`ServingReplica`. The supervisor
(serving/supervisor.py) writes a JSON spec, spawns this module, and
talks to it over a transport channel; everything engine-side reuses the
in-process replica unchanged — the worker is a thin message loop around
``replica.pump()``.

Startup handshake: the worker binds its socket (or opens its spool
lanes), atomically writes a ready file ``{"pid", "port", "channel"}``
next to the spec, and accepts the supervisor's connection. Determinism
across processes comes from the spec's ``seed``: every worker builds
the same model and calls ``model.init(PRNGKey(seed))``, so N processes
serve one set of weights without shipping arrays over the wire.

Message protocol (all dicts through transport/messages.py):

  supervisor -> worker
    {"type": "submit", "uid", "tokens", "max_new_tokens",
     "span_notes", "handoff"}      routed request (handoff: encoded
                                   KVHandoff or None)
    {"type": "serialize", "req", "tokens"}
                                   serialize this worker's KV prefix;
                                   reply carries the same req id
    {"type": "migrate_out", "req", "uid", "wire"}
                                   capture + release a live session's
                                   full decode state (ISSUE 20);
                                   reply: session_payload (session may
                                   be None = already finished/gone)
    {"type": "install_session", "uid", "tokens", "max_new_tokens",
     "span_notes", "session"}      install a migrated session (encoded
                                   SessionHandoff); tokens carry the
                                   recompute fallback
    {"type": "reload", "req", "ckpt_dir", "seed"}
                                   rolling weight hot-swap: validate
                                   the manifest, reload params, run the
                                   canary prompt set, reply reload_done
                                   with the measured token chains
    {"type": "drain"}              stop = finish in-flight, then exit 0
    {"type": "ping"}               liveness probe -> {"type": "pong"}

  worker -> supervisor
    {"type": "emit", "emitted", "report", "traces", "geometry"}
                                   per-round emissions + load report
                                   (also sent bare as the heartbeat)
    {"type": "handoff_payload", "req", "handoff"}
    {"type": "session_payload", "req", "session"}
    {"type": "reload_done", "req", "ok", "error", "tag", "seed",
     "canary_chains"}
    {"type": "exiting", "replica"} drain complete, about to exit

Channel FIFO is what makes migrate-then-drain race-free: the
supervisor sends every ``migrate_out`` before the ``drain`` flag, so
the worker captures sessions while still serving; and every emission
sent before a ``session_payload`` reply arrived first, so the
supervisor's folded token state is complete when the capture lands.

Graceful drain is SIGTERM *or* the drain message: both flip the same
flag, the worker stops admitting, finishes what it holds, announces
``exiting``, and leaves. Chaos drills reuse the training-side
``DSTPU_CHAOS`` spec (resilience/chaos.py): ``kill_rank`` is matched
against the replica id and ``kill_step`` against *busy* serve rounds,
so the kill lands mid-request — the supervisor's restart path and the
router's zero-drop failover are what the drill measures.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
from typing import Any, Dict, Optional


def _atomic_write_json(path: str, doc: Dict[str, Any]) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _resolve_dtypes(d: Dict[str, Any]) -> Dict[str, Any]:
    """Map dtype names back to jnp dtypes ("float32" came over JSON)."""
    import jax.numpy as jnp

    out = dict(d)
    for k, v in d.items():
        if k.endswith("dtype") and isinstance(v, str):
            out[k] = getattr(jnp, v)
    return out


def build_replica(spec: Dict[str, Any]):
    """Model + params + ServingReplica from the spec — deterministic:
    same spec seed => bit-identical params in every process. A worker
    is one replica on one device: the first this process can see (the
    supervisor leaves a tpu worker exactly one chip visible)."""
    import jax
    import jax.numpy as jnp  # noqa: F401  (dtype resolution)

    from deepspeed_tpu.models.zoo import get_model
    from deepspeed_tpu.serving.replica import ServingReplica

    mspec = spec.get("model") or {"name": "tiny"}
    model = get_model(mspec.get("name", "tiny"),
                      **_resolve_dtypes(mspec.get("overrides") or {}))
    params = model.init(jax.random.PRNGKey(int(spec.get("seed", 0))))
    engine_kw = _resolve_dtypes(spec.get("engine") or {})
    return ServingReplica.create(
        model, int(spec["replica_id"]), role=spec.get("role", "unified"),
        run_dir=spec.get("run_dir"), device=jax.devices()[0],
        params=params, **engine_kw)


def open_channel(spec: Dict[str, Any]):
    """Bind the transport, publish the ready file, return the connected
    channel. Socket is the primary; the file channel is the degraded
    fallback for socketless sandboxes (docs/serving.md matrix)."""
    from deepspeed_tpu.serving.transport import (FileChannel, SocketServer)

    max_frame = int(spec.get("max_frame_mb", 64)) << 20
    kind = spec.get("channel", "socket")
    import jax

    # the worker's backend is up (build_replica ran): name what it got
    devs = jax.devices()
    ready = {"pid": os.getpid(), "channel": kind, "port": None,
             "device": {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs)}}
    if kind == "socket":
        srv = SocketServer(max_frame_bytes=max_frame)
        ready["port"] = srv.port
        _atomic_write_json(spec["ready_path"], ready)
        chan = srv.accept(timeout=60.0)
        srv.close()  # one supervisor per worker; stop listening
        return chan
    if kind == "file":
        chan = FileChannel(spec["spool_dir"], side="b",
                           max_frame_bytes=max_frame)
        _atomic_write_json(spec["ready_path"], ready)
        return chan
    raise ValueError(f"unknown channel kind {kind!r}")


class WorkerLoop:
    def __init__(self, spec: Dict[str, Any]):
        self.spec = spec
        self.replica = build_replica(spec)
        self.channel = open_channel(spec)
        self.eos_token_id = spec.get("eos_token_id")
        self.step_delay_s = float(spec.get("step_delay_ms", 0.0)) / 1e3
        self.heartbeat_s = float(spec.get("heartbeat_s", 0.1))
        self.draining = False
        self._last_send = 0.0  # time.monotonic(); cadence only
        self._sent_traces: set = set()
        self._busy_steps = 0
        self._received_submits = 0  # acked back in every report
        from deepspeed_tpu.resilience.chaos import ChaosInjector, ChaosSpec

        self.chaos = ChaosInjector(ChaosSpec.from_env(),
                                   rank=self.replica.replica_id)
        signal.signal(signal.SIGTERM, self._on_sigterm)
        # heartbeats come from their own thread so liveness survives a
        # long engine step — the first serve round JIT-compiles for
        # seconds, and a heartbeat gap that long reads as a dead
        # replica to the router's staleness check
        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, daemon=True,
            name=f"hb-r{self.replica.replica_id}")

    def _on_sigterm(self, signum, frame) -> None:
        self.draining = True

    # -- inbound -------------------------------------------------------
    def _drain_channel(self) -> None:
        from deepspeed_tpu.serving.replica import Submission
        from deepspeed_tpu.serving.transport import (decode_handoff,
                                                     decode_session)

        while True:
            msg = self.channel.recv(timeout=0.0)
            if msg is None:
                return
            kind = msg.get("type")
            if kind == "submit":
                self._received_submits += 1
                notes = [(str(k), dict(f))
                         for k, f in msg.get("span_notes") or []]
                self.replica.submit(Submission(
                    uid=int(msg["uid"]), tokens=msg["tokens"],
                    max_new_tokens=int(msg["max_new_tokens"]),
                    span_notes=notes,
                    handoff=decode_handoff(msg.get("handoff"))))
            elif kind == "serialize":
                self._serialize(msg)
            elif kind == "migrate_out":
                self._migrate_out(msg)
            elif kind == "install_session":
                self._received_submits += 1
                notes = [(str(k), dict(f))
                         for k, f in msg.get("span_notes") or []]
                self.replica.submit(Submission(
                    uid=int(msg["uid"]), tokens=msg["tokens"],
                    max_new_tokens=int(msg["max_new_tokens"]),
                    span_notes=notes,
                    session=decode_session(msg.get("session"))))
            elif kind == "reload":
                self._reload(msg)
            elif kind == "drain":
                self.draining = True
            elif kind == "ping":
                self.channel.send({"type": "pong",
                                   "replica": self.replica.replica_id})

    def _serialize(self, msg: Dict[str, Any]) -> None:
        from deepspeed_tpu.serving.disagg import serialize_prefix
        from deepspeed_tpu.serving.transport import encode_handoff

        payload = serialize_prefix(self.replica.engine, msg["tokens"])
        self.channel.send({"type": "handoff_payload",
                           "req": msg["req"],
                           "handoff": encode_handoff(payload)})

    def _migrate_out(self, msg: Dict[str, Any]) -> None:
        """Capture + release a live session on this (the pump) thread.
        Runs directly — _drain_channel and pump share the worker main
        thread, so the engine is quiescent here. Every emission this
        session produced was sent before this reply (channel FIFO), so
        the supervisor's folded token state is complete."""
        from deepspeed_tpu.serving.disagg import serialize_session
        from deepspeed_tpu.serving.transport import encode_session

        try:
            sess = serialize_session(self.replica.engine,
                                     int(msg["uid"]),
                                     wire=msg.get("wire"))
        except Exception:
            sess = None  # degrade to recompute, never wedge the worker
        # the capture read the engine's burst in flight first: those
        # tokens are emissions too, and go before the reply
        unread = self.replica.engine.take_undelivered()
        if unread:
            self._send_emit(unread)
        self.channel.send({"type": "session_payload",
                           "req": msg["req"],
                           "session": encode_session(sess)})

    def _reload(self, msg: Dict[str, Any]) -> None:
        """Rolling weight hot-swap, worker side: validate the published
        checkpoint's manifest, rebuild params (zero recompilation — all
        step functions take params as arguments), then re-measure the
        canary prompt set and reply with its token checksum chains. The
        supervisor compares them against the publisher's expected
        chains (A/B token parity) before letting this replica rejoin.
        The caller drained us first, so the engine is empty; canary
        uids live in the 3_000_000+ range and are flushed after."""
        from deepspeed_tpu.observability.journal import chain_tokens
        from deepspeed_tpu.resilience.manifest import validate_manifest

        req = msg.get("req")
        reply: Dict[str, Any] = {"type": "reload_done", "req": req,
                                 "ok": False, "error": None, "tag": None,
                                 "seed": None, "canary_chains": {}}
        try:
            ckpt_dir = msg.get("ckpt_dir")
            seed = msg.get("seed")
            canary = {}
            if ckpt_dir:
                validate_manifest(ckpt_dir)  # raises on torn/corrupt
                with open(os.path.join(ckpt_dir, "weights.json")) as f:
                    wdoc = json.load(f)
                reply["tag"] = wdoc.get("tag")
                seed = wdoc.get("seed", seed)
                canary = wdoc.get("canary") or {}
            eng = self.replica.engine
            eng.reload_params(seed=int(seed or 0))
            reply["seed"] = int(seed or 0)
            prompts = canary.get("prompts") or []
            if prompts:
                import numpy as np

                gen = int(canary.get("gen", 8))
                uids = [3_000_000 + i for i in range(len(prompts))]
                eng.put(uids, [np.asarray(p, np.int32) for p in prompts],
                        max_new_tokens=gen)
                out = eng.generate_all(eos_token_id=self.eos_token_id)
                eng.flush(uids)
                reply["canary_chains"] = {
                    str(i): chain_tokens(out.get(uid, []))
                    for i, uid in enumerate(uids)}
            reply["ok"] = True
        except Exception as exc:  # parity gate aborts on any failure
            reply["error"] = f"{type(exc).__name__}: {exc}"
        self.channel.send(reply)

    # -- outbound ------------------------------------------------------
    def _geometry(self) -> Dict[str, Any]:
        e = self.replica.engine
        return {"block_size": int(e.kv_cache.config.block_size),
                "total_blocks": int(e.kv_cache.allocator.total_blocks),
                "max_blocks_per_seq": int(e.max_blocks_per_seq)}

    def _new_traces(self):
        out = []
        for t in self.replica.engine.tracer.finished():
            if t.trace_id not in self._sent_traces:
                self._sent_traces.add(t.trace_id)
                out.append(t.to_dict())
        return out

    def _report(self) -> Dict[str, Any]:
        """Load report with the submit ack counter: the supervisor's
        stub subtracts it from its own sent counter to size the
        still-on-the-wire window (RemoteReplica._unacked)."""
        rep = self.replica.load_report()
        rep["received_submits"] = self._received_submits
        return rep

    def _metrics(self) -> Dict[str, Any]:
        """Compact snapshot of this process's hub, piggybacked on every
        emit so the supervisor's fleet metrics plane needs no shared
        filesystem. Empty (and omitted from the wire message) when the
        hub has nothing under the serving prefixes."""
        from deepspeed_tpu.observability.fleet_metrics import \
            compact_snapshot
        from deepspeed_tpu.observability.hub import peek_hub

        return compact_snapshot(peek_hub())

    def _send_emit(self, emitted: Dict[int, list]) -> None:
        msg = {
            "type": "emit",
            "emitted": {str(u): [int(t) for t in toks]
                        for u, toks in emitted.items()},
            "report": self._report(),
            "traces": self._new_traces(),
            "geometry": self._geometry(),
        }
        metrics = self._metrics()
        if metrics:
            msg["metrics"] = metrics
        self.channel.send(msg)
        self._last_send = time.monotonic()

    def _heartbeat_loop(self) -> None:
        """Report-only sends at heartbeat cadence (monotonic clock — a
        wall-clock step must not stall or burst the heartbeat); no
        emissions or traces, so the main loop stays the only writer of
        those."""
        while not self._hb_stop.is_set():
            if (time.monotonic() - self._last_send) >= self.heartbeat_s:
                try:
                    msg = {"type": "emit", "emitted": {},
                           "report": self._report(),
                           "traces": [], "geometry": self._geometry()}
                    metrics = self._metrics()
                    if metrics:
                        msg["metrics"] = metrics
                    self.channel.send(msg)
                    self._last_send = time.monotonic()
                except Exception:
                    return  # channel gone; the main loop exits too
            self._hb_stop.wait(self.heartbeat_s / 4.0)

    # -- the loop ------------------------------------------------------
    def _idle(self) -> bool:
        e = self.replica.engine
        return (not e.state.seqs and not e._queue
                and self.replica.inbox.empty())

    def run(self) -> int:
        self._hb_thread.start()
        try:
            return self._run()
        finally:
            self._hb_stop.set()

    def _run(self) -> int:
        while True:
            try:
                self._drain_channel()
            except Exception:
                # supervisor gone: nothing to serve for; exit loud so
                # the (possibly new) supervisor sees a non-zero status
                return 1
            emitted = self.replica.pump(eos_token_id=self.eos_token_id)
            if emitted:
                self._busy_steps += 1
                # chaos drills count busy rounds so the kill lands
                # mid-request, not during warmup idle
                self.chaos.on_step(self._busy_steps)
            if self.step_delay_s > 0.0:
                time.sleep(self.step_delay_s)  # simulated degradation
            now = time.monotonic()
            if emitted or (now - self._last_send) >= self.heartbeat_s:
                try:
                    self._send_emit(emitted)
                except Exception:
                    return 1
            if self.draining and self._idle():
                try:
                    self._send_emit({})
                    self.channel.send({"type": "exiting",
                                       "replica": self.replica.replica_id})
                except Exception:
                    pass
                return 0
            if not emitted:
                time.sleep(0.001)


def main(argv: Optional[list] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m deepspeed_tpu.serving.proc_worker "
              "<spec.json>", file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        spec = json.load(f)
    # the spec decides the platform, before jax is imported: not an
    # inherited variable and not a default — a worker that was told tpu
    # and cannot have it fails instead of serving from the CPU
    os.environ["JAX_PLATFORMS"] = spec["jax_platform"]
    return WorkerLoop(spec).run()


if __name__ == "__main__":
    sys.exit(main())

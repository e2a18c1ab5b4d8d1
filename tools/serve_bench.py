"""Serving benchmarks: v2-vs-v1 throughput, and an open-loop SLO harness.

Two entry points:

- :func:`run` — the round-1 closed-loop throughput comparison (v2 ragged
  continuous batching vs the naive v1 dense engine);
- :func:`run_slo` — an OPEN-LOOP SLO harness (``BENCH_MODE=serve_slo``,
  ``make serve-slo``): requests arrive on a Poisson clock regardless of
  whether the engine keeps up (the production traffic model — closed
  loops hide queueing collapse because a slow server slows its own
  offered load). Reports p50/p99 TTFT (queue wait INCLUDED), per-decode-
  token latency, tokens/s, goodput under a TTFT deadline, the queue-
  depth timeline, and the prefix-cache / speculative-decode counters, as
  one JSON line. ``SLO_COMPARE=1`` reruns the same workload with the
  prefix cache + speculation disabled and reports the speedup. The JSON
  embeds the per-request SLO attribution (per-phase p50/p99 + dominant
  miss phase; observability/request_trace.py); ``SLO_TRACE=1``
  additionally (a) asserts every trace's phase decomposition sums to
  its measured e2e/TTFT wall time (check_phase_closure — the trace-math
  regression gate), (b) dumps the per-request trace JSONL that
  ``tools/serve_top.py report`` consumes, and (c) exports per-request
  Perfetto lanes (``SLO_TRACE_DIR``, default /tmp/dstpu_serve_slo),
  printing the "why did p99 miss" table to stderr.


VERDICT r4 #9 asked for a serving performance number against the
reference's FastGen claim (2.3x vs vLLM, blogs/deepspeed-fastgen/
README.md:28 — the win comes from continuous batching + SplitFuse
keeping the chip at a constant token budget while the naive engine
decodes lock-step with the slowest sequence).

This benchmark serves the same workload through both engines on the
current backend and prints ONE JSON line:

  {"metric": "serve tokens/s (v2 ragged)", "value": ..., "v1_value": ...,
   "speedup_vs_v1": ...}

Workload: N prompts of mixed length, G new tokens each, greedy. The v2
engine admits continuously under a token budget; v1 decodes the whole
batch dense and synchronous (its per-step work scales with max prompt
length padding + every sequence decoding until the last finishes).

Env knobs: SERVE_MODEL (zoo name, default llama3-8b geometry cut to
SERVE_LAYERS=3), SERVE_SEQS (default 24), SERVE_PROMPT (default 128),
SERVE_GEN (default 128), SERVE_BUDGET (v2 max_tokens_per_step, 256).

Driver capture: ``BENCH_MODE=serve python bench.py`` routes here
(bench.py), so the serving number is recordable by the same harness as
the training headline.
"""

from __future__ import annotations

import json
import os
import sys
import time

from deepspeed_tpu.observability.roofline import on_tpu_or_named_cpu_smoke

# the process-fleet drills (serve_procs, chaos_fleet, replay_fleet,
# deploy_drill) certify routing, failover and replay with toy-size
# workers on the CPU: they gate on counts and bit-identity and report
# no device number. Their supervisor therefore names "cpu" outright.
_DRILL_PLATFORM = "cpu"


def run() -> dict:
    import jax
    import numpy as np

    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.zoo import get_model

    on_tpu = on_tpu_or_named_cpu_smoke()
    model_name = os.environ.get("SERVE_MODEL", "llama3-8b")
    layers = int(os.environ.get("SERVE_LAYERS", 3))
    n_seqs = int(os.environ.get("SERVE_SEQS", 24 if on_tpu else 4))
    prompt_len = int(os.environ.get("SERVE_PROMPT", 128 if on_tpu else 16))
    gen = int(os.environ.get("SERVE_GEN", 128 if on_tpu else 8))
    budget = int(os.environ.get("SERVE_BUDGET", 256 if on_tpu else 32))
    decode_steps = int(os.environ.get("SERVE_DECODE_STEPS", 8))
    max_seq_len = 1 << (prompt_len + gen + 1).bit_length()

    model = get_model(model_name, num_layers=layers, max_seq_len=max_seq_len,
                      remat=False)
    cfg = model.config
    rng = np.random.default_rng(0)
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)

    # mixed prompt lengths: half full, quarter 3/4, quarter 1/2 — the
    # ragged engine's reason to exist
    lens = [prompt_len, prompt_len * 3 // 4, prompt_len // 2,
            prompt_len] * (n_seqs // 4 + 1)
    lens = [max(4, l) for l in lens[:n_seqs]]
    prompts = [rng.integers(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in lens]

    # -- v1: dense synchronous decode -----------------------------------
    v1 = InferenceEngine(model, params=params, max_batch=n_seqs,
                         max_seq_len=max_seq_len)
    pad = max(lens)
    batch = np.zeros((n_seqs, pad), np.int32)
    for i, p in enumerate(prompts):
        batch[i, :len(p)] = p  # right-pad; v1 decodes from the padded end

    def v1_run():
        return v1.generate(batch, max_new_tokens=gen)

    v1_run()  # compile
    t0 = time.perf_counter()
    v1_run()
    t1 = time.perf_counter()
    v1_toks = n_seqs * gen / (t1 - t0)

    # -- v2: ragged continuous batching ---------------------------------
    block = 16
    blocks_per_seq = (max(lens) + gen) // block + 2
    kv_blocks = blocks_per_seq * n_seqs + 2

    def make_v2():
        return InferenceEngineV2(
            model, params=params, kv_blocks=kv_blocks, kv_block_size=block,
            max_tokens_per_step=budget,
            max_seqs_per_step=min(n_seqs, budget),
            max_blocks_per_seq=blocks_per_seq, decode_steps=decode_steps)

    def v2_run(engine):
        engine.put(list(range(n_seqs)), prompts, max_new_tokens=gen)
        out = engine.generate_all()
        total = sum(len(v) for v in out.values())
        assert total >= n_seqs * (gen - 1), (total, n_seqs * gen)
        return total

    engine = make_v2()
    v2_run(engine)  # compile pass; generate_all drains the KV pool
    t0 = time.perf_counter()
    total = v2_run(engine)
    t1 = time.perf_counter()
    v2_toks = total / (t1 - t0)
    snap = engine.snapshot()

    return {
        "metric": f"{model_name}-geometry({layers}L) serve tokens/s "
                  f"(v2 ragged, {n_seqs} seqs, prompt~{prompt_len}, "
                  f"gen {gen}, {'tpu' if on_tpu else 'cpu'})",
        "value": round(v2_toks, 1),
        "unit": "tokens/s",
        "v1_value": round(v1_toks, 1),
        "speedup_vs_v1": round(v2_toks / max(v1_toks, 1e-9), 3),
        "v1_note": (
            "upper-bound comparison: the v1 baseline right-pads every "
            "prompt to the longest in the batch, so it computes (and is "
            "billed for) padded-prompt work the ragged v2 path never "
            "runs — a length-sorted or uniform-length workload would "
            "narrow the gap"),
        "kernel_steps": (engine.stats.get("decode_kernel_steps", 0)
                         + engine.stats.get("prefill_kernel_steps", 0)),
        "fallback_steps": engine.stats.get("prefill_gather_fallbacks", 0),
        "serve_snapshot": {
            k: snap[k]
            for k in ("ttft", "decode_token_latency", "burst_efficiency")
            if k in snap},
    }


def _drive_open_loop(engine, prompts, arrivals, gen, deadline_s):
    """Drive one engine through an open-loop arrival schedule.

    Requests are put() at their scheduled arrival instant whether or not
    the engine has room (that is the open loop); TTFT is measured from
    the SCHEDULED arrival, so admission-queue wait counts against the
    SLO exactly as a client would experience it.
    """
    import numpy as np

    # warm pass: the whole workload once, closed loop — compiles every
    # bucket shape the timed phase will hit (cold prefill, prefix-hit
    # prefill, decode bursts, speculative chunks) and brings the prefix
    # cache to serving steady state, so the timed open-loop phase
    # measures serving, not XLA
    engine.put([(1 << 30) + i for i in range(len(prompts))], prompts,
               max_new_tokens=gen)
    engine.generate_all()
    # ...plus one lone request: the open loop's ramp-up runs low-
    # cardinality batches the all-at-once pass never shapes
    engine.put([1 << 29], [prompts[0]], max_new_tokens=gen)
    engine.generate_all()
    counter_keys = ("admitted", "preempted", "requeued", "prefix_hit_tokens",
                    "spec_steps", "spec_proposed", "spec_accepted",
                    "truncated")
    base = {k: engine.stats.get(k, 0) for k in counter_keys}
    base_prefill = engine.scheduler.stats["prefill_tokens"]
    for h in (engine._ttft_hist, engine._decode_hist, engine._step_hist,
              engine._admission_hist, engine._spec_hist):
        h.reset()
    tracer = getattr(engine, "tracer", None)
    if tracer is not None:
        tracer.reset()  # warmup traces must not pollute attribution

    n = len(prompts)
    first = {}
    counts = {uid: 0 for uid in range(n)}
    timeline = []
    completed = 0
    i = 0
    t0 = time.perf_counter()
    while completed < n:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            engine.put([i], [prompts[i]], max_new_tokens=gen)
            i += 1
        if not engine.state.seqs and not engine._queue:
            if i >= n:
                break  # drained; anything incomplete was truncated
            time.sleep(min(max(arrivals[i] - (time.perf_counter() - t0),
                               0.0), 0.02))
            continue
        out = engine.serve_step()
        tnow = time.perf_counter() - t0
        timeline.append((round(tnow, 4), len(engine._queue),
                         len(engine.state.seqs)))
        for uid, toks in out.items():
            if not toks or uid not in counts:
                continue
            if uid not in first:
                first[uid] = tnow - arrivals[uid]
            counts[uid] += len(toks)
            if counts[uid] >= gen:
                completed += 1
    wall = time.perf_counter() - t0

    ttfts = np.asarray(sorted(first.values()), np.float64)
    total_tokens = int(sum(counts.values()))
    good_tokens = sum(counts[uid] for uid, t in first.items()
                      if t <= deadline_s)
    stride = max(1, len(timeline) // 40)
    decode = engine._decode_hist.snapshot()
    attribution = None
    if tracer is not None and tracer.enabled:
        from deepspeed_tpu.observability.request_trace import \
            slo_attribution

        rep = slo_attribution(tracer.finished(), deadline_s)
        # compact embed: per-phase p50/p99 + the "why" aggregates; the
        # per-request detail rows live in the trace JSONL that
        # tools/serve_top.py consumes, not in the one-line bench JSON
        attribution = {k: rep[k] for k in
                       ("schema", "requests", "slo_misses", "phase_seconds",
                        "miss_ttft_phase_seconds", "miss_dominant_phase",
                        "ttft", "e2e")}
    return {
        "completed": completed,
        "dropped": n - completed,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_tokens / max(wall, 1e-9), 1),
        "goodput_tokens_per_s": round(good_tokens / max(wall, 1e-9), 1),
        "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4)
                      if len(ttfts) else None,
        "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 4)
                      if len(ttfts) else None,
        "decode_token_p50_s": decode.get("p50"),
        "decode_token_p99_s": decode.get("p99"),
        "queue_depth_timeline": [list(t) for t in timeline[::stride]],
        "prefill_tokens": engine.scheduler.stats["prefill_tokens"]
                          - base_prefill,
        "attribution": attribution,
        **{k: engine.stats.get(k, 0) - base[k] for k in counter_keys},
    }


def run_slo() -> dict:
    import jax
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.zoo import get_model

    on_tpu = on_tpu_or_named_cpu_smoke()
    model_name = os.environ.get("SLO_MODEL",
                                "llama3-8b" if on_tpu else "tiny")
    layers = int(os.environ.get("SLO_LAYERS", 3 if on_tpu else 2))
    n_req = int(os.environ.get("SLO_REQUESTS", 96 if on_tpu else 24))
    prompt_len = int(os.environ.get("SLO_PROMPT", 256 if on_tpu else 48))
    shared_len = int(os.environ.get("SLO_SHARED_PREFIX",
                                    prompt_len * 3 // 4))
    gen = int(os.environ.get("SLO_GEN", 64 if on_tpu else 16))
    rate = float(os.environ.get("SLO_RATE", 8.0 if on_tpu else 40.0))
    deadline_s = float(os.environ.get("SLO_DEADLINE_MS",
                                      2000 if on_tpu else 4000)) / 1000.0
    budget = int(os.environ.get("SLO_BUDGET", 256 if on_tpu else 64))
    seed = int(os.environ.get("SLO_SEED", 0))
    use_spec = os.environ.get("SLO_SPEC", "1") == "1"
    use_prefix = os.environ.get("SLO_PREFIX_CACHE", "1") == "1"
    compare = os.environ.get("SLO_COMPARE", "0") == "1"
    trace_arm = os.environ.get("SLO_TRACE", "0") == "1"
    # full sampling by default: the bench wants the attribution over the
    # whole window, not a slice (production default is 0.05 — see
    # config.observability.request_trace)
    trace_sample = float(os.environ.get("SLO_TRACE_SAMPLE", 1.0))
    block = 16
    max_seq_len = 1 << (prompt_len + gen + 8).bit_length()

    model = get_model(model_name, num_layers=layers,
                      max_seq_len=max_seq_len, remat=False)
    cfg = model.config
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    if on_tpu:
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)

    # workload: one shared prefix (the system-prompt pattern the prefix
    # cache exists for) + a short repeated per-request motif (the
    # repetitive tail prompt-lookup speculation exists for)
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, (shared_len,))
    prompts = []
    for _ in range(n_req):
        motif = rng.integers(0, cfg.vocab_size, (4,))
        tail = np.tile(motif, (prompt_len - shared_len) // 4 + 1)
        prompts.append(np.concatenate(
            [shared, tail])[:prompt_len].astype(np.int32))
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))

    # KV pool sized to ~1/3 of the offered concurrency so the Poisson
    # burst actually exercises the admission queue and preemption paths
    blocks_per_seq = (prompt_len + gen) // block + 3
    kv_blocks = int(os.environ.get(
        "SLO_KV_BLOCKS", blocks_per_seq * max(3, n_req // 3) + 2))

    def make_engine(prefix_cache, spec_decode):
        return InferenceEngineV2(
            model, params=params, kv_blocks=kv_blocks, kv_block_size=block,
            max_tokens_per_step=budget,
            max_seqs_per_step=min(16 if not on_tpu else 64, budget),
            max_blocks_per_seq=blocks_per_seq,
            decode_steps=int(os.environ.get("SLO_DECODE_STEPS", 4)),
            prefix_cache=prefix_cache, spec_decode=spec_decode,
            spec_k=int(os.environ.get("SLO_SPEC_K", 4)),
            request_trace={"sample_rate": trace_sample,
                           "ring_size": max(4096, 2 * n_req),
                           "slo_deadline_ms": deadline_s * 1000.0})

    engine = make_engine(use_prefix, use_spec)
    opt = _drive_open_loop(engine, prompts, arrivals, gen, deadline_s)
    out = {
        "metric": f"{model_name}-geometry({layers}L) serve_slo "
                  f"tokens/s ({n_req} req, poisson {rate}/s, "
                  f"prompt {prompt_len} shared {shared_len}, gen {gen}, "
                  f"{'tpu' if on_tpu else 'cpu'})",
        "value": opt["tokens_per_s"],
        "unit": "tokens/s",
        "slo_deadline_ms": deadline_s * 1000.0,
        "kv_blocks": kv_blocks,
        "spec_decode": use_spec,
        "prefix_cache": use_prefix,
        "slo": opt,
    }
    if trace_arm and engine.tracer.enabled:
        from deepspeed_tpu.observability.chrome_trace import \
            export_request_traces
        from deepspeed_tpu.observability.request_trace import \
            check_phase_closure, slo_attribution_markdown

        traces = engine.tracer.finished()
        # the regression gate: every trace's phase decomposition must
        # sum to its measured e2e (and TTFT) wall time — raises on drift
        out["phase_closure"] = check_phase_closure(traces)
        trace_dir = os.environ.get("SLO_TRACE_DIR", "/tmp/dstpu_serve_slo")
        os.makedirs(trace_dir, exist_ok=True)
        out["trace_jsonl"] = engine.tracer.dump_jsonl(
            os.path.join(trace_dir, "request_traces.jsonl"))
        flight_events = [{"ts": ts, "kind": kind, **fields}
                         for ts, kind, fields in engine._flight.events()]
        out["perfetto_trace"] = export_request_traces(
            os.path.join(trace_dir, "request_lanes.json"), traces,
            flight_events=flight_events)
        report = slo_attribution_markdown(dict(
            opt["attribution"], phases=list(opt["attribution"][
                "phase_seconds"]), deadline_s=deadline_s))
        print(report, file=sys.stderr)
    if compare:
        base = _drive_open_loop(make_engine(False, False), prompts,
                                arrivals, gen, deadline_s)
        out["baseline"] = base
        out["speedup_vs_baseline"] = round(
            opt["tokens_per_s"] / max(base["tokens_per_s"], 1e-9), 3)
    return out


def _drive_fleet_arm(arm, model, params, prompts, arrivals, gen,
                     deadline_s, knobs) -> dict:
    """One fleet arm (``unified`` or ``disagg``) over the SAME workload
    and arrival schedule: warm pass (compile + prefix-cache steady
    state), then a timed open-loop run on threaded replicas."""
    import threading

    import numpy as np

    from deepspeed_tpu.config.config import RouterConfig
    from deepspeed_tpu.serving.router import build_fleet

    cfg = RouterConfig(
        replicas=knobs["replicas"], mode=arm,
        prefill_replicas=knobs["prefill_replicas"] if arm == "disagg" else 1,
        stale_after_seconds=knobs["stale_after_s"])
    cfg.validate()
    router = build_fleet(model, cfg, engine_kw=dict(
        params=params, kv_blocks=knobs["kv_blocks"],
        kv_block_size=knobs["block"],
        max_tokens_per_step=knobs["budget"],
        max_seqs_per_step=min(16, knobs["budget"]),
        max_blocks_per_seq=knobs["blocks_per_seq"],
        decode_steps=knobs["decode_steps"],
        prefix_cache=True,
        request_trace={"sample_rate": 1.0,
                       "ring_size": max(4096, 2 * len(prompts)),
                       "slo_deadline_ms": deadline_s * 1000.0}))

    n = len(prompts)
    warm_base = 1 << 30
    for i, p in enumerate(prompts):
        router.submit(warm_base + i, p, max_new_tokens=gen)
    router.run_until_complete()
    warm = {uid - warm_base: toks for uid, toks in router.results().items()
            if uid >= warm_base}
    for r in router.replicas.values():
        e = r.engine
        for h in (e._ttft_hist, e._decode_hist, e._step_hist,
                  e._admission_hist, e._spec_hist):
            h.reset()
        e.tracer.reset()  # warm traces must not pollute attribution
    base_stats = dict(router.stats)

    # TTFT from the SCHEDULED arrival, observed at the router's emission
    # callback — for the disagg arm this is the prefill replica's first
    # token, i.e. the client-visible TTFT before the handoff
    first_tok = {}
    tlock = threading.Lock()
    t0_box = [None]
    for r in router.replicas.values():
        orig_cb = r.emit_callback

        def cb(replica, emitted, _orig=orig_cb):
            if t0_box[0] is not None:
                tnow = time.perf_counter() - t0_box[0]
                with tlock:
                    for uid in emitted:
                        if uid < warm_base and uid not in first_tok:
                            first_tok[uid] = tnow
            _orig(replica, emitted)

        r.emit_callback = cb

    router.start()
    t0 = time.perf_counter()
    t0_box[0] = t0
    for i, p in enumerate(prompts):
        delay = arrivals[i] - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        router.submit(i, p, max_new_tokens=gen)
    router.drain(timeout_s=knobs["drain_timeout_s"])
    wall = time.perf_counter() - t0
    router.stop()

    out = {uid: toks for uid, toks in router.results().items()
           if uid < warm_base}
    completed = sum(1 for toks in out.values() if len(toks) >= gen)
    total_tokens = sum(len(t) for t in out.values())
    ttfts = np.asarray(sorted(
        first_tok[uid] - arrivals[uid] for uid in first_tok), np.float64)
    good_tokens = sum(len(out.get(uid, []))
                      for uid, t in first_tok.items()
                      if t - arrivals[uid] <= deadline_s)

    # per-replica decode latency: each engine owns its own (labeled)
    # histogram, so the decode pool's p99 is directly readable — the
    # disagg acceptance number (decode never waits behind a prompt)
    per_replica = {}
    for rid, r in sorted(router.replicas.items()):
        snap = r.engine._decode_hist.snapshot()
        rep = r.load_report()
        per_replica[r.name] = {
            "role": r.role, "steps": r.steps,
            "decode_token_p50_s": snap.get("p50"),
            "decode_token_p99_s": snap.get("p99"),
            "goodput_tokens_per_s": rep["goodput_tokens_per_s"],
        }
    decode_pool = [router.replicas[rid] for rid in router.decode_pool]
    pool_p99 = [s for s in (per_replica[r.name]["decode_token_p99_s"]
                            for r in decode_pool) if s is not None]
    pool_p50 = [s for s in (per_replica[r.name]["decode_token_p50_s"]
                            for r in decode_pool) if s is not None]

    trace_dir = knobs["trace_dir"]
    os.makedirs(trace_dir, exist_ok=True)
    snapshot = router.fleet_snapshot(deadline_s=deadline_s)
    snap_path = os.path.join(trace_dir, f"fleet_{arm}.json")
    with open(snap_path, "w") as f:
        json.dump(snapshot, f, indent=1)
    perfetto = router.export_perfetto(
        os.path.join(trace_dir, f"fleet_{arm}_lanes.json"))

    stats = {k: router.stats[k] - base_stats.get(k, 0)
             for k in router.stats}
    attribution = snapshot["slo_attribution"]
    return {
        "arm": arm,
        "replicas": cfg.replicas,
        "prefill_replicas": len(router.prefill_pool),
        "requests": n,
        "completed": completed,
        "dropped": n - completed,
        # informational, not a gate: the warm pass runs closed-loop (all
        # prompts in one ragged batch) while the timed pass batches by
        # arrival, and greedy argmax can flip on near-tied logits across
        # batch compositions — the random tiny CPU model near-ties often;
        # the test-asserted bit-identity contract compares runs of equal
        # composition (tests/test_serving_fleet.py)
        "warm_reference_match_frac": round(sum(
            1 for uid in range(n)
            if out.get(uid) == warm.get(uid)) / max(n, 1), 3),
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_tokens / max(wall, 1e-9), 1),
        "goodput_tokens_per_s": round(good_tokens / max(wall, 1e-9), 1),
        "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4)
                      if len(ttfts) else None,
        "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 4)
                      if len(ttfts) else None,
        # worst decode-pool replica: the conservative fleet p99
        "decode_token_p50_s": max(pool_p50) if pool_p50 else None,
        "decode_token_p99_s": max(pool_p99) if pool_p99 else None,
        "handoffs": stats["handoffs"],
        "handoff_recompute": stats["handoff_recompute"],
        "affinity_hits": stats["affinity_hits"],
        "failovers": stats["failovers"],
        "slo_misses": attribution.get("slo_misses"),
        "per_replica": per_replica,
        "fleet_snapshot": snap_path,
        "perfetto_trace": perfetto,
    }


def run_fleet() -> list:
    """Multi-replica open-loop bench (``BENCH_MODE=serve_fleet``,
    ``make serve-fleet``): the SAME Poisson workload served by (a) a
    unified fleet — every replica prefills and decodes — and (b) a
    disaggregated fleet — prefill replicas hand KV blocks to decode
    replicas (serving/disagg.py). Replicas are in-process threads, so
    the arm runs on CPU CI; the number that matters is the decode-pool
    token p99: the disagg arm's decode replicas never run a prompt, so
    decode latency stays flat under concurrent prefill load. One JSON
    line per arm; each arm also writes the fleet snapshot (for
    ``serve_top --fleet``) and the per-replica Perfetto lanes into
    FLEET_TRACE_DIR."""
    import jax
    import numpy as np

    from deepspeed_tpu.models.zoo import get_model

    on_tpu = on_tpu_or_named_cpu_smoke()
    model_name = os.environ.get("FLEET_MODEL",
                                "llama3-8b" if on_tpu else "tiny")
    layers = int(os.environ.get("FLEET_LAYERS", 3 if on_tpu else 2))
    # CPU defaults pick a SUSTAINED arrival rate (inter-arrival on the
    # order of a serve step) rather than a one-shot burst: the disagg
    # claim — decode p99 isolated from prefill — only shows when
    # prompts keep arriving while earlier requests are still decoding
    n_req = int(os.environ.get("FLEET_REQUESTS", 96 if on_tpu else 24))
    prompt_len = int(os.environ.get("FLEET_PROMPT", 256 if on_tpu else 48))
    shared_len = int(os.environ.get("FLEET_SHARED_PREFIX",
                                    prompt_len * 3 // 4))
    gen = int(os.environ.get("FLEET_GEN", 64 if on_tpu else 24))
    rate = float(os.environ.get("FLEET_RATE", 16.0 if on_tpu else 12.0))
    deadline_s = float(os.environ.get("FLEET_DEADLINE_MS",
                                      2000 if on_tpu else 6000)) / 1000.0
    budget = int(os.environ.get("FLEET_BUDGET", 256 if on_tpu else 64))
    seed = int(os.environ.get("FLEET_SEED", 0))
    replicas = int(os.environ.get("FLEET_REPLICAS", 2))
    prefill_replicas = int(os.environ.get("FLEET_PREFILL", 1))
    arms = os.environ.get("FLEET_ARMS", "unified,disagg").split(",")
    block = 16
    max_seq_len = 1 << (prompt_len + gen + 8).bit_length()

    model = get_model(model_name, num_layers=layers,
                      max_seq_len=max_seq_len, remat=False)
    cfg = model.config
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    if on_tpu:
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)

    # same workload shape as run_slo: shared system prefix + per-request
    # motif tail, Poisson arrivals — identical schedule for both arms
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, (shared_len,))
    prompts = []
    for _ in range(n_req):
        motif = rng.integers(0, cfg.vocab_size, (4,))
        tail = np.tile(motif, (prompt_len - shared_len) // 4 + 1)
        prompts.append(np.concatenate(
            [shared, tail])[:prompt_len].astype(np.int32))
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))

    blocks_per_seq = (prompt_len + gen) // block + 3
    kv_blocks = int(os.environ.get(
        "FLEET_KV_BLOCKS", blocks_per_seq * max(4, n_req // 2) + 2))
    knobs = {
        "replicas": replicas, "prefill_replicas": prefill_replicas,
        "block": block, "blocks_per_seq": blocks_per_seq,
        "kv_blocks": kv_blocks, "budget": budget,
        "decode_steps": int(os.environ.get("FLEET_DECODE_STEPS", 4)),
        "stale_after_s": float(os.environ.get("FLEET_STALE_AFTER_S", 5.0)),
        "drain_timeout_s": float(os.environ.get("FLEET_DRAIN_TIMEOUT_S",
                                                300.0)),
        "trace_dir": os.environ.get("FLEET_TRACE_DIR",
                                    "/tmp/dstpu_serve_fleet"),
    }
    results = []
    for arm in arms:
        arm = arm.strip()
        res = _drive_fleet_arm(arm, model, params, prompts, arrivals, gen,
                               deadline_s, knobs)
        res["metric"] = (
            f"{model_name}-geometry({layers}L) serve_fleet[{arm}] "
            f"tokens/s ({replicas} replicas, {n_req} req, "
            f"poisson {rate}/s, prompt {prompt_len}, gen {gen}, "
            f"{'tpu' if on_tpu else 'cpu'})")
        res["value"] = res["tokens_per_s"]
        res["unit"] = "tokens/s"
        results.append(res)
    return results


def run_quant() -> dict:
    """Serving-quant capacity bench (``BENCH_MODE=serve_quant``,
    ``make serve-quant``): the int8 KV pool's two acceptance numbers on
    ONE fixed HBM byte budget.

    - **sessions per HBM budget** — both arms get the same pool byte
      budget; blocks come from the quant-aware
      ``KVCacheConfig.bytes_per_block`` (int8 payload + fp32 scale per
      head vector vs bf16), so the int8 arm fits
      ``2*head_dim/(head_dim+4)``x the blocks. Each arm then actually
      SERVES its capacity worth of concurrent sessions and reports the
      measured peak live count — the ratio must hold >=
      ``QUANT_SERVE_MIN_SESSIONS_RATIO`` (default 1.8).
    - **handoff wire bytes** — the same cached prompt chain serialized
      raw vs int4-packed (serving/disagg.py); the quantized wire must
      ship <= ``QUANT_SERVE_MAX_WIRE_FRAC`` (default 0.35) of the raw
      bytes.
    - **int4 storage arm** — the packed-nibble uint8 pool serves
      >= ``QUANT_SERVE_MIN_SESSIONS_RATIO_INT4`` (default 1.7) x the
      int8 arm's sessions on the same budget (head_dim 128: 1.94x
      blocks), and the codec's decode round-trip on the bf16 arm's real
      KV pool must hold >= ``QUANT_SERVE_MIN_DECODE_SNR_DB`` (default
      14 dB; per-vector int4 measures ~18-19 dB).

    Violations ride the payload's ``ok``/``violations`` keys, the same
    contract as ``make bench-quant`` — ``tools/bench_diff.py`` fails the
    run on any violation without needing a sentinel per number."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.models.zoo import get_model
    from deepspeed_tpu.serving import disagg

    on_tpu = on_tpu_or_named_cpu_smoke()
    model_name = os.environ.get("QUANT_SERVE_MODEL", "llama3-8b")
    layers = int(os.environ.get("QUANT_SERVE_LAYERS", 3 if on_tpu else 2))
    vocab = int(os.environ.get("QUANT_SERVE_VOCAB",
                               0 if on_tpu else 4096))
    prompt_len = int(os.environ.get("QUANT_SERVE_PROMPT",
                                    256 if on_tpu else 48))
    gen = int(os.environ.get("QUANT_SERVE_GEN", 64 if on_tpu else 8))
    # >= 6 sessions keeps the capacity ratio's floor-division
    # granularity below the 1.8x gate's slack (at 3 the int8 arm's
    # 1.94x byte advantage floors to 5/3 sessions)
    base_sessions = int(os.environ.get("QUANT_SERVE_SESSIONS",
                                       16 if on_tpu else 6))
    min_ratio = float(os.environ.get("QUANT_SERVE_MIN_SESSIONS_RATIO", 1.8))
    # int4 arm: packed-nibble pool must roughly double int8's capacity
    # again (head_dim 128: (128+4)/(64+4) = 1.94x blocks) and its
    # decoded KV must stay above the SNR floor — per-vector int4
    # measures ~18-19 dB on gaussian KV, a broken codec lands near 0
    min_ratio4 = float(os.environ.get(
        "QUANT_SERVE_MIN_SESSIONS_RATIO_INT4", 1.7))
    min_snr4 = float(os.environ.get(
        "QUANT_SERVE_MIN_DECODE_SNR_DB", 14.0))
    max_wire = float(os.environ.get("QUANT_SERVE_MAX_WIRE_FRAC", 0.35))
    block = 16
    max_seq_len = 1 << (prompt_len + gen + 1).bit_length()

    overrides = dict(num_layers=layers, max_seq_len=max_seq_len,
                     remat=False)
    if vocab:
        overrides["vocab_size"] = vocab  # CPU arm: shrink the embed table
    model = get_model(model_name, **overrides)
    cfg = model.config
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)

    rng = np.random.default_rng(0)
    blocks_per_seq = (prompt_len + gen) // block + 2

    def kv_cfg(bits, num_blocks=1):
        return KVCacheConfig(num_layers=layers, kv_heads=cfg.kv_heads,
                             head_dim=cfg.head_dim, block_size=block,
                             num_blocks=num_blocks, quant_bits=bits)

    # ONE byte budget for both arms: exactly base_sessions worth of bf16
    # blocks — the int8 arm's extra capacity is the headline
    hbm_budget = kv_cfg(None).bytes_per_block * blocks_per_seq * base_sessions

    def drive_arm(bits):
        kv_blocks = hbm_budget // kv_cfg(bits).bytes_per_block
        capacity = int(kv_blocks) // blocks_per_seq
        n_req = capacity
        prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
                   .astype(np.int32) for _ in range(n_req)]
        engine = InferenceEngineV2(
            model, params=params, kv_blocks=int(kv_blocks),
            kv_block_size=block, max_tokens_per_step=max(64, prompt_len),
            max_seqs_per_step=max(4, n_req),
            max_blocks_per_seq=blocks_per_seq, prefix_cache=True,
            kv_quant_bits=bits)
        engine.put(list(range(n_req)), prompts, max_new_tokens=gen)
        peak_live = 0
        emitted = {}
        t0 = time.perf_counter()
        while engine.state.seqs or engine._queue:
            out = engine.serve_step()
            live = sum(1 for s in engine.state.seqs.values() if not s.done)
            peak_live = max(peak_live, live)
            for uid, toks in out.items():
                emitted.setdefault(uid, []).extend(toks)
        wall = time.perf_counter() - t0
        total = sum(len(t) for t in emitted.values())
        return engine, prompts, {
            "kv_quant_bits": bits,
            "kv_blocks": int(kv_blocks),
            "bytes_per_block": kv_cfg(bits).bytes_per_block,
            "pool_bytes": int(kv_blocks) * kv_cfg(bits).bytes_per_block,
            "sessions_capacity": capacity,
            "peak_concurrent_sessions": peak_live,
            "requests": n_req,
            "tokens": total,
            "tokens_per_s": round(total / max(wall, 1e-9), 1),
        }

    bf16_engine, bf16_prompts, bf16_arm = drive_arm(None)
    _, _, int8_arm = drive_arm(8)
    _, _, int4_arm = drive_arm(4)
    ratio = (int8_arm["peak_concurrent_sessions"]
             / max(bf16_arm["peak_concurrent_sessions"], 1))
    ratio4 = (int4_arm["peak_concurrent_sessions"]
              / max(int8_arm["peak_concurrent_sessions"], 1))

    # decode-SNR of the packed-nibble codec on the bf16 arm's REAL kv
    # pool (the blocks the serve run just wrote, not synthetic data):
    # quantize → pack → unpack → dequantize round-trip
    from deepspeed_tpu.ops.pallas.quantization import (
        kv_dequantize, kv_pack, kv_quantize, kv_unpack)

    pool = np.asarray(bf16_engine.kv_cache.data, np.float32)
    live = np.abs(pool).reshape(pool.shape[0], pool.shape[1], -1).sum(
        (0, 2)) > 0
    sample = jnp.asarray(pool[:, live][:, :8])
    q4, s4 = kv_quantize(sample, bits=4)
    back = np.asarray(kv_dequantize(kv_unpack(kv_pack(q4, 4), 4), s4,
                                    dtype=jnp.float32))
    src = np.asarray(sample, np.float32)
    noise = float(((src - back) ** 2).mean())
    decode_snr_db = float(10.0 * np.log10(
        max(float((src ** 2).mean()), 1e-12) / max(noise, 1e-12)))

    # handoff wire: the SAME cached chain raw vs int4-packed
    raw_h = disagg.serialize_prefix(bf16_engine, bf16_prompts[0],
                                    wire="raw")
    q_h = disagg.serialize_prefix(bf16_engine, bf16_prompts[0],
                                  wire="int4")
    wire_frac = (q_h.wire_nbytes / max(raw_h.wire_nbytes, 1)
                 if raw_h is not None and q_h is not None else None)

    violations = []
    if ratio < min_ratio:
        violations.append({
            "region": "kv_capacity", "gate": "min_sessions_ratio",
            "limit": min_ratio, "got": round(ratio, 3)})
    if ratio4 < min_ratio4:
        violations.append({
            "region": "kv_capacity", "gate": "min_sessions_ratio_int4",
            "limit": min_ratio4, "got": round(ratio4, 3)})
    if decode_snr_db < min_snr4:
        violations.append({
            "region": "kv_decode", "gate": "min_decode_snr_db",
            "limit": min_snr4, "got": round(decode_snr_db, 2)})
    if wire_frac is None:
        violations.append({
            "region": "kv_wire", "gate": "serialized",
            "limit": "chain cached", "got": "no cached chain"})
    elif wire_frac > max_wire:
        violations.append({
            "region": "kv_wire", "gate": "max_wire_frac",
            "limit": max_wire, "got": round(wire_frac, 3)})
    return {
        "metric": f"{model_name}-geometry({layers}L) serve_quant "
                  f"sessions-per-HBM-budget ratio (int8/bf16, "
                  f"{'tpu' if on_tpu else 'cpu'})",
        "value": round(ratio, 3),
        "unit": "x",
        "hbm_budget_bytes": int(hbm_budget),
        "bf16": bf16_arm,
        "int8": int8_arm,
        "int4": int4_arm,
        "int4_sessions_ratio": round(ratio4, 3),
        "int4_decode_snr_db": round(decode_snr_db, 2),
        "handoff_wire_bytes_raw": (raw_h.wire_nbytes
                                   if raw_h is not None else None),
        "handoff_wire_bytes_int4": (q_h.wire_nbytes
                                    if q_h is not None else None),
        "handoff_wire_frac": (round(wire_frac, 4)
                              if wire_frac is not None else None),
        "handoff_wire_snr_db": (round(q_h.wire_snr_db, 2)
                                if q_h is not None
                                and q_h.wire_snr_db is not None else None),
        "ok": not violations,
        "violations": violations,
    }


def run_tier() -> dict:
    """Tiered-KV + adaptive-speculation bench (``BENCH_MODE=serve_tier``,
    ``make serve-tier``): the host-memory KV tier's two acceptance
    numbers plus the distilled drafter's acceptance edge, one JSON line.

    - **sessions per HBM GB** — both arms serve ``oversub``x more
      sessions than one fixed HBM byte budget holds. The HBM-only arm
      evicts cold chains (a returning session pays full re-prefill); the
      tiered arm pages them to host memory instead. A session counts as
      *held* when its full prompt chain is still servable without
      prefill (HBM prefix cache or host tier). The tiered arm must hold
      >= ``TIER_SERVE_MIN_SESSIONS_RATIO`` (default 2.0) x the HBM-only
      arm on the SAME budget.
    - **warm-resume TTFT** — a mid-decode session pages out
      (``engine.page_out``), then resumes: host->HBM block restore + one
      decode step, vs the cold path re-prefilling the same token count.
      Warm must cost <= ``TIER_SERVE_MAX_RESUME_RATIO`` (default 0.5) x
      cold.
    - **drafter acceptance** — a ``TransformerDrafter`` distilled
      against the target (weights persisted like ``docs/autotuned/``
      artifacts) vs model-free prompt lookup, both with adaptive draft
      length on: the distilled drafter must bank
      >= ``TIER_SERVE_MIN_ACCEPT_EDGE`` (default 1.05) x prompt
      lookup's ACCEPTED DRAFT TOKENS PER ENGINE STEP on the workload
      it was distilled for. Per-step, not raw accept_rate: lookup
      abstains whenever no n-gram matches, and abstention inflates
      accept_rate (a drafter that only drafts sure things scores ~1.0
      with zero speedup) — tokens banked per verify round is the
      number that pays for speculation.

    Violations ride ``ok``/``violations`` (the ``make serve-quant``
    contract); ``tier.sessions_per_gb`` / ``tier.warm_resume_ttft_ratio``
    / ``spec.accept_rate`` are round-over-round sentinels in
    ``tools/bench_diff.py``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.inference.spec_decode import (PromptLookupDrafter,
                                                     TransformerDrafter)
    from deepspeed_tpu.models.zoo import get_model

    on_tpu = on_tpu_or_named_cpu_smoke()
    block = 8
    prompt_len = int(os.environ.get("TIER_SERVE_PROMPT", 24))
    gen = int(os.environ.get("TIER_SERVE_GEN", 8))
    base_sessions = int(os.environ.get("TIER_SERVE_SESSIONS", 4))
    oversub = int(os.environ.get("TIER_SERVE_OVERSUB", 3))
    min_ratio = float(os.environ.get("TIER_SERVE_MIN_SESSIONS_RATIO", 2.0))
    max_resume = float(os.environ.get("TIER_SERVE_MAX_RESUME_RATIO", 0.5))
    min_edge = float(os.environ.get("TIER_SERVE_MIN_ACCEPT_EDGE", 1.05))
    distill_steps = int(os.environ.get("TIER_SERVE_DISTILL_STEPS", 300))

    model = get_model("tiny", dtype=jnp.float32, param_dtype=jnp.float32)
    cfg = model.config
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    blocks_per_seq = (prompt_len + gen) // block + 2
    kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                           kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
                           block_size=block, num_blocks=1)
    hbm_budget = kv_cfg.bytes_per_block * blocks_per_seq * base_sessions
    kv_blocks = hbm_budget // kv_cfg.bytes_per_block
    n_req = base_sessions * oversub
    full_chain = (prompt_len - 1) // block  # final token stays uncached
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
               .astype(np.int32) for _ in range(n_req)]

    def drive_arm(tiered: bool):
        engine = InferenceEngineV2(
            model, params=params, dtype=jnp.float32,
            kv_blocks=int(kv_blocks), kv_block_size=block,
            max_tokens_per_step=32, max_seqs_per_step=base_sessions,
            max_blocks_per_seq=blocks_per_seq, prefix_cache=True,
            host_kv_tier=tiered, host_tier_mb=64)
        engine.put(list(range(n_req)), prompts, max_new_tokens=gen)
        tier = getattr(engine.kv_cache, "host_tier", None)
        peak_resident = 0
        t0 = time.perf_counter()
        emitted = {}
        while engine.state.seqs or engine._queue:
            out = engine.serve_step()
            live = sum(1 for s in engine.state.seqs.values() if not s.done)
            parked = 0 if tier is None else tier.session_count
            peak_resident = max(peak_resident, live + parked)
            for uid, toks in out.items():
                emitted.setdefault(uid, []).extend(toks)
        wall = time.perf_counter() - t0
        # a session is HELD when its whole prompt chain is still
        # servable without prefill (HBM prefix cache or host tier)
        held = sum(1 for p in prompts
                   if engine.holds_prefix_blocks(p) >= full_chain)
        snap = engine.snapshot()
        return engine, emitted, {
            "tiered": tiered,
            "kv_blocks": int(kv_blocks),
            "hbm_budget_bytes": int(hbm_budget),
            "requests": n_req,
            "sessions_held": held,
            "sessions_held_per_hbm_gb": round(
                held / (hbm_budget / (1 << 30)), 1),
            "peak_resident_sessions": peak_resident,
            "paged_out": snap["stats"]["paged_out"],
            "paged_in": snap["stats"]["paged_in"],
            "warm_resume_tokens": snap["stats"]["warm_resume_tokens"],
            "preempted": snap["stats"]["preempted"],
            "tokens": sum(len(t) for t in emitted.values()),
            "wall_s": round(wall, 3),
            "host_tier": snap.get("host_tier"),
        }

    base_engine, base_out, base_arm = drive_arm(False)
    tier_engine, tier_out, tier_arm = drive_arm(True)
    # paging is an optimization, never a semantics change: both arms
    # must emit the identical greedy streams
    bit_identical = all(base_out.get(u) == tier_out.get(u)
                        for u in range(n_req))
    sessions_ratio = (tier_arm["sessions_held"]
                      / max(base_arm["sessions_held"], 1))

    # -- warm-resume TTFT vs cold re-prefill (same engine, warm jit) ----
    resume_prompt_len = int(os.environ.get("TIER_SERVE_RESUME_PROMPT", 96))
    resume_gen = int(os.environ.get("TIER_SERVE_RESUME_GEN", 16))
    rng = np.random.default_rng(1)  # own stream: arms stay independent
    r_blocks_per_seq = (resume_prompt_len + 2 * resume_gen) // block + 2
    # decode_steps=1 keeps the TTFT honest: a multi-token burst would
    # pad BOTH arms' first-token step with K-1 extra decode tokens and
    # compress the warm/cold ratio toward 1
    r_engine = InferenceEngineV2(
        model, params=params, dtype=jnp.float32,
        kv_blocks=4 * r_blocks_per_seq, kv_block_size=block,
        max_tokens_per_step=16, max_seqs_per_step=2, decode_steps=1,
        max_blocks_per_seq=r_blocks_per_seq, prefix_cache=True,
        host_kv_tier=True, host_tier_mb=64)

    def first_token_latency(uid, toks, max_new):
        r_engine.put([uid], [toks], max_new_tokens=max_new)
        t0 = time.perf_counter()
        while True:
            out = r_engine.serve_step()
            if out.get(uid):
                return time.perf_counter() - t0

    def resume_cycle(uid, prompt, measure):
        """Decode ``resume_gen`` tokens, page out mid-decode, resume;
        returns the paged-out -> first-resumed-token latency. The
        un-measured warmup call runs the IDENTICAL shape first so the
        measured cycle times the steady state (host->HBM restore + one
        decode step), not first-compile of the restore path."""
        r_engine.put([uid], [prompt], max_new_tokens=2 * resume_gen)
        got = 0
        while got < resume_gen:
            got += len(r_engine.serve_step().get(uid, []))
        assert r_engine.page_out(uid), "page_out refused a live session"
        t0 = time.perf_counter()
        while True:
            if r_engine.serve_step().get(uid):
                dt = time.perf_counter() - t0
                break
        # drain to completion only for the warmup (compiles tail paths)
        if not measure:
            while any(not s.done
                      for s in r_engine.state.seqs.values()):
                r_engine.serve_step()
        r_engine.flush([uid])
        return dt

    warm_prompt = rng.integers(0, cfg.vocab_size, (resume_prompt_len,)
                               ).astype(np.int32)
    resume_cycle(1000, warm_prompt, measure=False)
    a_prompt = rng.integers(0, cfg.vocab_size, (resume_prompt_len,)
                            ).astype(np.int32)
    warm_ttft = resume_cycle(1, a_prompt, measure=True)
    # cold arm: the SAME token count arrives fresh (different tokens —
    # no prefix-cache help) and pays full re-prefill before its first
    # token
    cold_toks = rng.integers(
        0, cfg.vocab_size,
        (resume_prompt_len + resume_gen,)).astype(np.int32)
    cold_ttft = first_token_latency(2, cold_toks, resume_gen)
    resume_ratio = warm_ttft / max(cold_ttft, 1e-9)

    # -- distilled drafter vs prompt lookup (adaptive k on both) --------
    drafter_path = os.environ.get(
        "TIER_SERVE_DRAFTER_PATH",
        os.path.join(os.path.dirname(__file__), "..", "docs", "autotuned",
                     "spec_drafter_tiny.npz"))
    distilled = None
    if os.path.exists(drafter_path):
        try:
            distilled = TransformerDrafter.load(drafter_path)
            if distilled.model.config.vocab_size != cfg.vocab_size:
                distilled = None
        except Exception:
            distilled = None  # stale artifact: re-distill below
    if distilled is None:
        distilled = TransformerDrafter.small(cfg.vocab_size, window=64)
        # prefix_len tracks the serve prompt length: the drafter must
        # see random tokens in every position a prompt can occupy
        distilled.distill_from(model, params, steps=distill_steps,
                               batch=16, seed=0, prefix_len=16)
        distilled.save(drafter_path)

    rng = np.random.default_rng(2)  # own stream: arms stay independent
    spec_prompts = [rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
                    for _ in range(10)]

    def spec_arm(drafter):
        engine = InferenceEngineV2(
            model, params=params, dtype=jnp.float32,
            kv_blocks=64, kv_block_size=block,
            max_tokens_per_step=64, max_seqs_per_step=8,
            max_blocks_per_seq=16, prefix_cache=False,
            spec_decode=True, spec_k=4, spec_adaptive_k=True,
            drafter=drafter)
        engine.put(list(range(len(spec_prompts))), spec_prompts,
                   max_new_tokens=24)
        out, steps = {}, 0
        while engine.state.seqs or engine._queue:
            for uid, toks in engine.serve_step().items():
                out.setdefault(uid, []).extend(toks)
            steps += 1
        snap = engine.snapshot()
        drafted = snap["stats"]["spec_proposed"]
        accepted = snap["stats"]["spec_accepted"]
        return out, {
            "drafted_tokens": drafted,
            "accepted_tokens": accepted,
            "accept_rate": round(accepted / max(drafted, 1), 4),
            # the throughput number: extra tokens each verify round
            # actually banked. Raw accept_rate rewards ABSTENTION (a
            # drafter that only drafts sure things scores ~1.0 with
            # zero speedup), so the drafter-vs-drafter edge is judged
            # on accepted tokens per engine step instead.
            "accepted_per_step": round(accepted / max(steps, 1), 4),
            "engine_steps": steps,
            "accept_ewma": snap.get("spec_accept_ewma"),
            "wasted_verify_tokens": snap.get(
                "spec_wasted_verify_tokens", 0),
            "spec_backoff_rounds": snap["stats"]["spec_backoff_rounds"],
        }

    lookup_out, lookup_arm = spec_arm(PromptLookupDrafter(max_ngram=3))
    distilled_out, distilled_arm = spec_arm(distilled)
    spec_identical = all(lookup_out.get(u) == distilled_out.get(u)
                         for u in range(len(spec_prompts)))
    accept_edge = (distilled_arm["accepted_per_step"]
                   / max(lookup_arm["accepted_per_step"], 1e-9))

    violations = []
    if sessions_ratio < min_ratio:
        violations.append({
            "region": "kv_tier", "gate": "min_sessions_ratio",
            "limit": min_ratio, "got": round(sessions_ratio, 3)})
    if resume_ratio > max_resume:
        violations.append({
            "region": "kv_tier", "gate": "max_warm_resume_ttft_ratio",
            "limit": max_resume, "got": round(resume_ratio, 3)})
    if not bit_identical:
        violations.append({
            "region": "kv_tier", "gate": "bit_identical_streams",
            "limit": True, "got": False})
    if not spec_identical:
        violations.append({
            "region": "spec", "gate": "bit_identical_streams",
            "limit": True, "got": False})
    if accept_edge < min_edge:
        violations.append({
            "region": "spec", "gate": "min_distilled_accept_edge",
            "limit": min_edge, "got": round(accept_edge, 3)})
    return {
        "metric": f"tiny serve_tier sessions-held ratio (tiered/HBM-only,"
                  f" {'tpu' if on_tpu else 'cpu'})",
        "value": round(sessions_ratio, 3),
        "unit": "x",
        "hbm_budget_bytes": int(hbm_budget),
        "hbm_only": base_arm,
        "tiered": tier_arm,
        "tier.sessions_per_gb": tier_arm["sessions_held_per_hbm_gb"],
        "tier.warm_resume_ttft_ratio": round(resume_ratio, 4),
        "warm_resume_ttft_ms": round(warm_ttft * 1e3, 2),
        "cold_ttft_ms": round(cold_ttft * 1e3, 2),
        "bit_identical": bit_identical,
        "spec_lookup": lookup_arm,
        "spec_distilled": distilled_arm,
        "spec.accept_rate": distilled_arm["accept_rate"],
        "spec_accept_edge": round(accept_edge, 3),
        "drafter_artifact": os.path.relpath(
            drafter_path, os.path.join(os.path.dirname(__file__), "..")),
        "drafter_distill": distilled.distill_summary,
        "ok": not violations,
        "violations": violations,
    }


def _nhpp_arrivals(n, rate, period_s, burst_factor, burst_frac, rng):
    """Nonhomogeneous Poisson arrivals by thinning: a diurnal sinusoid
    (the day/night cycle compressed to ``period_s``) with a burst window
    at ``burst_factor``x the base rate in the first ``burst_frac`` of
    each period — the two arrival shapes a router's tail latency has to
    survive (slow swell and sudden spike)."""
    import math

    import numpy as np

    lam_max = rate * (1.5 + burst_factor)
    out = []
    t = 0.0
    while len(out) < n:
        t += rng.exponential(1.0 / lam_max)
        diurnal = 1.0 + 0.5 * math.sin(2.0 * math.pi * t / period_s)
        in_burst = (t % period_s) / period_s < burst_frac
        lam = rate * diurnal * (burst_factor if in_burst else 1.0)
        if rng.random() < lam / lam_max:
            out.append(t)
    return np.asarray(out)


def _percentiles_ms(ttfts):
    import numpy as np

    if not len(ttfts):
        return {"ttft_p50_ms": None, "ttft_p99_ms": None,
                "ttft_p999_ms": None}
    a = np.asarray(sorted(ttfts), np.float64) * 1e3
    return {"ttft_p50_ms": round(float(np.percentile(a, 50)), 2),
            "ttft_p99_ms": round(float(np.percentile(a, 99)), 2),
            "ttft_p999_ms": round(float(np.percentile(a, 99.9)), 2)}


def _drive_procs_arm(arm, base_dir, model_spec, engine_spec, prompts,
                     arrivals, gen, deadline_s, knobs):
    """One process-fleet arm over the SAME workload and schedule.

    ``least_loaded`` / ``predictive``: N unified workers, the last one
    degraded by ``slow_step_ms`` of per-round delay — the A/B that
    predictive routing must win on TTFT p99. ``chaos``: healthy workers
    plus a ``DSTPU_CHAOS`` self-kill on one of them mid-run (the
    training-side kill_rank spec, reused verbatim) and a scripted
    autoscale swing — measures p99.9 TTFT and zero drops through
    SIGKILL + restart + scale-up/drain. ``disagg``: prefill->decode over
    the socket with the int4 wire codec.
    """
    import threading

    import numpy as np

    from deepspeed_tpu.serving import (AutoscaleSignal, FleetRouter,
                                       ReplicaSupervisor)

    run_dir = os.path.join(base_dir, arm)
    engine = dict(engine_spec)
    if arm == "disagg":
        engine["handoff_wire"] = knobs["wire"]
    sup = ReplicaSupervisor(run_dir, jax_platform=_DRILL_PLATFORM,
                            model=model_spec, engine=engine,
                            seed=knobs["seed"])
    n_rep = knobs["replicas"]
    chaos_victim = None
    if arm == "disagg":
        remotes = [sup.spawn(role="prefill")]
        remotes += [sup.spawn(role="decode")
                    for _ in range(max(1, n_rep - 1))]
    elif arm == "chaos":
        remotes = [sup.spawn(role="unified")]
        # the victim self-kills via the training-side chaos spec after
        # kill_step busy serve rounds — no test scaffolding, the worker
        # dies exactly the way a chaos drill kills a training rank
        chaos_victim = sup.spawn(role="unified", env_extra={
            "DSTPU_CHAOS": (f"kill_rank=1,kill_step={knobs['kill_step']},"
                            f"kill_signal=SIGKILL")})
        remotes.append(chaos_victim)
        remotes += [sup.spawn(role="unified")
                    for _ in range(max(0, n_rep - 2))]
    else:
        remotes = [sup.spawn(role="unified")
                   for _ in range(max(1, n_rep - 1))]
        remotes.append(sup.spawn(role="unified",
                                 step_delay_ms=knobs["slow_step_ms"]))
    # chaos arm only: a signal whose organic thresholds can never fire
    # (queue_low < 0, queue_high huge), so the victim is not drained
    # out from under the chaos kill — the scripted desired swing and
    # the restart act are what land in its decision history
    autoscale = AutoscaleSignal(
        min_replicas=n_rep, max_replicas=n_rep + 2,
        queue_low=-1.0, queue_high=1e9) if arm == "chaos" else None
    router = FleetRouter(
        remotes, stale_after_s=knobs["stale_after_s"],
        affinity_blocks=0,
        routing="predictive" if arm in ("predictive", "chaos") else
        "least_loaded", autoscale=autoscale)
    sup.router = router

    n = len(prompts)
    first_tok = {}
    tlock = threading.Lock()
    t0_box = [None]

    def _wrap_new():
        for r in router.replicas.values():
            if getattr(r, "_bench_wrapped", False):
                continue
            orig_cb = r.emit_callback

            def cb(replica, emitted, _orig=orig_cb):
                if t0_box[0] is not None:
                    tnow = time.perf_counter() - t0_box[0]
                    with tlock:
                        for uid in emitted:
                            if uid not in first_tok:
                                first_tok[uid] = tnow
                _orig(replica, emitted)

            r.emit_callback = cb
            r._bench_wrapped = True

    _wrap_new()
    # compile warm-up OUTSIDE the timed window (run_slo's warm-pass
    # idiom): one request per worker. Routed THROUGH the router — cold
    # predictions tie, so load-score round-robins the warmups across
    # the workers — which doubles as a canary probe: by the time the
    # clock starts, the predictor has a measured service EWMA and
    # prefill rate for every replica instead of a cold-start guess
    # (a cold replica with no observed prefill rate predicts
    # optimistically and would swallow a whole burst). The chaos arm
    # warms via the stubs instead and skips the victim: its busy-round
    # budget belongs to the mid-run kill, and the predictor's cold
    # optimism toward the unprobed victim is exactly what feeds it
    # work before the kill fires.
    from deepspeed_tpu.serving.replica import Submission
    if arm == "chaos":
        warm = [r for r in remotes if r is not chaos_victim]
        for j, r in enumerate(warm):
            r.submit(Submission(uid=1_000_000 + j, tokens=prompts[0],
                                max_new_tokens=gen))

        def _warm_done():
            return all(r.load_report().get("inflight", 0) == 0
                       for r in warm)
    else:
        # TWO sequential rounds: round 1 pays the one-time JIT compile
        # (the router discards each signal's first per-replica sample
        # as exactly that), round 2 measures steady-state — its rates
        # are the first samples the EWMAs keep. Within a round the cold
        # predictions tie at zero, so the load-score tiebreak spreads
        # the probes one per replica.
        for wround in range(2):
            for j in range(len(remotes)):
                router.submit(1_000_000 + wround * len(remotes) + j,
                              prompts[0], max_new_tokens=gen)
            round_deadline = time.time() + 120.0
            while time.time() < round_deadline and router.pending() > 0:
                sup.maintain()
                router.check_health()
                time.sleep(0.05)

        def _warm_done():
            return router.pending() == 0

    warm_deadline = time.time() + 120.0
    while time.time() < warm_deadline and not _warm_done():
        sup.maintain()
        router.check_health()
        time.sleep(0.05)
    t0 = time.perf_counter()
    t0_box[0] = t0
    i = 0
    scaled_up = scaled_down = False
    last_maint = 0.0
    while i < n:
        now = time.perf_counter() - t0
        if arrivals[i] <= now:
            router.submit(i, prompts[i], max_new_tokens=gen)
            i += 1
            if autoscale is not None:
                # scripted swing: the signal demands one more replica
                # mid-burst, then releases it — maintain() does the
                # spin-up and the drain, both recorded in the history
                # fixed targets, not live-count deltas: a crash in the
                # same burst would make `live+1` collapse back to the
                # fleet size and the swing would never move the needle
                if not scaled_up and i >= int(0.5 * n):
                    autoscale.desired = n_rep + 1
                    scaled_up = True
                    sup.maintain()  # act now: a burst can starve the
                    _wrap_new()     # cadenced maintain past the swing
                elif scaled_up and not scaled_down and i >= int(0.85 * n):
                    autoscale.desired = max(1, n_rep)
                    scaled_down = True
                    sup.maintain()
            continue
        if now - last_maint >= knobs["maintain_s"]:
            sup.maintain()
            router.check_health()
            _wrap_new()
            last_maint = now
        time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
    deadline = time.time() + knobs["drain_timeout_s"]
    while time.time() < deadline:
        sup.maintain()
        router.check_health()
        _wrap_new()
        if router.pending() == 0:
            break
        time.sleep(0.02)
    wall = time.perf_counter() - t0
    snapshot_path = sup.write_fleet_snapshot()
    results = router.results()
    reports = [r.load_report() for r in sup.replicas.values()]
    transport = {r.name: dict(zip(("tx_bytes", "rx_bytes"),
                                  r.transport_bytes()))
                 for r in sup.replicas.values()}
    sup.shutdown()

    # uids >= 1e6 are router-routed warm-up probes, not workload
    results = {uid: t for uid, t in results.items() if uid < n}
    completed = sum(1 for t in results.values() if len(t) >= gen)
    total_tokens = sum(len(t) for t in results.values())
    ttfts = {uid: t - arrivals[uid] for uid, t in first_tok.items()
             if uid < n}
    good = sum(len(results.get(uid, [])) for uid, t in ttfts.items()
               if t <= deadline_s)
    wire = sum(r["handoff_wire_bytes"] for r in reports)
    logical = sum(r["handoff_logical_bytes"] for r in reports)
    out = {
        "arm": arm,
        "routing": router.routing,
        "requests": n,
        "completed": completed,
        "dropped": n - completed,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_tokens / max(wall, 1e-9), 1),
        "goodput_tokens_per_s": round(good / max(wall, 1e-9), 1),
        **_percentiles_ms(list(ttfts.values())),
        "handoffs": router.stats["handoffs"],
        "handoff_recompute": router.stats["handoff_recompute"],
        "failed_over_requests": router.stats["failed_over_requests"],
        "handoff_wire_bytes": wire,
        "handoff_logical_bytes": logical,
        "kv_wire_ratio": (round(wire / logical, 4) if logical else None),
        "transport": transport,
        "supervisor_actions": [[round(ts - t0, 3), act, rid]
                               for ts, act, rid in sup.actions],
        "fleet_snapshot": snapshot_path,
    }
    if autoscale is not None:
        out["autoscale_history"] = [
            list(h[1:]) for h in autoscale.history]
    return out


def run_procs() -> dict:
    """Cross-process fleet bench (``BENCH_MODE=serve_procs``,
    ``make serve-procs``): real worker subprocesses behind the socket
    transport, serving one diurnal + bursty open-loop workload through
    four arms — ``least_loaded`` vs ``predictive`` (same fleet with one
    degraded worker: the routing A/B), ``chaos`` (mid-run SIGKILL via
    the DSTPU_CHAOS kill_rank spec + a scripted autoscale swing: p99.9
    TTFT and the zero-drop guarantee), and ``disagg`` (prefill->decode
    KV handoffs over the int4 wire). One JSON line; violations ride the
    ``ok``/``violations`` keys, so ``tools/bench_diff.py`` fails the
    round on any broken gate.

    Gates: predictive TTFT p99 < least_loaded TTFT p99; chaos arm
    drops == 0 with a restart recorded and both scale acts in the
    autoscale decision history; disagg ships >=1 handoff with
    ``kv_wire_ratio`` <= PROCS_MAX_WIRE_RATIO (default 0.5 — int4 wire
    bytes vs the logical pool bytes) whose payloads crossed a real
    socket (the prefill channel's rx byte counter bounds them below).

    Env knobs (CPU defaults in parens): PROCS_REQUESTS (20) — a
    10k-session sweep on real accelerators is PROCS_REQUESTS=10000
    PROCS_RATE=200 PROCS_PERIOD_S=50 PROCS_GEN=32 PROCS_REPLICAS=8
    with PROCS_DRAIN_TIMEOUT_S raised to ~3600; PROCS_PROMPT (48),
    PROCS_SHARED_PREFIX (3/4 of prompt), PROCS_GEN (12), PROCS_RATE
    (1.5/s — ~1.2-1.5x one CPU worker's service rate, see
    _drive_procs_arm), PROCS_PERIOD_S (6) diurnal period,
    PROCS_BURST_FACTOR (3), PROCS_BURST_FRAC (0.2); PROCS_REPLICAS (2),
    PROCS_SLOW_STEP_MS (2000) — the degraded worker's per-round delay;
    PROCS_KILL_STEP (3) busy rounds before the chaos self-kill (a
    round emits decode_steps tokens per sequence, so one request is
    only a handful of busy rounds);
    PROCS_WIRE (int4), PROCS_MAX_WIRE_RATIO (0.5);
    PROCS_DEADLINE_MS (6000), PROCS_ARMS, PROCS_RUN_DIR, PROCS_SEED.
    """
    import numpy as np

    base_dir = os.environ.get("PROCS_RUN_DIR", "/tmp/dstpu_serve_procs")
    model_name = os.environ.get("PROCS_MODEL", "tiny")
    n_req = int(os.environ.get("PROCS_REQUESTS", 20))
    prompt_len = int(os.environ.get("PROCS_PROMPT", 48))
    shared_len = int(os.environ.get("PROCS_SHARED_PREFIX",
                                    prompt_len * 3 // 4))
    gen = int(os.environ.get("PROCS_GEN", 12))
    # ~1.2-1.5x the fast worker's CPU service rate: enough contention
    # that least-loaded overflows onto the degraded worker while the
    # predictor can still win by queueing on the fast one — full
    # saturation would make every policy equally bad
    rate = float(os.environ.get("PROCS_RATE", 1.5))
    period_s = float(os.environ.get("PROCS_PERIOD_S", 6.0))
    burst_factor = float(os.environ.get("PROCS_BURST_FACTOR", 3.0))
    burst_frac = float(os.environ.get("PROCS_BURST_FRAC", 0.2))
    deadline_s = float(os.environ.get("PROCS_DEADLINE_MS", 6000)) / 1e3
    seed = int(os.environ.get("PROCS_SEED", 0))
    arms = os.environ.get(
        "PROCS_ARMS", "least_loaded,predictive,chaos,disagg").split(",")
    block = 8
    blocks_per_seq = (prompt_len + gen) // block + 3

    model_spec = {"name": model_name,
                  "overrides": {"dtype": "float32",
                                "param_dtype": "float32"}}
    engine_spec = dict(
        kv_blocks=blocks_per_seq * max(4, n_req // 2) + 2,
        kv_block_size=block,
        max_tokens_per_step=int(os.environ.get("PROCS_BUDGET", 64)),
        max_seqs_per_step=8, max_blocks_per_seq=blocks_per_seq,
        dtype="float32", request_trace={"sample_rate": 1.0})

    rng = np.random.default_rng(seed)
    vocab = 256
    shared = rng.integers(0, vocab, (shared_len,))
    prompts = []
    for _ in range(n_req):
        motif = rng.integers(0, vocab, (4,))
        tail = np.tile(motif, (prompt_len - shared_len) // 4 + 1)
        prompts.append(np.concatenate(
            [shared, tail])[:prompt_len].astype(np.int32))
    arrivals = _nhpp_arrivals(n_req, rate, period_s, burst_factor,
                              burst_frac, rng)

    knobs = {
        "replicas": int(os.environ.get("PROCS_REPLICAS", 2)),
        "slow_step_ms": float(os.environ.get("PROCS_SLOW_STEP_MS", 2000.0)),
        # busy PUMP ROUNDS, not tokens: a round emits decode_steps
        # tokens per sequence, so one request is only ~4-5 busy rounds —
        # 3 lands the kill mid-first-request on the victim
        "kill_step": int(os.environ.get("PROCS_KILL_STEP", 3)),
        "wire": os.environ.get("PROCS_WIRE", "int4"),
        "stale_after_s": float(os.environ.get("PROCS_STALE_AFTER_S", 5.0)),
        "maintain_s": 0.05,
        "drain_timeout_s": float(os.environ.get("PROCS_DRAIN_TIMEOUT_S",
                                                300.0)),
        "seed": seed,
        "max_wire_ratio": float(os.environ.get("PROCS_MAX_WIRE_RATIO",
                                               0.5)),
    }
    results = {}
    for arm in arms:
        arm = arm.strip()
        results[arm] = _drive_procs_arm(
            arm, base_dir, model_spec, engine_spec, prompts, arrivals,
            gen, deadline_s, knobs)

    violations = []
    ll, pred = results.get("least_loaded"), results.get("predictive")
    if ll and pred and ll["ttft_p99_ms"] and pred["ttft_p99_ms"]:
        if pred["ttft_p99_ms"] >= ll["ttft_p99_ms"]:
            violations.append({
                "region": "routing", "gate": "predictive_beats_p99",
                "limit": ll["ttft_p99_ms"], "got": pred["ttft_p99_ms"]})
    chaos = results.get("chaos")
    if chaos:
        if chaos["dropped"] > 0:
            violations.append({
                "region": "chaos", "gate": "zero_drops",
                "limit": 0, "got": chaos["dropped"]})
        acts = [a[1] for a in chaos["supervisor_actions"]]
        if "restart" not in acts:
            violations.append({
                "region": "chaos", "gate": "restart_recorded",
                "limit": ">=1 restart", "got": acts})
        hist_acts = [h[1] for h in chaos.get("autoscale_history", [])
                     if len(h) == 2]
        if not any(a.startswith("spawn:") for a in hist_acts) or \
                not any(a.startswith("drain:") for a in hist_acts):
            violations.append({
                "region": "autoscale", "gate": "acts_in_history",
                "limit": "spawn + drain", "got": hist_acts})
    dis = results.get("disagg")
    if dis:
        if dis["handoffs"] < 1:
            violations.append({
                "region": "disagg", "gate": "handoffs",
                "limit": ">=1", "got": dis["handoffs"]})
        ratio = dis["kv_wire_ratio"]
        if ratio is None or ratio > knobs["max_wire_ratio"]:
            violations.append({
                "region": "disagg", "gate": "kv_wire_ratio",
                "limit": knobs["max_wire_ratio"], "got": ratio})
        prefill_rx = max((t["rx_bytes"]
                          for t in dis["transport"].values()), default=0)
        if dis["handoff_wire_bytes"] > 0 and \
                prefill_rx < dis["handoff_wire_bytes"]:
            violations.append({
                "region": "disagg", "gate": "wire_over_socket",
                "limit": dis["handoff_wire_bytes"], "got": prefill_rx})

    headline = pred or ll or chaos or dis
    return {
        "metric": f"{model_name} serve_procs tokens/s "
                  f"({knobs['replicas']} worker procs, {n_req} req, "
                  f"nhpp {rate}/s x{burst_factor} bursts, "
                  f"prompt {prompt_len}, gen {gen}, socket transport)",
        "value": headline["tokens_per_s"] if headline else None,
        "unit": "tokens/s",
        "ttft_p999_ms": (chaos or headline or {}).get("ttft_p999_ms"),
        "kv_wire_ratio": (dis or {}).get("kv_wire_ratio"),
        "deadline_ms": deadline_s * 1e3,
        "arms": results,
        "ok": not violations,
        "violations": violations,
    }


def _drive_chaos_arm(arm, base_dir, model_spec, engine_spec, prompts,
                     arrivals, gen, knobs):
    """One chaos-certification arm: the SAME workload and arrival
    schedule through a 2-worker socket fleet, with exactly one fault
    family armed.

    Net faults (``drop``/``delay``/``dup``/``corrupt``/``partition``)
    are armed as the process-global chaos injector in THIS process, so
    they hit the supervisor-side channel endpoints — real frames on the
    real socket. ``kill`` and ``crashloop`` reuse the worker-side
    ``DSTPU_CHAOS`` self-kill. ``hedge`` degrades one worker with a
    per-round delay and lets hedged requests race around it. Fault arms
    run with hedging enabled: a submit frame the fault family ate is a
    request with no stream anywhere, and the hedge deadline is what
    resurrects it (the seq-gap ChannelError then recycles the worker).
    """
    import threading

    from deepspeed_tpu.resilience.chaos import (ChaosInjector, ChaosSpec,
                                                reset_chaos_injector,
                                                set_chaos_injector)
    from deepspeed_tpu.serving import FleetRouter, ReplicaSupervisor
    from deepspeed_tpu.serving.replica import Submission

    net_specs = {
        "drop": f"net_drop_frac={knobs['drop_frac']},net_seed=7",
        "delay": "net_delay_ms=5",
        "dup": "net_dup=2",
        "corrupt": "net_corrupt=6",
        "partition": f"net_partition=r1:{knobs['partition_ops']}",
    }
    run_dir = os.path.join(base_dir, arm)
    crashloop = arm == "crashloop"
    sup = ReplicaSupervisor(
        run_dir, jax_platform=_DRILL_PLATFORM, model=model_spec,
        engine=dict(engine_spec), seed=knobs["seed"],
        max_restarts_per_window=2 if crashloop else 3,
        restart_window_s=60.0 if crashloop else 30.0,
        min_healthy=1)
    n_rep = knobs["replicas"]
    remotes = [sup.spawn(role="unified")]
    if arm == "kill":
        remotes.append(sup.spawn(role="unified", env_extra={
            "DSTPU_CHAOS": "kill_rank=1,kill_step=2,kill_signal=SIGKILL"}))
    elif crashloop:
        # no kill_rank: every respawned incarnation crashes on its
        # first busy round — the supervisor's breaker must contain it
        remotes.append(sup.spawn(role="unified", env_extra={
            "DSTPU_CHAOS": "kill_step=1,kill_signal=SIGKILL"}))
    elif arm == "hedge":
        remotes.append(sup.spawn(role="unified",
                                 step_delay_ms=knobs["slow_step_ms"]))
    else:
        remotes += [sup.spawn(role="unified")
                    for _ in range(max(1, n_rep - 1))]
    router = FleetRouter(
        remotes, stale_after_s=knobs["stale_after_s"],
        affinity_blocks=0,
        # least_loaded for the hedge arm so the degraded worker keeps
        # RECEIVING work (predictive would learn to dodge it and the
        # hedge path would never fire)
        routing="least_loaded" if arm == "hedge" else "predictive",
        hedge_enabled=arm != "none",
        hedge_ttft_factor=2.0 if arm == "hedge" else 3.0,
        hedge_min_s=0.3 if arm == "hedge" else 1.0)
    sup.router = router

    n = len(prompts)
    first_tok = {}
    tlock = threading.Lock()
    t0_box = [None]

    def _wrap_new():
        for r in router.replicas.values():
            if getattr(r, "_bench_wrapped", False):
                continue
            orig_cb = r.emit_callback

            def cb(replica, emitted, _orig=orig_cb):
                if t0_box[0] is not None:
                    tnow = time.perf_counter() - t0_box[0]
                    with tlock:
                        for uid in emitted:
                            if uid not in first_tok:
                                first_tok[uid] = tnow
                _orig(replica, emitted)

            r.emit_callback = cb
            r._bench_wrapped = True

    _wrap_new()

    # each DSTPU_CHAOS incarnation gets one direct probe (uid >= 2e6,
    # outside the workload) so its busy-round kill actually fires —
    # routed traffic alone might starve a fresh replica and leave the
    # drill unexercised
    probed = set()

    def _probe_chaos_workers():
        for rid, remote in list(sup.replicas.items()):
            if rid in probed or remote.draining or remote.exited:
                continue
            if "DSTPU_CHAOS" not in (sup._env_extra.get(rid) or {}):
                continue
            probed.add(rid)
            remote.submit(Submission(uid=2_000_000 + rid,
                                     tokens=prompts[0],
                                     max_new_tokens=4))

    # compile warm-up OUTSIDE the timed window and BEFORE the injector
    # arms (a dropped warm probe would wedge the warm barrier): direct
    # stub probes, skipping DSTPU_CHAOS victims — their busy-round
    # budget belongs to the drill
    warm = [r for r in remotes
            if "DSTPU_CHAOS" not in (
                sup._env_extra.get(r.replica_id) or {})]
    for j, r in enumerate(warm):
        r.submit(Submission(uid=1_000_000 + j, tokens=prompts[0],
                            max_new_tokens=gen))
    warm_deadline = time.time() + 180.0
    while time.time() < warm_deadline and not all(
            r.load_report().get("inflight", 0) == 0 for r in warm):
        sup.maintain()
        router.check_health()
        time.sleep(0.05)

    if arm in net_specs:
        set_chaos_injector(
            ChaosInjector(ChaosSpec.parse(net_specs[arm]), rank=0))
    try:
        from deepspeed_tpu.resilience.chaos import get_chaos_injector

        t0 = time.perf_counter()
        t0_box[0] = t0
        i = 0
        last_maint = 0.0
        inj_stats = None
        while i < n:
            now = time.perf_counter() - t0
            if arrivals[i] <= now:
                router.submit(i, prompts[i], max_new_tokens=gen)
                i += 1
                continue
            if now - last_maint >= knobs["maintain_s"]:
                sup.maintain()
                router.check_health()
                _wrap_new()
                _probe_chaos_workers()
                last_maint = now
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        if arm == "corrupt":
            # corruption is the one fault that kills workers faster
            # than the restart window forgives: every Nth frame corrupt
            # FOREVER means each failover burst re-corrupts, and the
            # breaker (correctly) quarantines the whole fleet — that is
            # a broken NIC, not a survivable fault. The drill models a
            # bounded corruption burst instead: faults through the
            # arrival window, clean wire for the drain, so what gets
            # certified is the recovery (CRC trip -> worker dies loud
            # -> restart + failover) and not a dead-wire verdict.
            inj_stats = dict(get_chaos_injector().net_stats)
            reset_chaos_injector()
        deadline = time.time() + knobs["drain_timeout_s"]
        while time.time() < deadline:
            sup.maintain()
            router.check_health()
            _wrap_new()
            _probe_chaos_workers()
            if router.pending() == 0:
                break
            time.sleep(0.02)
        if crashloop:
            # the workload can drain before the looper's final crash —
            # keep supervising until the breaker verdict is in (each
            # respawned incarnation is probed so its busy-round kill
            # actually fires)
            cl_deadline = time.time() + 60.0
            while time.time() < cl_deadline and not sup.quarantined:
                sup.maintain()
                router.check_health()
                _probe_chaos_workers()
                time.sleep(0.05)
        wall = time.perf_counter() - t0
        if inj_stats is None and arm in net_specs:
            inj_stats = dict(get_chaos_injector().net_stats)
    finally:
        if arm in net_specs:
            reset_chaos_injector()
    sup.write_fleet_snapshot()
    results = router.results()
    live_end = len(sup._live_ids())
    dup_frames = sum(getattr(r.channel, "dup_frames", 0)
                     for r in sup.replicas.values())
    sup.shutdown()

    results = {uid: t for uid, t in results.items() if uid < n}
    completed = sum(1 for t in results.values() if len(t) >= gen)
    total_tokens = sum(len(t) for t in results.values())
    ttfts = {uid: t - arrivals[uid] for uid, t in first_tok.items()
             if uid < n}
    acts = [a[1] for a in sup.actions]
    return {
        "arm": arm,
        "requests": n,
        "completed": completed,
        "dropped": n - completed,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_tokens / max(wall, 1e-9), 1),
        **_percentiles_ms(list(ttfts.values())),
        "tokens": {str(uid): results[uid] for uid in sorted(results)},
        "restarts": acts.count("restart"),
        "quarantines": acts.count("quarantine"),
        "quarantined_lineages": sorted(sup.quarantined),
        "drain_refused": acts.count("drain_refused"),
        "live_at_end": live_end,
        "failed_over_requests": router.stats["failed_over_requests"],
        "hedged": router.stats["hedged"],
        "hedge_wins": router.stats["hedge_wins"],
        "rx_dup_frames": dup_frames,
        "net_faults": inj_stats,
        "supervisor_actions": [[round(ts - t0, 3), act, rid]
                               for ts, act, rid in sup.actions],
    }


def run_chaos_fleet() -> dict:
    """Chaos-certification bench (``BENCH_MODE=chaos_fleet``,
    ``make chaos-fleet``): the PR-13 diurnal + bursty open-loop workload
    served through a socket process fleet while one fault family at a
    time is armed — ``drop``/``delay``/``dup``/``corrupt`` (seeded
    frame-level transport faults), ``partition`` (both directions of one
    worker's link blackholed for a wire-op window), ``kill`` (worker
    SIGKILLs itself mid-request), ``crashloop`` (every respawn crashes
    until the supervisor's circuit breaker quarantines the lineage), and
    ``hedge`` (one degraded worker, hedged requests race around it) —
    against a fault-free ``none`` baseline. One JSON line; violations
    ride ``ok``/``violations`` so ``tools/bench_diff.py`` fails the
    round on any broken gate.

    Gates: every arm drops zero requests (``chaos.zero_drops``); every
    completed stream is bit-identical to the fault-free baseline
    (``chaos.bit_identical`` — greedy decoding through failover,
    hedging, dups and partitions must not change a single token); the
    worst fault-arm TTFT p99.9 stays within CHAOS_MAX_P999_RATIO of the
    baseline (``chaos.ttft_p999_ratio``); the crash-looper is
    quarantined exactly once with restarts bounded by the breaker
    window and the min-healthy floor held; no other arm quarantines
    anything; the hedge arm records ``hedge_wins >= 1``
    (``chaos.hedge_wins``).

    Env knobs (CPU defaults in parens): CHAOS_FLEET_REQUESTS (8),
    CHAOS_FLEET_PROMPT (32), CHAOS_FLEET_GEN (8), CHAOS_FLEET_RATE
    (2.0/s), CHAOS_FLEET_PERIOD_S (4), CHAOS_FLEET_REPLICAS (2),
    CHAOS_FLEET_STALE_S (1.0), CHAOS_FLEET_SLOW_STEP_MS (1500),
    CHAOS_FLEET_DROP_FRAC (0.12), CHAOS_FLEET_PARTITION_OPS (60),
    CHAOS_MAX_P999_RATIO (50), CHAOS_FLEET_ARMS, CHAOS_FLEET_RUN_DIR,
    CHAOS_FLEET_SEED, CHAOS_FLEET_DRAIN_TIMEOUT_S (180)."""
    import numpy as np

    base_dir = os.environ.get("CHAOS_FLEET_RUN_DIR",
                              "/tmp/dstpu_chaos_fleet")
    model_name = os.environ.get("CHAOS_FLEET_MODEL", "tiny")
    n_req = int(os.environ.get("CHAOS_FLEET_REQUESTS", 8))
    prompt_len = int(os.environ.get("CHAOS_FLEET_PROMPT", 32))
    gen = int(os.environ.get("CHAOS_FLEET_GEN", 8))
    rate = float(os.environ.get("CHAOS_FLEET_RATE", 2.0))
    period_s = float(os.environ.get("CHAOS_FLEET_PERIOD_S", 4.0))
    seed = int(os.environ.get("CHAOS_FLEET_SEED", 0))
    max_ratio = float(os.environ.get("CHAOS_MAX_P999_RATIO", 50.0))
    arms = os.environ.get(
        "CHAOS_FLEET_ARMS",
        "none,drop,delay,dup,corrupt,partition,kill,crashloop,hedge"
    ).split(",")
    block = 8
    blocks_per_seq = (prompt_len + gen) // block + 3

    model_spec = {"name": model_name,
                  "overrides": {"dtype": "float32",
                                "param_dtype": "float32"}}
    engine_spec = dict(
        kv_blocks=blocks_per_seq * max(4, n_req) + 2,
        kv_block_size=block, max_tokens_per_step=64,
        max_seqs_per_step=8, max_blocks_per_seq=blocks_per_seq,
        dtype="float32", request_trace={"sample_rate": 1.0})

    rng = np.random.default_rng(seed)
    vocab = 256
    shared = rng.integers(0, vocab, (prompt_len * 3 // 4,))
    prompts = []
    for _ in range(n_req):
        tail = rng.integers(0, vocab,
                            (prompt_len - len(shared),))
        prompts.append(np.concatenate(
            [shared, tail]).astype(np.int32))
    arrivals = _nhpp_arrivals(n_req, rate, period_s, 3.0, 0.2, rng)

    knobs = {
        "replicas": int(os.environ.get("CHAOS_FLEET_REPLICAS", 2)),
        "stale_after_s": float(os.environ.get("CHAOS_FLEET_STALE_S",
                                              1.0)),
        "slow_step_ms": float(os.environ.get("CHAOS_FLEET_SLOW_STEP_MS",
                                             1500.0)),
        "drop_frac": float(os.environ.get("CHAOS_FLEET_DROP_FRAC",
                                          0.12)),
        "partition_ops": int(os.environ.get("CHAOS_FLEET_PARTITION_OPS",
                                            60)),
        "maintain_s": 0.05,
        "drain_timeout_s": float(os.environ.get(
            "CHAOS_FLEET_DRAIN_TIMEOUT_S", 180.0)),
        "seed": seed,
    }
    results = {}
    for arm in arms:
        arm = arm.strip()
        results[arm] = _drive_chaos_arm(
            arm, base_dir, model_spec, engine_spec, prompts, arrivals,
            gen, knobs)

    violations = []
    base = results.get("none")
    fault_arms = [a for a in results if a != "none"]
    for arm, r in results.items():
        if r["dropped"] > 0:
            violations.append({
                "region": arm, "gate": "zero_drops",
                "limit": 0, "got": r["dropped"]})
    bit_identical = True
    if base:
        for arm in fault_arms:
            if results[arm]["tokens"] != base["tokens"]:
                bit_identical = False
                diff = [u for u in base["tokens"]
                        if results[arm]["tokens"].get(u)
                        != base["tokens"][u]]
                violations.append({
                    "region": arm, "gate": "bit_identical",
                    "limit": "tokens == fault-free baseline",
                    "got": f"streams differ for uids {diff[:8]}"})
    p999_ratio = None
    if base and base.get("ttft_p999_ms"):
        worst = max((results[a]["ttft_p999_ms"] for a in fault_arms
                     if results[a].get("ttft_p999_ms")), default=None)
        if worst is not None:
            p999_ratio = round(worst / base["ttft_p999_ms"], 3)
            if p999_ratio > max_ratio:
                violations.append({
                    "region": "chaos", "gate": "ttft_p999_ratio",
                    "limit": max_ratio, "got": p999_ratio})
    cl = results.get("crashloop")
    if cl:
        if not cl["quarantined_lineages"]:
            violations.append({
                "region": "crashloop", "gate": "quarantined",
                "limit": ">=1 lineage", "got": cl["quarantines"]})
        if cl["quarantines"] > len(cl["quarantined_lineages"]):
            violations.append({
                "region": "crashloop", "gate": "no_quarantine_flaps",
                "limit": "one quarantine act per lineage",
                "got": cl["quarantines"]})
        if cl["restarts"] > 2:
            violations.append({
                "region": "crashloop", "gate": "restarts_bounded",
                "limit": 2, "got": cl["restarts"]})
        if cl["live_at_end"] < 1:
            violations.append({
                "region": "crashloop", "gate": "min_healthy_floor",
                "limit": ">=1 live worker", "got": cl["live_at_end"]})
    for arm in results:
        if arm != "crashloop" and results[arm]["quarantines"] > 0:
            violations.append({
                "region": arm, "gate": "no_stray_quarantine",
                "limit": 0, "got": results[arm]["quarantines"]})
    hedge = results.get("hedge")
    if hedge and hedge["hedge_wins"] < 1:
        violations.append({
            "region": "hedge", "gate": "hedge_wins",
            "limit": ">=1", "got": hedge["hedge_wins"]})
    for r in results.values():
        r.pop("tokens", None)  # compared above; too bulky to print

    return {
        "metric": f"{model_name} chaos_fleet tokens/s "
                  f"({knobs['replicas']} worker procs, {n_req} req, "
                  f"{len(results)} fault arms, socket transport)",
        "value": base["tokens_per_s"] if base else None,
        "unit": "tokens/s",
        "chaos.zero_drops": all(r["dropped"] == 0
                                for r in results.values()),
        "chaos.bit_identical": bit_identical,
        "chaos.ttft_p999_ratio": p999_ratio,
        "chaos.hedge_wins": hedge["hedge_wins"] if hedge else None,
        "chaos.quarantined": (len(cl["quarantined_lineages"])
                              if cl else None),
        "arms": results,
        "ok": not violations,
        "violations": violations,
    }


def _obs_clock_arm(arm: str, spec_text: str, skew_s: float,
                   rounds: int) -> dict:
    """One clock-sync accuracy arm: an echo worker subprocess whose wall
    clock is skewed by ``skew_s`` (DSTPU_CLOCK_SKEW_S in its env), pinged
    ``rounds`` times through a real socket channel while the parent-side
    chaos injector runs one net-fault family. Pings are interleaved with
    regular echo messages so the worker's 10 s recv timeout never fires
    and the parent's recv drains the pongs en route.

    ``net_drop`` is deliberately NOT in the matrix: a dropped frame is a
    sequence gap, i.e. a dead channel by design — clock sync on a dead
    channel is meaningless. Delay and dup are the faults a live channel
    survives. The delay arm slows every parent-side outbound frame,
    which both delays the ping's departure (after t0 is stamped) and —
    because the interleaved data send sleeps before the parent drains
    its socket — the pong's processing (t3): the round trip inflates by
    ~2x the delay, and the gate asserts the estimator's *widened*
    uncertainty still covers its true error (the honest-bound
    property), not that the error stays tiny."""
    import subprocess

    from deepspeed_tpu.observability.clocksync import ClockSyncEstimator
    from deepspeed_tpu.resilience.chaos import (ChaosInjector, ChaosSpec,
                                                reset_chaos_injector,
                                                set_chaos_injector)
    from deepspeed_tpu.serving.transport import ChannelError, SocketServer

    echo_worker = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests", "transport_echo_worker.py")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # the worker never imports jax
    env["DSTPU_CLOCK_SKEW_S"] = repr(skew_s)
    env.pop("DSTPU_CHAOS", None)  # faults are parent-side only

    srv = SocketServer()
    proc = subprocess.Popen([sys.executable, echo_worker, str(srv.port)],
                            env=env)
    out = {"arm": arm, "synced": False, "offset_ms": None,
           "uncertainty_ms": None, "err_ms": None, "within_bound": False,
           "rounds": 0}
    chan = None
    try:
        chan = srv.accept(timeout=10.0)
        chan.clock = ClockSyncEstimator()
        if spec_text:
            set_chaos_injector(ChaosInjector(ChaosSpec.parse(spec_text)))
        try:
            for i in range(rounds):
                chan.ping_clock()
                chan.send({"type": "obs", "i": i})
                reply = chan.recv(timeout=10.0)
                if reply is None:
                    break
                out["rounds"] += 1
        finally:
            reset_chaos_injector()
        est = chan.clock
        out["synced"] = est.synced
        if est.synced:
            off, unc = est.offset_s, est.uncertainty_s
            err = abs(off - skew_s)
            out["offset_ms"] = round(off * 1e3, 3)
            out["uncertainty_ms"] = round(unc * 1e3, 3)
            out["err_ms"] = round(err * 1e3, 3)
            # honest-bound gate: the error must sit inside the
            # estimator's own reported uncertainty (+1 ms measurement
            # noise floor for CI jitter)
            out["within_bound"] = err <= unc + 1e-3
        chan.send({"type": "quit"})
    except ChannelError as e:
        out["error"] = str(e)
    finally:
        if chan is not None:
            chan.close()
        srv.close()
        try:
            proc.wait(timeout=10.0)
        except Exception:
            proc.kill()
            proc.wait(timeout=10.0)
    return out


def run_obs_fleet() -> dict:
    """Observability-plane certification (``BENCH_MODE=obs_fleet``,
    ``make obs-fleet``): two gates, one JSON line.

    1. **Tracing overhead** — drive N synthetic request lifecycles
       (enqueue/admit/prefill/emit*G/finish) through a RequestTracer at
       sample_rate=1.0 and again through a disabled tracer; the per-
       request delta must stay under OBS_MAX_TRACE_OVERHEAD_US
       (``obs.trace_overhead_ok``). This is the "tracing is within noise
       of the untraced serve bench" gate, measured at the emit points
       themselves so it cannot be washed out by model time.

    2. **Clock-sync accuracy under the chaos matrix** — a real echo-
       worker subprocess with a skewed wall clock (OBS_FLEET_SKEW_S,
       default 0.25 s — the ±250 ms fleet-skew scenario) is pinged
       through a socket channel under ``clean`` / ``delay``
       (net_delay_ms on the parent's wire path) / ``dup`` arms. Every
       arm must
       converge with |estimate - true skew| inside the estimator's OWN
       reported uncertainty (``obs.offset_bound_ok``) and under the
       absolute cap OBS_MAX_OFFSET_ERR_MS.

    Env knobs: OBS_TRACE_REQUESTS (200), OBS_TRACE_GEN (16),
    OBS_MAX_TRACE_OVERHEAD_US (250), OBS_FLEET_SKEW_S (0.25),
    OBS_CLOCK_ROUNDS (12), OBS_MAX_OFFSET_ERR_MS (50),
    OBS_FLEET_DELAY_MS (5), OBS_FLEET_ARMS (clean,delay,dup)."""
    from deepspeed_tpu.observability.request_trace import RequestTracer

    n_req = int(os.environ.get("OBS_TRACE_REQUESTS", 200))
    gen = int(os.environ.get("OBS_TRACE_GEN", 16))
    max_overhead_us = float(os.environ.get("OBS_MAX_TRACE_OVERHEAD_US",
                                           250.0))
    skew_s = float(os.environ.get("OBS_FLEET_SKEW_S", 0.25))
    rounds = int(os.environ.get("OBS_CLOCK_ROUNDS", 12))
    max_err_ms = float(os.environ.get("OBS_MAX_OFFSET_ERR_MS", 50.0))
    delay_ms = float(os.environ.get("OBS_FLEET_DELAY_MS", 5.0))
    arm_names = os.environ.get("OBS_FLEET_ARMS",
                               "clean,delay,dup").split(",")

    # -- gate 1: emit-point overhead, traced vs disabled ---------------
    def _drive(tracer: RequestTracer) -> float:
        t0 = time.perf_counter()
        for uid in range(n_req):
            tracer.on_enqueue(uid, prompt_tokens=32, queue_depth=1)
            tracer.on_admit(uid, wait_s=0.0)
            tracer.on_prefill(uid, start=time.time(), dur_ms=1.0,
                              tokens=32, start_pos=0)
            for _ in range(gen):
                tracer.on_emit(uid, 1)
            tracer.on_finish(uid)
        return time.perf_counter() - t0

    _drive(RequestTracer(enabled=True, sample_rate=1.0,
                         ring_size=n_req))  # warm up code paths
    traced_s = _drive(RequestTracer(enabled=True, sample_rate=1.0,
                                    ring_size=n_req))
    disabled_s = _drive(RequestTracer(enabled=False))
    overhead_us = max(0.0, (traced_s - disabled_s) / n_req * 1e6)

    # -- gate 2: clock offset accuracy under net faults ----------------
    specs = {"clean": "", "delay": f"net_delay_ms={delay_ms}",
             "dup": "net_dup=3"}
    arms = {}
    for arm in arm_names:
        arm = arm.strip()
        arms[arm] = _obs_clock_arm(arm, specs.get(arm, ""), skew_s,
                                   rounds)

    violations = []
    if overhead_us > max_overhead_us:
        violations.append({"region": "trace", "gate": "overhead_us",
                           "limit": max_overhead_us,
                           "got": round(overhead_us, 1)})
    for arm, r in arms.items():
        if not r["synced"]:
            violations.append({"region": arm, "gate": "clock_synced",
                               "limit": "estimator converged",
                               "got": r.get("error", "unsynced")})
            continue
        if not r["within_bound"]:
            violations.append({"region": arm, "gate": "offset_bound",
                               "limit": f"err <= {r['uncertainty_ms']}ms"
                                        " (own bound)",
                               "got": r["err_ms"]})
        if r["err_ms"] > max_err_ms:
            violations.append({"region": arm, "gate": "offset_err_ms",
                               "limit": max_err_ms, "got": r["err_ms"]})

    worst_err = max((r["err_ms"] for r in arms.values()
                     if r.get("err_ms") is not None), default=None)
    return {
        "metric": f"obs_fleet trace overhead ({n_req} req, "
                  f"{len(arms)} clock arms, skew {skew_s * 1e3:.0f}ms)",
        "value": round(overhead_us, 2),
        "unit": "us/request",
        "obs.trace_overhead_us": round(overhead_us, 2),
        "obs.trace_overhead_ok": overhead_us <= max_overhead_us,
        "obs.offset_err_ms": worst_err,
        "obs.offset_bound_ok": all(r["synced"] and r["within_bound"]
                                   for r in arms.values()),
        "arms": arms,
        "ok": not violations,
        "violations": violations,
    }


def _record_replay_arm(base_dir, journal_path, model_spec, engine_spec,
                       prompts, arrivals, gen, knobs, fault_spec):
    """Record arm of the replay bench: one chaos-fault pass of the
    2-worker socket fleet with the fleet journal installed in THIS
    (driver) process — so the router's ADMIT/ROUTE/EMIT ingress, the
    supervisor's lifecycle acts and the injector's frame-level faults
    all land in one journal, stamped with the config fingerprint and
    the literal re-drive recipe ``tools/replay.py`` consumes."""
    from deepspeed_tpu.observability.clocksync import wall_time
    from deepspeed_tpu.observability.journal import (FleetJournal,
                                                     config_fingerprint,
                                                     reset_journal,
                                                     set_journal)
    from deepspeed_tpu.resilience.chaos import (ChaosInjector, ChaosSpec,
                                                get_chaos_injector,
                                                reset_chaos_injector,
                                                set_chaos_injector)
    from deepspeed_tpu.serving import FleetRouter, ReplicaSupervisor
    from deepspeed_tpu.serving.replica import Submission

    n = len(prompts)
    n_rep = knobs["replicas"]
    router_kw = dict(stale_after_s=knobs["stale_after_s"],
                     affinity_blocks=0, routing="predictive",
                     hedge_enabled=True, hedge_ttft_factor=3.0,
                     hedge_min_s=1.0)
    recipe = {"model": model_spec, "seed": knobs["seed"],
              "engine": dict(engine_spec), "router": router_kw,
              "eos_token_id": None,
              "replicas": [{"replica_id": i, "role": "unified"}
                           for i in range(n_rep)]}
    jr = FleetJournal(journal_path, max_mb=64.0)
    set_journal(jr)
    jr.write_header(
        config_fingerprint(model=model_spec, engine=engine_spec,
                           router=router_kw, seed=knobs["seed"],
                           fault=fault_spec),
        replay=recipe, fault=fault_spec)

    sup = ReplicaSupervisor(
        os.path.join(base_dir, "record"), jax_platform=_DRILL_PLATFORM,
        model=model_spec, engine=dict(engine_spec), seed=knobs["seed"],
        min_healthy=1)
    remotes = [sup.spawn(role="unified") for _ in range(n_rep)]
    router = FleetRouter(remotes, **router_kw)
    sup.router = router

    # compile warm-up outside the recorded workload: direct probes
    # (no router.submit, so nothing lands in the journal's admissions)
    for j, r in enumerate(remotes):
        r.submit(Submission(uid=1_000_000 + j, tokens=prompts[0],
                            max_new_tokens=gen))
    warm_deadline = time.time() + 180.0
    while time.time() < warm_deadline and not all(
            r.load_report().get("inflight", 0) == 0 for r in remotes):
        sup.maintain()
        router.check_health()
        time.sleep(0.05)

    if fault_spec:
        # the replayer re-arms exactly this spec (CHAOS_SPEC note)
        jr.note("CHAOS_SPEC", spec=fault_spec, rank=0)
        set_chaos_injector(
            ChaosInjector(ChaosSpec.parse(fault_spec), rank=0))
    # rebase the journal clock to the workload start so ADMIT offsets
    # encode the replayable arrival schedule, not spawn/warm-up time
    jr.t0 = wall_time()
    try:
        t0 = time.perf_counter()
        i = 0
        last_maint = 0.0
        while i < n:
            now = time.perf_counter() - t0
            if arrivals[i] <= now:
                router.submit(i, prompts[i], max_new_tokens=gen)
                i += 1
                continue
            if now - last_maint >= knobs["maintain_s"]:
                sup.maintain()
                router.check_health()
                last_maint = now
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        if fault_spec:
            # bounded fault burst, same rationale as the chaos bench's
            # corrupt arm: faults through the arrival window, clean
            # wire for the drain. A fault armed FOREVER means each
            # failover burst re-trips it, and on a loaded box the
            # restart churn outruns the breaker window — that is a
            # broken NIC, not a survivable incident. The journal gate
            # certifies the capture of faults + recovery decisions and
            # the replay's bit-identity, not a dead-wire verdict.
            inj = get_chaos_injector()
            if inj is not None:
                jr.note("CHAOS_DISARM", stats=dict(inj.net_stats))
            reset_chaos_injector()
        deadline = time.time() + knobs["drain_timeout_s"]
        while time.time() < deadline:
            sup.maintain()
            router.check_health()
            if router.pending() == 0:
                break
            time.sleep(0.02)
        wall = time.perf_counter() - t0
    finally:
        if fault_spec:
            reset_chaos_injector()
    sup.write_fleet_snapshot()  # serving_fleet/v3 with the journal block
    results = router.results()
    live_end = len(sup._live_ids())
    sup.shutdown()
    stats = jr.snapshot()
    reset_journal()  # close + uninstall: the replay must not re-record

    results = {uid: t for uid, t in results.items() if uid < n}
    completed = sum(1 for t in results.values() if len(t) >= gen)
    total_tokens = sum(len(t) for t in results.values())
    acts = [a[1] for a in sup.actions]
    return {
        "requests": n,
        "completed": completed,
        "dropped": n - completed,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_tokens / max(wall, 1e-9), 1),
        "hedged": router.stats["hedged"],
        "failed_over_requests": router.stats["failed_over_requests"],
        "restarts": acts.count("restart"),
        "quarantines": acts.count("quarantine"),
        "live_at_end": live_end,
        "journal": stats,
    }


def run_replay_fleet() -> dict:
    """Fleet black-box certification (``BENCH_MODE=replay_fleet``,
    ``make replay-fleet``): record one chaos-fault fleet arm into the
    append-only journal (observability/journal.py), then (a) re-drive a
    fresh in-process fleet from the journal alone (``tools/replay.py``,
    scheduled-arrival mode) and require every replayed token stream
    bit-identical to the recorded checksum chains; (b) corrupt exactly
    one recorded chain link, replay again through the CLI path, and
    require a nonzero exit naming the exact diverging uid + decode
    step; (c) bound the recorder's cost — journal append overhead per
    request and journal bytes per request.

    Gates → bench_diff sentinels: ``replay.bit_identical``
    (must_stay_true), ``replay.journal_overhead_us`` (max_ratio),
    ``replay.journal_bytes_per_request`` (max_ratio).

    Env knobs (CPU defaults in parens): REPLAY_FLEET_REQUESTS (6),
    REPLAY_FLEET_PROMPT (32), REPLAY_FLEET_GEN (8), REPLAY_FLEET_RATE
    (2.0/s), REPLAY_FLEET_PERIOD_S (4), REPLAY_FLEET_REPLICAS (2),
    REPLAY_FLEET_STALE_S (1.0), REPLAY_FLEET_SEED (0),
    REPLAY_FLEET_FAULT (drop | delay | dup | none | raw ChaosSpec
    text), REPLAY_FLEET_MODE (scheduled | afap), REPLAY_FLEET_RUN_DIR
    (/tmp/dstpu_replay_fleet), REPLAY_MAX_JOURNAL_US (2500),
    REPLAY_MAX_JOURNAL_BYTES (8192),
    REPLAY_FLEET_DRAIN_TIMEOUT_S (180)."""
    import contextlib

    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import replay as replay_tool

    from deepspeed_tpu.observability.journal import (dump_journal,
                                                     load_journal)

    base_dir = os.environ.get("REPLAY_FLEET_RUN_DIR",
                              "/tmp/dstpu_replay_fleet")
    model_name = os.environ.get("REPLAY_FLEET_MODEL", "tiny")
    n_req = int(os.environ.get("REPLAY_FLEET_REQUESTS", 6))
    prompt_len = int(os.environ.get("REPLAY_FLEET_PROMPT", 32))
    gen = int(os.environ.get("REPLAY_FLEET_GEN", 8))
    rate = float(os.environ.get("REPLAY_FLEET_RATE", 2.0))
    period_s = float(os.environ.get("REPLAY_FLEET_PERIOD_S", 4.0))
    seed = int(os.environ.get("REPLAY_FLEET_SEED", 0))
    mode = os.environ.get("REPLAY_FLEET_MODE", "scheduled")
    fault = os.environ.get("REPLAY_FLEET_FAULT", "drop")
    max_us = float(os.environ.get("REPLAY_MAX_JOURNAL_US", 2500.0))
    max_bytes = float(os.environ.get("REPLAY_MAX_JOURNAL_BYTES",
                                     8192.0))
    # delay injects without recording (nothing to journal); drop is the
    # default because every eaten frame lands as a CHAOS record
    fault_specs = {"drop": "net_drop_frac=0.12,net_seed=7",
                   "delay": "net_delay_ms=5", "dup": "net_dup=2",
                   "none": ""}
    fault_spec = fault_specs.get(fault, fault)
    block = 8
    blocks_per_seq = (prompt_len + gen) // block + 3
    model_spec = {"name": model_name,
                  "overrides": {"dtype": "float32",
                                "param_dtype": "float32"}}
    engine_spec = dict(
        kv_blocks=blocks_per_seq * max(4, n_req) + 2,
        kv_block_size=block, max_tokens_per_step=64,
        max_seqs_per_step=8, max_blocks_per_seq=blocks_per_seq,
        dtype="float32", request_trace={"sample_rate": 1.0})

    rng = np.random.default_rng(seed)
    vocab = 256
    shared = rng.integers(0, vocab, (prompt_len * 3 // 4,))
    prompts = []
    for _ in range(n_req):
        tail = rng.integers(0, vocab, (prompt_len - len(shared),))
        prompts.append(np.concatenate([shared, tail]).astype(np.int32))
    arrivals = _nhpp_arrivals(n_req, rate, period_s, 3.0, 0.2, rng)

    knobs = {
        "replicas": int(os.environ.get("REPLAY_FLEET_REPLICAS", 2)),
        "stale_after_s": float(os.environ.get("REPLAY_FLEET_STALE_S",
                                              1.0)),
        "maintain_s": 0.05,
        "drain_timeout_s": float(os.environ.get(
            "REPLAY_FLEET_DRAIN_TIMEOUT_S", 180.0)),
        "seed": seed,
    }
    os.makedirs(base_dir, exist_ok=True)
    journal_path = os.path.join(base_dir, "fleet.journal")
    record = _record_replay_arm(base_dir, journal_path, model_spec,
                                engine_spec, prompts, arrivals, gen,
                                knobs, fault_spec)

    # (a) clean replay: fresh in-process fleet from the journal alone
    with contextlib.redirect_stdout(sys.stderr):
        verdict = replay_tool.replay_journal(
            journal_path, mode=mode, perfetto=True,
            drain_timeout_s=knobs["drain_timeout_s"])

    # (b) corrupt one chain link mid-journal; the CLI replay must exit
    # nonzero and name exactly that uid + decode step
    records = load_journal(journal_path)
    corrupt_path = os.path.join(base_dir, "fleet.corrupt.journal")
    mut_uid = mut_step = None
    for rec in records:
        if rec.get("kind") == "EMIT" and rec.get("chain"):
            rec["chain"][-1] = int(rec["chain"][-1]) ^ 0x5A5A5A
            mut_uid = rec.get("uid")
            mut_step = int(rec.get("start", 0)) + len(rec["chain"]) - 1
            break
    dump_journal(corrupt_path, records)
    with contextlib.redirect_stdout(sys.stderr):
        corrupt_rc = replay_tool.main(
            [corrupt_path, "--mode", "afap", "--no-warm",
             "--drain-timeout-s", str(knobs["drain_timeout_s"])])
    try:
        with open(corrupt_path + ".verdict.json") as f:
            cd = json.load(f).get("first_divergence") or {}
    except (OSError, ValueError):
        cd = {}
    corrupt_named = (corrupt_rc != 0
                     and str(cd.get("uid")) == str(mut_uid)
                     and cd.get("step") == mut_step)

    overhead_us = record["journal"]["append_us_per_request"]
    bytes_pr = record["journal"]["bytes_per_request"]
    violations = []
    if record["dropped"] > 0:
        violations.append({"region": "record", "gate": "zero_drops",
                           "limit": 0, "got": record["dropped"]})
    if not verdict.get("bit_identical"):
        violations.append({
            "region": "replay", "gate": "bit_identical",
            "limit": "replayed streams == recorded chains",
            "got": verdict.get("first_divergence")})
    if overhead_us > max_us:
        violations.append({"region": "record",
                           "gate": "journal_overhead_us",
                           "limit": max_us, "got": overhead_us})
    if bytes_pr > max_bytes:
        violations.append({"region": "record",
                           "gate": "journal_bytes_per_request",
                           "limit": max_bytes, "got": bytes_pr})
    if mut_uid is None or not corrupt_named:
        violations.append({
            "region": "corrupt", "gate": "divergence_named",
            "limit": f"rc!=0 naming uid={mut_uid} step={mut_step}",
            "got": {"rc": corrupt_rc, "first_divergence": cd}})

    return {
        "metric": f"{model_name} replay_fleet journal overhead "
                  f"({n_req} req, {knobs['replicas']} worker procs, "
                  f"fault={fault or 'none'}, {mode} replay)",
        "value": overhead_us,
        "unit": "us/request",
        "replay.bit_identical": bool(verdict.get("bit_identical")),
        "replay.journal_overhead_us": overhead_us,
        "replay.journal_bytes_per_request": bytes_pr,
        "replay.verified_tokens": verdict.get("verified_tokens"),
        "replay.corrupt_detected": bool(corrupt_named),
        "record": record,
        "replay": {k: verdict.get(k) for k in
                   ("bit_identical", "requests", "verified_tokens",
                    "divergent_requests", "first_divergence", "mode",
                    "chaos_rearmed", "wall_s", "perfetto")},
        "corrupt": {"rc": corrupt_rc,
                    "expected": {"uid": mut_uid, "step": mut_step},
                    "first_divergence": cd},
        "ok": not violations,
        "violations": violations,
    }


def _drive_deploy_arm(arm, base_dir, model_spec, engine_spec, prompts,
                      arrivals, gen, knobs):
    """One deploy-drill arm. ``quiet`` is the reference: the diurnal
    peak workload through a plain 2-worker socket fleet, no events.
    ``drill`` serves the SAME workload and arrival schedule while the
    whole zero-downtime playbook runs against it in one pass:

    * one worker SIGKILLs itself mid-request (``DSTPU_CHAOS``) — the
      supervisor restarts it, the router fails the stream over;
    * a same-seed weight release rolls across the fleet
      (``rolling_swap``) while a designated long decode session is
      mid-stream — quiescing its owner migrates it out WARM (committed
      KV over the quantized wire, zero re-prefill on the target);
    * the autoscale signal swings desired up one (supervisor spawns)
      then back down (migration-backed drain of the newest worker);
    * after the drain, a release with deliberately corrupted canary
      chains is rolled — the A/B parity gate must abort the rollout,
      roll the replica back, and leave the fleet serving.

    Every event is gated later in ``run_deploy_drill``: zero drops,
    token streams bit-identical to the quiet arm, p99.9 TTFT ratio
    bounded, >=1 warm migration, parity-abort observed. The drill arm
    records a fleet journal so MIGRATE/SWAP/SCALE decisions land as
    replayable forensics."""
    import threading

    from deepspeed_tpu.observability.journal import (FleetJournal,
                                                     config_fingerprint,
                                                     reset_journal,
                                                     set_journal)
    from deepspeed_tpu.serving import FleetRouter, ReplicaSupervisor
    from deepspeed_tpu.serving.autoscale import AutoscaleSignal
    from deepspeed_tpu.serving.replica import Submission

    drill = arm == "drill"
    run_dir = os.path.join(base_dir, arm)
    os.makedirs(run_dir, exist_ok=True)
    jr = None
    if drill:
        jr = FleetJournal(os.path.join(run_dir, "journal.bin"),
                          max_mb=64.0)
        set_journal(jr)
        jr.write_header(config_fingerprint(
            model=model_spec, engine=engine_spec, seed=knobs["seed"],
            drill=True))
    sup = ReplicaSupervisor(
        run_dir, jax_platform=_DRILL_PLATFORM, model=model_spec,
        engine=dict(engine_spec), seed=knobs["seed"], min_healthy=1)
    remotes = [sup.spawn(role="unified")]
    if drill:
        # the rush-hour casualty: SIGKILLs itself on its second busy
        # round (same self-kill the chaos bench certifies); its respawn
        # carries a different rank, so the kill fires exactly once
        remotes.append(sup.spawn(role="unified", env_extra={
            "DSTPU_CHAOS": "kill_rank=1,kill_step=2,kill_signal=SIGKILL"}))
    else:
        remotes += [sup.spawn(role="unified")
                    for _ in range(max(1, knobs["replicas"] - 1))]
    router = FleetRouter(
        remotes, stale_after_s=knobs["stale_after_s"],
        affinity_blocks=0, routing="predictive",
        hedge_enabled=drill, hedge_ttft_factor=3.0, hedge_min_s=1.0)
    sup.router = router
    auto = None
    if drill:
        # scripted swing: the drill drives ``desired`` directly (the
        # signal's own thresholds are certified in unit tests) — what
        # is certified HERE is that the supervisor closes the
        # desired-vs-live loop with spawn and migration-backed drain
        auto = AutoscaleSignal(min_replicas=knobs["replicas"],
                               max_replicas=knobs["replicas"] + 1)
        router.autoscale = auto

    n = len(prompts)
    mig_uid = 900_000  # the long session the swap must move warm
    first_tok = {}
    tlock = threading.Lock()
    t0_box = [None]

    def _wrap_new():
        for r in router.replicas.values():
            if getattr(r, "_bench_wrapped", False):
                continue
            orig_cb = r.emit_callback

            def cb(replica, emitted, _orig=orig_cb):
                if t0_box[0] is not None:
                    tnow = time.perf_counter() - t0_box[0]
                    with tlock:
                        for uid in emitted:
                            if uid not in first_tok:
                                first_tok[uid] = tnow
                _orig(replica, emitted)

            r.emit_callback = cb
            r._bench_wrapped = True

    _wrap_new()

    probed = set()

    def _probe_chaos_workers():
        for rid, remote in list(sup.replicas.items()):
            if rid in probed or remote.draining or remote.exited:
                continue
            if "DSTPU_CHAOS" not in (sup._env_extra.get(rid) or {}):
                continue
            probed.add(rid)
            remote.submit(Submission(uid=2_000_000 + rid,
                                     tokens=prompts[0],
                                     max_new_tokens=4))

    # warm-up outside the timed window, skipping the chaos victim (its
    # busy-round budget belongs to the drill)
    warm = [r for r in remotes
            if "DSTPU_CHAOS" not in (
                sup._env_extra.get(r.replica_id) or {})]
    for j, r in enumerate(warm):
        r.submit(Submission(uid=1_000_000 + j, tokens=prompts[0],
                            max_new_tokens=gen))
    warm_deadline = time.time() + 180.0
    while time.time() < warm_deadline and not all(
            r.load_report().get("inflight", 0) == 0 for r in warm):
        sup.maintain()
        router.check_health()
        time.sleep(0.05)

    if drill:
        # publish both releases before the clock starts: "v2" is the
        # honest same-seed release (bit-identical weights, so swapped
        # replicas keep producing the reference streams); "bad" seals a
        # VALID manifest around deliberately wrong canary chains — the
        # parity gate, not the checksum gate, must catch it
        sup.publish_weights("v2", seed=knobs["seed"],
                            canary_prompts=knobs["canary_prompts"],
                            canary_gen=knobs["canary_gen"])
        sup.publish_weights("bad", seed=knobs["seed"],
                            canary_prompts=knobs["canary_prompts"],
                            canary_gen=knobs["canary_gen"],
                            canary_chains={"0": [12345]})

    st = {"swap": None, "scaled_up": False, "scaled_down": False}

    def _events():
        if not drill:
            return
        if st["swap"] is None:
            # deploy mid-rush, but only after the SIGKILL casualty has
            # been restarted (the rollout walks LIVE replicas) and the
            # long session is provably mid-decode — that is what makes
            # the warm migration deterministic, not a timing race
            rec = router._requests.get(mig_uid)
            acts = [a[1] for a in sup.actions]
            if (rec is not None and not rec.done
                    and len(rec.emitted) >= 2
                    and "restart" in acts
                    and len(sup._live_ids()) >= knobs["replicas"]):
                st["swap"] = sup.rolling_swap(
                    "v2", timeout_s=knobs["swap_timeout_s"])
            return
        if not st["scaled_up"]:
            auto.desired = knobs["replicas"] + 1
            st["scaled_up"] = True
            return
        if (not st["scaled_down"]
                and len(sup._live_ids()) >= knobs["replicas"] + 1):
            auto.desired = knobs["replicas"]
            st["scaled_down"] = True

    t0 = time.perf_counter()
    t0_box[0] = t0
    # the designated migration victim: a decode stream long enough to
    # still be mid-flight when its owner quiesces for the swap; the
    # quiet arm runs it too, so its tokens are reference-compared
    router.submit(mig_uid, prompts[0],
                  max_new_tokens=knobs["mig_gen"])
    i = 0
    last_maint = 0.0
    while i < n:
        now = time.perf_counter() - t0
        if arrivals[i] <= now:
            router.submit(i, prompts[i], max_new_tokens=gen)
            i += 1
            continue
        if now - last_maint >= knobs["maintain_s"]:
            sup.maintain()
            router.check_health()
            _wrap_new()
            _probe_chaos_workers()
            _events()
            last_maint = now
        time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
    deadline = time.time() + knobs["drain_timeout_s"]
    while time.time() < deadline:
        sup.maintain()
        router.check_health()
        _wrap_new()
        _probe_chaos_workers()
        _events()
        if router.pending() == 0 and (not drill
                                      or st["scaled_down"]):
            break
        time.sleep(0.02)
    wall = time.perf_counter() - t0

    swap_bad = None
    post_abort_ok = None
    if drill:
        # parity-abort sub-drill on the live (now idle) fleet: the
        # corrupted release must abort, roll back, and leave the fleet
        # able to serve — certified by a probe request afterwards
        swap_bad = sup.rolling_swap("bad",
                                    timeout_s=knobs["swap_timeout_s"])
        router.submit(910_000, prompts[0], max_new_tokens=4)
        probe_deadline = time.time() + 60.0
        while time.time() < probe_deadline:
            sup.maintain()
            router.check_health()
            if router.pending() == 0:
                break
            time.sleep(0.02)
        post_abort_ok = len(router.results().get(910_000, [])) >= 4

    sup.write_fleet_snapshot()
    results = router.results()
    live_end = len(sup._live_ids())
    migrated_in = 0
    for r in sup.replicas.values():
        if r.exited or r._send_failed:
            continue
        try:
            migrated_in += int(r.load_report().get("migrated_in", 0))
        except Exception:
            pass
    sup.shutdown()
    journal_stats = None
    journal_warm = 0
    if jr is not None:
        journal_stats = jr.snapshot()
        jpath = jr.path
        reset_journal()
        # the durable evidence of a warm move: worker-side migrated_in
        # counters are wiped when the target itself gets swapped
        # (reload = fresh engine), so certify from the decision journal
        try:
            from deepspeed_tpu.observability.journal import load_journal
            journal_warm = sum(
                1 for rec in load_journal(jpath)
                if rec.get("kind") == "MIGRATE"
                and rec.get("rung") == "warm")
        except Exception:
            journal_warm = 0

    tokens = {str(uid): results[uid] for uid in sorted(results)
              if uid < n or uid == mig_uid}
    completed = sum(1 for uid in results
                    if uid < n and len(results[uid]) >= gen)
    mig_done = len(results.get(mig_uid, [])) >= knobs["mig_gen"]
    ttfts = {uid: t - arrivals[uid] for uid, t in first_tok.items()
             if uid < n}
    acts = [a[1] for a in sup.actions]
    rs = router.stats
    out = {
        "arm": arm,
        "requests": n + 1,
        "completed": completed + (1 if mig_done else 0),
        "dropped": (n - completed) + (0 if mig_done else 1),
        "wall_s": round(wall, 3),
        **_percentiles_ms(list(ttfts.values())),
        "tokens": tokens,
        "restarts": acts.count("restart"),
        "spawns": acts.count("spawn"),
        "drains": acts.count("drain"),
        "drain_refused": acts.count("drain_refused"),
        "live_at_end": live_end,
        "failed_over_requests": rs["failed_over_requests"],
        "migrations": rs["migrations"],
        "migrate_recompute": rs["migrate_recompute"],
        "migrate_skipped": rs["migrate_skipped"],
        "migrate_wire_bytes": rs["migrate_wire_bytes"],
        "migrated_in_workers": migrated_in,
        "supervisor_actions": [[round(ts - t0, 3), act, rid]
                               for ts, act, rid in sup.actions],
    }
    if drill:
        out["swap"] = st["swap"]
        out["swap_bad"] = swap_bad
        out["post_abort_probe_ok"] = post_abort_ok
        out["journal"] = journal_stats
        out["journal_warm_migrations"] = journal_warm
    return out


def run_deploy_drill() -> dict:
    """Deploy-during-rush-hour certification (``BENCH_MODE=
    deploy_drill``, ``make deploy-drill``): the PR-13 diurnal peak
    workload through a socket process fleet while the ENTIRE
    zero-downtime playbook runs in one pass — a worker SIGKILLed
    mid-request, a same-seed weight release rolled replica-by-replica
    (live sessions migrating out warm ahead of each reload, A/B canary
    parity gating each rejoin), an autoscale swing up and back down
    (migration-backed drain), and a corrupted-canary release whose
    parity gate must abort the rollout and roll back — against a quiet
    2-worker reference arm serving the same schedule.

    Gates: zero dropped requests in both arms (``drill.zero_drops``);
    every stream — including the deliberately migrated long session —
    bit-identical to the quiet arm (``drill.bit_identical``); drill
    TTFT p99.9 within DRILL_MAX_P999_RATIO of quiet
    (``drill.ttft_p999_ratio``); at least one session moved WARM with
    its wire bytes accounted (``migrate.wire_bytes_per_session``); the
    good rollout swaps every replica with parity intact
    (``swap.parity_ok``); the corrupted rollout aborts, rolls back,
    and the fleet still serves (``swap.abort_ok``); the autoscale
    swing both spawned and drained, ending at the floor.

    Env knobs (CPU defaults in parens): DRILL_REQUESTS (8),
    DRILL_PROMPT (32), DRILL_GEN (8), DRILL_MIG_GEN (48), DRILL_RATE
    (2.0/s), DRILL_PERIOD_S (4), DRILL_REPLICAS (2), DRILL_STALE_S
    (1.0), DRILL_MAX_P999_RATIO (80), DRILL_SEED (0), DRILL_RUN_DIR,
    DRILL_DRAIN_TIMEOUT_S (180), DRILL_SWAP_TIMEOUT_S (60)."""
    import numpy as np

    base_dir = os.environ.get("DRILL_RUN_DIR", "/tmp/dstpu_deploy_drill")
    model_name = os.environ.get("DRILL_MODEL", "tiny")
    n_req = int(os.environ.get("DRILL_REQUESTS", 8))
    prompt_len = int(os.environ.get("DRILL_PROMPT", 32))
    gen = int(os.environ.get("DRILL_GEN", 8))
    mig_gen = int(os.environ.get("DRILL_MIG_GEN", 48))
    rate = float(os.environ.get("DRILL_RATE", 2.0))
    period_s = float(os.environ.get("DRILL_PERIOD_S", 4.0))
    seed = int(os.environ.get("DRILL_SEED", 0))
    max_ratio = float(os.environ.get("DRILL_MAX_P999_RATIO", 80.0))
    n_rep = int(os.environ.get("DRILL_REPLICAS", 2))
    block = 8
    blocks_per_seq = (prompt_len + max(gen, mig_gen)) // block + 3

    model_spec = {"name": model_name,
                  "overrides": {"dtype": "float32",
                                "param_dtype": "float32"}}
    engine_spec = dict(
        kv_blocks=blocks_per_seq * max(4, n_req + 1) + 2,
        kv_block_size=block, max_tokens_per_step=64,
        max_seqs_per_step=8, max_blocks_per_seq=blocks_per_seq,
        dtype="float32", request_trace={"sample_rate": 1.0})

    rng = np.random.default_rng(seed)
    vocab = 256
    shared = rng.integers(0, vocab, (prompt_len * 3 // 4,))
    prompts = []
    for _ in range(n_req):
        tail = rng.integers(0, vocab, (prompt_len - len(shared),))
        prompts.append(np.concatenate([shared, tail]).astype(np.int32))
    arrivals = _nhpp_arrivals(n_req, rate, period_s, 3.0, 0.2, rng)
    canary_prompts = [
        [int(t) for t in rng.integers(0, vocab, (prompt_len // 2,))]
        for _ in range(2)]

    knobs = {
        "replicas": n_rep,
        "stale_after_s": float(os.environ.get("DRILL_STALE_S", 1.0)),
        "maintain_s": 0.05,
        "drain_timeout_s": float(os.environ.get(
            "DRILL_DRAIN_TIMEOUT_S", 180.0)),
        "swap_timeout_s": float(os.environ.get(
            "DRILL_SWAP_TIMEOUT_S", 60.0)),
        "seed": seed,
        "mig_gen": mig_gen,
        "canary_prompts": canary_prompts,
        "canary_gen": 8,
    }
    quiet = _drive_deploy_arm("quiet", base_dir, model_spec,
                              engine_spec, prompts, arrivals, gen,
                              knobs)
    drill = _drive_deploy_arm("drill", base_dir, model_spec,
                              engine_spec, prompts, arrivals, gen,
                              knobs)

    violations = []
    for r in (quiet, drill):
        if r["dropped"] > 0:
            violations.append({"region": r["arm"], "gate": "zero_drops",
                               "limit": 0, "got": r["dropped"]})
    bit_identical = drill["tokens"] == quiet["tokens"]
    if not bit_identical:
        diff = [u for u in quiet["tokens"]
                if drill["tokens"].get(u) != quiet["tokens"][u]]
        violations.append({
            "region": "drill", "gate": "bit_identical",
            "limit": "tokens == quiet reference",
            "got": f"streams differ for uids {diff[:8]}"})
    p999_ratio = None
    if quiet.get("ttft_p999_ms") and drill.get("ttft_p999_ms"):
        p999_ratio = round(drill["ttft_p999_ms"]
                           / quiet["ttft_p999_ms"], 3)
        if p999_ratio > max_ratio:
            violations.append({
                "region": "drill", "gate": "ttft_p999_ratio",
                "limit": max_ratio, "got": p999_ratio})
    if drill["migrations"] < 1:
        violations.append({
            "region": "drill", "gate": "warm_migrations",
            "limit": ">=1", "got": drill["migrations"]})
    # worker-side migrated_in counters die with the target's own swap
    # reload, so the warm-install proof comes from the decision journal
    if drill.get("journal_warm_migrations", 0) < 1:
        violations.append({
            "region": "drill", "gate": "journal_warm_migrations",
            "limit": ">=1", "got": drill.get("journal_warm_migrations")})
    wire_per_session = (
        round(drill["migrate_wire_bytes"]
              / max(1, drill["migrations"]), 1)
        if drill["migrations"] else None)
    swap = drill.get("swap") or {}
    parity_ok = bool(swap and not swap.get("aborted")
                     and swap.get("parity_ok")
                     and swap.get("swapped", 0) >= 1)
    if not parity_ok:
        violations.append({
            "region": "swap", "gate": "parity_ok",
            "limit": "rollout completes with canary parity",
            "got": swap or "swap never ran"})
    bad = drill.get("swap_bad") or {}
    abort_ok = bool(bad.get("aborted")
                    and bad.get("parity_ok") is False
                    and bad.get("rolled_back", 0) >= 1
                    and drill.get("post_abort_probe_ok"))
    if not abort_ok:
        violations.append({
            "region": "swap", "gate": "abort_ok",
            "limit": "corrupt canary aborts + rolls back + serves",
            "got": {"swap_bad": bad,
                    "post_abort_probe_ok":
                        drill.get("post_abort_probe_ok")}})
    if drill["spawns"] < 1 or drill["drains"] < 1:
        violations.append({
            "region": "autoscale", "gate": "swing",
            "limit": ">=1 spawn and >=1 migration-backed drain",
            "got": {"spawns": drill["spawns"],
                    "drains": drill["drains"]}})
    if drill["live_at_end"] != n_rep:
        violations.append({
            "region": "autoscale", "gate": "settled_at_floor",
            "limit": n_rep, "got": drill["live_at_end"]})
    for r in (quiet, drill):
        r.pop("tokens", None)  # compared above; too bulky to print

    total_tokens_s = None
    if quiet["wall_s"]:
        total_tokens_s = round(
            (quiet["requests"] - 1) * gen / quiet["wall_s"], 1)
    return {
        "metric": f"{model_name} deploy_drill "
                  f"({n_rep} worker procs, {n_req}+1 req, kill + "
                  f"rolling swap + autoscale swing, socket transport)",
        "value": total_tokens_s,
        "unit": "tokens/s",
        "drill.zero_drops": all(r["dropped"] == 0
                                for r in (quiet, drill)),
        "drill.bit_identical": bit_identical,
        "drill.ttft_p999_ratio": p999_ratio,
        "drill.warm_migrations": drill["migrations"],
        "swap.parity_ok": parity_ok,
        "swap.abort_ok": abort_ok,
        "migrate.wire_bytes_per_session": wire_per_session,
        "arms": {"quiet": quiet, "drill": drill},
        "ok": not violations,
        "violations": violations,
    }


if __name__ == "__main__":
    mode = os.environ.get("BENCH_MODE", "serve")
    if mode == "serve_fleet":
        for arm_result in run_fleet():
            print(json.dumps(arm_result))
    elif mode == "serve_procs":
        _pp = run_procs()
        print(json.dumps(_pp))
        if not _pp.get("ok", True):
            raise SystemExit(1)
    elif mode == "chaos_fleet":
        _cp = run_chaos_fleet()
        print(json.dumps(_cp))
        if not _cp.get("ok", True):
            raise SystemExit(1)
    elif mode == "obs_fleet":
        _op = run_obs_fleet()
        print(json.dumps(_op))
        if not _op.get("ok", True):
            raise SystemExit(1)
    elif mode == "deploy_drill":
        _dp = run_deploy_drill()
        print(json.dumps(_dp))
        if not _dp.get("ok", True):
            raise SystemExit(1)
    elif mode == "replay_fleet":
        _rp = run_replay_fleet()
        print(json.dumps(_rp))
        if not _rp.get("ok", True):
            raise SystemExit(1)
    elif mode == "serve_quant":
        _qp = run_quant()
        print(json.dumps(_qp))
        if not _qp.get("ok", True):
            raise SystemExit(1)
    elif mode == "serve_tier":
        _tp = run_tier()
        print(json.dumps(_tp))
        if not _tp.get("ok", True):
            raise SystemExit(1)
    else:
        print(json.dumps(run_slo() if mode == "serve_slo" else run()))

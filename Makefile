# Test entry points (VERDICT r2 #9: driver-observable tiers).
#
# Tiers (reference analog: modal CI's curated tests/unit/v1 subset vs the
# full nightly matrix, .github/workflows/*):
#   make smoke  — fast tier, target <15 min: excludes tests marked `slow`
#   make test   — full suite
#   make bench  — the headline bench.py JSON line (real TPU when present)
#
# XDIST workers default to auto; on single-core CI hosts xdist overhead
# outweighs parallelism, so auto collapses to plain pytest there.

NPROC := $(shell nproc)
# xdist only when installed AND the host has spare cores
XDIST ?= $(shell if [ $(NPROC) -gt 2 ] && python -c "import xdist" 2>/dev/null; then echo "-n $$(( $(NPROC) - 1 )) --dist loadfile"; fi)
PYTEST ?= python -m pytest

.PHONY: test smoke slow bench bench-real bench-proxy bench-hostgap bench-overlap bench-longctx bench-quant bench-diff quant-sweep fleet-demo chaos serve-slo serve-fleet serve-quant serve-tier serve-procs chaos-fleet obs-fleet replay-fleet deploy-drill

smoke:
	$(PYTEST) tests/ -q -m "not slow" $(XDIST)

test:
	$(PYTEST) tests/ -q $(XDIST)

slow:
	$(PYTEST) tests/ -q -m "slow" $(XDIST)

bench:
	python bench.py

# The real shape (8L · 131,072 vocab, ZeRO-Infinity streaming) is the
# default; bench-real spells it out, bench-proxy restores the 3L/8k
# resident-param proxy shape (docs/roofline.md round 6).
bench-real:
	python bench.py

bench-proxy:
	BENCH_PROXY=1 python bench.py

# A/B the per-layer overlap engine: one unstaged run (depth 0 — the
# pre-round-7 schedule; the prefetch ring stays on) then one staged run
# (depth 4 — pin_stage barriers sequence the full ring's fetches against
# layer compute). Compare tokens/s/chip, hidden_comm_frac and
# exposed_param_fetch_ms across the two JSON lines (docs/performance.md).
bench-overlap:
	BENCH_OVERLAP_DEPTH=0 python bench.py
	BENCH_OVERLAP_DEPTH=4 python bench.py

# Long-context tier: the unified sp planner + analytic per-region
# attribution (attn / sp_comm / host_kv_stream, exposed vs hidden) at
# 256k and 1M tokens on a simulated sp degree — no compiled step, runs
# on the CPU sim (docs/roofline.md round 8; BENCH_SEQ/BENCH_SP/
# BENCH_HBM_GB and the dim knobs documented in bench.py).
bench-longctx:
	BENCH_LONGCTX=1 BENCH_PEAK_TFLOPS=197 BENCH_HBM_GBPS=819 python bench.py
	BENCH_LONGCTX=1 BENCH_PEAK_TFLOPS=197 BENCH_HBM_GBPS=819 BENCH_SEQ=1048576 BENCH_SP=8 python bench.py

# Quantization acceptance gates (observability/quant_stats.py
# run_quant_bench): measures the ZeRO++ trio's error on real tensors —
# qwZ int8 param-fetch SNR, qgZ two-level int8+int4 grad-reduce SNR,
# fp8 e4m3 MLP — against the DEFAULT_GATES bounds, verifies the
# all-knobs-off path is bit-exact, and exits nonzero on any violation.
# BENCH_QUANT_INJECT=corrupt_scale demonstrates the trip. CPU-safe
# (docs/quantized_comm.md "Measuring the trade").
bench-quant:
	BENCH_QUANT=1 python bench.py

# Fail-loud regression sentinel over the BENCH_r*.json trajectory:
# newest vs previous round per headline metric (throughput, mfu,
# hidden_comm_frac, host_gap_ms, quant gates); exits nonzero past the
# thresholds (tools/bench_diff.py).
bench-diff:
	python tools/bench_diff.py

# The {qwZ x qgZ x hpZ} before/after attribution sweep on the real
# 8L · 131k-vocab shape (analytic, CPU-safe). --persist writes the
# winning mode into the autotuner's real-shape defaults file, which
# bench.py reads back as quant_mode (tools/quant_sweep.py).
quant-sweep:
	python tools/quant_sweep.py --persist docs/autotuned/real_shape.json

# Two-process CPU demo of the fleet observability layer: both ranks
# publish shards into a temp run dir, then the aggregated report (skew,
# slowest-rank attribution, straggler score) is printed. No TPU needed.
fleet-demo:
	JAX_PLATFORMS=cpu python tools/fleet_top.py --demo

# A/B the pipelined loop: one blocking run (depth 0) then one pipelined
# run (depth 2). Compare tokens/s/chip and host_gap_ms across the two
# JSON lines — the gap is the host overhead dispatch-ahead hides.
bench-hostgap:
	BENCH_PIPELINE_DEPTH=0 BENCH_PREFETCH_DEPTH=0 python bench.py
	BENCH_PIPELINE_DEPTH=2 BENCH_PREFETCH_DEPTH=2 python bench.py

# Open-loop serving SLO harness (tools/serve_bench.py run_slo): Poisson
# arrivals against the v2 engine with the admission queue, shared-prefix
# KV cache and prompt-lookup speculation on, then the same workload with
# both off (SLO_COMPARE=1). One JSON line: p50/p99 TTFT (queue wait
# included), per-decode-token latency, goodput under SLO_DEADLINE_MS,
# queue-depth timeline, speedup_vs_baseline, and the per-request SLO
# attribution (per-phase p50/p99 + dominant miss phase). SLO_TRACE=1
# additionally asserts phase-sum closure against measured wall time,
# dumps the trace JSONL for tools/serve_top.py, and exports per-request
# Perfetto lanes to SLO_TRACE_DIR. CPU-sized defaults; scale with
# SLO_REQUESTS/SLO_RATE/SLO_PROMPT/SLO_GEN/SLO_KV_BLOCKS
# (docs/serving.md).
serve-slo:
	BENCH_MODE=serve_slo SLO_COMPARE=1 SLO_TRACE=1 python bench.py

# Multi-replica serving fleet (tools/serve_bench.py run_fleet): the SAME
# open-loop Poisson workload served by a unified fleet (every replica
# prefills + decodes) and a disaggregated fleet (prefill replicas hand
# KV blocks to decode replicas — serving/disagg.py). One JSON line per
# arm: tokens/s, TTFT p50/p99 from scheduled arrival, the decode-pool
# per-token p99 (the disagg win: decode never waits behind a prompt),
# handoff counts, per-replica breakdown. Each arm writes the fleet
# snapshot for `python tools/serve_top.py --fleet <snap.json>` plus
# per-replica Perfetto lanes into FLEET_TRACE_DIR (default
# /tmp/dstpu_serve_fleet). Replicas are in-process threads — runs on
# CPU CI; scale with FLEET_REPLICAS/FLEET_REQUESTS/FLEET_RATE
# (docs/serving.md "Multi-replica fleet").
serve-fleet:
	BENCH_MODE=serve_fleet python bench.py

# int8-KV serving capacity arm: concurrent sessions per fixed HBM byte
# budget (int8 pool vs bf16 pool, same budget — must hold >= 1.8x) and
# the disagg handoff wire bytes raw vs int4-packed (must ship <= 0.35x).
# Violations ride the payload's ok/violations keys, so bench_diff fails
# the round on a regression (QUANT_SERVE_* env knobs; docs/serving.md
# "Quantized KV cache & handoff wire").
serve-quant:
	BENCH_MODE=serve_quant python bench.py

# Tiered-KV + adaptive-speculation arm: sessions held per HBM GB with
# the host-memory tier vs HBM-only on the same byte budget (must hold
# >= 2x), warm-resume TTFT vs cold re-prefill (must cost <= 0.5x), and
# the distilled drafter's accepted-tokens-per-step edge over prompt
# lookup (must beat >= 1.05x) — all three streams asserted
# bit-identical. Violations ride ok/violations, so bench_diff fails
# the round on a regression (TIER_SERVE_* env knobs; docs/serving.md
# "Tiered KV hierarchy" / "Adaptive speculation").
serve-tier:
	BENCH_MODE=serve_tier python bench.py

# Cross-process fleet (tools/serve_bench.py run_procs): real worker
# SUBPROCESSES behind the length-prefixed CRC socket transport
# (serving/transport/), one diurnal+bursty open-loop workload through
# four arms — least_loaded vs predictive routing on a fleet with one
# degraded worker (the routing A/B: predictive must beat p99 TTFT),
# chaos (mid-run SIGKILL via DSTPU_CHAOS kill_rank + a scripted
# autoscale swing: zero drops, restart + spawn/drain acts recorded,
# p99.9 TTFT), and disagg (prefill->decode KV handoffs over the int4
# wire across real sockets, kv_wire_ratio gate). One JSON line;
# violations ride ok/violations so bench_diff fails the round. CPU
# defaults; scale with PROCS_REQUESTS/PROCS_RATE/PROCS_REPLICAS
# (docs/serving.md "Cross-process fleet").
serve-procs:
	BENCH_MODE=serve_procs python bench.py

# Chaos-certified fleet (tools/serve_bench.py run_chaos_fleet): the full
# transport fault matrix injected INSIDE the socket channel's wire path —
# seeded frame drops, fixed per-frame delay, frame duplication, payload
# byte corruption (CRC trip), and a one-way partition blackholing one
# replica — plus mid-run SIGKILL, a crash-looping worker (quarantined by
# the restart circuit breaker), and a hedged-requests arm against a slow
# replica. Every arm replays the serve-procs diurnal+bursty schedule and
# must finish with zero drops and token streams bit-identical to the
# fault-free baseline (greedy decoding makes recovery observable);
# crash-loop must quarantine without flapping while holding the
# min-healthy floor, and the hedge arm must record >= 1 hedge win. The
# one JSON line carries chaos.* keys bench_diff sentinels consume
# (chaos.zero_drops must stay true, chaos.ttft_p999_ratio bounded).
# CPU defaults; scale with CHAOS_FLEET_REQUESTS/CHAOS_FLEET_ARMS
# (docs/resilience.md "Serving fleet fault matrix").
chaos-fleet:
	BENCH_MODE=chaos_fleet python bench.py

# Observability-plane certification (tools/serve_bench.py run_obs_fleet):
# (a) request-tracer emit-point overhead at sample_rate=1.0 vs a disabled
# tracer, gated at OBS_MAX_TRACE_OVERHEAD_US per request — tracing must
# stay within noise of the untraced serve path; (b) clock-sync offset
# accuracy: an echo-worker subprocess with a ±250 ms skewed wall clock
# (DSTPU_CLOCK_SKEW_S) is pinged through a real socket channel under the
# clean / delay / dup net-fault arms, and every arm's
# |estimate - true skew| must land inside the estimator's own reported
# uncertainty (the honest-bound gate) and under OBS_MAX_OFFSET_ERR_MS.
# One JSON line with obs.* keys bench_diff sentinels consume
# (docs/observability.md "Fleet tracing & clock sync").
obs-fleet:
	BENCH_MODE=obs_fleet python bench.py

# Fleet black-box certification (tools/serve_bench.py run_replay_fleet):
# record one chaos-fault fleet arm into the append-only CRC-framed
# journal (admissions + per-candidate routing forensics + chaos
# injections + per-request token checksum chains), then re-drive a
# fresh fleet from the journal alone (tools/replay.py) and require
# every replayed token stream bit-identical to the recorded chains;
# corrupt one chain link and require the replay CLI to exit nonzero
# naming the exact uid + decode step; bound the recorder's cost under
# REPLAY_MAX_JOURNAL_US / REPLAY_MAX_JOURNAL_BYTES per request. One
# JSON line with replay.* keys bench_diff sentinels consume
# (docs/observability.md "Fleet black box & incident replay").
replay-fleet:
	BENCH_MODE=replay_fleet python bench.py

# Zero-downtime operations certification (tools/serve_bench.py
# run_deploy_drill): the diurnal-peak workload through a socket process
# fleet while the whole playbook runs in ONE pass — a worker SIGKILLed
# mid-request, a same-seed weight release rolled replica-by-replica
# (live sessions migrate out WARM over the quantized wire before each
# reload, A/B canary token parity gates each rejoin), an autoscale
# swing up and back down (migration-backed drain), and a release with
# deliberately corrupted canary chains whose parity gate must abort the
# rollout and roll the replica back. Gated on zero dropped requests,
# every stream bit-identical to a quiet reference fleet, bounded TTFT
# p99.9 ratio, and >=1 warm migration (zero re-prefill). One JSON line
# with drill.*/swap.*/migrate.* keys bench_diff sentinels consume
# (docs/serving.md "Zero-downtime operations").
deploy-drill:
	BENCH_MODE=deploy_drill python bench.py

# Fault-injection drill on the 8-device CPU sim: SIGKILL a training rank
# mid-run, let the elastic agent restart it, and assert the auto-resumed
# run's final loss is bit-identical to a fault-free run
# (docs/resilience.md; tools/chaos_run.py --signal SIGTERM drills the
# graceful drain + emergency-checkpoint path instead).
chaos:
	JAX_PLATFORMS=cpu python tools/chaos_run.py

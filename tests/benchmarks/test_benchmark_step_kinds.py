"""A serve step by its kind, on the benchmark's side: the four readers of
``harness/step_kinds.py`` on a hand-made span list and counter window, a
tiny engine's profile put into the kinds its own counters name, each new
metric's entry and file, and the CPU rehearsal of a tiny closed loop, which
reports the two counter metrics and neither span metric."""

import glob
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import program_trace as P
from benchmarks.harness import step_kinds as K
from benchmarks.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "fixtures", "BENCHMARK.tiny-steps.json")


def step(step_id, start, dur):
    return P.Span("serve_step", start, dur, {"step_id": step_id}, "main")


def phase(name, start, dur, **ids):
    return P.Span(name, start, dur, ids, "main")


def dispatch(step_id, start, program, token_steps=1, **ids):
    return phase("dispatch", start, 0.001, step_id=step_id, program=program,
                 token_steps=token_steps, **ids)


# A slice of one second, five steps. Step 10, before the slice, issued the
# burst that step 11 collects (``ahead=1``) while it issues none itself.
# Steps 12 and 14 are mixed: a decode call and a chunk call (14: two). Step
# 13 is a lone decode step; step 15 reads nothing.
SPANS = [
    dispatch(10, -0.050, "multi_decode", 8, ahead=1),
    step(11, 0.100, 0.060),
    phase("fetch", 0.105, 0.050), phase("bookkeep", 0.156, 0.003),
    step(12, 0.200, 0.024),
    phase("schedule", 0.2002, 0.0008), phase("build_batch", 0.2010, 0.0005),
    dispatch(12, 0.2015, "decode"), phase("build_batch", 0.2026, 0.0010),
    dispatch(12, 0.2036, "prefill", S=1, tq=256),
    phase("fetch", 0.2050, 0.0170), phase("bookkeep", 0.2221, 0.0015),
    step(13, 0.300, 0.012),
    dispatch(13, 0.3005, "decode"), phase("fetch", 0.3016, 0.0100),
    step(14, 0.400, 0.030),
    phase("schedule", 0.4002, 0.0010),
    dispatch(14, 0.4015, "decode"), dispatch(14, 0.4030, "prefill"),
    dispatch(14, 0.4045, "prefill"),
    phase("fetch", 0.4060, 0.0220), phase("bookkeep", 0.4281, 0.0017),
    step(15, 0.500, 0.0004),
]
# the device: busy under the burst, and in each mixed step from a little
# after its first dispatch to a little before its fetch returns
DEVICE = [("%fusion.1 = f32[] fusion()", 0.090, 0.065),      # step 11: no gap
          ("%fusion.2 = f32[] fusion()", 0.2020, 0.0195),    # step 12
          ("%fusion.3 = f32[] fusion()", 0.3010, 0.0104),    # step 13
          ("%fusion.4 = f32[] fusion()", 0.4020, 0.0250)]    # step 14


def program_trace(spans=SPANS, device=DEVICE):
    return P.ProgramTrace(T.Trace({0: list(device)}, [], 0.0, 1.0, {0: []}),
                          sorted(spans, key=lambda s: (s.start_s, -s.dur_s)),
                          {})


class Ctx:
    def __init__(self, trace_dir=""):
        self.config, self.notes = {"kind": "serve"}, []
        self.bench_dir, self.trace_dir = mf.BENCH_DIR, trace_dir

    def note(self, obj):
        self.notes.append(obj)


def test_the_slices_steps_by_kind():
    by = K.steps_by_kind(program_trace())
    assert {k: [s.ids["step_id"] for s in v] for k, v in by.items()} == {
        "mixed": [12, 14], "prefill": [], "lone": [13], "burst": [11],
        "empty": [15]}
    # the burst a step collects is the one the step before it issued
    assert K.calls_by_step(SPANS)[11] == [("multi_decode", 8)]
    assert 10 not in K.calls_by_step(SPANS)
    # every step of the slice has a kind: the counts add up to the spans
    assert sum(len(v) for v in by.values()) == 5


def test_mixed_step_ms_is_the_median_of_the_mixed_steps(monkeypatch):
    pt = program_trace()
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    ctx = Ctx()
    reader = mf.load_module("layer_metrics", "mixed_step_ms.gen")
    assert reader.read(ctx, {}) == pytest.approx(27.0)    # of 24 and 30
    note = ctx.notes[0]["steps_by_kind"]
    assert {k: v["steps"] for k, v in note.items()} == {
        "mixed": 2, "lone": 1, "burst": 1, "empty": 1}
    assert note["burst"]["median_ms"] == pytest.approx(60.0)
    assert note["lone"]["median_ms"] == pytest.approx(12.0)


def test_mixed_step_host_ms_is_the_idle_inside_them_by_phase(monkeypatch):
    pt = program_trace()
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    ctx = Ctx()
    reader = mf.load_module("layer_metrics", "mixed_step_host_ms.gen")
    # step 12: idle 0.2000-0.2020 and 0.2215-0.2240 (4.5 ms); step 14:
    # 0.4000-0.4020 and 0.4270-0.4300 (5.0 ms)
    assert reader.read(ctx, {}) == pytest.approx(4.75)
    idle = ctx.notes[0]["idle_by_kind"]
    mixed = idle["mixed"]["by_phase"]
    assert sum(mixed.values()) == pytest.approx(4.75)
    # by the innermost phase open: step 12's 0.2000-0.2020 is 0.2 ms of the
    # step's own, 0.8 schedule, 0.5 build_batch, 0.5 dispatch; ...
    assert mixed["schedule"] == pytest.approx((0.8 + 1.0) / 2)
    assert mixed["bookkeep"] == pytest.approx((1.5 + 1.7) / 2)
    assert mixed["fetch"] == pytest.approx((0.5 + 1.0) / 2)
    assert idle["burst"]["ms_per_step"] == pytest.approx(5.0)  # its tail
    assert idle["empty"]["ms_per_step"] == pytest.approx(0.4)
    assert idle["lone"]["steps"] == 1


@pytest.mark.parametrize("metric", ["mixed_step_ms.gen",
                                    "mixed_step_host_ms.gen"])
def test_a_slice_without_a_mixed_step_reads_nothing(monkeypatch, metric,
                                                    tmp_path):
    reader = mf.load_module("layer_metrics", metric)
    pt = program_trace(spans=[s for s in SPANS
                              if s.ids.get("step_id") not in (12, 14)
                              and not 0.2 <= s.start_s < 0.3
                              and not 0.4 <= s.start_s < 0.5])
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    ctx = Ctx()
    assert reader.read(ctx, {}) is None
    assert ctx.notes                 # the other kinds are still printed
    # no step at all, an engine that names no kinds, no profile: nothing
    pt = program_trace(spans=[])
    assert reader.read(Ctx(), {}) is None
    pt = program_trace()
    monkeypatch.setattr(K, "step_kind", None)
    assert reader.read(Ctx(), {}) is None
    monkeypatch.undo()
    ctx = Ctx(str(tmp_path))
    assert reader.read(ctx, {"trace": None}) is None
    assert reader.read(ctx, {"trace": T.Trace({}, [], 0.0, 1.0)}) is None
    assert ctx.notes == []


# the window of the same four kinds, by hand: 2 mixed steps (54 ms, 40 of
# them in fetch; 9 tokens), 1 lone (12 ms; 4), 1 burst (60 ms; 32) and an
# empty one; 3 bursts planned of which 1 was issued ahead and 2 collected
COUNTERS = {
    "steps_mixed": 2, "step_s_mixed": 0.054, "step_wait_s_mixed": 0.040,
    "step_tokens_mixed": 9,
    "steps_prefill": 0, "step_s_prefill": 0.0, "step_wait_s_prefill": 0.0,
    "step_tokens_prefill": 0,
    "steps_lone": 1, "step_s_lone": 0.012, "step_wait_s_lone": 0.010,
    "step_tokens_lone": 4,
    "steps_burst": 1, "step_s_burst": 0.060, "step_wait_s_burst": 0.050,
    "step_tokens_burst": 32,
    "steps_empty": 1, "step_s_empty": 0.0004, "step_wait_s_empty": 0.0,
    "step_tokens_empty": 0,
    "calls_multi_decode": 2, "calls_issued_ahead": 1,
    "bursts_planned": 3, "burst_steps_clamped": 5,
    "burst_refused_prefill_pending": 2, "burst_refused_budget": 1,
    "burst_refused_seq_cap": 0, "burst_refused_pool": 0,
    "ahead_refused_free_slot": 1, "ahead_refused_budget": 1}


def test_mixed_step_token_share_over_the_window():
    reader = mf.load_module("layer_metrics", "mixed_step_token_share.gen")
    ctx = Ctx()
    assert reader.read(ctx, {"counters": {"engine": COUNTERS}}) \
        == pytest.approx(100.0 * 9 / 45)
    note = ctx.notes[0]["window_steps_by_kind"]
    assert set(note) == {"mixed", "lone", "burst", "empty"}
    assert note["mixed"] == {"steps": 2, "tokens": 9,
                             "step_ms": pytest.approx(27.0),
                             "wait_ms": pytest.approx(20.0)}
    # a window without a mixed step: nothing, never 0 (the note stays)
    none = dict(COUNTERS, steps_mixed=0, step_tokens_mixed=0)
    ctx = Ctx()
    assert reader.read(ctx, {"counters": {"engine": none}}) is None
    assert "mixed" not in ctx.notes[0]["window_steps_by_kind"]
    # the parent's engine does not count its steps by kind
    parent = {k: v for k, v in COUNTERS.items() if k.startswith("calls_")}
    ctx = Ctx()
    assert reader.read(ctx, {"counters": {"engine": parent}}) is None
    assert reader.read(ctx, {}) is None and ctx.notes == []


def test_issued_ahead_share_and_the_plans_reasons():
    reader = mf.load_module("layer_metrics", "issued_ahead_share.gen")
    ctx = Ctx()
    assert reader.read(ctx, {"counters": {"engine": COUNTERS}}) \
        == pytest.approx(50.0)
    assert ctx.notes[0]["burst_plans"] == {
        k: v for k, v in COUNTERS.items()
        if k.startswith(("burst_", "ahead_", "bursts_"))}
    # the parent's engine counts the calls and no reason: the share, no note
    parent = {k: v for k, v in COUNTERS.items() if k.startswith("calls_")}
    ctx = Ctx()
    assert reader.read(ctx, {"counters": {"engine": parent}}) \
        == pytest.approx(50.0)
    assert ctx.notes == []
    # a window without a burst, or an engine without the counters: nothing
    idle = dict.fromkeys(COUNTERS, 0)
    assert reader.read(Ctx(), {"counters": {"engine": idle}}) is None
    assert reader.read(Ctx(), {}) is None


# -- the profile and the counters name one thing -------------------------------

def test_a_tiny_engines_profile_falls_into_the_kinds_its_counters_name(
        devices, tmp_path):
    """Prompts, mixed steps, a full batch's bursts issued ahead and a lone
    step under a profiler session on the CPU: the ``serve_step`` spans, put
    into kinds from their ``dispatch`` spans, count what ``steps_<kind>``
    counted over the same steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import (STEP_KINDS,
                                                   InferenceEngineV2)
    from deepspeed_tpu.models.zoo import get_model
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    model = get_model("tiny", param_dtype=jnp.float32, dtype=jnp.float32)
    engine = InferenceEngineV2(
        model, mesh=build_mesh(TopologyConfig(), devices=jax.devices()[:1]),
        params=model.init(jax.random.PRNGKey(0)), dtype=jnp.float32,
        kv_blocks=64, kv_block_size=8, max_tokens_per_step=32,
        max_seqs_per_step=2, max_blocks_per_seq=16, decode_steps=4,
        prefix_cache=False)

    def prompt(n, seed):
        return np.random.default_rng(seed).integers(0, 200, n).astype(
            np.int32)

    def run(base):
        # two slots: a full batch (bursts issued ahead), a third request
        # that is admitted when one ends (a mixed step), tails of one token
        engine.put([base + 1], [prompt(20, 1)], max_new_tokens=22)
        engine.put([base + 2], [prompt(5, 2)], max_new_tokens=10)
        for _ in range(3):                  # nothing queued: a call ahead
            engine.serve_step()
        engine.put([base + 3], [prompt(40, 3)], max_new_tokens=6)
        engine.generate_all()
        engine.serve_step()                                   # an empty one

    run(0)                                                    # compile outside
    before = dict(engine.stats)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run(10)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans = P.read_spans(path)
    lo = min(s.start_s for s in spans) - 1.0
    hi = max(s.end_s for s in spans) + 1.0
    pt = P.ProgramTrace(T.Trace({0: []}, [], lo, hi, {0: []}), spans, {})
    by = K.steps_by_kind(pt)
    counted = {k: engine.stats[f"steps_{k}"] - before[f"steps_{k}"]
               for k in STEP_KINDS}
    assert {k: len(v) for k, v in by.items()} == counted
    assert counted["mixed"] >= 1 and counted["burst"] >= 2
    assert counted["empty"] == 1 and counted["prefill"] >= 1
    assert engine.stats["calls_issued_ahead"] > before["calls_issued_ahead"]
    assert sum(counted.values()) == len(pt.named("serve_step"))
    engine.close()


# -- the manifest ----------------------------------------------------------------

GEN = ["serve-gen-closed", "serve-qnext-gen-closed"]
LONG = ["serve-sala-longctx-decode", "serve-kimi-code-longctx-decode",
        "serve-dots3-longctx-decode"]
# name -> (layer, source, moves, unit, better, workloads), in the list's order
NEW = {
    "mixed_step_ms.gen": ("serve entry", "program_span", "tpot_p90_ms", "ms",
                          "lower", GEN),
    "mixed_step_host_ms.gen": ("serve entry", "program_span", "tpot_p90_ms",
                               "ms", "lower", GEN),
    "mixed_step_token_share.gen": ("scheduler / KV", "program_counter",
                                   "tpot_p90_ms", "%", "lower", GEN),
    "issued_ahead_share.gen": ("serve entry", "program_counter",
                               "serve_tokens_per_s", "%", "higher",
                               GEN + LONG)}


@pytest.mark.parametrize("metric", list(NEW))
def test_the_manifest_finds_the_new_metrics_reader(metric):
    man = mf.load_manifest()
    (entry,) = [m for m in man["per_layer"] if m["name"] == metric]
    assert (entry["layer"], entry["source"], entry["moves"], entry["unit"],
            entry["better"], entry["workloads"]) == NEW[metric]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for cell in entry["workloads"]:
        assert entry["moves"] in {
            m["name"] for m in mf.metrics_of(man, "end_to_end", cell)}
        assert entry in mf.metrics_of(man, "per_layer", cell)
    assert callable(mf.load_module("layer_metrics", metric).read)
    assert metric in [m["name"] for m in json.load(open(TINY))["per_layer"]]


def test_the_new_entries_stand_behind_what_was_there_in_their_order():
    """By relative order, not pinned to the tail: a later PR appends."""
    names = [m["name"] for m in mf.load_manifest()["per_layer"]]
    at = [names.index(n) for n in NEW]
    assert at == sorted(at) and at[0] > names.index("loop_passes_per_token")
    # the cell whose set of readers its own test pins lists none of them
    man = mf.load_manifest()
    assert not {m["name"] for m in mf.metrics_of(
        man, "per_layer", "serve-ouro-gen-closed")} & set(NEW)
    # tpot_p90_ms has readers now, in each closed loop whose window admits
    for cell in GEN:
        moving = [m["name"] for m in mf.metrics_of(man, "per_layer", cell)
                  if m["moves"] == "tpot_p90_ms"]
        assert set(moving) >= {"mixed_step_ms.gen", "mixed_step_host_ms.gen",
                               "mixed_step_token_share.gen"}


# -- a tiny closed loop, rehearsed on the CPU ------------------------------------

ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           JAX_ENABLE_COMPILATION_CACHE="false",
           XLA_FLAGS="--xla_force_host_platform_device_count=4")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)


def test_traced_rehearsal_reports_the_counter_metrics_and_their_notes():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "tiny-gen", "--seed", str(2**31 + 54), "--seconds",
         "2", "--trace", "1", "--manifest", TINY, "--rehearse"],
        env=ENV, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(x) for x in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in last["metrics"].items()}
    # the two counter metrics, and no span metric off a TPU
    assert set(got) == {"mixed_step_token_share.gen", "issued_ahead_share.gen"}
    assert 0.0 < got["mixed_step_token_share.gen"] < 100.0
    assert 0.0 <= got["issued_ahead_share.gen"] <= 100.0
    notes = {k: v for x in lines[:-1] for k, v in x.get("note", {}).items()}
    window = notes["window_steps_by_kind"]
    # what the kinds returned is what the runner counted over the window
    counted = notes["counters"]["engine"]
    assert sum(v["tokens"] for v in window.values()) == sum(
        counted[k] for k in ("tokens_gather", "tokens_prefill_kernel",
                             "tokens_decode", "tokens_multi_decode"))
    assert all(v["wait_ms"] <= v["step_ms"] for v in window.values())
    plans = notes["burst_plans"]
    assert plans["bursts_planned"] >= 1
    assert plans["burst_refused_prefill_pending"] >= 1

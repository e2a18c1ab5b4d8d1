"""The program call as the unit of the serving trace, on the benchmark's
side: the join of ``dstpu/dispatch`` spans with the executions of their
programs (``harness/program_calls.py``) on hand-made timelines, each new
per-layer reader on a hand-made counter delta, every new metric's file and
entry, and the CPU rehearsal of a tiny cell of each kind, which reports the
counter metrics and no span metric."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import program_calls as C
from benchmarks.harness import program_trace as P
from benchmarks.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "fixtures", "BENCHMARK.tiny-calls.json")

PREFILL, DECODE = "jit_dstpu_serve_prefill(7)", "jit_dstpu_serve_decode(3)"
BURST, GATHER = "jit_dstpu_serve_multi_decode(2)", "jit_dstpu_serve_gather(1)"


def dispatch(start, program, tokens, **ids):
    return P.Span("dispatch", start, 0.001,
                  dict(ids, program=program, tokens=tokens), "main")


# a slice of one second. Its first execution was dispatched before the
# slice began; a split step follows whose three calls the host issues 2 ms
# apart while the device takes 18, 15 and 12 ms over them (the second call
# of the prefill program is issued before the first one starts); a burst; a
# speculative round (the gather program); a pick program, which nobody
# dispatches under a span; and a chunk call whose execution leaves the slice
MODULES = [(PREFILL, 0.005, 0.010),
           (DECODE, 0.1005, 0.018), (PREFILL, 0.1185, 0.015),
           (PREFILL, 0.1335, 0.012),
           (BURST, 0.2003, 0.150), ("jit_dstpu_pick_greedy(5)", 0.3600, 0.001),
           (GATHER, 0.4002, 0.080),
           (PREFILL, 0.9905, 0.015)]
SPANS = [P.Span("serve_step", 0.099, 0.050, {"step_id": 7}, "main"),
         dispatch(0.100, "decode", 31, padded_rows=32, call=0),
         dispatch(0.102, "prefill", 25, padded_rows=64, call=1, S=2, tq=32),
         dispatch(0.104, "prefill", 5, padded_rows=8, call=2, S=1, tq=8),
         dispatch(0.200, "multi_decode", 256, padded_rows=256, token_steps=8),
         dispatch(0.400, "spec", 12, padded_rows=256),
         dispatch(0.990, "prefill", 32, padded_rows=32, S=1, tq=32)]


def program_trace(modules=MODULES, spans=SPANS, t0=0.0, t1=1.0):
    return P.ProgramTrace(T.Trace({0: []}, [], t0, t1, {0: modules}),
                          spans, {})


def test_join_pairs_calls_in_the_order_of_issue_and_drops_the_borders():
    joined = C.join_run(program_trace())
    assert [(p.span.ids["program"], p.span.ids["tokens"],
             round(p.start_s, 4), round(1e3 * p.device_s, 3))
            for p in joined.pairs] == [
        ("decode", 31, 0.1005, 18.0), ("prefill", 25, 0.1185, 15.0),
        ("prefill", 5, 0.1335, 12.0), ("multi_decode", 256, 0.2003, 150.0),
        ("spec", 12, 0.4002, 80.0)]
    # the straddling first call (an execution with no span in the slice)
    # and the last (a span whose execution leaves it)
    assert joined.dropped == {"jit_dstpu_serve_prefill": 1, "prefill": 1}
    assert C.us_per_row(joined, "prefill") == pytest.approx(
        1e6 * (0.015 + 0.012) / 30)
    assert C.us_per_row(joined, "multi_decode") == pytest.approx(
        1e6 * 0.150 / 256)
    assert C.us_per_row(joined, "gather") is None


def test_summary_by_shape_and_lead():
    s = C.summary(C.join_run(program_trace()))
    assert s["prefill_by_S_x_tq"] == {
        "2x32": {"calls": 1, "device_ms": pytest.approx(15.0), "rows": 25.0},
        "1x8": {"calls": 1, "device_ms": pytest.approx(12.0), "rows": 5.0}}
    pre = s["by_program"]["prefill"]
    assert (pre["calls"], pre["rows"]) == (2, 15.0)
    assert pre["device_ms"] == pytest.approx(13.5)
    # the host ran ahead: the calls waited for the device, not it for them
    assert pre["lead_ms"] == pytest.approx((16.5 + 29.5) / 2)
    assert pre["least_lead_ms"] == pytest.approx(16.5)
    assert s["by_program"]["decode"]["lead_ms"] == pytest.approx(0.5)
    assert list(s["by_program"]) == ["decode", "multi_decode", "prefill",
                                     "spec"]
    assert s["dropped"] == {"jit_dstpu_serve_prefill": 1, "prefill": 1}


def test_an_execution_before_its_span_is_the_heads():
    """Two executions of one program and one span: by order, the span's
    call is the execution that starts after it."""
    joined = C.join([dispatch(0.5, "decode", 4)],
                    [(0.1, 0.2, "jit_dstpu_serve_decode"),
                     (0.6, 0.7, "jit_dstpu_serve_decode")])
    assert [(p.start_s, p.end_s) for p in joined.pairs] == [(0.6, 0.7)]
    assert joined.dropped == {"jit_dstpu_serve_decode": 1}
    # ... and with the executions the other way about nothing pairs
    joined = C.join([dispatch(0.5, "decode", 4)],
                    [(0.1, 0.2, "jit_dstpu_serve_decode")])
    assert joined.pairs == [] and joined.dropped == {
        "jit_dstpu_serve_decode": 1, "decode": 1}


def test_the_device_planes_stamps_may_read_ahead_of_the_hosts():
    """On the chip an execution reads up to 0.9 ms *before* its dispatch
    span (the two planes' stamps are a run's constant apart): it still
    pairs, and the note's least lead shows the shift."""
    joined = C.join([dispatch(0.5000, "prefill", 200, S=1, tq=256),
                     dispatch(0.5300, "decode", 4)],
                    [(0.4991, 0.5135, "jit_dstpu_serve_prefill"),
                     (0.5292, 0.5480, "jit_dstpu_serve_decode")])
    assert len(joined.pairs) == 2 and joined.dropped == {}
    by = C.summary(joined)["by_program"]
    assert by["prefill"]["least_lead_ms"] == pytest.approx(-0.9)
    # but not a call's length before: that execution is another call's
    joined = C.join([dispatch(0.5000, "prefill", 200)],
                    [(0.4890, 0.5035, "jit_dstpu_serve_prefill")])
    assert joined.pairs == []


def test_a_lost_event_pairs_nothing():
    """A span the profile lost would shift every later pair by one. The
    programs' names then line up for no head, and the join vouches for
    nothing: every execution and every span is dropped, which the note
    shows, and the reader reads nothing."""
    spans = [s for s in SPANS if s.ids.get("tokens") != 25]
    joined = C.join_run(program_trace(spans=spans))
    assert joined.pairs == []
    assert joined.dropped == {
        "jit_dstpu_serve_prefill": 3, "jit_dstpu_serve_decode": 1,
        "jit_dstpu_serve_multi_decode": 1, "jit_dstpu_serve_gather": 1,
        "decode": 1, "prefill": 2, "multi_decode": 1, "spec": 1}
    assert C.us_per_row(joined, "prefill") is None


def test_spans_of_another_vocabulary_are_not_calls():
    train_steps = P.Span("dispatch", 0.1, 0.01, {}, "main")
    joined = C.join([train_steps, dispatch(0.2, "decode", 1)],
                    [(0.21, 0.22, "jit_dstpu_serve_decode")])
    assert len(joined.pairs) == 1 and joined.dropped == {}


class Ctx:
    def __init__(self, trace_dir=""):
        self.config, self.notes = {"kind": "serve"}, []
        self.bench_dir, self.trace_dir = mf.BENCH_DIR, trace_dir

    def note(self, obj):
        self.notes.append(obj)


@pytest.mark.parametrize("metric", [
    "prefill_us_per_row", "prefill_us_per_row.burst",
    "prefill_us_per_row.gen"])
def test_the_span_reader_on_the_hand_made_timeline(monkeypatch, metric):
    pt = program_trace()
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    reader = mf.load_module("layer_metrics", metric)
    ctx = Ctx()
    assert reader.read(ctx, {}) == pytest.approx(900.0)
    note = ctx.notes[0]["program_calls"]
    assert note["dropped"] == {"jit_dstpu_serve_prefill": 1, "prefill": 1}
    assert set(note["prefill_by_S_x_tq"]) == {"2x32", "1x8"}
    # times the mean rows of a paired call: the mean call
    rows = note["by_program"]["prefill"]["rows"]
    assert 900.0 * rows / 1e3 == pytest.approx(
        note["by_program"]["prefill"]["device_ms"])
    # a program with no execution in the slice reads nothing, never 0
    only_decode = [m for m in MODULES if m[0] != PREFILL]
    pt = program_trace(modules=only_decode)
    assert reader.read(Ctx(), {}) is None
    # nor does a slice with no dispatch span, or none of the programs
    pt = program_trace(spans=[])
    assert reader.read(Ctx(), {}) is None
    pt = program_trace(modules=[("jit_dstpu_pick_greedy(5)", 0.3, 0.001)])
    assert reader.read(Ctx(), {}) is None


def test_the_parents_spans_join_too(monkeypatch):
    """The parent's dispatch spans name ``program``, ``seqs`` and
    ``tokens`` and nothing else: the join holds, the note has no shapes."""
    bare = [P.Span(s.name, s.start_s, s.dur_s,
                   {k: v for k, v in s.ids.items()
                    if k in ("program", "tokens", "step_id")}, s.thread)
            for s in SPANS]
    pt = program_trace(spans=bare)
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    ctx = Ctx()
    reader = mf.load_module("layer_metrics", "prefill_us_per_row.burst")
    assert reader.read(ctx, {}) == pytest.approx(900.0)
    assert ctx.notes[0]["program_calls"]["prefill_by_S_x_tq"] == {}


@pytest.mark.parametrize("metric", [
    "prefill_us_per_row.burst", "prefill_us_per_row.gen"])
def test_the_span_reader_returns_nothing_off_a_tpu(metric, tmp_path):
    reader = mf.load_module("layer_metrics", metric)
    ctx = Ctx(str(tmp_path))
    assert reader.read(ctx, {"trace": None}) is None
    assert reader.read(ctx, {"trace": T.Trace({}, [], 0.0, 1.0)}) is None
    assert ctx.notes == []


# a window of a closed loop, by hand: 10 bursts of 8 and 6 lone token steps
# (32 slots; 2,560 + 150 rows), 12 chunk calls that carried 900 rows as
# 1,536, one gather step of 40 rows as 256, two speculative rounds; 2,700
# tokens out. 5 first tokens behind 40 calls of which 15 carried them.
COUNTERS = {
    "calls_decode": 6, "rows_decode": 150, "padded_rows_decode": 192,
    "token_steps_decode": 6,
    "calls_multi_decode": 10, "rows_multi_decode": 2560,
    "padded_rows_multi_decode": 2560, "token_steps_multi_decode": 80,
    "calls_prefill": 12, "rows_prefill": 900, "padded_rows_prefill": 1536,
    "token_steps_prefill": 12,
    "calls_gather": 1, "rows_gather": 40, "padded_rows_gather": 256,
    "token_steps_gather": 1,
    "calls_spec": 2, "rows_spec": 24, "padded_rows_spec": 512,
    "token_steps_spec": 2, "steps_dispatched": 25,
    "tokens_gather": 10, "tokens_prefill_kernel": 12, "tokens_decode": 150,
    "tokens_multi_decode": 2528,
    "first_tokens": 5, "first_token_calls": 40, "first_token_own_calls": 15}
PAD = 100.0 * (1 - 940 / 1792)
READINGS = [
    ("prompt_pad_share", PAD), ("prompt_pad_share.burst", PAD),
    ("prompt_pad_share.gen", PAD),
    ("decode_steps_per_call.gen", 86 / 16),
    ("weight_passes_per_token.gen", 101 / 2700),
    ("ttft_calls.burst", 8.0), ("ttft_foreign_call_share.burst", 62.5)]


@pytest.mark.parametrize("metric,want", READINGS)
def test_counter_reader_on_a_hand_made_delta(metric, want):
    reader = mf.load_module("layer_metrics", metric)
    assert reader.read(Ctx(), {"counters": {"engine": COUNTERS}}) \
        == pytest.approx(want)
    # the parent's engine does not count its calls: nothing, and no error
    parent = {k: v for k, v in COUNTERS.items() if k.startswith("tokens_")
              or k == "first_tokens"}
    assert reader.read(Ctx(), {"counters": {"engine": parent}}) is None
    assert reader.read(Ctx(), {}) is None
    # a window in which nothing of the kind happened: nothing, never 0 / 0
    idle = dict.fromkeys(COUNTERS, 0)
    assert reader.read(Ctx(), {"counters": {"engine": idle}}) is None


def test_a_speculative_round_is_no_prompt_call():
    """``spec`` rounds run the gather program and are counted apart: a
    window of them alone has no prompt row to pad."""
    reader = mf.load_module("layer_metrics", "prompt_pad_share")
    c = dict(COUNTERS, calls_prefill=0, rows_prefill=0,
             padded_rows_prefill=0, calls_gather=0, rows_gather=0,
             padded_rows_gather=0)
    assert reader.read(Ctx(), {"counters": {"engine": c}}) is None
    c.update(rows_gather=256, padded_rows_gather=256)
    assert reader.read(Ctx(), {"counters": {"engine": c}}) == 0.0


def test_ratio_and_counted():
    result = {"counters": {"engine": {"calls_decode": 3, "calls_prefill": 1,
                                      "rows_decode": 0}}}
    assert C.counted(result, ["calls_decode", "calls_prefill"]) == 4
    assert C.counted(result, ["calls_decode", "calls_gather"]) is None
    assert C.ratio(result, ["calls_prefill"], ["calls_decode"], 100.0) \
        == pytest.approx(100.0 / 3)
    assert C.ratio(result, ["calls_decode"], ["rows_decode"]) is None
    assert C.per_program("calls") == [
        "calls_gather", "calls_spec", "calls_prefill", "calls_decode",
        "calls_multi_decode"]
    from deepspeed_tpu.inference.engine_v2 import PROGRAMS

    assert sorted(PROGRAMS) == sorted(C.PROGRAMS)    # the engine's own list


# -- the manifest -------------------------------------------------------------

NEW = {
    "prompt_pad_share.burst": ("serve entry", "program_counter",
                               "ttft_p50_ms", "%", "lower"),
    "prompt_pad_share.gen": ("serve entry", "program_counter",
                             "serve_tokens_per_s", "%", "lower"),
    "decode_steps_per_call.gen": ("serve entry", "program_counter",
                                  "serve_tokens_per_s", "steps/call",
                                  "higher"),
    "weight_passes_per_token.gen": ("serve entry", "program_counter",
                                    "serve_tokens_per_s", "passes/token",
                                    "lower"),
    "ttft_calls.burst": ("scheduler / KV", "program_counter", "ttft_p50_ms",
                         "calls", "lower"),
    "ttft_foreign_call_share.burst": ("scheduler / KV", "program_counter",
                                      "ttft_p50_ms", "%", "lower"),
    "prefill_us_per_row.burst": ("serve step programs", "program_span",
                                 "ttft_p50_ms", "us/row", "lower"),
    "prefill_us_per_row.gen": ("serve step programs", "program_span",
                               "serve_tokens_per_s", "us/row", "lower")}
OPEN = ["serve-rag-burst", "serve-chat-steady"]
CLOSED = ["serve-gen-closed", "serve-qnext-gen-closed",
          "serve-sala-longctx-decode", "serve-kimi-code-longctx-decode"]
CELLS = {"prompt_pad_share.burst": OPEN, "ttft_calls.burst": OPEN,
         "ttft_foreign_call_share.burst": OPEN,
         "prefill_us_per_row.burst": OPEN,
         "prompt_pad_share.gen": CLOSED[:2],
         "decode_steps_per_call.gen": CLOSED,
         "weight_passes_per_token.gen": CLOSED,
         "prefill_us_per_row.gen": CLOSED[:1]}


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_manifest_finds_the_new_metrics_reader(metric):
    man = mf.load_manifest()
    (entry,) = [m for m in man["per_layer"] if m["name"] == metric]
    assert (entry["layer"], entry["source"], entry["moves"], entry["unit"],
            entry["better"]) == NEW[metric]
    assert entry["workloads"] == CELLS[metric]
    # each cell reports the end-to-end metric this one moves, and lists it
    for cell in entry["workloads"]:
        assert entry["moves"] in {
            m["name"] for m in mf.metrics_of(man, "end_to_end", cell)}
        assert entry in mf.metrics_of(man, "per_layer", cell)
    assert callable(mf.load_module("layer_metrics", metric).read)
    # the fixture manifest of the rehearsal below has it under the same name
    assert metric in [m["name"] for m in json.load(open(TINY))["per_layer"]]


def test_the_new_entries_stand_at_the_end_of_the_list():
    names = [m["name"] for m in mf.load_manifest()["per_layer"]]
    assert set(names[-len(NEW):]) == set(NEW)
    assert names[-len(NEW) - 1] == "prefill_calls_per_chunk.gen"


# -- a tiny cell of each kind, rehearsed on the CPU ----------------------------

ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           JAX_ENABLE_COMPILATION_CACHE="false",
           XLA_FLAGS="--xla_force_host_platform_device_count=4")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)

COUNTED = {"tiny-gen": {"prompt_pad_share.gen", "decode_steps_per_call.gen",
                        "weight_passes_per_token.gen"},
           "tiny-burst": {"prompt_pad_share.burst", "ttft_calls.burst",
                          "ttft_foreign_call_share.burst"}}


@pytest.mark.parametrize("cell", sorted(COUNTED))
def test_traced_rehearsal_reports_the_counter_metrics(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 41), "--seconds", "2",
         "--trace", "1", "--manifest", TINY, "--rehearse"],
        env=ENV, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(got) == COUNTED[cell]           # and no span metric
    if cell == "tiny-gen":
        assert 1.0 <= got["decode_steps_per_call.gen"] <= 8.0
        # 8 slots: a full step is an eighth of a pass a token
        assert 1 / 8 <= got["weight_passes_per_token.gen"] < 1.0
        assert 0.0 <= got["prompt_pad_share.gen"] < 100.0
    else:
        assert 0.0 <= got["prompt_pad_share.burst"] < 100.0
        assert got["ttft_calls.burst"] >= 1.0
        assert 0.0 <= got["ttft_foreign_call_share.burst"] < 100.0

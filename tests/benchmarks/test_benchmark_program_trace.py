"""The readers of what the program names (``harness/program_trace.py`` and
the per-layer metrics over it) on hand-made timelines; the profile's wire
format on a hand-encoded file; the ``dstpu/`` spans of a real capture on
the CPU; and the CPU rehearsal of every cell, which reports the counter
metrics and no device or span metric."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import program_trace as P
from benchmarks.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "fixtures", "BENCHMARK.tiny-program.json")

FB = "jit(dstpu_train_step)/forward_backward/"
OP_NAMES = {
    "while.1": FB + "jvp()/while",
    "fusion.1": FB + "jvp()/while/body/closed_call/attn/dot_general",
    "flash_fwd.2": FB + "jvp()/while/body/closed_call/attn/flash_fwd/pallas_call",
    "fusion.2": FB + "jvp(head_loss)/reduce_sum",
    "while.2": FB + "transpose(jvp())/while",
    "fusion.3": FB + "transpose(jvp())/while/body/closed_call/checkpoint/"
                     "rematted_computation/mlp/dot_general",
    "fusion.4": FB + "transpose(jvp())/while/body/closed_call/mlp/transpose",
    "fusion.5": "jit(dstpu_train_step)/optimizer/mul",
    "fusion.6": "jit(dstpu_train_step)/reduce_sum",
}


def ev(name, start, dur):
    return (f"%{name} = bf16[8,128]{{1,0}} fusion(bf16[8,128] %p.1)", start, dur)


# one chip, two steps of 1 s at 0 and 2; each: a forward while (0.3: two
# ops and an unnamed copy that inherits from the while), the head (0.1),
# a backward while (0.4: recomputed and transposed), the optimizer (0.1),
# an op under no scope (0.05), and 0.05 between operations
def step_ops(t):
    return [ev("while.1", t, 0.3), ev("fusion.1", t, 0.1),
            ev("flash_fwd.2", t + 0.1, 0.1), ev("copy.9", t + 0.2, 0.1),
            ev("fusion.2", t + 0.3, 0.1),
            ev("while.2", t + 0.4, 0.4), ev("fusion.3", t + 0.4, 0.15),
            ev("fusion.4", t + 0.55, 0.25),
            ev("fusion.5", t + 0.8, 0.1), ev("fusion.6", t + 0.9, 0.05)]


OPS = step_ops(0.0) + step_ops(2.0)
MODULES = [("jit_dstpu_train_step(123)", 0.0, 1.0),
           ("jit_convert_element_type(7)", 1.5, 0.001),
           ("jit_dstpu_train_step(123)", 2.0, 1.0),
           ("jit_dstpu_train_step(123)", 3.9, 1.0)]      # leaves the window


def span(name, start, dur, thread="main", **ids):
    return P.Span(name, start, dur, ids, thread)


# the host around the second step: pulls a batch, dispatches (the device
# starts at 2.0), waits, reads, writes its row; the device is idle 1.0-2.0
SPANS = [span("train_batch", 0.95, 0.95, step_num=1),     # 0.95-1.9
         span("after_step_host", 1.0, 0.3),                # idle 1.0-1.3
         span("step_trace", 1.3, 0.5),                     # idle 1.3-1.8
         span("train_batch", 1.9, 1.3, step_num=2),        # 1.9-3.2
         span("next_batches", 1.9, 0.04),
         span("dispatch", 1.95, 0.1),                      # idle 1.95-2.0
         span("drain_wait", 2.05, 0.97),                   # idle 2.95-3.02
         span("after_step_host", 3.02, 0.1),
         span("train_batch", 3.5, 1.0, step_num=3)]        # leaves the window


def program_trace(ops=OPS, modules=MODULES, spans=SPANS, t0=0.9, t1=3.3,
                  scopes=None):
    trace = T.Trace({0: ops}, [], t0, t1, {0: modules})
    return P.ProgramTrace(trace, spans, {P.TRAIN_STEP: OP_NAMES}
                          if scopes is None else scopes)


def test_train_region_reads_jax_own_marks():
    assert P.train_region(OP_NAMES["fusion.1"]) == P.FWD
    assert P.train_region(OP_NAMES["fusion.2"]) == P.FWD
    assert P.train_region(OP_NAMES["fusion.3"]) == P.BWD       # recomputed
    assert P.train_region(OP_NAMES["fusion.4"]) == P.BWD       # transposed
    assert P.train_region(OP_NAMES["fusion.5"]) == P.OPT
    assert P.train_region(OP_NAMES["fusion.6"]) == P.OTHER
    assert P.train_region(None) == P.OTHER
    # a scope is a path component, not a substring
    assert P.train_region("jit(f)/my_optimizer_state/add") == P.OTHER


def test_seconds_by_region_counts_leaves_and_inherits_from_the_while():
    by = P.seconds_by_region(OPS, OP_NAMES, [(0.0, 1.0), (2.0, 3.0)])
    assert by[P.FWD] == pytest.approx(2 * 0.4)      # 0.1 + 0.1 + copy 0.1 + head 0.1
    assert by[P.BWD] == pytest.approx(2 * 0.4)
    assert by[P.OPT] == pytest.approx(2 * 0.1)
    assert by[P.OTHER] == pytest.approx(2 * 0.05)
    only_first = P.seconds_by_region(OPS, OP_NAMES, [(0.0, 1.0)])
    assert sum(only_first.values()) == pytest.approx(0.95)
    assert P.seconds_by_region(OPS, {}, [(0.0, 1.0)]) == {
        P.OTHER: pytest.approx(0.95)}


def test_train_split_is_per_step_of_the_executions_inside_the_window():
    pt = program_trace(t0=-0.1, t1=3.3)
    assert pt.executions(P.TRAIN_STEP) == [(0.0, 1.0), (2.0, 3.0)]
    assert pt.programs() == {"jit_dstpu_train_step": 2,
                             "jit_convert_element_type": 1}
    s = P.train_split(pt)
    assert s["steps"] == 2
    assert (s[P.FWD], s[P.BWD], s[P.OPT]) == (pytest.approx(400.0),
                                              pytest.approx(400.0),
                                              pytest.approx(100.0))
    assert s[P.OTHER] == pytest.approx(50.0)
    assert s["step_ms"] == pytest.approx(950.0)     # 50 ms between operations
    assert P.train_split(program_trace(scopes={})) is None       # the parent


def test_exposed_time_is_cut_at_span_borders():
    """One idle second (1.0-2.0) straddles two steps' spans: each span
    gets what falls inside it, not the whole gap by its middle."""
    pt = program_trace()
    out = P.host_exposed(pt, "train_batch")
    assert out["spans"] == 2                       # the third leaves the window
    by = out["by_child"]
    assert by["after_step_host"] == pytest.approx(0.3 + 0.1)
    assert by["step_trace"] == pytest.approx(0.5)
    assert by["next_batches"] == pytest.approx(0.04)
    assert by["dispatch"] == pytest.approx(0.05)
    assert by["drain_wait"] == pytest.approx(0.07)
    # the steps' own code: 0.95-1.0, 1.8-1.9, 1.94-1.95 and 3.12-3.2
    assert by["(self)"] == pytest.approx(0.05 + 0.1 + 0.01 + 0.08)
    assert out["ms_per_span"] == pytest.approx(1e3 * sum(by.values()) / 2)
    assert list(by)[0] == "step_trace"             # the largest first
    assert P.host_exposed(pt, "serve_step") is None
    assert P.host_exposed(program_trace(spans=[]), "train_batch") is None


def test_nested_children_keep_their_own_share():
    busy = [(0.0, 1.0), (2.0, 3.0)]
    parent = span("serve_step", 0.5, 2.0)
    outer, inner = span("bookkeep", 1.0, 0.8), span("journal", 1.2, 0.2)
    by = P.exposed_by_child(busy, parent, [outer, inner])
    assert by == {"bookkeep": pytest.approx(0.6), "journal": pytest.approx(0.2),
                  "(self)": pytest.approx(0.2)}


def test_mean_execution_by_program_name():
    mods = [("jit_dstpu_serve_gather(1)", 0.0, 0.4),
            ("jit_dstpu_serve_multi_decode(2)", 0.5, 0.3),
            ("jit_dstpu_serve_gather(1)", 1.0, 0.5)]
    pt = program_trace(ops=[], modules=mods, spans=[], t0=0.0, t1=2.0)
    assert P.mean_execution_ms(pt, P.SERVE_GATHER) == pytest.approx(450.0)
    assert P.mean_execution_ms(pt, "jit__unknown") is None


def test_counter_ratio_and_a_program_without_the_counters():
    result = {"counters": {"engine": {
        "tokens_gather": 13, "tokens_prefill_kernel": 2, "tokens_decode": 5,
        "tokens_multi_decode": 80, "admission_wait_s": 0.5, "admitted": 4,
        "first_tokens": 0, "ttft_s": 0.0}}}
    toks = ("tokens_gather", "tokens_prefill_kernel", "tokens_decode",
            "tokens_multi_decode")
    assert P.counter_ratio(result, "tokens_gather", toks, 100.0) == 13.0
    assert P.counter_ratio(result, "admission_wait_s", "admitted", 1e3) == 125.0
    assert P.counter_ratio(result, "ttft_s", "first_tokens") is None   # 0 / 0
    parent = {"counters": {"engine": {"admitted": 4}}}
    assert P.counter_ratio(parent, "admission_wait_s", "admitted") is None
    assert P.counter_ratio({}, "ttft_s", "first_tokens") is None


# -- each reader, by its file, on the hand-made timeline ---------------------

class Ctx:
    def __init__(self, kind):
        self.config, self.notes = {"kind": kind}, []
        self.bench_dir = mf.BENCH_DIR

    def note(self, obj):
        self.notes.append(obj)


READINGS = [
    ("train_fwd_ms", "train", 400.0), ("train_bwd_ms", "train", 400.0),
    ("train_opt_ms", "train", 100.0),
    ("host_exposed_ms_per_step.train", "train", 1e3 * 1.3 / 2),
]


@pytest.mark.parametrize("metric,kind,want", READINGS)
def test_trace_reader_on_the_hand_made_timeline(monkeypatch, metric, kind,
                                                want):
    pt = program_trace(t0=-0.1 if "train_" in metric else 0.9)
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    ctx = Ctx(kind)
    reader = mf.load_module("layer_metrics", metric)
    assert reader.read(ctx, {}) == pytest.approx(want)
    if metric == "train_fwd_ms":      # what the three scalars leave out
        note = ctx.notes[0]["train_step_split"]
        assert note["program_ms"] == pytest.approx(1000.0)
        assert note["remainder_ms"] == pytest.approx(100.0)
        assert note["unattributed_ops_ms"] == pytest.approx(50.0)
        assert note["between_ops_ms"] == pytest.approx(50.0)
        assert note["fwd_ms"] + note["bwd_ms"] + note["opt_ms"] \
            + note["remainder_ms"] == pytest.approx(note["program_ms"])
    if metric.startswith("host_exposed"):
        assert ctx.notes[0]["host_exposed"]["parent"] == "train_batch"
        assert "step_trace" in ctx.notes[0]["host_exposed"]["by_child"]


MODULE_LINE = [("jit_dstpu_serve_gather(1)", 0.1, 0.43),
               ("jit_dstpu_serve_prefill(7)", 0.55, 0.012),
               ("jit_dstpu_serve_decode(3)", 0.6, 0.04),
               ("jit_dstpu_serve_prefill(7)", 0.7, 0.016),
               ("jit_dstpu_serve_prefill(7)", 0.99, 0.02)]    # leaves the window


@pytest.mark.parametrize("metric,want", [
    ("gather_step_ms", 430.0), ("gather_step_ms.gen", 430.0),
    ("prefill_call_ms", 14.0), ("prefill_call_ms.burst", 14.0),
    ("prefill_call_ms.gen", 14.0)])
def test_program_readers_read_the_module_line(monkeypatch, metric, want):
    """Mean device time of one execution of the program, by its name on
    the module line; an execution that leaves the traced window is not
    counted, and a trace without the program reads nothing (never 0)."""
    pt = program_trace(ops=[], modules=MODULE_LINE, spans=[], t0=0.0, t1=1.0)
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    ctx = Ctx("serve")
    reader = mf.load_module("layer_metrics", metric)
    assert reader.read(ctx, {}) == pytest.approx(want)
    assert ctx.notes[0]["programs_in_trace"] == {
        "jit_dstpu_serve_gather": 1, "jit_dstpu_serve_decode": 1,
        "jit_dstpu_serve_prefill": 2}
    other = [m for m in MODULE_LINE
             if ("gather" in m[0]) != ("gather" in metric)
             and "decode" not in m[0]]
    pt = program_trace(ops=[], modules=other, spans=[], t0=0.0, t1=1.0)
    assert reader.read(Ctx("serve"), {}) is None


COUNTERS = {"tokens_gather": 13, "tokens_prefill_kernel": 0,
            "tokens_decode": 7, "tokens_multi_decode": 80,
            "admission_wait_s": 0.08, "admitted": 4, "prefill_chunks": 10,
            "prefill_chunk_calls": 9, "ttft_s": 2.0, "first_tokens": 4}


@pytest.mark.parametrize("metric,want", [
    ("gather_token_share", 13.0), ("queue_wait_ms", 20.0),
    ("prefill_chunks_per_req", 2.5), ("ttft_engine_ms", 500.0),
    ("prefill_calls_per_chunk", 0.9), ("prefill_calls_per_chunk.burst", 0.9),
    ("prefill_calls_per_chunk.gen", 0.9)])
def test_counter_reader(metric, want):
    reader = mf.load_module("layer_metrics", metric)
    assert reader.read(Ctx("serve"), {"counters": {"engine": COUNTERS}}) \
        == pytest.approx(want)
    # the parent's engine has no such counter: nothing, and no error
    assert reader.read(Ctx("serve"), {"counters": {"engine": {
        "admitted": 4}}}) is None


@pytest.mark.parametrize("metric", [
    m["name"] for m in json.load(open(TINY))["per_layer"]
    if m["source"] != "program_counter"])
def test_device_and_span_readers_return_nothing_off_a_tpu(metric, tmp_path):
    class C(Ctx):
        trace_dir = str(tmp_path)

    reader = mf.load_module("layer_metrics", metric)
    kind = "train" if "train" in metric else "serve"
    assert reader.read(C(kind), {"trace": None}) is None
    assert reader.read(C(kind), {"trace": T.Trace({}, [], 0.0, 1.0)}) is None


def test_the_manifest_lists_a_reader_file_for_every_new_metric():
    names = [m["name"] for m in mf.load_manifest()["per_layer"]]
    for m in json.load(open(TINY))["per_layer"]:
        assert m["name"] in names
        assert os.path.isfile(os.path.join(mf.BENCH_DIR, "layer_metrics",
                                           m["name"] + ".py"))


# -- the wire format ---------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def msg(*pairs):
    """Encode (field, value) pairs: int -> varint, bytes/str -> length-
    delimited."""
    out = b""
    for num, v in pairs:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_scopes_from_a_hand_encoded_profile(tmp_path):
    def instruction(name, op_name=None):
        pairs = [(1, name), (2, "fusion")]
        if op_name:
            pairs.append((7, msg((1, "dot_general"), (2, op_name))))
        return msg(*pairs)

    module = msg((1, "jit_dstpu_train_step"), (3, msg(
        (1, "body"), (2, instruction("fusion.1", OP_NAMES["fusion.1"])),
        (2, instruction("copy.9")))), (3, msg(
            (1, "main"), (2, instruction("fusion.5", OP_NAMES["fusion.5"])))))
    stat = msg((1, 1), (6, msg((1, module))))              # Hlo Proto bytes
    meta = msg((1, 1), (2, "jit_dstpu_train_step(123)"), (5, stat))
    metadata_plane = msg((1, 2), (2, "/host:metadata"),
                         (4, msg((1, 1), (2, meta))))
    device_plane = msg((1, 1), (2, "/device:TPU:0"),
                       (4, msg((1, 5), (2, msg((1, 5), (2, "%fusion.1"))))))
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(msg((1, device_plane), (1, metadata_plane)))
    assert P.read_scopes(str(path)) == {"jit_dstpu_train_step": {
        "fusion.1": OP_NAMES["fusion.1"], "fusion.5": OP_NAMES["fusion.5"]}}
    assert P.instruction_of("%fusion.1 = bf16[8]{0} fusion(%p)") == "fusion.1"
    assert P.module_of("jit_dstpu_serve_gather(99)") == "jit_dstpu_serve_gather"


# -- a real capture on the CPU ----------------------------------------------

def test_program_spans_of_a_real_capture_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.zoo import get_model

    engine = InferenceEngineV2(
        get_model("tiny"), kv_blocks=32, kv_block_size=8,
        max_tokens_per_step=16, max_seqs_per_step=2, max_blocks_per_seq=8,
        dtype=jnp.float32)
    with T.Capture(str(tmp_path / "tr")) as cap:
        engine.put([7], [np.arange(5, dtype=np.int32)], max_new_tokens=3)
        engine.generate_all()
    engine.close()
    path = P.newest_xplane(str(tmp_path / "tr"))
    spans = P.read_spans(path)
    names = {s.name for s in spans}
    assert {"put", "serve_step", "admit", "dispatch", "bookkeep"} <= names
    put = next(s for s in spans if s.name == "put")
    assert put.ids == {"uid": 7, "requests": 1}
    steps = [s for s in spans if s.name == "serve_step"]
    assert [s.ids["step_id"] for s in steps] == list(range(1, len(steps) + 1))
    # the benchmark's own spans are not the program's, and the other way
    assert all(not s[0].startswith("dstpu") for s in cap.trace.host_spans)
    # no device plane on the CPU: open_run has nothing to read
    class Ctx2:
        trace_dir = str(tmp_path / "tr")
    assert cap.trace.device_ops == {}
    assert P.open_run(Ctx2(), {"trace": cap.trace}) is None
    assert isinstance(P.read_scopes(path), dict)
    assert jax.devices()[0].platform == "cpu"


# -- every cell's CPU rehearsal ---------------------------------------------

ENV = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
           XLA_FLAGS="--xla_force_host_platform_device_count=4")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)

COUNTED = {"tiny-train": set(), "tiny-gen": {"gather_token_share"},
           "tiny-burst": {"queue_wait_ms", "prefill_chunks_per_req",
                          "prefill_calls_per_chunk.burst", "ttft_engine_ms"}}


@pytest.mark.parametrize("cell", sorted(COUNTED))
def test_traced_rehearsal_reports_the_counter_metrics_only(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 23), "--seconds", "2",
         "--trace", "1", "--manifest", TINY, "--rehearse"],
        env=ENV, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == COUNTED[cell]
    assert all(v["value"] >= 0 for v in last["metrics"].values())


# -- decode programs told by the module line ---------------------------------

def test_decode_step_reader_tells_decode_programs_by_module_name(monkeypatch):
    """Device-busy time inside the executions of the two decode programs,
    per decode token step of the traced steps: a gather program, a pick
    program between them and the idle inside a burst are not counted."""
    mods = [("jit_dstpu_serve_gather(1)", 0.0, 0.40),
            ("jit_dstpu_serve_multi_decode(2)", 0.5, 0.16),
            ("jit_dstpu_pick_greedy(5)", 0.70, 0.01),
            ("jit_dstpu_serve_decode(3)", 0.8, 0.02),
            ("jit_dstpu_serve_multi_decode(2)", 1.95, 0.16)]   # leaves the window
    ops = [ev("fusion.1", 0.0, 0.40),
           ev("paged_decode.6", 0.5, 0.07),
           ev("fusion.2", 0.58, 0.08),                # 0.01 idle inside the burst
           ev("fusion.3", 0.70, 0.01), ev("fusion.4", 0.8, 0.02)]
    pt = program_trace(ops=ops, modules=mods, spans=[], t0=0.0, t1=2.0)
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    served = type("S", (), {"steps": [
        {"decode_kernel_steps": 0}, {"decode_kernel_steps": 8},
        {"decode_kernel_steps": 1}, {"decode_kernel_steps": 8}]})()
    result = {"facts": {"traced_steps": (0, 3)}, "served": served}
    reader = mf.load_module("layer_metrics", "decode_step_ms")
    assert reader.read(Ctx("serve"), result) == pytest.approx(
        1e3 * (0.07 + 0.08 + 0.02) / 9)
    result["facts"]["traced_steps"] = (0, 1)          # no decode step traced
    assert reader.read(Ctx("serve"), result) is None


def test_where_the_close_fell_names_the_straddling_step_and_a_stall():
    from benchmarks.runners import serve

    steps = [(0.00, 0.39, 32), (0.40, 0.12, 192), (0.53, 0.12, 192),
             (1.66, 0.39, 32)]                   # 1.0 s lost before the last
    note = serve.where_the_close_fell(steps, 0.0, 1.79)
    assert note["straddling_step_tokens"] == 32
    assert note["outside_serve_step_s"] == pytest.approx(1.79 - 0.39 - 0.24 - 0.13)
    assert serve.where_the_close_fell(steps, 0.0, 1.0) == {
        "straddling_step_tokens": 0,
        "outside_serve_step_s": pytest.approx(1.0 - 0.39 - 0.24)}


def test_window_steps_names_a_stalled_step_and_splits_the_window_by_kind():
    """``benchmarks/tools/window_steps.py``: the diagnostics that do not
    belong in every run's note."""
    tool = mf.load_module("tools", "window_steps")
    cycle = [(0.0, 0.39, 32), (0.39, 0.12, 192), (0.51, 0.12, 192)]
    steps = [(s + 0.63 * k, d, n) for k in range(5) for s, d, n in cycle]
    steps.append((3.15, 8.32, 32))          # a gather step that took 8.32 s
    steps.append((11.47, 0.12, 192))
    r = tool.report(steps, 0.0, 12.0)
    assert r["slow_steps"] == [[3.15, 8.32, 32]]
    assert r["steps_by_tokens"]["32"] == [6, pytest.approx(5 * 0.39 + 8.32)]
    assert r["steps_by_tokens"]["192"][0] == 11
    assert r["last_steps"][-1] == [11.47, 0.12, 192] and len(r["last_steps"]) == 5
    assert r["outside_serve_step_s"] == pytest.approx(12.0 - 11.59)
    assert r["serve_tokens_per_s"] != r["one_close_tokens_per_s"]

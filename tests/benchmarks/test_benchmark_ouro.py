"""The eighth architecture (``benchmarks/references/ouro.py``, the dense
runner with ``ut_steps`` passes over a K/V pool of ``passes x layers`` slots)
through the unedited serving runner on the CPU at a toy size: a fixture
manifest, configuration and published file of its own (three layers run four
times) under the closed-loop fixture mix, judged ``correct`` against the
reference and not ``correct`` against three planted ones; the committed
configuration against its published file; the traffic file against ISSUE 52's
numbers; the two counting functions against a count by hand; the five new
readers on a recorded trace and on a run with nothing to read."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import program_trace as P
from benchmarks.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FX = os.path.join(HERE, "fixtures")
MANIFEST = os.path.join(FX, "BENCHMARK.tiny-ouro.json")
CELL, CONFIG, TRAFFIC = ("serve-ouro-gen-closed", "ouro-2.6b-serve-c1",
                         "gen-closed-12")
ENV = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)
NEW_METRICS = ["loop_pass_ms", "loop_kv_decode_roofline",
               "loop_decode_step_roofline", "loop_serve_mfu",
               "loop_passes_per_token"]
GEN_READERS = ["decode_step_ms", "batch_seqs_per_step",
               "device_idle_share.gen", "host_exposed_ms_per_step.gen"]


def _config(path, name):
    with open(path) as f:
        man = json.load(f)
    here = os.path.dirname(path)
    bench_dir = os.path.normpath(os.path.join(here, man.get("bench_dir",
                                                            "benchmarks")))
    rel = next(c["file"] for c in man["configs"] if c["name"] == name)
    with open(os.path.join(here, rel)) as f:
        return json.load(f), bench_dir


def _committed_arch():
    cfg, _ = _config(os.path.join(mf.ROOT, "BENCHMARK.json"), CONFIG)
    return mf.load_module("references", "ouro").Arch.from_model(cfg)


@pytest.mark.parametrize("reference", [None, "ouro_shared_kv",
                                       "ouro_no_pass_norm",
                                       "ouro_three_passes"])
def test_ouro_cell_rehearses_on_the_cpu_and_tells_a_planted_fault(tmp_path,
                                                                  reference):
    """The cell end to end through the unedited runner: four clients in a
    closed loop, prompts of 8-40 tokens in chunks of at most 16 (they end
    inside blocks of 8), token steps in bursts of two, ``correct`` against
    the reference, tokens a second, the gap between tokens and set-up
    reported, nothing compiled in the window, every row through four
    passes. And the program as it is against a reference whose passes share
    a layer's keys and values, that drops the norm between passes, or that
    runs three passes for four (``fixtures/references/``): not ``correct``,
    by every number compared (0.63 / 0.83 / 0.83, 0.28 / 0.37 / 0.11 and
    0.29 / 0.34 / 0.14 where the sound program reads 0.010 / 0.010 /
    0.000)."""
    manifest, cell = MANIFEST, "tiny-ouro-gen-closed"
    if reference:
        cfg, _ = _config(MANIFEST, "tiny-ouro-serve-c1")
        (tmp_path / "broken.json").write_text(json.dumps(
            dict(cfg, name="tiny-ouro-broken", reference=reference)))
        with open(MANIFEST) as f:
            man = json.load(f)
        man["bench_dir"], cell = FX, "tiny-ouro-broken-gen-closed"
        man["configs"] = [{"name": "tiny-ouro-broken", "file": "broken.json"}]
        man["workloads"] = [dict(man["workloads"][0], name=cell,
                                 config="tiny-ouro-broken")]
        manifest = str(tmp_path / "BENCHMARK.json")
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 23), "--seconds", "1",
         "--trace", "0" if reference else "1", "--manifest", manifest,
         "--rehearse"],
        env=ENV, capture_output=True, text=True, timeout=1200, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    compared = [json.loads(l)["compared"] for l in lines if '"compared"' in l]
    if reference:
        with open(os.path.join(mf.ROOT, "benchmarks", "configs",
                               CONFIG + ".json")) as f:
            real = json.load(f)["check"]["limits"]
        assert last["correct"] is False
        failed = {c["check"]: c["value"] for c in compared if not c["ok"]}
        assert set(failed) == {"serve.logits_prefill", "serve.logits_decode",
                               "serve.token_margin"}
        # past the real cell's limits too, twice and more
        for k, limit in real.items():
            assert failed["serve." + k] > 2 * limit, (k, limit)
        return
    assert last["correct"] is True and last["failed"] == 0
    # a CPU rehearsal carries the counter's metric and no device metric
    assert set(last["metrics"]) == {"loop_passes_per_token"}
    assert last["metrics"]["loop_passes_per_token"]["value"] == 4.0
    assert {c["check"] for c in compared} >= {
        "serve.logits_prefill", "serve.logits_decode", "serve.token_margin",
        "serve.every_token_delivered", "no_compile_in_window"}
    note = next(json.loads(l)["note"] for l in lines if '"counters"' in l)
    c = note["counters"]["engine"]
    assert c["tokens_gather"] == 0 and c["calls_multi_decode"] > 0
    assert c["preempted"] == 0 and c["prefix_hit_tokens"] == 0
    for program in ("prefill", "decode", "multi_decode"):
        assert c["ut_passes_" + program] == 4 * c["rows_" + program]
    assert set(note["end_to_end"]) >= {"serve_tokens_per_s", "tpot_p90_ms"}


@pytest.mark.parametrize("path,name", [
    (os.path.join(mf.ROOT, "BENCHMARK.json"), CONFIG),
    (MANIFEST, "tiny-ouro-serve-c1")], ids=["committed", "fixture"])
def test_ouro_configuration_keeps_every_published_value(path, name):
    """Against its published file: nothing differs and ``reduced`` is empty;
    the preset the engine builds has the reference's sizes; the reference's
    leaf table is the program's tree; the pool has a slot a pass and
    layer."""
    import jax

    from deepspeed_tpu.inference import model_runner
    from deepspeed_tpu.models.zoo import get_model

    cfg, bench_dir = _config(path, name)
    pub = mf.published_of(cfg, bench_dir)
    assert cfg["reduced"] == [] and pub["layer_period"] == 1
    for k, v in pub["config"].items():
        assert cfg[k] == v and type(cfg[k]) is type(v), k
    arch = mf.reference_of(cfg, bench_dir).Arch.from_model(cfg)
    held = cfg["num_hidden_layers"]
    assert arch.cache_layers == cfg["total_ut_steps"] * held
    model = get_model(cfg["preset"], num_layers=held, max_seq_len=64)
    c = model.config
    assert (c.hidden_size, c.num_heads, c.kv_heads, c.head_dim, c.ffn,
            c.vocab_size, c.num_layers, c.rope_theta, c.norm_eps, c.ut_steps,
            c.early_exit_threshold, c.post_norms, c.tie_embeddings,
            c.activation, c.norm, c.pos_emb) == (
        arch.hidden_size, arch.num_attention_heads, arch.num_key_value_heads,
        arch.head_dim, arch.intermediate_size, arch.vocab_size, held,
        arch.rope_theta, arch.rms_norm_eps, arch.total_ut_steps,
        arch.early_exit_threshold, True, False, "swiglu", "rmsnorm", "rope")
    e = cfg["engine"]
    spec, beside = model_runner.store_specs(
        c, kv_blocks=e["kv_blocks"], kv_block_size=e["kv_block_size"],
        max_seqs=e["max_seqs_per_step"], state_slots=None, dtype=None,
        quant_bits=None)
    assert spec.num_layers == arch.cache_layers and beside == []
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat = {"/".join(str(k.key) for k in p): leaf.shape for p, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    table = {("layers/" if l.per_layer else "") + l.path.replace(".", "/"):
             ((held,) if l.per_layer else ()) + tuple(l.shape)
             for l in arch.leaf_table()}
    assert flat == table
    assert {l.published for l in arch.leaf_table()} >= {
        "input_layernorm_2", "post_attention_layernorm_2", "early_exit_gate",
        "early_exit_gate_bias"}
    assert not [l for l in arch.leaf_table() if "." in l.published]
    if name == CONFIG:
        assert (held, arch.cache_layers, cfg["vocab_size"]) == (48, 192, 49152)
        assert pub["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
        for key in ("attention_bias", "qk_norm", "early_exit_gate",
                    "early_exit_rule", "weights"):
            assert key in cfg["assumed"], key
        assert "sizing" in cfg and "limits_from" in cfg["check"]
        assert (e["kv_blocks"], e["kv_block_size"], e["max_blocks_per_seq"],
                e["max_seqs_per_step"], e["max_tokens_per_step"]) == (
            337, 16, 28, 16, 256)
        # 12 clients x 28 pages in use, and the engine's scratch page
        assert e["kv_blocks"] - 1 == 12 * e["max_blocks_per_seq"]
        assert cfg["check"]["control"] == "fp8"
        assert cfg["check"]["sample_requests"] == 3


def test_the_traffic_is_issue_52_s_mix():
    man = mf.load_manifest()
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    traffic = mf.load_json("traffic", TRAFFIC)
    assert (traffic["name"], traffic["generator"], traffic["clients"],
            traffic["window_starts"]) == (
        TRAFFIC, "closed_loop", 12, "all_clients_decoding")
    assert traffic["prompt_tokens"] == {"lo": 64, "hi": 160, "shape": 1.6,
                                        "count": 64}
    assert traffic["answer_tokens"] == {"lo": 128, "hi": 288, "shape": 1.6,
                                        "count": 64}
    from benchmarks.generators import closed_loop
    d = closed_loop.describe(traffic)
    cfg, _ = _config(os.path.join(mf.ROOT, "BENCHMARK.json"), CONFIG)
    e = cfg["engine"]
    # the mix's longest prompt and answer (159 + 286) fill a sequence's 28
    # pages, and every client's longest fits the pool at once: no preemption
    ceiling = e["max_blocks_per_seq"] * e["kv_block_size"]
    assert ceiling - e["kv_block_size"] < max(d["prompt_tokens"]) \
        + max(d["answer_tokens"]) <= ceiling == 448
    assert d["clients"] * e["max_blocks_per_seq"] == e["kv_blocks"] - 1
    # one order of lengths for every seed; no prefix shared
    a = [len(r.prompt) for r in closed_loop.sample(traffic, 1, 49152, 3)]
    b = closed_loop.sample(traffic, 2**31 + 5, 49152, 3)
    assert a == [len(r.prompt) for r in b]
    assert len({int(r.prompt[0]) for r in b}) > 1


def test_the_two_counting_functions_against_a_count_by_hand():
    from benchmarks.kernels import loop_decode_step

    a = _committed_arch()
    ref = mf.load_module("references", "ouro")
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert loop_decode_step.layer_weights(a) == layer \
        == ref.layer_matmul_params(a) == 51_380_224
    head, ctx = 2048 * 49152, [230, 231, 0, 100]
    flops, nbytes = loop_decode_step.step(a, ctx)
    kv_a_token = 192 * 2 * 16 * 128 * 2           # ISSUE 52: 1.573 MB
    assert kv_a_token == 1_572_864
    assert nbytes == 2 * (4 * 48 * layer + head) + 561 * kv_a_token
    assert flops == 3 * 2.0 * (192 * layer + head) \
        + 4.0 * 128 * 16 * 561 * 192
    # four fifths of a step of twelve 230-token contexts is the weights'
    # re-read; at 819 GB/s the floor is ISSUE 52's 30 ms
    flops, nbytes = loop_decode_step.step(a, [230] * 12)
    assert 0.80 < 2 * 192 * layer / nbytes < 0.83
    assert 29.0e-3 < nbytes / 819e9 < 30.5e-3
    assert flops / nbytes < 12                    # the memory's
    # a row's operations: every pass of the layers, the head, attention
    per = ref.serve_flops_per_token(a, 230.0)
    assert per == 4 * 48 * (2.0 * layer + 4.0 * 230 * 128 * 16) + 2.0 * head
    assert per * 12 == pytest.approx(flops)
    assert ref.serve_flops_per_token(a, 0.0) == 2.0 * (192 * layer + head)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_read_nothing_where_there_is_nothing_to_read(metric):
    """A run without a trace, of a program without the counters or the
    scopes (the parent commit's), of another architecture: None, and no
    exception."""
    reader = mf.load_module("layer_metrics", metric)
    ctx = types.SimpleNamespace(
        trace=False, device={"kind": "cpu", "platform": "cpu"},
        note=lambda obj: None, bench_dir=mf.BENCH_DIR,
        config={"kind": "serve", "reference": "mistral",
                "engine": {"kv_block_size": 16}})
    result = {"trace": None, "counters": {"engine": {"rows_decode": 3}},
              "facts": {"arch": object(), "traced_steps": (0, 0)},
              "served": types.SimpleNamespace(steps=[]), "window": {}}
    assert reader.read(ctx, result) is None


# -- the readers on a hand-made timeline --------------------------------------

BODY = "jit(dstpu_serve_multi_decode)/while/body/while/body/closed_call/"
OP_NAMES = {
    "fusion.1": BODY + "ut_pass/attn/dot_general",
    "fusion.2": BODY + "ut_pass/kv_write/scatter",
    "paged_decode.3": BODY + "ut_pass/attn/pallas_call",
    "fusion.4": BODY + "ut_pass/mlp/dot_general",
    "fusion.5": BODY + "pass_norm/reduce",
    "fusion.6": "jit(dstpu_serve_multi_decode)/while/body/head/dot_general",
}


def _ev(name, start, dur):
    if name.startswith("paged_decode"):
        return (f'%{name} = bf16[16,16,128]{{2,1,0}} custom-call(bf16[8] %p), '
                f'custom_call_target="tpu_custom_call"', start, dur)
    return (f"%{name} = bf16[16,2048]{{1,0}} fusion(bf16[16,2048] %p.1)",
            start, dur)


def _burst(t):
    """One execution of the 2-step decode program, 0.1 s busy of 0.11."""
    durs = [("fusion.1", .02), ("fusion.2", .002), ("paged_decode.3", .016),
            ("fusion.4", .05), ("fusion.5", .002), ("fusion.6", .01)]
    out = []
    for name, d in durs:
        out.append(_ev(name, t, d))
        t += d
    return out


class Ctx:
    bench_dir = mf.BENCH_DIR
    device = {"kind": "TPU v5 lite", "platform": "tpu"}

    def __init__(self):
        self.notes = []
        self.config, _ = _config(os.path.join(mf.ROOT, "BENCHMARK.json"),
                                 CONFIG)

    def note(self, obj):
        self.notes.append(obj)


STEPS = [{"decode_kernel_steps": 2, "decode_contexts": [200, 201, 300, 301]},
         {"decode_kernel_steps": 2, "decode_contexts": [202, 203, 302, 303]},
         {"decode_kernel_steps": 0}]


def _recorded(scopes):
    starts = (0.0, 0.2)
    mods = [("jit_dstpu_serve_multi_decode(7)", t, 0.11) for t in starts]
    events = [e for t in starts for e in _burst(t)]
    return P.ProgramTrace(T.Trace({0: events}, [], -0.1, 1.0, {0: mods}),
                          [], scopes)


def _result(pt):
    return {"trace": pt.trace,
            "facts": {"arch": _committed_arch(), "traced_steps": (0, 3)},
            "served": types.SimpleNamespace(steps=STEPS),
            "window": {"t0": 0.0, "t1": 40.0, "t_end": 40.5},
            "counters": {"engine": {
                "rows_decode": 10, "rows_multi_decode": 90,
                "rows_prefill": 50, "rows_gather": 0, "rows_spec": 0,
                "ut_passes_decode": 40, "ut_passes_multi_decode": 360,
                "ut_passes_prefill": 200}}}


def test_the_five_readers_on_a_recorded_trace(monkeypatch):
    from benchmarks.harness import device
    from benchmarks.kernels import loop_decode_step, paged_decode

    pt = _recorded({"jit_dstpu_serve_multi_decode": OP_NAMES})
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    ctx, result = Ctx(), _result(pt)
    a, peaks = result["facts"]["arch"], device.peaks("TPU v5 lite")

    def read(metric):
        return mf.load_module("layer_metrics", metric).read(ctx, result)

    # 0.088 s under ``ut_pass`` in each of two bursts of two token steps of
    # four passes
    assert read("loop_pass_ms") == pytest.approx(1e3 * 2 * 0.088 / (4 * 4))
    # the kernel: two sequences a token step, 192 calls each
    per_step = [(200, 300), (201, 301), (202, 302), (203, 303)]
    floor = sum(paged_decode.call(c, 16, 16, 128)[1] for c in per_step) \
        * 192 / peaks["hbm_bytes_per_s"]
    assert read("loop_kv_decode_roofline") == pytest.approx(
        100.0 * floor / (2 * 0.016))
    assert ctx.notes[-1]["loop_kv_decode_roofline"]["calls_expected"] == 4 * 192
    floor = sum(loop_decode_step.step(a, c)[1] for c in per_step) \
        / peaks["hbm_bytes_per_s"]
    got = read("loop_decode_step_roofline")
    assert got == pytest.approx(100.0 * floor / (2 * 0.1))
    assert 0 < got < 100
    ref = mf.load_module("references", "ouro")
    need = 100 * ref.serve_flops_per_token(a, 251.5) \
        + 50 * ref.serve_flops_per_token(a, 0.0)
    assert read("loop_serve_mfu") == pytest.approx(
        100.0 * need / 40.5 / peaks["bf16_flops"])
    assert read("loop_passes_per_token") == 4.0
    # a program without the scope or the counters (the parent's), and a CPU
    bare = _recorded({"jit_dstpu_serve_multi_decode": {
        k: v.replace("ut_pass", "x") for k, v in OP_NAMES.items()}})
    monkeypatch.setattr(P, "open_run", lambda ctx, result: bare)
    assert read("loop_pass_ms") is None
    result["counters"] = {"engine": {"rows_decode": 10}}
    assert read("loop_passes_per_token") is None
    assert read("loop_serve_mfu") is None
    ctx.device = {"kind": "cpu", "platform": "cpu"}
    assert read("loop_serve_mfu") is None


def test_the_manifest_lists_the_new_pieces_behind_what_was_there():
    """PR 52's entries stand, in order and together, behind PR 50's (a later
    PR appends behind them: nothing here pins the end of a list)."""
    man = mf.load_manifest()
    configs = [c["name"] for c in man["configs"]]
    assert configs.index(CONFIG) > configs.index("minimax-m3-serve-c1")
    entry = man["configs"][configs.index(CONFIG)]
    assert entry["reduced"] == [] and len(entry["why"]) <= 200
    cells = [w["name"] for w in man["workloads"]]
    assert cells.index(CELL) > cells.index("serve-m3-longdoc-ttft")
    assert len(man["workloads"][cells.index(CELL)]["why"]) <= 200
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 5] == NEW_METRICS
    assert at > names.index("msa_tile_visited_share")
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
               for m in man["per_layer"][at:at + 5])
    by = {m["name"]: m for m in man["per_layer"]}
    assert [(by[n]["source"], by[n]["layer"]) for n in NEW_METRICS] == [
        ("device_trace", "serve step programs"), ("device_trace", "kernels"),
        ("device_trace", "serve step programs"), ("host_clock", "serve entry"),
        ("program_counter", "serve step programs")]
    for n in NEW_METRICS[1:4]:
        assert by[n]["unit"] == "%" and by[n]["better"] == "higher"
    reported = {m["name"] for m in mf.metrics_of(man, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(GEN_READERS)
    # (the 90th percentile of the token gap is printed among the run's notes
    # and is not judged here: six runs spread it by 0.65%, over half its
    # bound of 1%: PERF.md section 2)
    assert {m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)} == {
        "serve_tokens_per_s", "setup_s"}
    for m in man["per_layer"] + man["end_to_end"]:
        if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS:
            # appended behind the cells that were there
            assert m["workloads"].index(CELL) >= 1
    # what reads the stack's depth for the pool's, or pins its own list
    for n in ("paged_decode_roofline", "kv_pool_copy_ms",
              "decode_steps_per_call.gen", "weight_passes_per_token.gen",
              "prompt_pad_share.gen"):
        assert CELL not in by[n]["workloads"], n

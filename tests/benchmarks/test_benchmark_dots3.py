"""The sixth architecture (``benchmarks/references/dots3_note.py``,
``deepspeed_tpu/models/hybrid.py`` with a learned selector over the latent
cache and windowed latent layers) through the unedited serving runner on the
CPU at a toy size: a fixture manifest, configuration and published file of
its own (5 of 13 layers, 4 of 16 experts), judged ``correct`` against the
reference, and not ``correct`` against a reference with the selector
dropped; the committed configuration against its published file; the
reference against the program's full forward; the new kernels' arithmetic;
the new readers on a recorded trace and on a run with nothing to read."""

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
MANIFEST = os.path.join(FX, "BENCHMARK.tiny-dots3.json")
CELL = "serve-dots3-longctx-decode"
ENV = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)
NEW_METRICS = ["dsa_index_ms", "dsa_attn_ms", "window_mla_decode_ms",
               "dsa_selected_share", "dsa_index_roofline",
               "dsa_attn_roofline", "window_mla_decode_roofline"]


def _run(workload, manifest=MANIFEST):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(2**31 + 23), "--seconds", "2",
         "--trace", "0", "--manifest", manifest, "--rehearse"],
        env=ENV, capture_output=True, text=True, timeout=1200, cwd=ROOT)


def _config(path, name):
    with open(path) as f:
        man = json.load(f)
    here = os.path.dirname(path)
    bench_dir = os.path.normpath(os.path.join(here, man.get("bench_dir",
                                                            "benchmarks")))
    rel = next(c["file"] for c in man["configs"] if c["name"] == name)
    with open(os.path.join(here, rel)) as f:
        return json.load(f), bench_dir


@pytest.mark.parametrize("reference,failed_check", [
    (None, None), ("dots3_no_selector", "serve.logits_decode")])
def test_dots3_cell_rehearses_on_the_cpu_and_tells_a_dropped_piece(
        tmp_path, reference, failed_check):
    """The cell end to end through the unedited runner: ``correct`` against
    the reference; and the program as it is against a reference that lets a
    full layer attend over its whole context (``fixtures/references/``): the
    two disagree as a program that skipped the selector would disagree with
    the reference, and the cell says so."""
    manifest, cell = MANIFEST, "tiny-dots3-longctx"
    if reference:
        cfg, _ = _config(MANIFEST, "tiny-dots3-serve-c1")
        broken = dict(cfg, name="tiny-dots3-broken", reference=reference)
        (tmp_path / "broken.json").write_text(json.dumps(broken))
        with open(MANIFEST) as f:
            man = json.load(f)
        man["bench_dir"], cell = FX, "tiny-dots3-broken-gen"
        man["configs"] = [{"name": "tiny-dots3-broken", "file": "broken.json"}]
        man["workloads"] = [dict(man["workloads"][0], name=cell,
                                 config="tiny-dots3-broken")]
        for m in man["end_to_end"]:
            if "workloads" in m:
                m["workloads"] = [cell]
        manifest = str(tmp_path / "BENCHMARK.json")
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out = _run(cell, manifest)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    compared = [json.loads(l)["compared"] for l in lines if '"compared"' in l]
    if reference:
        assert last["correct"] is False
        assert failed_check in {c["check"] for c in compared if not c["ok"]}
        return
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                    "setup_s"}
    assert {c["check"] for c in compared} >= {
        "serve.logits_prefill", "serve.logits_decode", "no_compile_in_window"}
    note = next(json.loads(l)["note"] for l in lines if '"counters"' in l)
    c = note["counters"]["engine"]
    assert c["tokens_gather"] == 0 and c["tokens_multi_decode"] > 0
    # past index_topk (16) tokens a query attends over 16 rows in each of
    # the 2 full layers, of all it could see, and over 13 in each of the 3
    # sliding ones (a new prompt's first tokens over fewer)
    assert 0 < c["dsa_rows_selected"] < c["dsa_rows_visible"] / 3
    assert 0.9 < (c["dsa_rows_selected"] * 13 * 3) / (
        c["window_rows_read"] * 16 * 2) < 1.1
    assert c["window_pages_recycled"] > 0 and c["mla_context_tokens"] == 0
    assert c["moe_local_pairs"] > 0 and c["state_slots"] == 0


@pytest.mark.parametrize("path,name", [
    (os.path.join(mf.ROOT, "BENCHMARK.json"), "dots3-note-prev-serve-c1"),
    (MANIFEST, "tiny-dots3-serve-c1")], ids=["committed", "fixture"])
def test_dots3_configuration_cuts_depth_experts_and_vocabulary_alone(path,
                                                                     name):
    """Against its published file: ``reduced`` is exactly what differs; the
    router keeps its published outputs; the preset the engine builds has the
    reference's sizes; the reference's leaf table is the program's tree."""
    import jax

    from deepspeed_tpu.models.zoo import get_model

    cfg, bench_dir = _config(path, name)
    pub = mf.published_of(cfg, bench_dir)
    changed = sorted(k for k, v in pub["config"].items() if cfg[k] != v)
    assert changed == sorted(cfg["reduced"])
    assert set(changed) <= {"num_hidden_layers", "n_routed_experts",
                            "vocab_size"}
    assert cfg["router_outputs"] == pub["config"]["n_routed_experts"]
    assert (pub["experts_key"], pub["layer_period"]) == ("n_routed_experts", 4)
    assert cfg["layer_types"] == pub["config"]["layer_types"]   # kept whole
    arch = mf.reference_of(cfg, bench_dir).Arch.from_model(cfg)
    held = cfg["num_hidden_layers"]
    assert (held - cfg["first_k_dense_replace"]) % pub["layer_period"] == 0
    model = get_model(cfg["preset"], num_layers=held, max_seq_len=64,
                      **cfg["preset_overrides"])
    c = model.config
    kinds = {"full_attention": True, "sliding_attention": "w"}
    assert c.mixer_kinds == tuple(kinds[t] for t in cfg["layer_types"][:held])
    assert (c.attention_kind, c.window_attention_kind, c.first_k_dense,
            c.num_experts, c.held, c.top_k, c.routed_scale, c.router_scoring,
            c.shared_gate, c.mla_lora_rescale, c.mla_head_gate) == (
        "mla", "mla", arch.first_k_dense_replace, arch.router_outputs,
        arch.n_routed_experts, arch.num_experts_per_tok,
        arch.routed_scaling_factor, "sigmoid", False, True, True)
    for windowed in (False, True):
        z, r = c.mla_sizes(windowed), arch.sizes(not windowed)
        assert (z.heads, z.q_rank, z.kv_rank, z.nope, z.rope, z.v, z.theta,
                z.q_rescale, z.kv_rescale) == (
            r.heads, r.q_rank, r.kv_rank, r.nope, r.rope, r.v, r.theta,
            r.s_q, r.s_kv)
    assert (c.index_topk, c.index_n_heads, c.index_head_dim, c.sliding_window,
            c.norm_eps) == (arch.index_topk, arch.index_n_heads,
                            arch.index_head_dim, arch.sliding_window_size,
                            arch.rms_norm_eps)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat = {"/".join(str(k.key) for k in p): leaf.shape for p, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    table = {("layers/" if l.per_layer else "") + l.path.replace(".", "/"):
             ((held,) if l.per_layer else ()) + tuple(l.shape)
             for l in arch.leaf_table()}
    assert flat == table
    if name == "dots3-note-prev-serve-c1":
        assert (held, cfg["n_routed_experts"], cfg["vocab_size"]) == (
            9, 8, 19072)
        for key in ("weights", "e_score_correction_bias", "lora_rescale",
                    "gate", "indexer", "window", "leaf_names", "rotary_pairs",
                    "towers", "indexer_cache", "unused_leaves", "latent_rows",
                    "context", "expert_load", "check_sample"):
            assert key in cfg["assumed"], key
        assert "ep=32" in cfg["deployment"] and "sizing" in cfg
        assert cfg["published_counts"] == {
            k: pub["config"][k] for k in cfg["reduced"]}
        assert "limits_from" in cfg["check"]
        e = cfg["engine"]
        assert (e["kv_blocks"], e["kv_block_size"], e["max_seqs_per_step"],
                e["max_blocks_per_seq"]) == (16384, 64, 48, 512)


@pytest.mark.parametrize("first_row,block", [(20, 32), (92, 8)],
                         ids=["most-rows", "last-rows"])
def test_reference_agrees_with_the_program_full_forward(first_row, block):
    """Two implementations that share no line (the reference blocks the
    context under a running softmax, chooses by ``top_k`` and computes every
    held expert for every token; the program's ``apply`` holds whole score
    matrices, finds the k-th score by its bits and sorts rows by expert),
    float32, seeded weights: logits to 1e-4. ``last-rows``: the rows of the
    last block alone, so that the sliding layers compute the few blocks
    their windows reach from it (``blocks_read``) and no other."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import weights
    from deepspeed_tpu.models.zoo import get_model

    cfg, bench_dir = _config(MANIFEST, "tiny-dots3-serve-c1")
    ref = mf.reference_of(cfg, bench_dir)
    arch = ref.Arch.from_model(cfg)
    model = get_model(cfg["preset"], num_layers=arch.num_hidden_layers,
                      max_seq_len=256, param_dtype=jnp.float32,
                      dtype=jnp.float32, **cfg["preset_overrides"])
    params = weights.make_program_params(arch, 7, jnp.float32)
    toks = np.random.default_rng(1).integers(0, 256, 100).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(toks)[None])[0])
    rows = np.arange(first_row, 100)
    blocks = ref.QUERY_BLOCK, ref.KEY_BLOCK
    ref.QUERY_BLOCK, ref.KEY_BLOCK = block, block   # several of each
    if block == 8:
        assert [len(b) for b in ref.blocks_read(arch, rows, 8)] == [
            13, 8, 6, 4, 2]
    try:
        top = weights.reference_top(arch, 7, jnp.float32)
        want = np.asarray(ref.forward_logits(
            arch, [np.pad(toks, (0, 28))], [rows],
            weights.reference_layer_fn(arch, 7, jnp.float32), top)[0])
    finally:
        ref.QUERY_BLOCK, ref.KEY_BLOCK = blocks
    assert top == {}                              # consumed, as it says
    err = (np.linalg.norm(got[rows] - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    assert err.max() < 1e-4, (err.max(), rows[err.argmax()])
    with pytest.raises(ValueError, match="scoring_func"):
        ref.Arch.from_model(dict(cfg, scoring_func="softmax"))
    with pytest.raises(ValueError, match="group limit"):
        ref.Arch.from_model(dict(cfg, n_group=1))


def _committed_arch():
    cfg, _ = _config(os.path.join(mf.ROOT, "BENCHMARK.json"),
                     "dots3-note-prev-serve-c1")
    return mf.load_module("references", "dots3_note").Arch.from_model(cfg)


def test_kernel_arithmetic_of_the_three_new_steps():
    from benchmarks.kernels import dsa_attn, dsa_index, window_mla_decode

    a = _committed_arch()
    assert dsa_index.sizes(a) == (64, 128) and dsa_index.full_layers(a) == 3
    flops, nbytes = dsa_index.call([1000, 0, 24000], 64, 128)
    assert flops == 2.0 * 64 * 128 * 25000
    assert nbytes == 25000 * 128 * 2 + 2 * 64 * (128 * 2 + 4)
    assert dsa_attn.sizes(a) == (2048, 128, 576, 512)
    flops, nbytes = dsa_attn.call([1000, 0, 24000], *dsa_attn.sizes(a))
    assert flops == 2.0 * 128 * (576 + 512) * (1000 + 2048)
    assert nbytes == (1000 + 2048) * 576 * 2 + 2 * 128 * (576 + 512) * 2
    assert window_mla_decode.sizes(a) == (513, 64, 1088, 1024)
    assert window_mla_decode.sliding_layers(a) == 6
    flops, nbytes = window_mla_decode.call([100, 0, 24000],
                                           *window_mla_decode.sizes(a))
    assert flops == 2.0 * 64 * (1088 + 1024) * (100 + 513)
    assert nbytes == (100 + 513) * 1088 * 2 + 2 * 64 * (1088 + 1024) * 2


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_read_nothing_where_there_is_nothing_to_read(metric):
    """A run without a trace, of a program without the counters or the
    scopes (the parent commit's): None, and no exception."""
    reader = mf.load_module("layer_metrics", metric)
    ctx = types.SimpleNamespace(trace=False, device={"kind": "cpu"},
                                note=lambda obj: None, bench_dir=mf.BENCH_DIR,
                                config={"kind": "serve",
                                        "engine": {"kv_block_size": 64}})
    result = {"trace": None, "counters": {"engine": {"tokens_decode": 3}},
              "facts": {"arch": object(), "traced_steps": (0, 0)},
              "served": types.SimpleNamespace(steps=[])}
    assert reader.read(ctx, result) is None


# -- the readers on a hand-made timeline --------------------------------------

BODY = "jit(dstpu_serve_multi_decode)/while/body/closed_call/"
OP_NAMES = {
    "fusion.1": BODY + "mla/mla_project/dot_general",
    "fusion.2": BODY + "mla/dsa_index/dot_general",
    "fusion.3": BODY + "mla/dsa_select/sort",
    "fusion.4": BODY + "mla/dsa_attn/gather",
    "custom-call.5": BODY + "mla/dsa_attn/mla_decode",
    "fusion.6": BODY + "mla/attn_gate/mul",
    "fusion.7": BODY + "wmla/mla_project/dot_general",
    "custom-call.8": BODY + "wmla/wmla_attn/mla_decode",
    "fusion.9": BODY + "moe/dot_general",
}


def _ev(name, start, dur):
    return (f"%{name} = bf16[8,128]{{1,0}} fusion(bf16[8,128] %p.1)", start,
            dur)


def _burst(t):
    """One execution of the burst program, 0.1 s: two token steps' work."""
    durs = [("fusion.1", .01), ("fusion.2", .02), ("fusion.3", .01),
            ("fusion.4", .012), ("custom-call.5", .008), ("fusion.6", .002),
            ("fusion.7", .01), ("custom-call.8", .016), ("fusion.9", .012)]
    out = []
    for name, d in durs:
        out.append(_ev(name, t, d))
        t += d
    return out


class Ctx:
    config, bench_dir = {"kind": "serve"}, mf.BENCH_DIR
    device = {"kind": "TPU v5 lite"}

    def __init__(self):
        self.notes = []

    def note(self, obj):
        self.notes.append(obj)


def _recorded(scopes):
    mods = [("jit_dstpu_serve_multi_decode(2)", 0.0, 0.1),
            ("jit_dstpu_serve_multi_decode(2)", 0.2, 0.1)]
    return P.ProgramTrace(T.Trace({0: _burst(0.0) + _burst(0.2)}, [], -0.1,
                                  1.0, {0: mods}), [], scopes)


def _result():
    steps = [{"decode_kernel_steps": 2,
              "decode_contexts": [10000, 10001, 20000, 20001]}] * 2
    return {"trace": object(), "facts": {"arch": _committed_arch(),
                                         "traced_steps": (0, 2)},
            "served": types.SimpleNamespace(steps=steps),
            "counters": {"engine": {"dsa_rows_selected": 4 * 2048 * 3,
                                    "dsa_rows_visible": 60002 * 3}}}


@pytest.mark.parametrize("metric,want", [
    ("dsa_index_ms", 1e3 * 2 * 0.03 / 4), ("dsa_attn_ms", 1e3 * 2 * 0.02 / 4),
    ("window_mla_decode_ms", 1e3 * 2 * 0.026 / 4),
    ("dsa_selected_share", 100.0 * 4 * 2048 / 60002)])
def test_ms_and_share_readers_on_a_recorded_trace(monkeypatch, metric, want):
    """Device time under the new scopes of the burst program, per decode
    token step (two executions of two token steps each), and the counters'
    ratio."""
    pt = _recorded({"jit_dstpu_serve_multi_decode": OP_NAMES})
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    reader = mf.load_module("layer_metrics", metric)
    assert reader.read(Ctx(), _result()) == pytest.approx(want)
    # a program without the scopes (the parent's) reads nothing
    bare = _recorded({"jit_dstpu_serve_multi_decode": {
        k: v.replace("dsa_", "x_").replace("wmla", "x") for k, v in
        OP_NAMES.items()}})
    monkeypatch.setattr(P, "open_run", lambda ctx, result: bare)
    if metric != "dsa_selected_share":
        assert reader.read(Ctx(), _result()) is None


@pytest.mark.parametrize("metric,spent,layers,kernel", [
    ("dsa_index_roofline", 0.06, 3, "dsa_index"),
    ("dsa_attn_roofline", 0.04, 3, "dsa_attn"),
    ("window_mla_decode_roofline", 0.032, 6, "window_mla_decode")])
def test_roofline_readers_on_a_recorded_trace(monkeypatch, metric, spent,
                                              layers, kernel):
    """The floor of the traced steps' contexts (times the layers of that
    kind) over the device time under the kernel's scope."""
    from benchmarks.harness import device
    from benchmarks.kernels import flash

    pt = _recorded({"jit_dstpu_serve_multi_decode": OP_NAMES})
    monkeypatch.setattr(P, "open_run", lambda ctx, result: pt)
    ctx, result = Ctx(), _result()
    got = mf.load_module("layer_metrics", metric).read(ctx, result)
    k = mf.load_module("kernels", kernel)
    a = result["facts"]["arch"]
    floor, bound = flash.floor_seconds(
        *k.call([10000, 10001, 20000, 20001], *k.sizes(a)),
        device.peaks("TPU v5 lite"))
    assert got == pytest.approx(100.0 * 2 * layers * floor / spent)
    assert 0 < got < 100 and bound == "memory"
    assert ctx.notes[0][metric]["layers"] == layers


def test_the_manifest_lists_the_new_pieces_at_the_end_of_their_lists():
    man = mf.load_manifest()
    assert man["configs"][-1]["name"] == "dots3-note-prev-serve-c1"
    cell = man["workloads"][-1]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "longctx-code-closed-48", 1)
    assert "x32" in cell["why"] and "host share" in cell["why"]
    assert [m["name"] for m in man["per_layer"][-7:]] == NEW_METRICS
    assert all(m["workloads"] == [CELL] for m in man["per_layer"][-7:])
    reported = {m["name"] for m in mf.metrics_of(man, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | {
        "decode_step_ms", "batch_seqs_per_step", "device_idle_share.gen",
        "host_exposed_ms_per_step.gen", "moe_grouped_roofline",
        "moe_decode_ms"}
    # (``decode_steps_per_call.gen`` and ``weight_passes_per_token.gen`` do
    # not list the cell: ``test_benchmark_program_calls.py`` pins their lists
    # to PR 41's four cells, and only a ``benchmark`` PR edits that file)
    assert {m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)} == {
        "serve_tokens_per_s", "tpot_p90_ms", "setup_s"}
    for m in man["per_layer"] + man["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL

"""The fourth architecture (``benchmarks/references/kimi_k2.py``,
``deepspeed_tpu/models/hybrid.py`` with latent attention, a dense prologue
and sigmoid routing) through the unedited serving runner on the CPU at a toy
size: a fixture manifest, configuration and published file of its own (3 of
6 layers, 4 of 16 experts), judged ``correct`` against the reference, and
not ``correct`` against a reference with the rotary key or the router's bias
dropped (what a program that dropped them would be judged as); the committed
configuration against its published file; the reference against the
program's full forward; the new kernel's arithmetic and the new readers on a
run with nothing to read."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks.harness import manifest as mf

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FX = os.path.join(HERE, "fixtures")
MANIFEST = os.path.join(FX, "BENCHMARK.tiny-kimi.json")
ENV = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)


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


def test_kimi_cell_rehearses_end_to_end_on_the_cpu():
    out = _run("tiny-kimi-longctx")
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                    "setup_s"}
    compared = [json.loads(l)["compared"] for l in lines if '"compared"' in l]
    assert {c["check"] for c in compared} >= {
        "serve.logits_prefill", "serve.logits_decode", "no_compile_in_window"}
    note = next(json.loads(l)["note"] for l in lines if '"counters"' in l)
    c = note["counters"]["engine"]
    assert c["tokens_gather"] == 0 and c["tokens_multi_decode"] > 0
    # whole pages fetched: at least the contexts asked for, less than a page
    # a (sequence, layer, step) more
    assert c["mla_context_tokens"] > 0
    assert c["mla_context_tokens"] <= c["mla_pages_read"] * 16 \
        < c["mla_context_tokens"] * 1.3
    assert c["moe_local_pairs"] > 0 and c["state_slots"] == 0


@pytest.mark.parametrize("reference,failed_check", [
    ("kimi_no_rope_key", "serve.logits_prefill"),
    ("kimi_no_bias", "serve.logits_decode")])
def test_kimi_cell_is_not_correct_without_the_rotary_key_or_the_bias(
        tmp_path, reference, failed_check):
    """The program as it is against a reference that leaves ``q_r . k_r`` out
    of the scores, or the bias out of the router's choice
    (``fixtures/references/``): the two disagree as a program that dropped
    either would disagree with the reference, and the cell says so."""
    cfg, _ = _config(MANIFEST, "tiny-kimi-serve-c1")
    broken = dict(cfg, name="tiny-kimi-broken", reference=reference)
    (tmp_path / "broken.json").write_text(json.dumps(broken))
    with open(MANIFEST) as f:
        man = json.load(f)
    man["bench_dir"] = FX
    man["configs"] = [{"name": "tiny-kimi-broken", "file": "broken.json"}]
    man["workloads"] = [dict(man["workloads"][0], name="tiny-kimi-broken-gen",
                             config="tiny-kimi-broken")]
    for m in man["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-kimi-broken-gen"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out = _run("tiny-kimi-broken-gen", str(tmp_path / "BENCHMARK.json"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is False
    failed = {json.loads(l)["compared"]["check"] for l in lines
              if '"compared"' in l and not json.loads(l)["compared"]["ok"]}
    assert failed_check in failed


@pytest.mark.parametrize("path,name", [
    (os.path.join(mf.ROOT, "BENCHMARK.json"), "kimi-k2.7-code-serve-c1"),
    (MANIFEST, "tiny-kimi-serve-c1")], ids=["committed", "fixture"])
def test_kimi_configuration_cuts_depth_experts_and_vocabulary_alone(path, name):
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
    assert pub["experts_key"] == "n_routed_experts"
    arch = mf.reference_of(cfg, bench_dir).Arch.from_model(cfg)
    held = cfg["num_hidden_layers"]
    assert held - cfg["first_k_dense_replace"] >= 2 * pub["layer_period"]
    model = get_model(cfg["preset"], num_layers=held, max_seq_len=64,
                      **cfg["preset_overrides"])
    c = model.config
    assert (c.attention_kind, c.first_k_dense, c.num_experts, c.held,
            c.top_k, c.routed_scale, c.router_scoring, c.shared_gate) == (
        "mla", arch.first_k_dense_replace, arch.router_outputs,
        arch.n_routed_experts, arch.num_experts_per_tok,
        arch.routed_scaling_factor, "sigmoid", False)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.norm_eps, c.rope_theta) == (
        arch.q_lora_rank, arch.kv_lora_rank, arch.qk_nope_head_dim,
        arch.qk_rope_head_dim, arch.v_head_dim, arch.rms_norm_eps,
        arch.rope_theta)
    assert (c.rope_yarn_factor, c.rope_original_max, c.rope_beta_fast,
            c.rope_beta_slow) == arch.yarn[:4]
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat = {"/".join(str(k.key) for k in p): leaf.shape for p, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    table = {("layers/" if l.per_layer else "") + l.path.replace(".", "/"):
             ((held,) if l.per_layer else ()) + tuple(l.shape)
             for l in arch.leaf_table()}
    assert flat == table
    if name == "kimi-k2.7-code-serve-c1":
        for key in ("weights", "rotary_pairs", "e_score_correction_bias",
                    "num_key_value_heads", "vision_tower", "context",
                    "unused_leaves", "latent_row"):
            assert key in cfg["assumed"], key
        assert "ep=32" in cfg["deployment"] and "sizing" in cfg
        assert cfg["published_counts"] == {
            k: pub["config"][k] for k in cfg["reduced"]}
        e = cfg["engine"]
        assert (e["kv_blocks"], e["kv_block_size"], e["max_seqs_per_step"],
                e["max_blocks_per_seq"]) == (14336, 64, 48, 512)


def test_reference_agrees_with_the_program_full_forward():
    """Two implementations that share no line (the reference blocks the
    context under a running softmax and computes every held expert for every
    token; the program's ``apply`` holds whole score matrices and sorts rows
    by expert), float32, seeded weights: logits to 1e-4."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import weights
    from deepspeed_tpu.models.zoo import get_model

    cfg, bench_dir = _config(MANIFEST, "tiny-kimi-serve-c1")
    ref = mf.reference_of(cfg, bench_dir)
    arch = ref.Arch.from_model(cfg)
    model = get_model(cfg["preset"], num_layers=arch.num_hidden_layers,
                      max_seq_len=256, param_dtype=jnp.float32,
                      dtype=jnp.float32, **cfg["preset_overrides"])
    params = weights.make_program_params(arch, 7, jnp.float32)
    toks = np.random.default_rng(1).integers(0, 256, 200).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(toks)[None])[0])
    rows = np.arange(40, 200)
    blocks = ref.QUERY_BLOCK, ref.KEY_BLOCK
    ref.QUERY_BLOCK, ref.KEY_BLOCK = 64, 32       # several of each
    try:
        top = weights.reference_top(arch, 7, jnp.float32)
        want = np.asarray(ref.forward_logits(
            arch, [np.pad(toks, (0, 56))], [rows],
            weights.reference_layer_fn(arch, 7, jnp.float32), top)[0])
    finally:
        ref.QUERY_BLOCK, ref.KEY_BLOCK = blocks
    assert top == {}                              # consumed, as it says
    err = (np.linalg.norm(got[rows] - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    assert err.max() < 1e-4, (err.max(), rows[err.argmax()])


def test_reference_gives_no_gradients_by_name_and_counts_operations():
    ref = mf.load_module("references", "kimi_k2")
    with pytest.raises(NotImplementedError, match="no loss_and_grads"):
        ref.loss_and_grads()
    cfg, _ = _config(os.path.join(mf.ROOT, "BENCHMARK.json"),
                     "kimi-k2.7-code-serve-c1")
    a = ref.Arch.from_model(cfg)
    mixer = 101.1e6
    per_token = 5 * mixer + 3 * 7168 * 18432 + 4 * (
        (8 * 12 / 384 + 1) * 3 * 7168 * 2048 + 7168 * 384) + 7168 * 20480
    assert ref.train_flops_per_token(a, 0) == pytest.approx(
        6 * per_token, rel=1e-3)
    with pytest.raises(ValueError, match="scoring_func"):
        ref.Arch.from_model(dict(cfg, scoring_func="softmax"))


def test_kernel_arithmetic_of_the_latent_decode_step():
    from benchmarks.kernels import mla_decode

    flops, nbytes = mla_decode.call([1000, 0, 24000], 64, 576, 512)
    assert flops == 2.0 * 64 * (576 + 512) * 25000
    assert nbytes == 25000 * 576 * 2 + 2 * 64 * (576 + 512) * 2
    # 121 operations a byte of context
    assert round(2 * 64 * (576 + 512) / (576 * 2)) == 121
    event = ('%mla_decode.22 = bf16[48,64,512]{2,1,0} custom-call(%a, %b), '
             'custom_call_target="tpu_custom_call"')
    assert mla_decode.classify(event) == "decode"
    assert mla_decode.classify(event.replace("mla", "paged")) is None
    arch = types.SimpleNamespace(num_attention_heads=64, kv_lora_rank=512,
                                 qk_rope_head_dim=64)
    assert mla_decode.sizes(arch) == (64, 576, 512)


@pytest.mark.parametrize("metric", [
    "mla_decode_ms", "mla_decode_roofline", "mla_page_overread_share"])
def test_new_readers_read_nothing_where_there_is_nothing_to_read(metric):
    """A run without a trace, of a program without the counters (the parent
    commit's): None, and no exception."""
    reader = mf.load_module("layer_metrics", metric)
    ctx = types.SimpleNamespace(trace=False, device={"kind": "cpu"},
                                note=lambda obj: None,
                                config={"engine": {"kv_block_size": 64}})
    result = {"trace": None, "counters": {"engine": {"tokens_decode": 3}},
              "facts": {"arch": object(), "traced_steps": (0, 0)},
              "served": types.SimpleNamespace(steps=[])}
    assert reader.read(ctx, result) is None


def test_page_overread_share_from_the_counters():
    reader = mf.load_module("layer_metrics", "mla_page_overread_share")
    ctx = types.SimpleNamespace(config={"engine": {"kv_block_size": 64}})
    result = {"counters": {"engine": {"mla_context_tokens": 8915088800,
                                      "mla_pages_read": 139577120}}}
    assert reader.read(ctx, result) == pytest.approx(0.2002, abs=1e-3)

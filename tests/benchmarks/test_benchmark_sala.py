"""The third architecture (``benchmarks/references/minicpm_sala.py``,
``deepspeed_tpu/models/hybrid.py`` with lightning and block-sparse layers)
through the unedited serving runner on the CPU at a toy size: a fixture
manifest, configuration, published file and traffic of its own (the layers
1-4 of 8, ``m l l m``; sparse sizes shrunk so that ``dense_len`` is crossed
inside a prompt and again while decoding), judged ``correct`` against the
reference, and not ``correct`` with the selection broken; the committed
configuration against its published file; the reference against the
program's full forward; the new kernels' arithmetic and the new readers on a
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
MANIFEST = os.path.join(FX, "BENCHMARK.tiny-sala.json")
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


def test_sala_cell_rehearses_end_to_end_on_the_cpu():
    out = _run("tiny-sala-longctx")
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
    counters = note["counters"]["engine"]
    assert counters["tokens_gather"] == 0
    assert 0 < counters["sparse_blocks_selected"] < counters[
        "sparse_blocks_visible"]
    # the check's prompts (72-140 tokens) end past dense_len (64)
    rows = next(json.loads(l)["note"]["numbers"]["worst_rows"]
                for l in lines if '"numbers"' in l)
    assert all(position >= 64 for _, _, position, _ in rows)


def test_sala_cell_with_the_selection_broken_is_not_correct(tmp_path):
    """The program told to keep a window of 16 tokens and 3 blocks where the
    reference keeps 32 and 5: refused on the logits."""
    cfg, _ = _config(MANIFEST, "tiny-sala-serve-c1")
    broken = dict(cfg, name="tiny-sala-broken", preset_overrides=dict(
        cfg["preset_overrides"], sparse_window_size=16, sparse_topk=3))
    (tmp_path / "broken.json").write_text(json.dumps(broken))
    with open(MANIFEST) as f:
        man = json.load(f)
    man["bench_dir"] = FX
    man["configs"] = [{"name": "tiny-sala-broken", "file": "broken.json"}]
    man["workloads"] = [dict(man["workloads"][0], name="tiny-sala-broken-gen",
                             config="tiny-sala-broken")]
    for m in man["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-sala-broken-gen"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out = _run("tiny-sala-broken-gen", str(tmp_path / "BENCHMARK.json"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is False
    failed = {json.loads(l)["compared"]["check"] for l in lines
              if '"compared"' in l and not json.loads(l)["compared"]["ok"]}
    assert "serve.logits_decode" in failed


@pytest.mark.parametrize("path,name", [
    (os.path.join(mf.ROOT, "BENCHMARK.json"), "minicpm-sala-serve-c1"),
    (MANIFEST, "tiny-sala-serve-c1")], ids=["committed", "fixture"])
def test_sala_configuration_cuts_depth_alone_and_states_its_stage(path, name):
    """Against its published file: only the depth differs; the stage is a
    contiguous run of the published mixers that keeps 1 sparse : 3 lightning
    over a whole number of periods; the sparse sizes the reference reads are
    the preset's; the reference's leaf table is the program's tree."""
    import jax

    from deepspeed_tpu.models.zoo import get_model

    cfg, bench_dir = _config(path, name)
    pub = mf.published_of(cfg, bench_dir)
    changed = sorted(k for k, v in pub["config"].items() if cfg[k] != v)
    assert changed == cfg["reduced"] == ["num_hidden_layers"]
    arch = mf.reference_of(cfg, bench_dir).Arch.from_model(cfg)
    first, held = cfg["first_layer"], cfg["num_hidden_layers"]
    assert arch.mixers == tuple(pub["config"]["mixer_types"][first:first + held])
    assert held % pub["layer_period"] == 0
    assert arch.published_layers == pub["config"]["num_hidden_layers"]
    model = get_model(cfg["preset"], num_layers=held, max_seq_len=64,
                      **cfg["preset_overrides"])
    c = model.config
    assert tuple("minicpm4" if full else "lightning-attn"
                 for full in c.layer_kinds) == arch.mixers
    sz = c.sparse
    assert (sz.kernel, sz.stride, sz.block, sz.init_blocks, sz.window,
            sz.topk, sz.dense_len) == arch.sparse
    assert cfg["engine"]["kv_block_size"] == sz.block
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat = {"/".join(str(k.key) for k in p): leaf.shape for p, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    table = {("layers/" if l.per_layer else "") + l.path.replace(".", "/"):
             ((held,) if l.per_layer else ()) + tuple(l.shape)
             for l in arch.leaf_table()}
    assert flat == table
    if name == "minicpm-sala-serve-c1":
        for key in ("sparse_config", "lightning_decay", "sparse_by_position",
                    "exact_normaliser", "output_gates", "mup_denominator",
                    "unused_mixer_leaves", "weights"):
            assert key in cfg["assumed"], key
        assert "9-16" in cfg["deployment"] and "sizing" in cfg
        # the published 1 sparse : 3 lightning
        assert arch.mixers.count("minicpm4") * pub["layer_period"] == held


def test_reference_agrees_with_the_program_full_forward_across_dense_len():
    """Two implementations that share no line (the reference scans tokens and
    masks a dense score matrix; the program's ``apply`` chunks the recurrence
    and selects blocks), float32, seeded weights: logits to 1e-4."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import weights
    from deepspeed_tpu.models.zoo import get_model

    cfg, bench_dir = _config(MANIFEST, "tiny-sala-serve-c1")
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
    want = np.asarray(ref.forward_logits(
        arch, [np.pad(toks, (0, 56))], [rows],
        weights.reference_layer_fn(arch, 7, jnp.float32),
        weights.reference_top(arch, 7, jnp.float32))[0])
    err = (np.linalg.norm(got[rows] - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    assert err.max() < 1e-4, (err.max(), rows[err.argmax()])


def test_reference_gives_no_gradients_by_name():
    ref = mf.load_module("references", "minicpm_sala")
    with pytest.raises(NotImplementedError, match="no loss_and_grads"):
        ref.loss_and_grads()


def test_kernel_arithmetic_of_the_new_decode_steps():
    from benchmarks.kernels import lightning_decode, sparse_decode

    flops, nbytes = lightning_decode.call(32, 32, 128)
    state = 32 * 128 * 128
    assert flops == 6.0 * state * 32
    assert nbytes == 32 * (8 * state + 32 * (4 * 128 + 1) * 4)
    sizes = (32, 16, 64, 1, 2048, 64, 8192)
    assert sparse_decode.blocks_read(8192, sizes) == (128, 128)   # t = 8191
    assert sparse_decode.blocks_read(8193, sizes) == (64, 129)
    assert sparse_decode.blocks_read(20000, sizes) == (64, 313)
    _, dense = sparse_decode.call([1000], sizes, 32, 2, 128)
    assert dense == 2 * 1000 * 2 * 128 * 2 + 2 * 32 * 128 * 2
    _, sparse = sparse_decode.call([20000], sizes, 32, 2, 128)
    windows = (20000 - 32) // 16 + 1
    assert sparse == (2 * 64 * 64 + windows) * 2 * 128 * 2 + 2 * 32 * 128 * 2
    event = ('%lightning_decode.3 = (f32[32,32,128]{2,1,0}, f32[6,41,32,128,128]'
             '{4,3,2,1,0}) custom-call(%a, %b), custom_call_target='
             '"tpu_custom_call"')
    assert lightning_decode.classify(event) == "decode"
    assert lightning_decode.classify(event.replace("lightning", "gdn")) is None


@pytest.mark.parametrize("metric", [
    "lightning_decode_ms", "sparse_attn_decode_ms", "lightning_decode_roofline",
    "sparse_decode_roofline", "sparse_selected_share"])
def test_new_readers_read_nothing_where_there_is_nothing_to_read(metric):
    """A run without a trace, of a program without the counters (the parent
    commit's): None, and no exception."""
    reader = mf.load_module("layer_metrics", metric)
    ctx = types.SimpleNamespace(trace=False, device={"kind": "cpu"},
                                note=lambda obj: None)
    result = {"trace": None, "counters": {"engine": {"tokens_decode": 3}},
              "facts": {"arch": object(), "traced_steps": (0, 0)},
              "served": types.SimpleNamespace(steps=[])}
    assert reader.read(ctx, result) is None

"""The hybrid architecture (``benchmarks/references/qwen3_next.py``,
``deepspeed_tpu/models/hybrid.py``) through the unedited serving runner on
the CPU at a toy size: a fixture manifest, configuration and published file
of its own (3 recurrent : 1 full layers in 2 periods, 4 of 16 experts held
from offset 4, an eighth of the vocabulary), judged ``correct`` against the
reference, and not ``correct`` with the share's offset wrong."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as mf

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FX = os.path.join(HERE, "fixtures")
MANIFEST = os.path.join(FX, "BENCHMARK.tiny-hybrid.json")
ENV = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)


def _run(workload, manifest=MANIFEST):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(2**31 + 23), "--seconds", "2",
         "--trace", "0", "--manifest", manifest, "--rehearse"],
        env=ENV, capture_output=True, text=True, timeout=1200, cwd=ROOT)


def test_hybrid_cell_rehearses_end_to_end_on_the_cpu():
    out = _run("tiny-hybrid-gen")
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                    "setup_s"}
    compared = [json.loads(l)["compared"] for l in lines if '"compared"' in l]
    assert {c["check"] for c in compared} >= {"serve.logits_prefill",
                                              "serve.logits_decode"}
    counters = next(json.loads(l)["note"]["counters"]["engine"]
                    for l in lines if '"counters"' in l)
    # 4 of 16 experts held, 4 chosen a token: one pair a token-layer
    ratio = counters["moe_local_pairs"] / counters["moe_token_layers"]
    assert 0.7 < ratio < 1.3, ratio
    assert counters["moe_experts_hit"] > 0


def test_hybrid_cell_with_the_wrong_share_is_not_correct(tmp_path):
    """The program told it holds experts 0..3 while the weights and the
    reference are those of 4..7: refused on the logits."""
    with open(os.path.join(FX, "configs", "tiny-hybrid-serve-c1.json")) as f:
        cfg = json.load(f)
    broken = dict(cfg, name="tiny-hybrid-broken", preset_overrides=dict(
        cfg["preset_overrides"], expert_offset=0))
    (tmp_path / "broken.json").write_text(json.dumps(broken))
    with open(MANIFEST) as f:
        man = json.load(f)
    man["bench_dir"] = FX
    man["configs"] = [{"name": "tiny-hybrid-broken", "file": "broken.json"}]
    man["workloads"] = [dict(man["workloads"][0], name="tiny-hybrid-broken-gen",
                             config="tiny-hybrid-broken")]
    for m in man["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-hybrid-broken-gen"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out = _run("tiny-hybrid-broken-gen", str(tmp_path / "BENCHMARK.json"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["correct"] is False


@pytest.mark.parametrize("path,name", [
    (os.path.join(mf.ROOT, "BENCHMARK.json"), "qwen3-next-80b-a3b-serve-c1"),
    (MANIFEST, "tiny-hybrid-serve-c1")], ids=["committed", "fixture"])
def test_hybrid_configuration_keeps_widths_and_states_its_share(path, name):
    """Against its published file: exactly depth, experts held and
    vocabulary are cut; the router keeps the published count of outputs;
    the reference's leaf table matches the program's parameter tree."""
    import jax

    from deepspeed_tpu.models.zoo import get_model

    with open(path) as f:
        man = json.load(f)
    here = os.path.dirname(path)
    bench_dir = os.path.normpath(os.path.join(here, man.get("bench_dir", "benchmarks")))
    rel = next(c["file"] for c in man["configs"] if c["name"] == name)
    with open(os.path.join(here, rel)) as f:
        cfg = json.load(f)
    pub = mf.published_of(cfg, bench_dir)
    changed = sorted(k for k, v in pub["config"].items() if cfg[k] != v)
    assert changed == sorted(cfg["reduced"]) == ["num_experts",
                                                 "num_hidden_layers",
                                                 "vocab_size"]
    assert cfg["router_outputs"] == pub["config"]["num_experts"]
    assert cfg["num_hidden_layers"] % pub["layer_period"] == 0
    arch = mf.reference_of(cfg, bench_dir).Arch.from_model(cfg)
    model = get_model(cfg["preset"], num_layers=arch.num_hidden_layers,
                      max_seq_len=64, **cfg["preset_overrides"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat = {"/".join(str(k.key) for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    table = {("layers/" if l.per_layer else "") + l.path.replace(".", "/"):
             ((arch.num_hidden_layers,) if l.per_layer else ()) + tuple(l.shape)
             for l in arch.leaf_table()}
    assert flat == table

"""``BENCHMARK.json`` keeps to the contract, every name resolves to a
file, and a configuration, a traffic mix, a metric and a cell can each be
added as new files and entries without touching a file that is there."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as mf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return mf.load_manifest()


def test_top_level_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51 and isinstance(man["run_seconds"], int)
    assert man["command"][:2] == ["python3", "benchmarks/run.py"]
    assert all(os.path.isdir(os.path.join(mf.ROOT, p)) for p in man["paths"])
    assert os.path.getsize(os.path.join(mf.ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_name_and_unit_uses_only_the_allowed_characters(man):
    names = [c["name"] for c in man["configs"]]
    for w in man["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"], w["name"]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in man["configs"]:
        names += c["reduced"]
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and len(c["source"]) <= 200
    for m in man["end_to_end"] + man["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for group in (man["configs"], man["workloads"],
                  man["end_to_end"] + man["per_layer"]):
        assert len({x["name"] for x in group}) == len(group)


def test_every_file_a_name_points_to_exists(man):
    used = set()
    for w in man["workloads"]:
        _, _, _, cfg, traffic = mf.resolve(
            os.path.join(mf.ROOT, "BENCHMARK.json"), w["name"])
        rel = next(c["file"] for c in man["configs"] if c["name"] == w["config"])
        assert rel.startswith("benchmarks/")
        used.add(w["config"])
        mf.load_module("runners", cfg["kind"])
        mf.load_module("generators", traffic["generator"])
        for m in mf.metrics_of(man, "per_layer", w["name"]):
            assert hasattr(mf.load_module("layer_metrics", m["name"]), "read")
    assert used == {c["name"] for c in man["configs"]}   # each used by a cell
    assert len({c["file"] for c in man["configs"]}) == len(man["configs"])


def test_a_missing_piece_names_the_file_it_looked_for():
    with pytest.raises(mf.MissingPiece, match=r"benchmarks/traffic/nope\.json"):
        mf.load_json("traffic", "nope")
    with pytest.raises(mf.MissingPiece, match=r"layer_metrics/nope\.py"):
        mf.load_module("layer_metrics", "nope")
    with pytest.raises(mf.MissingPiece, match="not in BENCHMARK.json"):
        mf.cell(mf.load_manifest(), "no-such-cell")


TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "BENCHMARK.tiny.json")


def _configs_with_a_published_file(manifest_path):
    """``(entry, configuration, published, bench_dir)`` of every
    configuration that names a published file: all of the real manifest's,
    and of the fixtures' the second architecture's."""
    with open(manifest_path) as f:
        man = json.load(f)
    here = os.path.dirname(manifest_path)
    bench_dir = os.path.normpath(os.path.join(here, man.get("bench_dir", "benchmarks")))
    for c in man["configs"]:
        with open(os.path.join(here, c["file"])) as f:
            cfg = json.load(f)
        if "published" in cfg or manifest_path != TINY:
            yield c, cfg, mf.published_of(cfg, bench_dir), bench_dir


def _violations(cfg, pub):
    """What a configuration breaks of the model-configs guide, section 4,
    against its architecture's published file: ``reduced`` is exactly what
    differs; only the depth, the count of routed experts and the vocabulary
    may differ, down to a whole period of the layer pattern, 8 experts and
    an eighth of the vocabulary; every other published value is kept
    letter for letter."""
    want, out = pub["config"], []
    depth, experts = pub.get("depth_key", "num_hidden_layers"), pub.get("experts_key")
    if any(k not in cfg for k in want):
        return ["missing " + k for k in want if k not in cfg]
    changed = sorted(k for k, v in want.items()
                     if cfg[k] != v or type(cfg[k]) is not type(v))
    if changed != sorted(cfg["reduced"]):
        out.append("reduced")
    if not set(changed) <= {depth, experts, "vocab_size"}:
        out.append("width")
    if not pub.get("layer_period", 1) <= cfg[depth] <= want[depth]:
        out.append("depth")
    if not want["vocab_size"] / 8 <= cfg["vocab_size"] <= want["vocab_size"]:
        out.append("vocab")
    if experts and not 8 <= cfg[experts] <= want[experts]:
        out.append("experts")
    return out


@pytest.mark.parametrize("manifest_path", [
    os.path.join(mf.ROOT, "BENCHMARK.json"), TINY], ids=["committed", "fixtures"])
def test_configs_keep_every_published_width_and_list_what_they_cut(manifest_path):
    """Each configuration against its *own* architecture's published file
    (``benchmarks/published/<name>.json``), and its reference resolves."""
    seen = 0
    for c, cfg, pub, bench_dir in _configs_with_a_published_file(manifest_path):
        seen += 1
        assert _violations(cfg, pub) == [], c["name"]
        assert sorted(c.get("reduced", cfg["reduced"])) == sorted(cfg["reduced"])
        assert cfg["source"] == pub["source"] == c.get("source", pub["source"])
        assert all(v is not None for v in cfg["check"]["limits"].values())
        arch = mf.reference_of(cfg, bench_dir).Arch.from_model(cfg)
        assert arch.num_hidden_layers == cfg[pub.get("depth_key", "num_hidden_layers")]
        assert arch.vocab_size == cfg["vocab_size"]
        table = arch.leaf_table()
        assert len({leaf.path for leaf in table}) == len(table)
        assert len({leaf.published for leaf in table}) == len(table)
    assert seen >= 2


def test_a_configuration_that_cuts_a_width_or_hides_a_cut_is_refused():
    """The rule above, shown to fail: on copies of the second
    architecture's configuration with a width cut, a cut not listed, too
    little vocabulary, less than a period of layers and a value missing."""
    fx = os.path.dirname(TINY)
    with open(os.path.join(fx, "configs", "tiny-alt-serve-c1.json")) as f:
        good = json.load(f)
    pub = mf.published_of(good, fx)
    assert _violations(good, pub) == []
    assert _violations(dict(good, ffn_hidden_size=128, reduced=good["reduced"]
                            + ["ffn_hidden_size"]), pub) == ["width"]
    assert _violations(dict(good, reduced=["num_hidden_layers"]), pub) == ["reduced"]
    assert _violations(dict(good, vocab_size=256), pub) == ["vocab"]
    assert _violations(dict(good, num_hidden_layers=0), pub) == ["depth"]
    assert _violations(dict(good, rope_theta=10000), pub) == ["reduced", "width"]
    short = {k: v for k, v in good.items() if k != "head_dim"}
    assert _violations(short, pub) == ["missing head_dim"]
    experts = dict(pub, experts_key="num_experts",
                   config=dict(pub["config"], num_experts=64))
    assert _violations(dict(good, num_experts=4, reduced=good["reduced"]
                            + ["num_experts"]), experts) == ["experts"]
    assert _violations(dict(good, num_experts=8, reduced=good["reduced"]
                            + ["num_experts"]), experts) == []


RUNNERS_AND_HARNESS = [os.path.join("runners", "serve.py"), os.path.join("runners", "train.py")] \
    + [os.path.join("harness", f) for f in sorted(os.listdir(os.path.join(mf.BENCH_DIR, "harness")))
       if f.endswith(".py")]


@pytest.mark.parametrize("rel", RUNNERS_AND_HARNESS)
def test_no_runner_or_harness_file_imports_an_architecture(rel):
    """What belongs to one architecture is found by name: the runners and
    the harness import no reference module and hold no leaf of one."""
    with open(os.path.join(mf.BENCH_DIR, rel)) as f:
        src = f.read()
    code = "\n".join(line.split("#")[0] for line in src.splitlines())
    code = re.sub(r'"""(?:.|\n)*?"""', "", code)          # docstrings are prose
    assert "benchmarks.references" not in code and "references import" not in code
    for word in ("mistral", "q_proj", "gate_proj", "attn.wq", "lm_head"):
        assert word not in code, (rel, word)


def test_no_metric_lists_a_reader_that_is_gone_and_no_reader_is_left_over(man):
    """A removal takes the entry and the reader file together (PR 40: the
    three metrics PR 38 silenced): every per-layer entry has its file, and
    every file is an entry's or the one reader behind an entry's dotted
    names."""
    listed = {m["name"] for m in man["per_layer"]}
    folder = os.path.join(mf.BENCH_DIR, "layer_metrics")
    files = {f[:-3] for f in os.listdir(folder)
             if f.endswith(".py") and f != "__init__.py"}
    assert listed <= files, sorted(listed - files)
    shared = {f for f in files - listed
              if any(n.startswith(f + ".") for n in listed)}
    assert files == listed | shared, sorted(files - listed - shared)
    for f in shared:            # a dotted name is that reader and no other
        for n in (n for n in listed if n.startswith(f + ".")):
            assert mf.load_module("layer_metrics", n).read \
                is mf.load_module("layer_metrics", f).read


MISTRAL_SERVING = {
    "serve-gen-closed": {"prefill_call_ms.gen", "prefill_calls_per_chunk.gen"},
    "serve-rag-burst": {"prefill_call_ms.burst",
                        "prefill_calls_per_chunk.burst"},
    "serve-chat-steady": {"prefill_call_ms.burst",
                          "prefill_calls_per_chunk.burst"}}


@pytest.mark.parametrize("cell", sorted(MISTRAL_SERVING))
def test_the_dense_serving_cells_list_the_prefill_program_and_no_gather_metric(
        man, cell):
    names = {m["name"] for m in mf.metrics_of(man, "per_layer", cell)}
    assert MISTRAL_SERVING[cell] <= names
    assert not {n for n in names if n.startswith("gather_")
                or n == "prefill_kernel_share"}
    by_name = {m["name"]: m for m in man["per_layer"]}
    for n in MISTRAL_SERVING[cell]:
        assert by_name[n]["moves"] == ("serve_tokens_per_s" if n.endswith(
            ".gen") else "ttft_p50_ms")


def test_cells_report_setup_one_more_metric_and_a_layer_metric(man):
    e2e_names = {m["name"] for m in man["end_to_end"]}
    assert "setup_s" in e2e_names
    four = [w for w in man["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(man["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in man["workloads"])
    cells = {w["name"] for w in man["workloads"]}
    pairs = {(w["config"], w["traffic"]) for w in man["workloads"]}
    assert len(pairs) == len(man["workloads"])
    for w in man["workloads"]:
        e2e = {m["name"] for m in mf.metrics_of(man, "end_to_end", w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert mf.metrics_of(man, "per_layer", w["name"]), w["name"]
    for m in man["per_layer"]:
        assert m["moves"] in e2e_names and m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert cell in cells
            moved = {x["name"] for x in mf.metrics_of(man, "end_to_end", cell)}
            assert m["moves"] in moved, (m["name"], cell)
    layers = {}
    for m in man["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())    # letter for letter


@pytest.mark.parametrize("metric", ["train_mfu", "flash_roofline",
                                    "paged_decode_roofline"])
def test_roofline_and_mfu_metrics_are_named_and_united_by_the_contract(man, metric):
    m = next(x for x in man["per_layer"] if x["name"] == metric)
    assert m["unit"] == "%" and m["better"] == "higher"
    assert metric.endswith("_roofline") or "mfu" in metric


def test_discovery_new_pieces_run_without_touching_an_existing_file(tmp_path):
    """A later PR's view: copy nothing, edit nothing; add a configuration,
    a traffic file and a metric reader in a directory of their own and a
    manifest that names them; the one command runs them."""
    here = os.path.dirname(os.path.abspath(__file__))
    fx = os.path.join(here, "fixtures")
    d = tmp_path / "later_pr"
    (d / "configs").mkdir(parents=True)
    (d / "traffic").mkdir()
    (d / "layer_metrics").mkdir()
    with open(os.path.join(fx, "configs", "tiny-train-c1.json")) as f:
        cfg = dict(json.load(f), name="dummy-train", num_hidden_layers=1)
    (d / "configs" / "dummy-train.json").write_text(json.dumps(cfg))
    (d / "traffic" / "dummy-stream.json").write_text(json.dumps(
        {"name": "dummy-stream", "generator": "train_stream", "tokens": "uniform"}))
    (d / "layer_metrics" / "dummy_steps.py").write_text(
        "def read(ctx, result):\n    return result['attempted']\n")
    (d / "layer_metrics" / "dummy_nothing.py").write_text(
        "def read(ctx, result):\n    return None\n")
    man = {"command": ["python3", "benchmarks/run.py"], "bench_dir": ".",
           "run_seconds": 1,
           "configs": [{"name": "dummy-train", "file": "configs/dummy-train.json"}],
           "workloads": [{"name": "dummy", "config": "dummy-train",
                          "traffic": "dummy-stream", "chips": 1}],
           "end_to_end": [
               {"name": "train_tokens_per_s_chip", "unit": "tokens/s/chip",
                "better": "higher", "bound": 0.01, "source": "host_clock"},
               {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
                "source": "host_clock"}],
           "per_layer": [
               {"name": "dummy_steps", "unit": "steps", "better": "higher",
                "source": "program_counter", "layer": "dummy",
                "moves": "train_tokens_per_s_chip"},
               {"name": "dummy_nothing", "unit": "steps", "better": "higher",
                "source": "program_counter", "layer": "dummy",
                "moves": "train_tokens_per_s_chip"}]}
    (d / "BENCHMARK.json").write_text(json.dumps(man))
    before = {p: os.path.getmtime(os.path.join(mf.BENCH_DIR, p))
              for p in os.listdir(mf.BENCH_DIR)}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmarks", "run.py"),
         "--workload", "dummy", "--seed", "5", "--seconds", "1", "--trace", "1",
         "--rehearse", "--manifest", str(d / "BENCHMARK.json")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["dummy_steps"]["value"] == line["attempted"]
    assert "dummy_nothing" not in line["metrics"]     # nothing read: left out
    after = {p: os.path.getmtime(os.path.join(mf.BENCH_DIR, p))
             for p in os.listdir(mf.BENCH_DIR) if p in before}
    assert before == after

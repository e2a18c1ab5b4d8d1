"""``run.py --workload <cell>`` end to end on the CPU at a tiny size (four
virtual devices for the four-chip cell): the contract's last line, with
``device.platform`` = ``cpu`` and no device metric; and the refusal to
measure off a TPU."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "fixtures", "BENCHMARK.tiny.json")
ENV = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
           XLA_FLAGS="--xla_force_host_platform_device_count=4")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)


def _run(workload, trace, extra=(), manifest=TINY, seconds="2"):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(2**31 + 17), "--seconds", seconds,
         "--trace", str(trace), "--manifest", manifest, *extra],
        env=ENV, capture_output=True, text=True, timeout=900, cwd=ROOT)


CELLS = {"tiny-train": {"train_tokens_per_s_chip"},
         "tiny-gen": {"serve_tokens_per_s", "tpot_p90_ms"},
         "tiny-burst": {"ttft_p50_ms"},
         "tiny-train-fsdp4": {"train_tokens_per_s_chip"},
         # the second architecture (fixtures/references/tiny_alt.py): other
         # leaves, another block, its own published file — through the same
         # runners, judged against its own reference
         "tiny-alt-train": {"train_tokens_per_s_chip"},
         "tiny-alt-gen": {"serve_tokens_per_s", "tpot_p90_ms"}}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_rehearses_end_to_end_on_the_cpu(cell):
    out = _run(cell, 0, ["--rehearse"])
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert all(l.startswith("{") for l in lines)          # JSON lines only
    last = json.loads(lines[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == CELLS[cell] | {"setup_s"}
    assert all(v["value"] > 0 and v["unit"] for v in last["metrics"].values())
    assert last["device"]["platform"] == "cpu"
    assert "busy_s" not in last["device"]
    compared = [json.loads(l)["compared"] for l in lines if '"compared"' in l]
    assert len(compared) >= 2 and all("limit" in c and "value" in c for c in compared)


def test_the_second_architecture_is_judged_against_its_own_reference(tmp_path):
    """Its own reference refuses what a broken block would produce: the
    same cell with the parallel residual left out of the *program* (the
    configuration's ``preset_overrides``) comes out not ``correct``, on the
    logits."""
    fx = os.path.join(HERE, "fixtures")
    with open(os.path.join(fx, "configs", "tiny-alt-serve-c1.json")) as f:
        cfg = json.load(f)
    broken = dict(cfg, name="tiny-alt-broken", preset_overrides=dict(
        cfg["preset_overrides"], parallel_block=False))
    (tmp_path / "broken.json").write_text(json.dumps(broken))
    with open(TINY) as f:
        man = json.load(f)
    man["bench_dir"] = fx
    man["configs"] = [{"name": "tiny-alt-broken", "file": "broken.json"}]
    man["workloads"] = [{"name": "tiny-alt-broken-gen", "config": "tiny-alt-broken",
                         "traffic": "tiny-gen-closed", "chips": 1}]
    for m in man["end_to_end"]:
        if "tiny-alt-gen" in m.get("workloads", []):
            m["workloads"] = ["tiny-alt-broken-gen"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out = _run("tiny-alt-broken-gen", 0, ["--rehearse"],
               manifest=str(tmp_path / "BENCHMARK.json"))
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False
    bad = [json.loads(l)["compared"] for l in out.stdout.splitlines()
           if '"compared"' in l and '"ok": false' in l]
    assert any(c["check"].startswith("serve.logits") for c in bad), bad


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-gen", "tiny-burst"])
def test_traced_run_on_the_cpu_reports_no_device_metric(cell):
    out = _run(cell, 1, ["--rehearse"])
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu"
    assert "busy_s" not in last["device"] and "breakdown" not in last
    assert last["metrics"] == {}          # the tiny manifest lists no reader


def test_without_a_tpu_the_measurement_path_fails_and_prints_no_result():
    out = _run("tiny-train", 0)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "no TPU" in out.stderr


def test_device_metric_readers_return_nothing_off_a_tpu():
    sys.path.insert(0, ROOT)
    from benchmarks.harness import manifest as mf

    class Ctx:
        device = {"platform": "cpu", "kind": "cpu", "count": 1}

        def note(self, _):
            pass

    result = {"trace": None, "end_to_end": {"train_tokens_per_s_chip": 1.0},
              "facts": {"flops_per_token": 1.0}}
    for name in ("train_mfu", "flash_roofline", "device_idle_share.train",
                 "collective_exposed_share", "paged_decode_roofline",
                 "decode_step_ms", "device_idle_share.gen",
                 "device_idle_share.burst"):
        assert mf.load_module("layer_metrics", name).read(Ctx(), result) is None, name


def test_real_cells_refuse_to_run_without_their_chips():
    out = _run("train-z3-2k", 0, manifest=os.path.join(ROOT, "BENCHMARK.json"))
    assert out.returncode != 0 and '"correct"' not in out.stdout


_SHARD_FAULTS = r"""
import json, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
from benchmarks.harness import compare, manifest as mf
man, bench_dir, cell, cfg, traffic = mf.resolve(sys.argv[2], "tiny-train-fsdp4")
mf.program_logs_to_stderr()
ref_mod = mf.reference_of(cfg, bench_dir)
runner = mf.load_module("runners", "train", bench_dir)
gen = mf.load_module("generators", traffic["generator"], bench_dir)
arch, seed, chips = ref_mod.Arch.from_model(cfg), 2**31 + 17, cell["chips"]
batch0, distinct = gen.check_batch(traffic, seed, arch.vocab_size, chips,
                                   cfg["seq_len"], cfg["check"]["sample_sequences"])
ref = runner.reference_numbers(ref_mod, arch, cfg, distinct, seed)
other = batch0.copy()
other[3] = np.random.default_rng(1).integers(0, arch.vocab_size, other[3].shape)
fed = {"sound": batch0,
       "every_chip_got_row_0": np.repeat(batch0[:1], chips, axis=0),
       "one_chip_got_other_data": other}
for name, batch in fed.items():
    _, engine = runner.build_engine(cfg, arch, seed, chips)
    loss0 = float(engine.train_batch(iter([{"input_ids": batch}])))
    engine.synchronize()
    rows = runner.engine_gradient_rows(engine, arch, ref["plan"])
    verdict = compare.Verdict()
    numbers = runner.compare_to_reference(verdict, ref, loss0, rows.pop("_norm"),
                                          rows, cfg["check"]["limits"])
    print(json.dumps({"case": name, "correct": verdict.correct,
                      "grad_leaves": numbers["grad_leaves"],
                      "rows_per_chip": len({r.tobytes() for r in batch})}), flush=True)
"""


@pytest.fixture(scope="module")
def shard_faults():
    """The four-chip check on four virtual devices, fed the sound check
    batch and two batches that stand for a fault in what crosses chips:
    every chip handed row 0, and one chip's gradient taken from other
    data (so the mean over chips is no longer the reference's)."""
    out = subprocess.run([sys.executable, "-c", _SHARD_FAULTS, ROOT, TINY], env=ENV,
                         capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return {r["case"]: r for r in map(json.loads, out.stdout.strip().splitlines())}


@pytest.mark.parametrize("case,correct", [("sound", True),
                                          ("every_chip_got_row_0", False),
                                          ("one_chip_got_other_data", False)])
def test_four_chip_check_fails_when_one_shards_gradient_is_wrong(
        shard_faults, case, correct):
    got = shard_faults[case]
    assert got["correct"] is correct, got
    if not correct:          # far outside, not at the edge of the limit
        assert got["grad_leaves"] > 3 * 0.2, got


def test_four_chip_configurations_check_a_sequence_for_every_chip():
    """One sequence repeated on every chip would leave the mean of the
    chips' gradients equal to each chip's own: the runner refuses it, and
    the committed four-chip configuration does not ask for it."""
    sys.path.insert(0, ROOT)
    from benchmarks.harness import manifest as mf

    man = mf.load_manifest()
    for cell in man["workloads"]:
        _, _, _, cfg, _ = mf.resolve(os.path.join(ROOT, "BENCHMARK.json"),
                                     cell["name"])
        if cfg["kind"] == "train":
            assert cfg["check"]["sample_sequences"] % cell["chips"] == 0, cell
    _, bench_dir, cell, cfg, traffic = mf.resolve(TINY, "tiny-train-fsdp4")

    class OneRow:
        config = dict(cfg, check=dict(cfg["check"], sample_sequences=1))
        seed = 1

    OneRow.traffic, OneRow.cell, OneRow.bench_dir = traffic, cell, bench_dir
    runner = mf.load_module("runners", "train", bench_dir)
    with pytest.raises(ValueError, match="no sequence each of their own"):
        runner.run(OneRow())

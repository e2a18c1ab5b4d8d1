"""Tier-1 contract for bench.py's default shape resolution: the
headline benchmark runs the REAL shape (8 layers, 131,072 vocab,
device-step measurement over ZeRO-Infinity streaming) by default on
TPU; BENCH_PROXY=1 restores the old 3-layer / 8k-vocab proxy."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import pytest  # noqa: E402

from bench import REAL_LAYERS, REAL_VOCAB, resolve_bench_defaults  # noqa: E402


@pytest.fixture(autouse=True)
def _no_tuned_file(monkeypatch, tmp_path):
    # read_tuned_defaults falls back to a committable docs/autotuned
    # file; point it nowhere so the contract below tests the measured
    # defaults, not whatever a local bench run persisted
    monkeypatch.setenv("BENCH_TUNED_DEFAULTS",
                       str(tmp_path / "absent.json"))


def test_real_shape_is_the_tpu_default():
    d = resolve_bench_defaults(env={}, on_tpu=True)
    assert d["real_shape"] is True
    assert d["layers"] == REAL_LAYERS == 8
    assert d["vocab"] == REAL_VOCAB == 131072
    assert d["measure"] == "device_step"
    assert d["offload"] == 2            # ZeRO-Infinity streaming
    assert d["zero_stage"] == 2
    assert d["param_prefetch_depth"] == 4
    assert d["overlap_depth"] == 4      # full ring staged against compute
    assert d["remat_policy"] == "nothing_saveable"
    assert d["tiled_logits"] == 8
    assert d["fp8_mlp"] is False        # opt-in only


def test_proxy_shape_behind_env_flag():
    d = resolve_bench_defaults(env={"BENCH_PROXY": "1"}, on_tpu=True)
    assert d["real_shape"] is False and d["proxy"] is True
    assert d["layers"] == 3
    assert d["vocab"] == 8192
    assert d["measure"] == "train_batch"
    assert d["offload"] == 0
    assert d["param_prefetch_depth"] is None
    assert d["overlap_depth"] is None   # no stream, nothing to stage


def test_env_overrides_beat_defaults():
    d = resolve_bench_defaults(
        env={"BENCH_LAYERS": "4", "BENCH_VOCAB": "4096",
             "BENCH_PARAM_PREFETCH": "2", "BENCH_FP8_MLP": "1",
             "BENCH_OVERLAP_DEPTH": "0",
             "BENCH_MEASURE": "train_batch"}, on_tpu=True)
    assert d["layers"] == 4 and d["vocab"] == 4096
    assert d["param_prefetch_depth"] == 2
    assert d["overlap_depth"] == 0      # explicit A/B baseline wins
    assert d["fp8_mlp"] is True
    assert d["measure"] == "train_batch"


def test_tuned_file_overlap_depth_read_back(monkeypatch, tmp_path):
    # dstpu-autotune --persist writes performance.overlap_depth; the
    # bench reads it back as the default, env still wins
    import json
    p = tmp_path / "tuned.json"
    p.write_text(json.dumps({"performance": {"overlap_depth": 3}}))
    monkeypatch.setenv("BENCH_TUNED_DEFAULTS", str(p))
    d = resolve_bench_defaults(env={}, on_tpu=True)
    assert d["overlap_depth"] == 3
    assert d["config_source"] == "autotuned-file"
    d = resolve_bench_defaults(env={"BENCH_OVERLAP_DEPTH": "1"},
                               on_tpu=True)
    assert d["overlap_depth"] == 1


def test_long_context_branch_unaffected():
    d = resolve_bench_defaults(env={"BENCH_SEQ": "32768"}, on_tpu=True)
    assert d["long_ctx"] is True and d["real_shape"] is False
    assert d["layers"] == 1 and d["micro"] == 1


def test_cpu_smoke_stays_small():
    d = resolve_bench_defaults(env={}, on_tpu=False)
    assert d["seq"] == 128 and d["micro"] == 1


def test_longctx_bench_tier_resolves():
    d = resolve_bench_defaults(env={"BENCH_LONGCTX": "1"}, on_tpu=False)
    assert d["longctx_bench"] is True
    assert d["seq"] == 262144          # 256k default, BENCH_SEQ wins
    assert d["longctx_sp"] == 4
    d = resolve_bench_defaults(
        env={"BENCH_LONGCTX": "1", "BENCH_SEQ": "1048576",
             "BENCH_SP": "8"}, on_tpu=False)
    assert d["seq"] == 1048576 and d["longctx_sp"] == 8
    # the flag is off by default and does not disturb the real shape
    d = resolve_bench_defaults(env={}, on_tpu=True)
    assert d["longctx_bench"] is False and d["real_shape"] is True


def test_longctx_bench_report_emits_three_regions():
    from bench import longctx_bench_report

    table, payload = longctx_bench_report(env={
        "BENCH_SEQ": "262144", "BENCH_SP": "4",
        # an analytic model of a chip: its peaks are named (v5e)
        "BENCH_PEAK_TFLOPS": "197", "BENCH_HBM_GBPS": "819"})
    assert "| attn |" in table and "| sp_comm |" in table
    assert "| host_kv_stream |" in table
    assert payload["unit"] == "modeled exposed ms/step"
    assert payload["plan"]["sp_degree"] == 4
    assert [r["region"] for r in payload["regions"]] == [
        "attn", "sp_comm", "host_kv_stream"]
    assert payload["plan"]["reasons"]

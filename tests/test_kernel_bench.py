"""The `make bench-kernels` tier end to end (slow, CPU smoke shapes).

Runs the real bench arm — every kernel raced against its XLA twin in
interpret mode — and asserts the one-JSON-line payload conventions the
CI diff rides on: a win/loss entry per (kernel, bucket), ratio defined
as xla_ms/kernel_ms, numerics checked on every arm, the winning_kernels
list tools/bench_diff.py guards against regression, and the dispatch
probe that proves ops/registry.py actually consults the recorded table.
"""

import json
import os

import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "") not in ("", "cpu", "tpu"),
    reason="needs a jax backend")


def test_kernel_bench_smoke_payload_and_recorded_table(tmp_path,
                                                      monkeypatch):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from kernel_bench import run_kernel_bench

    from deepspeed_tpu.ops import kernel_table

    record = tmp_path / "kernel_table.json"
    monkeypatch.setenv("KERNEL_BENCH_RECORD_PATH", str(record))
    monkeypatch.setenv("KERNEL_BENCH_ITERS", "1")
    table, payload, ok = run_kernel_bench()
    assert ok, payload.get("violations")

    # one-JSON-line conventions shared by every bench arm
    json.loads(json.dumps(payload))  # strictly serializable
    assert payload["metric"] == "kernel_win_ratio_geomean"
    assert payload["unit"] == "x"
    assert payload["ok"] is True and payload["violations"] == []
    assert isinstance(table, str) and "flash" in table

    # a row per kernel arm, each raced against XLA with numerics checked
    kernels = {e["kernel"] for e in payload["entries"]}
    assert kernels == {"flash_attention", "paged_attention",
                       "grouped_matmul", "blocksparse_attention"}
    for e in payload["entries"]:
        assert e["ratio"] == pytest.approx(e["xla_ms"] / e["kernel_ms"],
                                           rel=0.02)
        assert e["numerics_ok"]

    # winning_kernels is exactly the ratio >= 1 subset, sorted — the
    # set bench_diff's no-regression sentinel compares across runs
    wins = sorted(f"{e['kernel']}:{e['bucket']}"
                  for e in payload["entries"] if e["ratio"] >= 1.0)
    assert payload["winning_kernels"] == wins

    # the run persisted a dispatchable table at the record path
    assert payload["table_path"] == str(record)
    doc = json.loads(record.read_text())
    assert doc["_meta"]["schema"] == kernel_table.SCHEMA
    for e in payload["entries"]:
        row = doc["entries"][e["kernel"]][e["bucket"]]
        assert row["ratio"] == pytest.approx(e["ratio"], rel=0.02)
        assert row["backend"] == payload["backend"]


def test_bench_diff_flags_lost_kernel_win(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from bench_diff import diff_reports

    old = {"metric": "kernel_win_ratio_geomean", "unit": "x", "value": 1.8,
           "winning_kernels": ["flash_attention:s2048_d128_causal",
                               "paged_attention:s2048_d128_causal"]}
    good = diff_reports(old, dict(old, value=1.9))
    assert good["ok"], good["violations"]

    lost = diff_reports(
        old, dict(old, winning_kernels=["paged_attention:s2048_d128_causal"]))
    assert not lost["ok"]
    v = next(v for v in lost["violations"]
             if v["metric"] == "winning_kernels")
    assert v["regressed"] == ["flash_attention:s2048_d128_causal"]

"""Overlap-engine reporting surfaces (ISSUE 6 satellites): the
exposed-vs-hidden attribution split, the latency-hiding probe's JSON
schema, the comm-span flight-recorder events, and their chrome-trace
rendering as overlap lanes."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.observability.attribution import (
    RegionCost, attribution_markdown, overlap_split_ms,
    split_exposed_hidden)
from deepspeed_tpu.observability.chrome_trace import chrome_trace_events
from deepspeed_tpu.observability.flight_recorder import (
    FlightRecorder, get_flight_recorder, reset_flight_recorder)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))


# ---------------------------------------------------------------------------
# overlap_split_ms / split_exposed_hidden (the analytic schedule model)
# ---------------------------------------------------------------------------


def test_overlap_split_zero_depth_fully_exposed():
    # k=0 is the measured reality: XLA's default schedule hid none of
    # the host-link traffic (docs/latency_hiding.md)
    s = overlap_split_ms(100.0, stage_ms=10.0, overlap_depth=0, stages=4)
    assert s["hidden_ms"] == 0.0
    assert s["exposed_ms"] == s["total_ms"] == 100.0
    assert s["hidden_frac"] == 0.0


def test_overlap_split_monotone_in_depth():
    prev = -1.0
    for k in range(5):
        s = overlap_split_ms(100.0, stage_ms=10.0, overlap_depth=k,
                             stages=4)
        assert s["hidden_ms"] >= prev
        assert 0.0 <= s["hidden_frac"] <= 1.0
        assert s["hidden_ms"] + s["exposed_ms"] == pytest.approx(
            s["total_ms"])
        prev = s["hidden_ms"]
    # deep enough staging hides everything: per-stage 25ms < 3*10ms
    assert overlap_split_ms(100.0, 10.0, 3, 4)["hidden_frac"] == 1.0


def test_overlap_split_clips_at_compute_window():
    # per-stage transfer 25ms, one stage of compute is 10ms: k=1 hides
    # exactly the window, not the whole transfer
    s = overlap_split_ms(100.0, stage_ms=10.0, overlap_depth=1, stages=4)
    assert s["hidden_ms"] == pytest.approx(40.0)
    assert s["exposed_ms"] == pytest.approx(60.0)


def _regions():
    return [
        RegionCost("attn", 1e12, 1e9, note="t"),
        RegionCost("mlp", 3e12, 2e9, note="t"),
        RegionCost("param_fetch", 0.0, 6.6e9, note="t", overlapped=True),
    ]


def test_split_exposed_hidden_kinds_and_compute_exposure():
    split = split_exposed_hidden(_regions(), peak_tflops=100.0,
                                 hbm_gbps=100.0, fetch_gbps=3.3,
                                 overlap_depth=2, num_layers=2)
    by = {s["region"]: s for s in split}
    assert by["param_fetch"]["kind"] == "dma"
    assert by["attn"]["kind"] == by["mlp"]["kind"] == "compute"
    # compute regions ARE the step: never "hidden"
    for r in ("attn", "mlp"):
        assert by[r]["hidden_ms"] == 0.0
        assert by[r]["exposed_ms"] == by[r]["total_ms"]
    # the dma region's roofline time is bytes over the host link
    assert by["param_fetch"]["total_ms"] == pytest.approx(
        6.6e9 / (3.3 * 1e9) * 1e3)
    assert by["param_fetch"]["hidden_ms"] > 0.0


def test_markdown_gains_split_columns_only_when_asked():
    plain = attribution_markdown(_regions(), 100.0, 100.0)
    assert "exposed ms" not in plain
    wide = attribution_markdown(_regions(), 100.0, 100.0,
                                overlap_depth=2, num_layers=2)
    assert "exposed ms | hidden ms |" in wide
    assert "overlap_depth=2" in wide
    # same row count either way — only columns widen
    assert (len([l for l in plain.splitlines() if l.startswith("|")])
            == len([l for l in wide.splitlines() if l.startswith("|")]))


# ---------------------------------------------------------------------------
# latency_hiding_probe --analytic (JSON CLI schema)
# ---------------------------------------------------------------------------


def test_probe_analytic_schema(capsys, monkeypatch):
    import latency_hiding_probe as probe

    # the analytic split models a chip: name its peaks (v5e)
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "197")
    monkeypatch.setenv("BENCH_HBM_GBPS", "819")
    rc = probe.main(["--analytic", "--layers", "1", "--micro", "1",
                     "--seq", "32", "--vocab", "128",
                     "--overlap-depth", "2"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "latency_hiding_probe/v2"
    assert doc["mode"] == "analytic"
    assert doc["overlap_depth"] == 2
    assert doc["measured"] is None
    names = {r["name"] for r in doc["regions"]}
    assert {"attn", "mlp", "vocab_head", "param_fetch"} <= names
    for r in doc["regions"]:
        assert r["kind"] in ("compute", "dma")
        assert r["total_ms"] == pytest.approx(
            r["hidden_ms"] + r["exposed_ms"], abs=2e-3)
    t = doc["totals"]
    assert t["total_ms"] == pytest.approx(
        t["hidden_ms"] + t["exposed_ms"], abs=2e-3)
    assert 0.0 <= t["hidden_frac"] <= 1.0


# ---------------------------------------------------------------------------
# comm spans → flight recorder → chrome trace overlap lanes
# ---------------------------------------------------------------------------


def test_flight_recorder_span_records_dur_ms():
    rec = FlightRecorder(capacity=8)
    with rec.span("compile", step=3):
        pass
    (ts, kind, fields), = rec.events()
    assert kind == "compile"
    assert fields["step"] == 3
    assert fields["dur_ms"] >= 0.0


def test_traced_collective_lands_span_in_flight_recorder():
    from deepspeed_tpu.comm import comm

    reset_flight_recorder()
    try:
        rec = get_flight_recorder()
        out = jax.vmap(lambda x: comm.all_reduce(x, "i"),
                       axis_name="i")(jnp.ones((4, 2), jnp.float32))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full((4, 2), 4.0, np.float32))
        evs = [(k, f) for _, k, f in rec.events() if k == "collective"]
        assert evs, "traced all_reduce recorded no collective span"
        _, fields = evs[-1]
        assert fields["op"] == "all_reduce"
        assert fields["dur_ms"] >= 0.0
        # per-shard view inside the mapped body: (2,) fp32
        assert fields["bytes"] == 2 * 4
    finally:
        reset_flight_recorder()


def test_chrome_trace_renders_dur_ms_as_spans():
    evs = chrome_trace_events(flight_events=[
        {"ts": 10.0, "kind": "collective", "op": "all_gather",
         "dur_ms": 2.0},
        {"ts": 10.001, "kind": "collective", "op": "reduce_scatter",
         "dur_ms": 1.5},
        {"ts": 10.5, "kind": "offload_sync"},
    ])
    comm_spans = [e for e in evs if e.get("tid") == 3 and e["ph"] == "X"]
    assert len(comm_spans) == 2
    assert comm_spans[0]["name"] == "all_gather"
    assert comm_spans[0]["dur"] == pytest.approx(2000.0)  # us
    # the two dispatches overlap in time — both slices live on the comm
    # lane so Perfetto stacks them (the overlap view the engine is tuned
    # against)
    a, b = comm_spans
    assert a["ts"] < b["ts"] < a["ts"] + a["dur"]
    instants = [e for e in evs if e.get("tid") == 4 and e["ph"] == "i"]
    assert [e["name"] for e in instants] == ["offload_sync"]

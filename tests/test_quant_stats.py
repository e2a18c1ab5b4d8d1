"""Quantization measurement story (observability/quant_stats.py,
attribution.attribute_quant_step, tools/quant_sweep.py,
tools/bench_diff.py): closed-form error math against the RTN bounds,
fail-loud acceptance gates in both directions, the bit-exact
off-switch, hub/Prometheus export, the quant_modes autotuner axis, and
the bench-trajectory diff sentinel (docs/quantized_comm.md "Measuring
the trade")."""

import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from deepspeed_tpu.autotuning.autotuner import (Autotuner,  # noqa: E402
                                                format_quant_mode,
                                                parse_quant_mode)
from deepspeed_tpu.observability import quant_stats as qs  # noqa: E402
from deepspeed_tpu.observability.hub import get_hub, reset_hub  # noqa: E402

from tools import bench_diff, quant_sweep  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_hub():
    reset_hub()
    qs.set_injection(None)
    yield
    qs.set_injection(None)
    reset_hub()


# ---------------------------------------------------------------------------
# closed-form error metrics
# ---------------------------------------------------------------------------

class TestErrorMath:
    def test_snr_db_closed_form(self):
        # ref = 2.0 everywhere, err = +0.01 everywhere:
        # SNR = 10*log10(4 / 1e-4) = 46.0206 dB exactly
        ref = np.full(1024, 2.0, np.float32)
        approx = ref + 0.01
        assert qs.snr_db(ref, approx) == pytest.approx(
            10.0 * math.log10(4.0 / 1e-4), abs=1e-3)

    def test_snr_db_edges(self):
        x = np.ones(8, np.float32)
        assert qs.snr_db(x, x) == float("inf")          # bit-exact
        assert qs.snr_db(np.zeros(8), x) == float("-inf")

    def test_max_rel_error_blockwise(self):
        # two blocks with different amplitudes: the small block's
        # relative error dominates even though its absolute error is
        # smaller — the blockwise max is what RTN bounds
        ref = np.concatenate([np.full(4, 100.0), np.full(4, 1.0)]
                             ).astype(np.float32)
        approx = ref + np.concatenate([np.full(4, 0.5), np.full(4, 0.1)]
                                      ).astype(np.float32)
        assert qs.max_rel_error(ref, approx, block=4) == pytest.approx(
            0.1, rel=1e-5)
        # whole-tensor view dilutes it to 0.5/100
        assert qs.max_rel_error(ref, approx, block=0) == pytest.approx(
            0.005, rel=1e-5)

    @pytest.mark.parametrize("bits,bound", [(8, 0.5 / 127),
                                            (4, 0.5 / 7)])
    def test_rtn_bound_holds(self, bits, bound):
        # symmetric round-to-nearest: |err| <= scale/2 = max|ref|/(2*qmax)
        # per block, so blockwise max_rel_error <= 0.5/qmax exactly
        rng = np.random.default_rng(0)
        x = rng.standard_normal(4096).astype(np.float32)
        deq, s = qs.qdq_blockwise(x, 128, bits=bits)
        assert qs.max_rel_error(x, deq, block=128) <= bound + 1e-6

    def test_zero_block_is_exact_and_clamped(self):
        x = np.zeros(256, np.float32)
        x[128:] = np.linspace(-1, 1, 128)
        deq, s = qs.qdq_blockwise(x, 128, bits=8)
        assert np.array_equal(np.asarray(deq[:128]), x[:128])  # zeros exact
        summ = qs.scale_summary(s)
        assert summ["n_blocks"] == 2
        assert summ["clamped_frac"] == pytest.approx(0.5)

    def test_unblockable_falls_back_to_exact(self):
        x = np.linspace(-1, 1, 7).astype(np.float32)  # gcd(7,128)=1
        deq, s = qs.qdq_blockwise(x, 128, bits=8)
        assert np.array_equal(np.asarray(deq), x)
        assert s.size == 0

    def test_wire_bytes_formula(self):
        # int8 payload + one fp32 scale per block
        assert qs.wire_bytes(1024, 8, 128) == 1024 + 8 * 4
        # int4 packs two elements per byte
        assert qs.wire_bytes(1024, 4, 256) == 512 + 4 * 4
        # block <= 1: exact fp32 fallback path
        assert qs.wire_bytes(1024, 8, 1) == 4096


# ---------------------------------------------------------------------------
# region measurement + fault injection
# ---------------------------------------------------------------------------

class TestRegions:
    def test_measure_region_int8_within_gate(self):
        rng = np.random.default_rng(1)
        t = [rng.standard_normal((64, 128)).astype(np.float32)]
        st = qs.measure_region("qwz_param_fetch", t, block=128, bits=8)
        gate = qs.DEFAULT_GATES["qwz_param_fetch"]
        assert st.snr_db > gate["min_snr_db"]
        assert st.max_rel_err <= gate["max_rel_err"]
        # bf16 logical vs int8+scales wire: (1 + 4/128)/2 per elem
        assert st.compression == pytest.approx(2.0 / (1 + 4 / 128),
                                               rel=1e-6)

    def test_injection_trips_gates(self):
        rng = np.random.default_rng(2)
        t = [rng.standard_normal((64, 128)).astype(np.float32)]
        qs.set_injection("corrupt_scale")
        st = qs.measure_region("qwz_param_fetch", t, block=128, bits=8)
        ok, violations = qs.evaluate_gates([st])
        assert not ok
        assert {v["gate"] for v in violations} >= {"max_rel_err"}

    def test_injection_validation(self):
        with pytest.raises(ValueError):
            qs.set_injection("flip_bits")
        assert qs.injection_from_env({"BENCH_QUANT_INJECT":
                                      "corrupt_scale"}) == "corrupt_scale"
        assert qs.injection_from_env({"DSTPU_QUANT_CHAOS":
                                      "corrupt_scale"}) == "corrupt_scale"
        assert qs.injection_from_env({}) is None

    def test_grad_reduce_two_level(self):
        rng = np.random.default_rng(3)
        groups = [{"w": rng.standard_normal((16, 256)).astype(np.float32)}
                  for _ in range(4)]
        st = qs.measure_grad_reduce(groups)
        gate = qs.DEFAULT_GATES["qgz_grad_reduce"]
        assert st.snr_db > gate["min_snr_db"]
        assert st.max_rel_err <= gate["max_rel_err"]
        assert "int4 second level" in st.note
        # wire: 4 int8 group payloads + one int4 partial
        n = 16 * 256
        assert st.wire_bytes == (4 * qs.wire_bytes(n, 8, 256)
                                 + qs.wire_bytes(n, 4, 256))
        assert st.logical_bytes == 4 * n * 4

    def test_hpz_row_is_bit_exact(self):
        st = qs.hpz_partition_stats(1000, 8)
        assert st.bit_exact and st.snr_db is None
        assert st.max_rel_err == 0.0
        ok, _ = qs.evaluate_gates([st])
        assert ok

    def test_gates_fail_on_non_bit_exact_hpz(self):
        st = qs.hpz_partition_stats(1000, 8)
        st.bit_exact = False
        ok, violations = qs.evaluate_gates([st])
        assert not ok and violations[0]["gate"] == "bit_exact"

    def test_gates_both_directions(self):
        good = qs.QuantRegionStats(
            region="qwz_param_fetch", snr_db=40.0, max_rel_err=0.003,
            logical_bytes=100, wire_bytes=52, n_elements=50, bits=8,
            block=128)
        bad = qs.QuantRegionStats(
            region="qwz_param_fetch", snr_db=20.0, max_rel_err=0.3,
            logical_bytes=100, wire_bytes=52, n_elements=50, bits=8,
            block=128)
        ok, v = qs.evaluate_gates([good])
        assert ok and not v
        ok, v = qs.evaluate_gates([bad])
        assert not ok
        assert {x["gate"] for x in v} == {"min_snr_db", "max_rel_err"}
        # ungated regions pass; gated-but-absent regions are not
        # violations (the path may be off this run)
        import dataclasses

        ok, _ = qs.evaluate_gates([dataclasses.replace(good,
                                                       region="other")])
        assert ok
        ok, _ = qs.evaluate_gates([])
        assert ok


# ---------------------------------------------------------------------------
# export: hub gauges, Prometheus, JSONL event, flight-recorder context
# ---------------------------------------------------------------------------

class TestExport:
    def _stats(self):
        rng = np.random.default_rng(4)
        t = [rng.standard_normal((32, 128)).astype(np.float32)]
        return [qs.measure_region("qwz_param_fetch", t, block=128),
                qs.hpz_partition_stats(4096, 8)]

    def test_publish_hub_and_prometheus(self):
        qs.publish(self._stats(), step=7)
        prom = get_hub().to_prometheus()
        assert "dstpu_quant_qwz_param_fetch_snr_db" in prom
        assert "dstpu_quant_qwz_param_fetch_max_rel_err" in prom
        assert "dstpu_quant_qwz_param_fetch_compression" in prom
        assert "dstpu_quant_qwz_param_fetch_wire_bytes" in prom
        snap = qs.last_snapshot()
        assert snap["step"] == 7
        assert [r["region"] for r in snap["regions"]] == [
            "qwz_param_fetch", "hpz_partition"]

    def test_publish_jsonl_event(self, tmp_path):
        import types

        p = str(tmp_path / "m.jsonl")
        hub = get_hub()
        hub.configure(types.SimpleNamespace(jsonl_path=p,
                                            prometheus_path=None))
        qs.publish(self._stats(), hub=hub, step=3)
        hub.close()
        rows = [json.loads(l) for l in open(p)]
        ev = [r for r in rows if r.get("kind") == "quant_stats"]
        assert ev and ev[-1]["regions"][0]["region"] == "qwz_param_fetch"

    def test_flight_recorder_dump_context(self):
        from deepspeed_tpu.observability.flight_recorder import \
            get_flight_recorder

        qs.publish(self._stats(), step=11)
        ctx = get_flight_recorder()._dump_context  # registered once
        assert "quant_stats" in ctx
        assert ctx["quant_stats"]()["step"] == 11


# ---------------------------------------------------------------------------
# attribution: wire-bit model + link flips
# ---------------------------------------------------------------------------

class TestAttribution:
    @pytest.fixture(scope="class")
    def cfg(self):
        import dataclasses

        from deepspeed_tpu.models.zoo import get_model

        m = get_model("llama3-8b", max_seq_len=2048)
        return dataclasses.replace(m.config, num_layers=2,
                                   vocab_size=8192)

    def test_wire_ratios_closed_form(self):
        from deepspeed_tpu.observability.attribution import _wire_ratio

        assert _wire_ratio(8, 128, 2.0) == pytest.approx(0.515625)
        assert _wire_ratio(8, 256, 4.0) == pytest.approx(0.25390625)
        assert _wire_ratio(4, 256, 4.0) == pytest.approx(0.12890625)

    def test_qwz_shrinks_fetch_wire(self, cfg):
        from deepspeed_tpu.observability.attribution import \
            attribute_quant_step

        off = attribute_quant_step(cfg, qwz=False, n_chips=16,
                                   slice_size=8)
        on = attribute_quant_step(cfg, qwz=True, n_chips=16,
                                  slice_size=8)
        ratio = on[0].bytes_accessed / off[0].bytes_accessed
        assert ratio == pytest.approx(0.515625, rel=1e-6)

    def test_hpz_flips_fetch_link(self, cfg):
        from deepspeed_tpu.observability.attribution import \
            attribute_quant_step

        # 16 chips in slices of 8: full-group gather rides DCN; hpZ
        # k=8 keeps it intra-slice on ICI (and adds a dp level to the
        # reduction)
        base = attribute_quant_step(cfg, hpz=1, n_chips=16, slice_size=8)
        hpz = attribute_quant_step(cfg, hpz=8, n_chips=16, slice_size=8)
        assert base[0].link == "dcn" and hpz[0].link == "ici"
        assert hpz[0].gbps > base[0].gbps
        assert base[1].link == "dcn" and hpz[1].link == "ici+dcn"

    def test_qgz_shrinks_reduce_wire(self, cfg):
        from deepspeed_tpu.observability.attribution import \
            attribute_quant_step

        off = attribute_quant_step(cfg, qgz=False, n_chips=16,
                                   slice_size=8)
        on = attribute_quant_step(cfg, qgz=True, n_chips=16,
                                  slice_size=8)
        ratio = on[1].bytes_accessed / off[1].bytes_accessed
        assert ratio == pytest.approx(0.25390625, rel=1e-6)


# ---------------------------------------------------------------------------
# quant-mode grammar + autotuner axis
# ---------------------------------------------------------------------------

class TestQuantModes:
    @pytest.mark.parametrize("mode,expect", [
        ("off", (False, False, 1, False)),
        ("", (False, False, 1, False)),
        ("qwz", (True, False, 1, False)),
        ("qgz", (False, True, 1, False)),
        ("qar", (False, False, 1, True)),
        ("qwz+qgz", (True, True, 1, False)),
        ("qwz+qar", (True, False, 1, True)),
        ("qwz+qgz+hpz8", (True, True, 8, False)),
        ("hpz16", (False, False, 16, False)),
    ])
    def test_parse_roundtrip(self, mode, expect):
        out = parse_quant_mode(mode)
        qwz, qgz, hpz, qar = expect
        assert out == {"zero_quantized_weights": qwz,
                       "zero_quantized_gradients": qgz,
                       "zero_quantized_allreduce": qar,
                       "zero_hpz_partition_size": hpz}
        if mode not in ("",):
            assert parse_quant_mode(
                format_quant_mode(qwz, qgz, hpz, qar)) == out

    @pytest.mark.parametrize("bad", ["int8", "qwz+int4", "hpzx", "hpz",
                                     "qgz+qar"])
    def test_parse_rejects_junk(self, bad):
        with pytest.raises(ValueError):
            parse_quant_mode(bad)

    def test_autotuner_axis_expands_flags(self, tmp_path):
        from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                      TransformerLM)

        tiny = TransformerConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=32, pos_emb="learned", norm="layernorm",
            activation="gelu", tie_embeddings=True, remat=False)
        t = Autotuner(
            model_factory=lambda: TransformerLM(tiny),
            base_config={"optimizer": {"type": "adamw",
                                       "params": {"lr": 1e-3}}},
            batch_fn=lambda gb: {},
            tuning_space={"micro_batch_sizes": [1], "zero_stages": [3],
                          "quant_modes": ["off", "qwz+qgz+hpz8"]},
            hbm_budget_bytes=16 * 2**30, results_dir=str(tmp_path))
        cands = t.candidates()
        assert len(cands) == 2
        by_mode = {c["_quant_mode"]: c for c in cands}
        zo = by_mode["qwz+qgz+hpz8"]["zero_optimization"]
        assert zo["zero_quantized_weights"] is True
        assert zo["zero_quantized_gradients"] is True
        assert zo["zero_hpz_partition_size"] == 8
        zo_off = by_mode["off"]["zero_optimization"]
        assert zo_off["zero_quantized_weights"] is False
        # tuned_defaults surfaces the public knob name the bench reads
        pub = Autotuner.tuned_defaults(by_mode["qwz+qgz+hpz8"])
        assert pub["quant_mode"] == "qwz+qgz+hpz8"
        assert "_quant_mode" not in pub


# ---------------------------------------------------------------------------
# quant_sweep: knob grid + persisted winner
# ---------------------------------------------------------------------------

class TestQuantSweep:
    @pytest.fixture(scope="class")
    def payload(self):
        import dataclasses

        from deepspeed_tpu.models.zoo import get_model

        m = get_model("llama3-8b", max_seq_len=2048)
        cfg = dataclasses.replace(m.config, num_layers=2,
                                  vocab_size=8192)
        return quant_sweep.build_sweep(
            cfg, n_chips=16, slice_size=8, hpz_list=[1, 8], micro=4,
            seq=2048, peak_tflops=100.0, overlap_depth=4)

    def test_schema_and_grid(self, payload):
        assert payload["schema"] == "quant_sweep/v1"
        assert len(payload["rows"]) == 2 * 2 * 2  # qwz x qgz x hpz
        assert payload["rows"][0]["mode"] == "off"
        assert payload["rows"][0]["wire_vs_off"] == 1.0
        assert payload["rows"][0]["exposed_vs_off"] == 1.0
        modes = {r["mode"] for r in payload["rows"]}
        assert "qwz+qgz+hpz8" in modes
        for row in payload["rows"]:
            assert set(row["regions"]) == {"param_fetch", "grad_reduce"}

    def test_quantized_modes_beat_off(self, payload):
        by_mode = {r["mode"]: r for r in payload["rows"]}
        full = by_mode["qwz+qgz+hpz8"]
        assert full["wire_vs_off"] < 0.6
        assert full["exposed_vs_off"] < 1.0
        assert payload["winner"]["mode"] in by_mode
        # markdown embeds every mode row
        md = quant_sweep.sweep_markdown(payload)
        for mode in by_mode:
            assert f"| {mode}" in md

    def test_persist_winner(self, payload, tmp_path):
        path = str(tmp_path / "real_shape.json")
        tuned = quant_sweep.persist_winner(payload, path)
        on_disk = json.load(open(path))
        assert on_disk == tuned
        mode = payload["winner"]["mode"]
        assert on_disk["quant_mode"] == mode
        assert on_disk["zero_optimization"] == parse_quant_mode(mode)
        # creating the file seeds the measured bench defaults so the
        # persisted choice never shifts an untuned knob
        assert on_disk["train_micro_batch_size_per_chip"] == 4
        assert on_disk["_quant_sweep"]["schema"] == "quant_sweep/v1"

    def test_persist_preserves_existing_keys(self, payload, tmp_path):
        path = str(tmp_path / "tuned.json")
        with open(path, "w") as f:
            json.dump({"train_micro_batch_size_per_chip": 2,
                       "remat_policy": "save_attn_out"}, f)
        quant_sweep.persist_winner(payload, path)
        on_disk = json.load(open(path))
        assert on_disk["train_micro_batch_size_per_chip"] == 2
        assert on_disk["remat_policy"] == "save_attn_out"
        assert on_disk["quant_mode"] == payload["winner"]["mode"]

    def test_cli_json(self, capsys, tmp_path):
        rc = quant_sweep.main(["--layers", "1", "--vocab", "4096",
                               "--chips", "16", "--slice", "8",
                               "--hpz", "1", "8",
                               "--peak-tflops", "100", "--json",
                               "--persist",
                               str(tmp_path / "rs.json")])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["schema"] == "quant_sweep/v1"
        assert out["persisted"]["quant_mode"] == out["winner"]["mode"]
        assert os.path.exists(tmp_path / "rs.json")


# ---------------------------------------------------------------------------
# bench_diff: fail-loud trajectory sentinel
# ---------------------------------------------------------------------------

def _parsed(value=100.0, unit="tokens/s/chip", **kw):
    d = {"metric": "m", "value": value, "unit": unit}
    d.update(kw)
    return d


class TestBenchDiff:
    def test_throughput_drop_fails(self):
        r = bench_diff.diff_reports(_parsed(100.0), _parsed(80.0))
        assert not r["ok"]
        assert r["violations"][0]["metric"] == "value"

    def test_throughput_within_threshold_passes(self):
        r = bench_diff.diff_reports(_parsed(100.0), _parsed(90.0))
        assert r["ok"] and r["comparable"]

    def test_ms_unit_is_lower_better(self):
        # latency growing 30% fails; shrinking passes
        r = bench_diff.diff_reports(_parsed(100.0, unit="ms"),
                                    _parsed(130.0, unit="ms"))
        assert not r["ok"]
        r = bench_diff.diff_reports(_parsed(100.0, unit="ms"),
                                    _parsed(70.0, unit="ms"))
        assert r["ok"]

    def test_mfu_and_overlap_regressions(self):
        r = bench_diff.diff_reports(_parsed(mfu=0.5),
                                    _parsed(mfu=0.3))
        assert any(v["metric"] == "mfu" for v in r["violations"])
        r = bench_diff.diff_reports(_parsed(hidden_comm_frac=0.9),
                                    _parsed(hidden_comm_frac=0.5))
        assert any(v["metric"] == "hidden_comm_frac"
                   for v in r["violations"])

    def test_contended_rounds_loosen(self):
        # 20% drop fails clean but passes when the round was contended
        r = bench_diff.diff_reports(_parsed(100.0), _parsed(80.0))
        assert not r["ok"]
        r = bench_diff.diff_reports(_parsed(100.0),
                                    _parsed(80.0, contended=True))
        assert r["ok"]

    def test_incomparable_rounds(self):
        old = _parsed(100.0, unit="tokens/s/chip")
        new = _parsed(5.0, unit="ms")
        r = bench_diff.diff_reports(old, new)
        assert not r["comparable"] and r["ok"]
        r = bench_diff.diff_reports(old, new, strict=True)
        assert not r["ok"]
        assert r["violations"][0]["metric"] == "metric_identity"

    def test_quant_gates_ride_the_diff(self):
        ok_payload = _parsed(0, unit="gate violations", ok=True,
                             violations=[])
        bad = _parsed(2, unit="gate violations", ok=False,
                      violations=[{"region": "qwz_param_fetch"},
                                  {"region": "fp8_mlp"}])
        r = bench_diff.diff_reports(ok_payload, bad)
        assert not r["ok"]
        assert any(v["metric"] == "quant_gates" for v in r["violations"])
        r = bench_diff.diff_reports(ok_payload, ok_payload)
        assert r["ok"]

    def test_load_rounds_and_cli(self, tmp_path, capsys):
        for n, val in ((3, 100.0), (4, 101.0), (5, 99.0)):
            with open(tmp_path / f"BENCH_r{n:02d}.json", "w") as f:
                json.dump({"n": n, "rc": 0, "parsed": _parsed(val)}, f)
        rounds = bench_diff.load_rounds(str(tmp_path))
        assert [r[0] for r in rounds] == [3, 4, 5]
        rc = bench_diff.main(["--root", str(tmp_path), "--json"])
        assert rc == 0  # 99 vs 101 is within 0.85
        out = json.loads(capsys.readouterr().out)
        assert out["old"] == "BENCH_r04.json"
        assert out["new"] == "BENCH_r05.json"
        # a collapsed newest round fails the CLI
        with open(tmp_path / "BENCH_r06.json", "w") as f:
            json.dump({"n": 6, "rc": 0, "parsed": _parsed(10.0)}, f)
        assert bench_diff.main(["--root", str(tmp_path)]) == 1
        capsys.readouterr()

    @pytest.mark.parametrize("n_rounds,says", [
        (0, "no BENCH_r*.json under"), (1, "only BENCH_r01.json found")])
    def test_fewer_than_two_rounds_is_a_noop(self, tmp_path, capsys,
                                             n_rounds, says):
        """No records (the state of the repo's root) or a single one:
        a clear note and exit 0, never a traceback."""
        for n in range(1, n_rounds + 1):
            with open(tmp_path / f"BENCH_r{n:02d}.json", "w") as f:
                json.dump({"n": n, "rc": 0, "parsed": _parsed()}, f)
        assert bench_diff.main(["--root", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert says in out and "nothing to diff" in out


# ---------------------------------------------------------------------------
# bench defaults + the BENCH_QUANT arm
# ---------------------------------------------------------------------------

SMALL_BENCH_ENV = {
    "BENCH_QUANT_SKIP_EXACT": "1", "BENCH_LAYERS": "1",
    "BENCH_HIDDEN": "64", "BENCH_VOCAB": "256", "BENCH_SEQ": "32",
    "BENCH_QUANT_GROUPS": "3",
}


class TestBenchArm:
    def test_quant_mode_resolution(self, monkeypatch, tmp_path):
        from bench import resolve_bench_defaults

        absent = str(tmp_path / "absent.json")
        monkeypatch.setenv("BENCH_TUNED_DEFAULTS", absent)
        assert resolve_bench_defaults(env={}, on_tpu=True)[
            "quant_mode"] == "off"
        # tuned file supplies it (the quant_modes axis / quant_sweep
        # --persist write this key)
        tuned = str(tmp_path / "real_shape.json")
        with open(tuned, "w") as f:
            json.dump({"quant_mode": "qwz+qgz+hpz8"}, f)
        monkeypatch.setenv("BENCH_TUNED_DEFAULTS", tuned)
        assert resolve_bench_defaults(env={}, on_tpu=True)[
            "quant_mode"] == "qwz+qgz+hpz8"
        # env beats the tuned file
        assert resolve_bench_defaults(
            env={"BENCH_QUANT_MODE": "qwz"}, on_tpu=True)[
            "quant_mode"] == "qwz"

    def test_run_quant_bench_passes_clean(self):
        md, payload, ok = qs.run_quant_bench(dict(SMALL_BENCH_ENV))
        assert ok
        assert payload["value"] == 0 and payload["unit"] == \
            "gate violations"
        assert payload["injection"] is None
        regions = {r["region"] for r in payload["regions"]}
        assert regions == {"qwz_param_fetch", "qgz_grad_reduce",
                           "fp8_mlp", "hpz_partition",
                           "kv_cache", "kv_wire", "qar"}
        assert "PASS" in md and "FAIL" not in md
        # metrics landed on the hub for the sinks to export
        assert "dstpu_quant_qgz_grad_reduce_snr_db" in \
            get_hub().to_prometheus()

    def test_run_quant_bench_fails_under_injection(self):
        env = dict(SMALL_BENCH_ENV, BENCH_QUANT_INJECT="corrupt_scale")
        md, payload, ok = qs.run_quant_bench(env)
        assert not ok
        assert payload["value"] >= 1
        assert payload["injection"] == "corrupt_scale"
        assert "FAIL" in md
        # injection is always disarmed afterwards
        assert qs._INJECT is None

    def test_bench_main_exits_nonzero_on_violation(self, monkeypatch,
                                                   capsys):
        import bench

        for k, v in dict(SMALL_BENCH_ENV, BENCH_QUANT="1",
                         BENCH_QUANT_INJECT="corrupt_scale").items():
            monkeypatch.setenv(k, v)
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code == 1
        out = capsys.readouterr().out
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        payload = json.loads(line)
        assert payload["ok"] is False and payload["violations"]


class TestOffSwitch:
    def test_all_knobs_off_is_bit_exact(self, devices):
        # an explicit-off zero_optimization block must be bitwise
        # identical to one that never mentions the ZeRO++ knobs —
        # losses and final params compared exactly
        assert qs.off_switch_bitexact(steps=2) is True


# ---------------------------------------------------------------------------
# warn-once when quantization runs unmeasured
# ---------------------------------------------------------------------------

class TestWarnOnce:
    def _tiny_engine(self, monkeypatch, quant_stats_on):
        import deepspeed_tpu as dstpu
        from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                      TransformerLM)

        if quant_stats_on:
            monkeypatch.setenv("DSTPU_QUANT_STATS", "1")
        else:
            monkeypatch.delenv("DSTPU_QUANT_STATS", raising=False)
        tiny = TransformerConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=32, pos_emb="learned", norm="layernorm",
            activation="gelu", tie_embeddings=True, remat=False)
        engine, *_ = dstpu.initialize(model=TransformerLM(tiny), config={
            "train_micro_batch_size_per_chip": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2,
                                  "zero_quantized_gradients": True},
            "steps_per_print": 1_000_000,
        })
        return engine

    @pytest.fixture()
    def log_lines(self):
        # the dstpu logger writes through its own handler whose stream
        # predates pytest's capture, so capsys/capfd/caplog all miss
        # it — attach a recording handler to the real logger instead
        import logging as _logging

        from deepspeed_tpu.utils import logging as dlog

        records = []

        class _Rec(_logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        h = _Rec()
        dlog.logger.addHandler(h)
        yield records
        dlog.logger.removeHandler(h)

    def test_warns_when_unmeasured(self, monkeypatch, log_lines,
                                   devices):
        from deepspeed_tpu.utils import logging as dlog

        # warning_once dedups globally; clear so this test is
        # order-independent within the suite
        dlog._seen_warnings.clear()
        self._tiny_engine(monkeypatch, quant_stats_on=False)
        assert any("no quant.* collection is configured" in m
                   for m in log_lines)
        # ... and only once per process
        log_lines.clear()
        self._tiny_engine(monkeypatch, quant_stats_on=False)
        assert not any("no quant.* collection" in m for m in log_lines)

    def test_collector_installs_when_configured(self, monkeypatch,
                                                log_lines, devices):
        from deepspeed_tpu.utils import logging as dlog

        dlog._seen_warnings.clear()
        self._tiny_engine(monkeypatch, quant_stats_on=True)
        assert not any("no quant.* collection" in m for m in log_lines)
        # the init-time param-side sample landed as quant.* metrics
        assert "dstpu_quant_qwz_param_fetch_snr_db" in \
            get_hub().to_prometheus()
        snap = qs.last_snapshot()
        assert snap["regions"][0]["region"] == "qwz_param_fetch"

"""``gather_kv_ms`` (and its dotted names) on a hand-made timeline: device
time under the ``kv_gather`` scope, an execution of the gather program."""

import os

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import program_trace as P
from benchmarks.harness import trace as T

BODY = "jit(dstpu_serve_gather)/while/body/closed_call/"
OP_NAMES = {
    "while.1": "jit(dstpu_serve_gather)/while",
    "fusion.1": BODY + "kv_write/scatter",
    "fusion.2": BODY + "kv_gather/gather",
    "fusion.3": BODY + "kv_gather/gather",
    "fusion.4": BODY + "attn/bskgd,bmkd->bkgsm/dot_general",
    "fusion.5": BODY + "mlp/dot_general",
    "fusion.6": "jit(dstpu_serve_gather)/head/dot_general",
}


def ev(name, start, dur):
    return (f"%{name} = bf16[8,128]{{1,0}} fusion(bf16[8,128] %p.1)", start,
            dur)


def gather_program(t):
    """0.2 s: the layers' while (two takes of 0.03 and 0.05 under
    ``kv_gather``, a copy with no name of its own, attention, MLP), then
    the head."""
    return [ev("while.1", t, 0.18), ev("fusion.1", t, 0.01),
            ev("fusion.2", t + 0.01, 0.03), ev("fusion.3", t + 0.04, 0.05),
            ev("copy.7", t + 0.09, 0.02), ev("fusion.4", t + 0.11, 0.04),
            ev("fusion.5", t + 0.15, 0.03), ev("fusion.6", t + 0.18, 0.02)]


MODULES = [("jit_dstpu_serve_gather(1)", 0.0, 0.2),
           ("jit_dstpu_serve_multi_decode(2)", 0.3, 0.1),
           ("jit_dstpu_serve_gather(1)", 0.5, 0.2),
           ("jit_dstpu_serve_gather(1)", 0.9, 0.2)]      # leaves the window


class Ctx:
    config, bench_dir = {"kind": "serve"}, mf.BENCH_DIR

    def note(self, obj):
        pass


def trace(scopes):
    ops = gather_program(0.0) + [ev("fusion.9", 0.3, 0.1)] \
        + gather_program(0.5) + gather_program(0.9)
    return P.ProgramTrace(T.Trace({0: ops}, [], -0.1, 1.0, {0: MODULES}), [],
                          scopes)


@pytest.mark.parametrize("metric", ["gather_kv_ms", "gather_kv_ms.gen"])
def test_gather_kv_reads_the_scope_per_execution(monkeypatch, metric):
    reader = mf.load_module("layer_metrics", metric)
    monkeypatch.setattr(P, "open_run",
                        lambda ctx, result: trace({P.SERVE_GATHER: OP_NAMES}))
    # two executions inside the window, 0.08 s under the scope in each
    assert reader.read(Ctx(), {}) == pytest.approx(80.0)


@pytest.mark.parametrize("scopes", [
    {}, {P.SERVE_GATHER: {k: v.replace("kv_gather/", "")
                          for k, v in OP_NAMES.items()}}],
    ids=["no_scopes_in_the_profile", "a_program_without_the_scope"])
def test_gather_kv_reads_nothing_from_a_program_without_the_scope(
        monkeypatch, scopes):
    reader = mf.load_module("layer_metrics", "gather_kv_ms.gen")
    monkeypatch.setattr(P, "open_run", lambda ctx, result: trace(scopes))
    assert reader.read(Ctx(), {}) is None
    monkeypatch.setattr(P, "open_run", lambda ctx, result: None)
    assert reader.read(Ctx(), {}) is None


GONE = ["gather_step_ms.burst", "gather_kv_ms.burst", "prefill_kernel_share"]


@pytest.mark.parametrize("name", ["gather_kv_ms.gen", "gather_step_ms.gen",
                                  "gather_token_share"])
def test_the_manifest_lists_the_gather_metrics_in_the_cell_that_runs_the_program(
        name):
    """Since PR 38 no step of a dense model runs the gather program: its
    metrics list the one cell whose runner still does, and the entries
    that had no such cell left (PR 40) are gone with their readers."""
    manifest = mf.load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry = by_name[name]
    assert entry["layer"] == "serve step programs"
    assert entry["workloads"] == ["serve-qnext-gen-closed"]
    assert os.path.isfile(os.path.join(mf.BENCH_DIR, "layer_metrics",
                                       name + ".py"))
    for gone in GONE:
        assert gone not in by_name
        assert not os.path.exists(os.path.join(mf.BENCH_DIR, "layer_metrics",
                                               gone + ".py"))

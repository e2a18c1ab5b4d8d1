"""``kv_pool_copy_ms`` on a hand-made timeline: device time of the leaf
operations that *put out* the KV pool or one layer's slice of it, inside
the decode programs, per decode token step. Event names as a v5e trace
gives them (my chip run, PR 24)."""

import json
import types

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import program_trace as P
from benchmarks.harness import trace as T

READER = mf.load_module("layer_metrics", "kv_pool_copy_ms")

POOL = "bf16[16,2080,16,2,8,128]{5,4,3,2,1,0:T(8,128)(2,1)}"
SLICE = "bf16[2080,16,2,8,128]{4,3,2,1,0:T(8,128)(2,1)}"
ARCH = types.SimpleNamespace(num_hidden_layers=16, num_key_value_heads=8,
                             head_dim=128)
ENGINE = {"kv_blocks": 2080, "kv_block_size": 16}


def ev(name, out, opcode, start, dur, operands="%p.1"):
    return (f"%{name} = {out} {opcode}({operands})", start, dur)


# one burst of 8 token steps (0.1-0.5) and one single decode step (0.6-0.7)
# inside the window, a gather step (0.75-0.95) that copies the pool too and
# is not a decode program, and a burst that leaves the window
MODULES = [("jit_dstpu_serve_multi_decode(1)", 0.1, 0.4),
           ("jit_dstpu_pick_greedy(2)", 0.55, 0.001),
           ("jit_dstpu_serve_decode(3)", 0.6, 0.1),
           ("jit_dstpu_serve_gather(4)", 0.75, 0.2),
           ("jit_dstpu_serve_multi_decode(1)", 0.98, 0.4)]
OPS = [
    # the layer scan: a while that carries the pool is not a leaf
    ev("while.3", f"(s32[], {POOL}, bf16[32,4096])", "while", 0.1, 0.38),
    ev("dynamic-slice_bitcast_fusion.2", SLICE, "fusion", 0.10, 0.06,
       f"{POOL} %get-tuple-element.9"),
    # reads the slice, puts out attention rows: not counted
    ev("paged_decode.6", "bf16[32,8,8,128]{3,2,1,0}", "custom-call", 0.16,
       0.08, f"{SLICE} %fusion.7"),
    # a scatter fused with something else: the pool is one of two outputs
    ev("fusion.9", f"({POOL}, f32[32]{{0}})", "fusion", 0.24, 0.01),
    ev("bitcast_dynamic-update-slice_fusion.2", POOL, "fusion", 0.25, 0.07),
    ev("fusion.145", "bf16[32,14336]{1,0}", "fusion", 0.32, 0.1),
    ev("copy.74", POOL, "copy", 0.42, 0.06),
    # the single decode step
    ev("copy.75", POOL, "copy", 0.60, 0.03),
    ev("fusion.146", "bf16[32,14336]{1,0}", "fusion", 0.63, 0.05),
    # the gather program's copy, and one outside the window
    ev("copy.80", POOL, "copy", 0.75, 0.07),
    ev("copy.74", POOL, "copy", 1.0, 0.06),
]
STEPS = [{"decode_kernel_steps": 8}, {"decode_kernel_steps": 1},
         {"decode_kernel_steps": 0}]


class Ctx:
    def __init__(self):
        self.config, self.notes = {"kind": "serve", "engine": ENGINE}, []

    def note(self, obj):
        self.notes.append(obj)


def result(steps=STEPS):
    return {"facts": {"arch": ARCH, "traced_steps": (0, len(steps))},
            "served": types.SimpleNamespace(steps=steps)}


def program_trace(ops=OPS, modules=MODULES):
    return P.ProgramTrace(T.Trace({0: ops}, [], 0.0, 1.0, {0: modules}),
                          [], {})


def test_output_dims_reads_every_output_and_no_operand():
    assert READER.output_dims(OPS[0][0]) == ["", "16,2080,16,2,8,128",
                                             "32,4096"]
    assert READER.output_dims(OPS[2][0]) == ["32,8,8,128"]
    assert READER.output_dims(OPS[3][0]) == ["16,2080,16,2,8,128", "32"]
    assert READER.output_dims("not an instruction") == []


def test_pool_sized_outputs_inside_decode_programs_per_token_step(monkeypatch):
    monkeypatch.setattr(P, "open_run", lambda ctx, res: program_trace())
    ctx = Ctx()
    # 0.06 + 0.01 + 0.07 + 0.06 in the burst, 0.03 in the single step
    assert READER.read(ctx, result()) == pytest.approx(1e3 * 0.23 / 9)
    note = ctx.notes[0]["kv_pool_copy_ms"]
    assert note["token_steps"] == 9 and note["program_executions"] == 2
    by = note["ms_per_token_step_by_op"]
    assert by["copy.74 copy bf16[16,2080,16,2,8,128]"] \
        == pytest.approx(1e3 * 0.06 / 9)
    assert not any("paged_decode" in k or "while" in k for k in by)


def test_a_pool_kept_in_place_reads_the_scatter_alone(monkeypatch):
    ops = [ev("while.3", f"(s32[], {POOL})", "while", 0.1, 0.38),
           ev("fusion.124", POOL, "fusion", 0.10, 0.0004),
           ev("paged_decode.6", "bf16[32,8,8,128]{3,2,1,0}", "custom-call",
              0.11, 0.08, f"s32[32,64] %a, s32[1] %l, {POOL} %fusion.124")]
    monkeypatch.setattr(P, "open_run",
                        lambda ctx, res: program_trace(ops=ops))
    assert READER.read(Ctx(), result()) == pytest.approx(0.4 / 9)


def test_nothing_to_read_is_none_not_an_error(monkeypatch, tmp_path):
    class C(Ctx):
        trace_dir = str(tmp_path)

    # off a TPU: no trace, or one without device operations
    assert READER.read(C(), dict(result(), trace=None)) is None
    assert READER.read(C(), dict(result(),
                                 trace=T.Trace({}, [], 0.0, 1.0))) is None
    # a program that does not name its modules
    unnamed = [("jit__unknown(1)", s, d) for _, s, d in MODULES]
    monkeypatch.setattr(P, "open_run",
                        lambda ctx, res: program_trace(modules=unnamed))
    assert READER.read(Ctx(), result()) is None
    # a traced slice without a decode step
    monkeypatch.setattr(P, "open_run", lambda ctx, res: program_trace())
    assert READER.read(Ctx(), result([{"decode_kernel_steps": 0}])) is None


def test_the_manifest_entry():
    entry = [m for m in mf.load_manifest()["per_layer"]
             if m["name"] == "kv_pool_copy_ms"]
    assert entry == [json.loads(
        '{"name": "kv_pool_copy_ms", "unit": "ms", "better": "lower", '
        '"source": "device_trace", "layer": "serve step programs", '
        '"moves": "serve_tokens_per_s", "workloads": ["serve-gen-closed"]}')]

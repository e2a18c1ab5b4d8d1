"""tools/collective_table.py and tools/hlo_schedule.py on a timeline and a
program made by hand: every rule of the two readers pinned without a chip."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import collective_table as C  # noqa: E402
import hlo_schedule as H  # noqa: E402


def _iteration(t):
    return [
        ("%all-gather.3 = bf16[4096,8,128]{2,1,0} all-gather(%p), dims", t, 1.0),
        ("%fusion.2 = bf16[8,8] fusion(%a), kind=kOutput", t + 1.0, 3.0),
        ("%async-collective-done.1 = bf16[1,4096]{1,0} fusion(%x)", t + 4.0, .5),
        ("%flash_fwd.2 = (bf16[1]) custom-call(%q), "
         "custom_call_target=\"tpu_custom_call\"", t + 4.5, 0.5),
    ]


EVENTS = ([("%while.1 = (s32[]) while(%t), body=%b", 0.0, 10.0)]
          + _iteration(0.0) + _iteration(5.0)
          + [("%all-reduce.9 = f32[] all-reduce(%g)", 11.0, 0.25)])
SCOPES = {"while.1": "jit(f)/forward_backward/transpose(jvp(while))",
          "all-reduce.9": "jit(f)/optimizer/reduce_sum"}
RUNS = [(0.0, 12.0)]


@pytest.mark.parametrize("name,kind,counted", [
    ("%all-gather.3 = bf16[8] all-gather(%p)", "all-gather", True),
    ("%collective-permute-done.11 = bf16[8] collective-permute-done(%s)",
     "collective-permute-done", True),
    ("%async-collective-start.4 = (bf16[8]) fusion(%p)",
     "async-collective-start", False),
    ("%async-collective-done = bf16[8] fusion(%p)",
     "async-collective-done", False),
    ("%fusion.2 = bf16[8,8] fusion(%a)", "", False),
])
def test_kind_of_an_event(name, kind, counted):
    assert C.kind_of(name) == (kind, counted)


def test_one_row_an_instruction_with_its_part_and_loop():
    rows, totals = C.rows_of(EVENTS, SCOPES, RUNS)
    by = {r["instruction"]: r for r in rows}
    assert by["all-gather.3"]["calls"] == 2
    assert by["all-gather.3"]["seconds"] == 2.0
    assert by["all-gather.3"]["region"] == "bwd"        # the loop's part
    assert by["all-gather.3"]["loop"] == "while.1"
    assert by["all-gather.3"]["result"] == "bf16[4096,8,128]"
    assert not by["async-collective-done.1"]["counted"]
    assert by["all-reduce.9"]["region"] == "opt"
    assert by["all-reduce.9"]["loop"] == "-"
    assert totals["kernel"] == 1.0 and totals["other compute"] == 6.0


def test_a_loop_by_what_its_time_is_made_of():
    (loop,) = C.loops_of(EVENTS, RUNS)
    assert loop["iterations"] == 2 and loop["seconds"] == 10.0
    assert (loop["counted"], loop["async"], loop["compute"]) == (2.0, 1.0, 7.0)


def test_timeline_of_the_middle_iteration():
    lines = C.timeline(EVENTS, RUNS, "while.1")
    assert lines[0].startswith("while.1: iteration 1 of 2, 5000.000 ms")
    assert [l.split()[2] for l in lines[1:]] == [
        "all-gather.3", "fusion.2", "async-collective-done.1", "flash_fwd.2"]


def test_a_capture_without_a_device_plane_says_so(tmp_path):
    assert C.table(str(tmp_path)) == [f"no .xplane.pb under {tmp_path}"]


PROGRAM = """HloModule m

%fused_computation.1 (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  ROOT %convolution.1 = bf16[8,8]{1,0} convolution(%p, %p), dim_labels=bf_io->bf
}

%fused_computation.2 (p: bf16[8,8]) -> bf16[2,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  %convolution.2 = bf16[8,8]{1,0} convolution(%p, %p), dim_labels=bf_io->bf
  ROOT %all-reduce.3 = bf16[8,8]{1,0} all-reduce(%convolution.2), to_apply=%add
}

%async_fused (p: bf16[2,8]) -> bf16[8,8] {
  %p = bf16[2,8]{1,0} parameter(0)
  ROOT %all-gather.7 = bf16[8,8]{1,0} all-gather(%p), dimensions={0}
}

%body (t: (s32[], bf16[2,8])) -> (s32[], bf16[2,8]) {
  %t = (s32[], bf16[2,8]{1,0}) parameter(0)
  %w = bf16[2,8]{1,0} get-tuple-element(%t), index=1
  %all-gather.1 = bf16[8,8]{1,0} all-gather(%w), dimensions={0}, metadata={op_name="jit(f)/transpose(jvp(while))/body/attn/dot"}
  %async-collective-start = (bf16[2,8]{1,0}, bf16[8,8]{1,0}) fusion(%w), kind=kCustom, calls=%async_fused
  %fusion.5 = bf16[8,8]{1,0} fusion(%all-gather.1), kind=kOutput, calls=%fused_computation.1
  %async-collective-done = bf16[8,8]{1,0} fusion(%async-collective-start), kind=kCustom, calls=%async_fused
  %collective-permute-start.2 = (bf16[2,8]{1,0}, bf16[2,8]{1,0}) collective-permute-start(%w), source_target_pairs={{0,1}}
  %collective-permute-done.2 = bf16[2,8]{1,0} collective-permute-done(%collective-permute-start.2)
  %fusion.6 = bf16[2,8]{1,0} fusion(%fusion.5), kind=kOutput, calls=%fused_computation.2
  ROOT %tuple = (s32[], bf16[2,8]{1,0}) tuple(%i, %fusion.6)
}

ENTRY %main (a: bf16[2,8]) -> bf16[2,8] {
  %a = bf16[2,8]{1,0} parameter(0)
  %while.4 = (s32[], bf16[2,8]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %out = bf16[2,8]{1,0} get-tuple-element(%while.4), index=1
}
"""


def test_schedule_of_a_loop_body():
    lines = H.report(PROGRAM)
    assert lines[0].startswith("== body (body of while.4 in main")
    kinds = [l.split("  ")[1].strip() for l in lines[1:]]
    assert kinds == ["sync all-gather", "start all-gather", "product",
                     "done", "start collective-permute", "done",
                     "fused all-reduce"]
    done_gather, done_permute = lines[4], lines[6]
    assert "products and kernels since its start: 1" in done_gather
    assert "products and kernels since its start: 0" in done_permute
    assert "transpose(jvp(while))/body/attn" in lines[1]


@pytest.mark.parametrize("shape,mib", [
    ("bf16[4096,14336]{1,0}", 112.0),
    ("(bf16[1024,8,128]{2,1,0}, bf16[4096,8,128]{2,1,0}, u32[])", 8.0),
    ("f32[]", 4 / 2 ** 20),
])
def test_bytes_of_the_largest_array_of_a_result(shape, mib):
    assert H.nbytes(shape) / 2 ** 20 == mib

"""The trace reduction on a synthetic timeline, and one real capture on
the CPU (host spans only: the CPU has no device plane, so no device metric
can come of it)."""

import pytest

from benchmarks.harness import trace as T

# one device, seconds. A while loop spans its body; the body holds two
# fusions, a flash call and an all-gather that nothing overlaps.
OPS = [
    ("while.1", 1.0, 2.0),
    ("fusion.1", 1.0, 0.5),
    ("_fwd_kernel.3", 1.5, 0.25),
    ("%all-gather-done.2 = bf16[4096]{0} all-gather-done(%all-gather-start.2)", 1.75, 0.25),
    ("fusion.2", 2.0, 1.0),
    ("fusion.1", 4.0, 0.5),            # second step, after an idle second
    ("all-reduce.7", 4.5, 0.5),
]
SPANS = [("train_batch", 0.9, 2.2), ("result_fetch", 3.1, 0.8),
         ("serve_step", 3.2, 0.3)]


def test_merge_and_subtract():
    assert T.merge([(0, 1), (0.5, 2), (3, 4), (4, 5)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert T.subtract([(0, 1), (2, 3)], [(0.5, 2.5)]) == [(0, 0.5), (2.5, 3)]
    assert T.measure(T.clip([(0, 2), (3, 5)], 1, 4)) == 2


def test_leaves_drop_the_ops_that_span_others():
    names = [n for n, _, _ in T.leaves(OPS)]
    assert "while.1" not in names and len(names) == 6


def test_busy_union_and_idle_share():
    assert T.busy_seconds(OPS, 0.0, 5.0) == pytest.approx(3.0)
    assert T.idle_share(OPS, 0.0, 5.0) == pytest.approx(0.4)
    assert T.idle_share(OPS, 1.0, 3.0) == pytest.approx(0.0)


def test_kernel_time_by_name_counts_leaves_only():
    s, n = T.kernel_seconds(OPS, r"_fwd_kernel")
    assert (s, n) == (pytest.approx(0.25), 1)
    s, n = T.kernel_seconds(OPS, r"^fusion")
    assert (s, n) == (pytest.approx(2.0), 3)
    by = T.time_by_name(OPS)
    assert by["fusion.1"] == pytest.approx(1.0) and "while.1" not in by


def test_exposed_collective_time_excludes_what_compute_covers():
    assert T.exposed_collective_seconds(OPS) == pytest.approx(0.75)
    hidden = OPS + [("fusion.9", 4.25, 0.5)]      # compute under the all-reduce
    assert T.exposed_collective_seconds(hidden) == pytest.approx(0.5)


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = T.idle_gaps(OPS, SPANS, 0.0, 5.0)
    # 0-1: middle 0.5 has no span; 3-4: middle 3.5 lies in result_fetch and
    # in serve_step (3.2-3.5), the shorter of the two wins
    assert gaps["unattributed"] == pytest.approx(1.0)
    assert gaps["serve_step"] == pytest.approx(1.0)


def test_trace_object_averages_chips_and_takes_the_worst_for_collectives():
    quiet = [("fusion.1", 0.0, 5.0)]
    tr = T.Trace({0: OPS, 1: quiet}, SPANS, 0.0, 5.0)
    assert tr.busy_s() == pytest.approx(4.0)
    assert tr.idle_share() == pytest.approx(0.2)
    assert tr.exposed_collective_share() == pytest.approx(0.15)
    b = tr.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(1.0)] or \
        b["device_ops"][0][0] == "fusion.2"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_cut_to_a_window_keeps_whole_device_events_and_reaching_spans():
    """``Trace.between``: the slice a capture's reader cuts back to whole
    steps. A device event that the cut falls into goes (its step is not
    counted either); a host span that reaches into the window stays, so an
    idle gap at the window's start still finds the sleep that covers it."""
    whole = T.Trace({0: OPS}, SPANS, 0.0, 6.0, {0: [("jit_a(1)", 1.0, 2.0),
                                                    ("jit_a(1)", 4.0, 1.0)]})
    cut = whole.between(3.5, 5.0)
    assert (cut.t0, cut.t1, cut.window_s) == (3.5, 5.0, 1.5)
    assert cut.device_ops == {0: [("fusion.1", 4.0, 0.5), ("all-reduce.7", 4.5, 0.5)]}
    assert cut.modules == {0: [("jit_a(1)", 4.0, 1.0)]}
    assert cut.host_spans == [("result_fetch", 3.1, 0.8)]     # 3.1-3.9 reaches in
    assert cut.busy_s() == pytest.approx(1.0)
    assert whole.between(1.2, 3.0).device_ops == {0: [
        OPS[2], OPS[3], OPS[4]]}                   # the while and fusion.1 begin before
    assert whole.busy_s() == pytest.approx(3.0)   # the whole is left as it was


def test_a_capture_closed_without_reading_is_read_when_asked(tmp_path):
    """``read_on_exit=False``: the thread that closes a capture beside the
    generator's loop leaves the file for the main thread to read."""
    import jax.numpy as jnp

    with T.Capture(str(tmp_path / "tr"), read_on_exit=False) as cap:
        with T.span("serve_step"):
            jnp.ones((8, 8)).sum().block_until_ready()
    assert cap.trace is None
    got = cap.read()
    assert got is cap.trace and "serve_step" in {s[0] for s in got.host_spans}


def test_capture_on_the_cpu_reads_the_benchmarks_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    with T.Capture(str(tmp_path / "tr")) as cap:
        with T.span("train_batch"):
            jnp.ones((64, 64)).sum().block_until_ready()
    assert cap.trace is not None
    assert [s[0] for s in cap.trace.host_spans] == ["train_batch"]
    assert cap.trace.window_s > 0
    assert cap.trace.device_ops == {}      # no TPU plane: no device metric
    assert jax.devices()[0].platform == "cpu"


def test_short_names_and_kernel_classes_on_real_event_names():
    """Names as the v5e trace gives them (my chip run, PR 23; the
    kernels' own names since PR 24)."""
    from benchmarks.kernels import flash, paged_decode

    fwd = ('%flash_fwd.9 = (bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}, f32[128,2048,8]'
           '{2,1,0:T(8,128)}) custom-call(bf16[128,2048,128]{2,1,0:T(8,128)(2,1)} '
           '%bitcast.450, bf16[32,2048,128]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.457, '
           'bf16[32,2048,128]{2,1,0:T(8,128)(2,1)} %bitcast.473), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    bwd = ('%flash_bwd_dq.20 = bf16[128,2048,128]{2,1,0} custom-call(bf16[128,2048,128] '
           '%bitcast.449, bf16[32,2048,128] %bitcast.456, bf16[32,2048,128] %bitcast.472, '
           'bf16[128,2048,128] %bitcast.443, f32[128,2048,8] %pallas_call.56, '
           'f32[128,2048,8] %broadcast_in_dim.191), custom_call_target="tpu_custom_call"')
    adam = ('%fusion.307 = (bf16[4096,32000]{1,0:T(8,128)(2,1)}, f32[4096,32000]{1,0}) '
            'fusion(f32[4096,32000]{1,0:T(8,128)} %opt_state_master__unembed____kernel__.1, '
            'f32[]{:T(128)S(6)} %sub.27), kind=kLoop, calls=%fused_computation.1')
    assert flash.classify(fwd) == "fwd" and flash.classify(bwd) == "bwd"
    assert flash.classify(adam) is None and paged_decode.classify(adam) is None
    assert paged_decode.classify(fwd) is None
    assert paged_decode.classify(fwd.replace("flash_fwd.9", "paged_decode.6")) \
        == "decode"
    assert T.short_name(adam).startswith("fusion.307 fusion bf16[4096,32000]")
    assert "opt_state_master__unembed" in T.short_name(adam)
    assert "tpu_custom_call" in T.short_name(fwd) and len(T.short_name(fwd)) <= 120
    assert T.COLLECTIVE.match("%all-gather.3 = bf16[4096,14336]{1,0} all-gather(...)")
    assert not T.COLLECTIVE.match(adam)

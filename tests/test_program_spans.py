"""The program's host spans on the profiler's clock, and the counters where
the work happens: a tiny engine of each kind under a ``jax.profiler``
session on the CPU yields the ``dstpu/`` vocabulary of
docs/observability.md ("Profiler spans and names"), nested as documented
and joined by ``step_id`` to the request tracer; without a session the
engines compute the same bits."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.inference.engine_v2 import PROGRAMS, InferenceEngineV2
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.utils.annotate import SPAN_PREFIX

TRAIN_CHILDREN = ["next_batches", "dispatch", "ckpt_commit", "drain_wait",
                  "after_step_host", "step_trace"]
SERVE_CHILDREN = {"admit", "schedule", "build_batch", "dispatch", "fetch",
                  "bookkeep", "journal"}


def capture(tmp_path, fn):
    """Run ``fn`` under a profiler session; return its result and the
    ``dstpu/`` spans as dicts (name, start, end, ids), in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append({
                        "name": ev.name[len(SPAN_PREFIX):],
                        "start": ev.start_ns, "end": ev.start_ns + ev.duration_ns,
                        "ids": {k: v for k, v in ev.stats
                                if not k.startswith("_")}})
    return out, sorted(spans, key=lambda s: (s["start"], -s["end"]))


def inside(parent, spans):
    return [s for s in spans if s is not parent
            and s["start"] >= parent["start"] and s["end"] <= parent["end"]]


# -- training ---------------------------------------------------------------

def _train_engine():
    engine, *_ = dstpu.initialize(model=get_model("tiny"), config={
        "train_micro_batch_size_per_chip": 2, "steps_per_print": 10**9,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3}, "bf16": {"enabled": True},
        "seed": 3})
    return engine


def _train_steps(engine, n=3):
    rng = np.random.default_rng(0)
    data = iter([{"input_ids": rng.integers(
        0, 100, (engine.train_batch_size, 33)).astype(np.int32)}
        for _ in range(n)])
    return [float(engine.train_batch(data)) for _ in range(n)]


def test_train_spans_nest_in_their_step(devices, tmp_path):
    engine = _train_engine()
    _train_steps(engine, 1)                       # compile outside
    losses, spans = capture(tmp_path, lambda: _train_steps(engine, 3))
    steps = [s for s in spans if s["name"] == "train_batch"]
    assert [s["ids"]["step_num"] for s in steps] == [2, 3, 4]
    for step in steps:
        kids = inside(step, spans)
        assert [k["name"] for k in kids] == TRAIN_CHILDREN
        # siblings, in order, not overlapping
        assert all(a["end"] <= b["start"] for a, b in zip(kids, kids[1:]))
    assert len(spans) == 3 * (1 + len(TRAIN_CHILDREN))
    # the satellite: the drained step's norm, no None on this path
    assert float(engine.get_global_grad_norm()) > 0
    engine.close()
    engine.close()                                # idempotent
    assert engine.watchdog is None or engine.watchdog._stop


def test_train_losses_do_not_depend_on_a_profiler_session(devices, tmp_path):
    a, b = _train_engine(), _train_engine()
    plain = _train_steps(a, 3)
    traced, spans = capture(tmp_path, lambda: _train_steps(b, 3))
    assert plain == traced and spans
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    a.close(), b.close()


def test_trace_capture_keeps_the_last_step_span(devices, tmp_path,
                                                monkeypatch):
    """``DSTPU_TRACE_STEPS``: the session closes after the step's own
    span has, so the window's last ``dstpu/train_batch`` is in the file."""
    monkeypatch.setenv("DSTPU_TRACE_STEPS", "2:3")
    monkeypatch.setenv("DSTPU_TRACE_DIR", str(tmp_path))
    engine = _train_engine()
    _train_steps(engine, 4)
    assert engine._trace_capture.done and not engine._trace_capture.active
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    nums = sorted(dict(ev.stats)["step_num"]
                  for plane in jax.profiler.ProfileData.from_file(path).planes
                  for line in plane.lines for ev in line.events
                  if ev.name == SPAN_PREFIX + "train_batch")
    assert nums == [2, 3]
    engine.close()


# -- serving -----------------------------------------------------------------

_MODELS = {}


def _model(name, **kw):
    """One model a preset for the file: engines over one config object
    share their compiled step programs (``engine_v2._shared_step_fns``)."""
    if name not in _MODELS:
        _MODELS[name] = get_model(name, **kw)
    return _MODELS[name]


def _serve_engine(**kw):
    kw = dict(dict(kv_blocks=64, kv_block_size=8, max_tokens_per_step=32,
                   max_seqs_per_step=4, max_blocks_per_seq=16,
                   dtype=jnp.float32, request_trace={"sample_rate": 1.0}),
              **kw)
    return InferenceEngineV2(_model("tiny"), **kw)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 100, n).astype(np.int32)


def _mixed_run(engine):
    """Split, prefill, burst and single-decode steps, by hand:

    A. three prompts of 20, 5, 5 tokens, 10 new tokens each. One step
       takes all three chunks, split by program: padded to 4 slots x 32
       they are over twice the step's 32-token budget, so two calls of
       the prefill program take them (2 x 32, then 1 x 8), 3 tokens. A
       burst of 8 follows (24 tokens); with one token left to each a
       burst does not pay, and one decode step emits 3.
    B. one prompt of 40, 2 new tokens: chunks of 32 (no token) and 8 (1
       token) through the prefill program, then one decode step (1).
    """
    out = {}
    engine.put([1, 2, 3], [_prompt(20, 1), _prompt(5, 2), _prompt(5, 3)],
               max_new_tokens=10)
    out.update(engine.generate_all())
    engine.put([4], [_prompt(40, 4)], max_new_tokens=2)
    out.update(engine.generate_all())
    return out


EXPECTED = {"tokens_gather": 0, "tokens_multi_decode": 24,
            "tokens_decode": 3 + 1, "tokens_prefill_kernel": 3 + 1,
            "prefill_chunks": 3 + 2, "first_tokens": 4, "admitted": 4,
            "prefill_chunk_calls": 2 + 2,
            # steps whose chunks the prefill program took: A's one, B's two
            "prefill_kernel_steps": 1 + 2, "prefill_gather_fallbacks": 0,
            # what each program was given, a call at a time: A's two chunk
            # calls carry 25 rows as 2 x 32 and 5 as 1 x 8, B's 32 as
            # 1 x 32 and 8 as 1 x 8; the burst 8 x 3 rows as 8 x 4 slots;
            # the decode steps 3 and 1 rows as 4 slots each
            "steps_dispatched": 6,
            "calls_prefill": 4, "rows_prefill": 25 + 5 + 32 + 8,
            "padded_rows_prefill": 64 + 8 + 32 + 8, "token_steps_prefill": 4,
            "calls_multi_decode": 1, "rows_multi_decode": 24,
            "padded_rows_multi_decode": 32, "token_steps_multi_decode": 8,
            "calls_decode": 2, "rows_decode": 3 + 1,
            "padded_rows_decode": 4 + 4, "token_steps_decode": 2,
            "calls_gather": 0, "rows_gather": 0, "calls_spec": 0,
            "decode_kernel_steps": 8 + 2, "burst_steps": 1,
            # A's requests are put before any call and get their first
            # tokens once the step's two calls are out, each carried by
            # one of them; B is put with 4 calls made and waits for its
            # own two
            "first_token_calls": 3 * 2 + 2, "first_token_own_calls": 3 + 2}
# the ids of each ``dstpu/dispatch`` span of _mixed_run, in order
MIXED_CALLS = [
    dict(program="prefill", call=0, seqs=2, tokens=25, padded_rows=64,
         token_steps=1, chunks=2, S=2, tq=32),
    dict(program="prefill", call=1, seqs=1, tokens=5, padded_rows=8,
         token_steps=1, chunks=1, S=1, tq=8),
    dict(program="multi_decode", call=0, seqs=3, tokens=24, padded_rows=32,
         token_steps=8, chunks=0),
    dict(program="decode", call=0, seqs=3, tokens=3, padded_rows=4,
         token_steps=1, chunks=0),
    dict(program="prefill", call=0, seqs=1, tokens=32, padded_rows=32,
         token_steps=1, chunks=1, S=1, tq=32),
    dict(program="prefill", call=0, seqs=1, tokens=8, padded_rows=8,
         token_steps=1, chunks=1, S=1, tq=8),
    dict(program="decode", call=0, seqs=1, tokens=1, padded_rows=4,
         token_steps=1, chunks=0)]


def _dispatches(spans, keep_step_id=False, ut_steps=1):
    """The ``dstpu/dispatch`` spans' ids, in order, each checked against
    the ``serve_step`` span it lies in (which its own ``step_id`` has to
    name) and for the passes of the stack its model makes a token step
    (``ut_steps``: 1 but for a looped stack)."""
    steps = [s for s in spans if s["name"] == "serve_step"]
    out = []
    for d in (s for s in spans if s["name"] == "dispatch"):
        (step,) = [t for t in steps if t["start"] <= d["start"]
                   and d["end"] <= t["end"]]
        assert d["ids"]["step_id"] == step["ids"]["step_id"]
        assert d["ids"]["ut_steps"] == ut_steps
        out.append({k: v for k, v in d["ids"].items() if k != "ut_steps"
                    and (keep_step_id or k != "step_id")})
    return out


def test_counters_against_a_hand_counted_schedule(devices):
    # a label of its own: the hub's histograms are the process's, and the
    # sums below are compared with this engine's observations alone
    engine = _serve_engine(metric_labels={"engine": "hand-count"})
    out = _mixed_run(engine)
    assert {u: len(t) for u, t in out.items()} == {1: 10, 2: 10, 3: 10, 4: 2}
    got = {k: engine.stats[k] for k in EXPECTED}
    assert got == EXPECTED
    tokens = sum(engine.stats[k] for k in EXPECTED if k.startswith("tokens_"))
    assert tokens == sum(len(t) for t in out.values()) == 32
    st = engine.stats
    assert all(st[f"rows_{p}"] <= st[f"padded_rows_{p}"]
               and st[f"calls_{p}"] <= st[f"token_steps_{p}"]
               for p in PROGRAMS)
    assert engine._calls_issued == sum(st[f"calls_{p}"] for p in PROGRAMS)
    assert engine._calls_at_put == {}
    # the time sums hold the histograms' own observations
    snap = engine.snapshot()
    assert engine.stats["ttft_s"] == pytest.approx(snap["ttft"]["sum"],
                                                   rel=1e-4)
    assert snap["ttft"]["count"] == 4
    assert engine.stats["admission_wait_s"] == pytest.approx(
        snap["admission_wait"]["sum"], abs=1e-5)
    assert 0 < engine.stats["admission_wait_s"] < engine.stats["ttft_s"]
    engine.close()
    engine.close()


def test_admission_wait_counts_the_queue(devices):
    """Five requests into four slots: the fifth is admitted when a slot
    frees, and its wait is most of the counter."""
    engine = _serve_engine()
    engine.put(list(range(5)), [_prompt(6, i) for i in range(5)],
               max_new_tokens=4)
    assert engine.stats["admitted"] == 4
    early = engine.stats["admission_wait_s"]
    engine.generate_all()
    assert engine.stats["admitted"] == 5 == engine.stats["first_tokens"]
    waited = engine.stats["admission_wait_s"] - early
    assert waited > 10 * early and waited < engine.stats["ttft_s"]
    engine.close()


def test_serve_spans_and_request_trace_share_step_ids(devices, tmp_path):
    engine = _serve_engine(prefix_cache=False)    # both runs prefill alike
    _mixed_run(engine)                            # compile outside
    first = engine._step_id
    engine.flush([1, 2, 3, 4])
    out, spans = capture(tmp_path, lambda: _mixed_run(engine))
    steps = [s for s in spans if s["name"] == "serve_step"]
    ids = [s["ids"]["step_id"] for s in steps]
    assert ids == list(range(first + 1, engine._step_id + 1))   # one a step
    programs = []
    for step in steps:
        kids = inside(step, spans)
        names = [k["name"] for k in kids]
        assert set(names) <= SERVE_CHILDREN
        assert names[0] == "admit" and names[-1] == "journal"
        # one dispatch a program call, each behind its own build_batch
        calls = [k for k in kids if k["name"] == "dispatch"]
        assert len(calls) == names.count("build_batch") >= 1
        programs.append("+".join(d["ids"]["program"] for d in calls))
        assert all(d["ids"]["seqs"] >= 1 and d["ids"]["tokens"] >= 1
                   for d in calls)
        assert names.index("build_batch") < names.index("dispatch")
    assert programs == ["prefill+prefill", "multi_decode", "decode",
                        "prefill", "prefill", "decode"]
    # every call says what it carried, where it stood in its step, and
    # the step it belongs to
    assert _dispatches(spans) == MIXED_CALLS
    puts = [s for s in spans if s["name"] == "put"]
    assert [(p["ids"]["uid"], p["ids"]["requests"]) for p in puts] == \
        [(1, 3), (4, 1)]
    # every span is a put, a serve_step, or lies inside one
    tops = puts + steps
    assert all(any(s is t or (s["start"] >= t["start"]
                              and s["end"] <= t["end"]) for t in tops)
               for s in spans)
    # the request tracer's spans name the step that made them
    traces = {t.uid: t for t in engine.request_traces()}
    by_step = dict(zip(programs, ids))
    # ... and a PREFILL span the call of that step that carried the chunk
    assert [[s.fields["call"] for s in traces[u].spans
             if s.kind == "PREFILL"] for u in (1, 2, 3, 4)] == \
        [[0], [0], [1], [0, 0]]
    four = [(s.kind, s.fields.get("step_id")) for s in traces[4].spans
            if s.kind in ("PREFILL", "DECODE_EMIT")]
    assert four == [("PREFILL", ids[3]), ("PREFILL", ids[4]),
                    ("DECODE_EMIT", ids[4]), ("DECODE_EMIT", ids[5])]
    one = [s.fields["step_id"] for s in traces[1].spans
           if s.kind == "DECODE_EMIT"]
    assert one == [ids[0], by_step["multi_decode"], ids[2]]
    engine.close()


@pytest.mark.parametrize("kind", ["dense", "hybrid"])
def test_a_call_issued_ahead_says_so_and_names_the_step_that_issued_it(
        devices, tmp_path, kind):
    """A full batch (four slots, four requests, 25 tokens each: one from
    the prompts' step, three bursts of eight): the first burst step issues
    two calls and returns the first one's tokens, the next issues the third
    and returns the second's, the last issues none and returns the
    third's. A call issued while the one before it is unread says
    ``ahead=1`` and the ``step_id`` of the step that issued it; a
    request's ``DECODE_EMIT`` spans name the step that returned the
    tokens."""
    make = {"dense": _serve_engine, "hybrid": _hybrid_engine}[kind]
    engine = make(prefix_cache=False, request_trace={"sample_rate": 1.0})
    n, K = engine.max_seqs, engine.decode_steps
    uids = list(range(1, n + 1))
    assert (n, K) == (4, 8)

    def run():
        engine.put(uids, [_prompt(5, u) for u in uids],
                   max_new_tokens=1 + 3 * K)
        return engine.generate_all()

    run()                                         # compile outside
    first = engine._step_id
    ahead0 = engine.stats["calls_issued_ahead"]
    out, spans = capture(tmp_path, run)
    assert {u: len(t) for u, t in out.items()} == \
        dict.fromkeys(uids, 1 + 3 * K)
    assert engine.stats["calls_issued_ahead"] - ahead0 == 2
    steps = [s for s in spans if s["name"] == "serve_step"]
    ids = [s["ids"]["step_id"] for s in steps]
    assert ids == list(range(first + 1, first + 1 + len(steps)))
    bursts = [d for d in _dispatches(spans, keep_step_id=True)
              if d["program"] == "multi_decode"]
    # (the step's id, the call's place in it, ahead): two calls in the
    # first burst step, one in the next, none in the last
    b0 = bursts[0]["step_id"]
    assert [(d["step_id"], d["call"], d.get("ahead", 0)) for d in bursts] \
        == [(b0, 0, 0), (b0, 1, 1), (b0 + 1, 0, 1)]
    assert all(d["tokens"] == K * n and d["token_steps"] == K
               for d in bursts)
    kids = [[k["name"] for k in inside(step, spans)] for step in steps]
    by_id = dict(zip(ids, kids))
    assert by_id[b0].count("dispatch") == 2 == by_id[b0].count("build_batch")
    assert by_id[b0 + 2].count("dispatch") == 0       # it only reads
    assert "fetch" in by_id[b0 + 2] and "bookkeep" in by_id[b0 + 2]
    assert all(set(names) <= SERVE_CHILDREN for names in kids)
    for uid in uids:
        trace = [t for t in engine.request_traces() if t.uid == uid][-1]
        emits = [(s.fields["step_id"], s.fields["n"])
                 for s in trace.spans if s.kind == "DECODE_EMIT"]
        assert emits[-3:] == [(b0, K), (b0 + 1, K), (b0 + 2, K)]
    engine.close()


def test_speculative_round_spans(devices, tmp_path):
    engine = _serve_engine(spec_decode=True, spec_k=3)
    prompt = np.tile(np.arange(6, dtype=np.int32), 4)   # lookup finds drafts
    engine.put([1], [prompt], max_new_tokens=12)
    plain = _serve_engine()
    plain.put([1], [prompt], max_new_tokens=12)
    want = plain.generate_all()
    got, spans = capture(tmp_path, engine.generate_all)
    assert got == want
    spec = [s for s in spans if s["name"] == "dispatch"
            and s["ids"]["program"] == "spec"]
    assert len(spec) == engine.stats["spec_steps"] > 0
    # a speculative round runs the gather program over the flat budget, one
    # pass over the layers whatever it verifies, and is counted under its
    # own name
    s = engine.stats
    assert all(d["ids"]["padded_rows"] == 32 and d["ids"]["token_steps"] == 1
               and d["ids"]["call"] == 0 and d["ids"]["chunks"] == 0
               and d["ids"]["seqs"] == 1 for d in spec)
    assert s["calls_spec"] == len(spec) == s["token_steps_spec"]
    assert s["rows_spec"] == sum(d["ids"]["tokens"] for d in spec) \
        == s["spec_proposed"] + len(spec)
    assert s["padded_rows_spec"] == 32 * len(spec)
    assert s["calls_gather"] == 0 == s["tokens_prefill_kernel"] - 1
    assert (s["tokens_gather"] + s["tokens_prefill_kernel"]
            + s["tokens_decode"] + s["tokens_multi_decode"]) == 12
    engine.close(), plain.close()


def _hybrid_engine(**kw):
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    model = _model("tiny-hybrid", param_dtype=jnp.float32,
                   dtype=jnp.float32)
    kw = dict(dict(kv_blocks=64, kv_block_size=16, max_tokens_per_step=32,
                   max_seqs_per_step=4, max_blocks_per_seq=8, state_slots=4),
              **kw)
    return InferenceEngineV2(
        model, mesh=build_mesh(TopologyConfig(), devices=jax.devices()[:1]),
        params=model.init(jax.random.PRNGKey(0)), dtype=jnp.float32, **kw)


@pytest.mark.parametrize("kind", ["dense", "hybrid", "speculative"])
def test_serve_tokens_do_not_depend_on_a_profiler_session(devices, tmp_path,
                                                          kind):
    make = {"dense": _serve_engine, "hybrid": _hybrid_engine,
            "speculative": lambda: _serve_engine(spec_decode=True, spec_k=3)
            }[kind]
    a, b = make(), make()
    plain = _mixed_run(a)
    traced, spans = capture(tmp_path, lambda: _mixed_run(b))
    assert plain == traced and spans
    counted = [k for k, v in a.stats.items() if isinstance(v, int)]
    assert {k: a.stats[k] for k in counted} == \
        {k: b.stats[k] for k in counted}
    assert set(EXPECTED) <= set(counted)
    a.close(), b.close()


def test_a_looped_stack_says_its_passes_on_every_call(devices, tmp_path):
    """``tiny-ouro`` (three layers, four passes): every ``dstpu/dispatch``
    span says ``ut_steps`` 4, ``ut_passes_<program>`` counts four passes a
    row of every program, and ``kv_slots`` the pool's twelve."""
    engine = InferenceEngineV2(
        _model("tiny-ouro"), kv_blocks=64, kv_block_size=8,
        max_tokens_per_step=32, max_seqs_per_step=4, max_blocks_per_seq=16,
        dtype=jnp.float32, decode_steps=4, prefix_cache=False)

    def run():
        engine.put([1, 2], [_prompt(20, 1), _prompt(5, 2)], max_new_tokens=6)
        return engine.generate_all()

    out, spans = capture(tmp_path, run)
    calls = _dispatches(spans, ut_steps=4)
    assert {c["program"] for c in calls} == {"prefill", "multi_decode",
                                             "decode"}
    st = engine.stats
    assert st["kv_slots"] == 12 and all(len(t) == 6 for t in out.values())
    for program in ("prefill", "multi_decode", "decode"):
        assert st[f"ut_passes_{program}"] == 4 * st[f"rows_{program}"] \
            == 4 * sum(c["tokens"] for c in calls if c["program"] == program)
    assert st["ut_passes_gather"] == st["ut_passes_spec"] == 0
    engine.close()


def test_the_calls_of_a_split_step_say_their_place_and_what_they_carry(
        devices, tmp_path):
    """One sequence in decode and three prompts of 5, 20 and 5 arriving.
    The scheduler's scan of the waiting prompts starts one further with
    every step that had one waiting (this engine's second), so the chunks
    come as 20, 5, 5: the step is a call of the decode program (its row as
    4 slots), then two of the prefill program: 20 and 5 as 2 x 32, and 5
    as 1 x 8 (the three would pad to 4 x 32, over twice the budget of
    32)."""
    engine = _serve_engine(prefix_cache=False)

    def run():
        engine.put([7], [_prompt(5, 7)], max_new_tokens=10)
        engine.serve_step()
        stats0 = dict(engine.stats)
        engine.put([1, 2, 3], [_prompt(5, 1), _prompt(20, 2), _prompt(5, 3)],
                   max_new_tokens=10)
        engine.serve_step()
        return {k: v - stats0[k] for k, v in engine.stats.items()
                if isinstance(v, int)}

    new, spans = capture(tmp_path, run)
    assert _dispatches(spans)[1:] == [
        dict(program="decode", call=0, seqs=1, tokens=1, padded_rows=4,
             token_steps=1, chunks=0),
        dict(program="prefill", call=1, seqs=2, tokens=25, padded_rows=64,
             token_steps=1, chunks=2, S=2, tq=32),
        dict(program="prefill", call=2, seqs=1, tokens=5, padded_rows=8,
             token_steps=1, chunks=1, S=1, tq=8)]
    want = {"steps_dispatched": 1, "calls_decode": 1, "rows_decode": 1,
            "padded_rows_decode": 4, "calls_prefill": 2, "rows_prefill": 30,
            "padded_rows_prefill": 72, "token_steps_prefill": 2,
            "prefill_chunk_calls": 2, "decode_kernel_steps": 1,
            "calls_gather": 0, "calls_multi_decode": 0,
            # the three wait for all three calls of their step, the
            # decode program's among them, and ride in one each
            "first_tokens": 3, "first_token_calls": 9,
            "first_token_own_calls": 3}
    assert {k: new[k] for k in want} == want
    # the request tracer's PREFILL span names the call that carried it
    step = engine._step_id
    engine.generate_all()
    traces = {t.uid: t for t in engine.request_traces()}
    assert {u: [(s.fields["step_id"], s.fields["call"])
                for s in traces[u].spans if s.kind == "PREFILL"]
            for u in (1, 2, 3)} == {1: [(step, 2)], 2: [(step, 1)],
                                    3: [(step, 1)]}
    engine.close()


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "split"])
def test_a_hybrid_step_counts_the_runners_layout(devices, tmp_path, kernel):
    """The hybrid runner's gather program (the path with
    ``_use_paged_kernel`` off) lays the step's flat tokens out anew,
    ``max_seqs`` rows of ``max_tokens``, for the chunked recurrence: that,
    and not the flat budget, is what a call computes. On the kernel path
    the same steps are split by program: a chunk's call computes its own
    rows, bucketed from the recurrence's 64 (one sequence a call here:
    two of 64 rows would pass twice the budget of 32)."""
    from deepspeed_tpu.inference import hybrid_runner, model_runner

    assert model_runner.gather_rows_computed(4, 32) == 32
    assert hybrid_runner.gather_rows_computed(4, 32) == 4 * 32
    engine = _hybrid_engine()
    engine._use_paged_kernel = kernel

    def run():
        engine.put([1, 2, 3], [_prompt(3, i) for i in (1, 2, 3)],
                   max_new_tokens=12)
        engine.serve_step()
        engine.put([4], [_prompt(20, 4)], max_new_tokens=3)
        engine.serve_step()

    _, spans = capture(tmp_path, run)
    st = engine.stats
    if kernel:
        chunk = dict(program="prefill", seqs=1, padded_rows=64,
                     token_steps=1, chunks=1, S=1, tq=64)
        assert _dispatches(spans) == [
            dict(chunk, call=0, tokens=3), dict(chunk, call=1, tokens=3),
            dict(chunk, call=2, tokens=3),
            dict(program="decode", call=0, seqs=3, tokens=3, padded_rows=4,
                 token_steps=1, chunks=0),
            dict(chunk, call=1, tokens=20)]
        assert (st["calls_prefill"], st["rows_prefill"],
                st["padded_rows_prefill"], st["prefill_chunk_calls"],
                st["prefill_kernel_steps"]) == (4, 29, 256, 4, 2)
        assert st["calls_gather"] == 0 == st["tokens_gather"]
        assert (st["first_tokens"], st["first_token_calls"],
                st["first_token_own_calls"]) == (4, 3 * 3 + 2, 4)
    else:
        assert _dispatches(spans) == [
            dict(program="gather", call=0, seqs=3, tokens=9, padded_rows=128,
                 token_steps=1, chunks=3),
            dict(program="gather", call=0, seqs=4, tokens=23,
                 padded_rows=128, token_steps=1, chunks=1)]
        assert (st["calls_gather"], st["rows_gather"],
                st["padded_rows_gather"], st["token_steps_gather"]) == (
                    2, 32, 256, 2)
        assert st["calls_prefill"] == 0 == st["prefill_kernel_steps"]
        assert (st["first_tokens"], st["first_token_calls"],
                st["first_token_own_calls"]) == (4, 4, 4)
    assert st["prefill_gather_fallbacks"] == 0
    assert st["steps_dispatched"] == 2
    engine.close()


def test_first_token_calls_of_two_prompts_whose_chunks_alternate(devices):
    """Two prompts of 40 tokens put together, 32 tokens a step: the
    scheduler's rotating scan gives the first step to one, the second to
    the other, and the third carries the 8 left of each in one call. Each
    waited for three calls, and two of them were its own."""
    engine = _serve_engine(prefix_cache=False)
    engine.put([1, 2], [_prompt(40, 1), _prompt(40, 2)], max_new_tokens=2)
    assert engine._calls_at_put == {1: [0, 0], 2: [0, 0]}
    engine.serve_step(), engine.serve_step()
    assert engine._calls_at_put == {1: [0, 1], 2: [0, 1]}
    assert engine.serve_step().keys() == {1, 2}
    st = engine.stats
    assert (st["calls_prefill"], st["rows_prefill"],
            st["padded_rows_prefill"]) == (3, 80, 32 + 32 + 2 * 8)
    assert (st["first_tokens"], st["first_token_calls"],
            st["first_token_own_calls"]) == (2, 6, 4)
    assert engine._calls_at_put == {}
    engine.generate_all()
    # later tokens count nothing more
    assert (st["first_token_calls"], st["first_token_own_calls"]) == (6, 4)
    engine.close()


def test_a_requeued_request_keeps_its_stamp_of_calls(devices):
    """Preempted after its first chunk and requeued, a request still
    counts from its put(): the chunk call before the preemption, and the
    two that compute the prompt again, all its own; another request's
    call in between is counted and foreign."""
    engine = _serve_engine(prefix_cache=False)
    engine.put([1], [_prompt(40, 1)], max_new_tokens=2)
    engine.serve_step()
    assert engine._calls_at_put == {1: [0, 1]}
    engine._requeue(engine.state.seqs[1])
    assert engine._calls_at_put == {1: [0, 1]} and engine._admit_time == {}
    assert engine.stats["requeued"] == 1 and not engine.state.seqs
    engine.put([2], [_prompt(5, 2)], max_new_tokens=1)
    out = engine.generate_all()
    assert {u: len(t) for u, t in out.items()} == {1: 2, 2: 1}
    st = engine.stats
    # 1: chunk, [requeue] chunk (with 2's prompt beside it in the call),
    # chunk -> 3 calls, all its own; 2: put with one call made, its first
    # token with two
    assert st["calls_prefill"] == 3
    assert (st["first_tokens"], st["first_token_calls"],
            st["first_token_own_calls"]) == (2, 3 + 1, 3 + 1)
    assert engine._calls_at_put == {}
    # a request flushed before its first token leaves no stamp behind
    engine.put([3], [_prompt(40, 3)], max_new_tokens=2)
    engine.serve_step()
    engine.flush([3])
    assert engine._calls_at_put == {} and engine._admit_time == {}
    engine.close()


def test_close_detaches_the_request_tracer_from_the_flight_recorder(devices):
    from deepspeed_tpu.observability.flight_recorder import \
        get_flight_recorder

    flight = get_flight_recorder()
    first, second = _serve_engine(), _serve_engine()
    assert flight._dump_context["requests_in_flight"] == \
        second.tracer._inflight_summary
    first.close()           # a later engine's registration stays
    assert flight._dump_context["requests_in_flight"] == \
        second.tracer._inflight_summary
    second.close()
    assert "requests_in_flight" not in flight._dump_context

"""A serve step books itself: its kind (``engine_v2.step_kind``), its wall
clock by phase, the seconds it blocked on the device and the tokens it
returned, folded into ``stats`` and the flight recorder when it closes; the
burst plan counts the one reason of each return; and none of it touches a
step program (their lowered text is pinned)."""

import hashlib
import itertools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine_v2 import (
    AHEAD_REFUSALS, BURST_REFUSALS, PHASES, PROGRAMS, STEP_KINDS,
    InferenceEngineV2, _BurstInFlight, step_kind)
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

F32 = jnp.float32
K = 4                       # decode_steps of every engine here
_MODEL = []


def _engine(**kw):
    """Engines of one model object share their compiled step programs."""
    if not _MODEL:
        m = get_model("tiny", param_dtype=F32, dtype=F32)
        _MODEL.append((m, m.init(jax.random.PRNGKey(0))))
    model, params = _MODEL[0]
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    args = dict(kv_blocks=64, kv_block_size=8, max_tokens_per_step=32,
                max_seqs_per_step=4, max_blocks_per_seq=16, decode_steps=K,
                prefix_cache=False)
    args.update(kw)
    return InferenceEngineV2(model, mesh=mesh, params=params, dtype=F32,
                             **args)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 200, n).astype(np.int32)


# -- the kind of a step --------------------------------------------------------

CHUNKS, TOKEN_ROWS = ("prefill", "gather"), ("decode", "spec", "multi_decode")


@pytest.mark.parametrize("programs", [
    c for n in range(len(PROGRAMS) + 1)
    for c in itertools.combinations(PROGRAMS, n)], ids="+".join)
def test_step_kind_over_every_combination_of_programs(programs):
    """Every set of programs a step's calls can be of (the chunk calls come
    several a step): the rule, from the issue's words."""
    calls = [(p, K if p == "multi_decode" else 1) for p in programs]
    calls += [c for c in calls if c[0] == "prefill"]      # two chunk calls
    has_chunk = any(p in CHUNKS for p in programs)
    has_rows = any(p in TOKEN_ROWS for p in programs)
    if has_chunk:
        want = "mixed" if has_rows else "prefill"
    elif "multi_decode" in programs:
        want = "burst"
    else:
        want = "lone" if has_rows else "empty"
    assert step_kind(calls) == want
    assert step_kind(reversed(calls)) == want             # in any order
    assert want in STEP_KINDS


def test_step_kind_names_no_program_the_engine_does_not_dispatch():
    assert set(CHUNKS + TOKEN_ROWS) == set(PROGRAMS)
    assert step_kind([]) == "empty" and step_kind([("decode", 1)]) == "lone"


# -- the record of a run -------------------------------------------------------

def _run(engine):
    """A prompts' step, mixed steps, bursts and an empty step, counted
    by hand (four slots, ``decode_steps`` 4):

    1. requests 1, 2 (prompts of 20 and 5; 7 new tokens): a ``prefill``
       step, 2 tokens; then a burst of 4 (8 tokens);
    2. request 3 (a prompt of 40: two chunks) arrives: two ``mixed`` steps,
       its chunks beside the two token rows: 2 tokens, then 2 + 1 (the
       prompt's first); requests 1 and 2 have 7: done;
    3. request 3 alone, 6 to go: a burst of 4, then two to go: a burst of 2;
    4. nothing left: an ``empty`` step.
    """
    steps = tokens = 0

    def serve(until_idle=False):
        nonlocal steps, tokens
        while True:
            out = engine.serve_step()
            steps += 1
            tokens += sum(len(t) for t in out.values())
            if not until_idle or not (engine.state.seqs or engine._queue):
                return out

    engine.put([1, 2], [_prompt(20, 1), _prompt(5, 2)], max_new_tokens=7)
    serve(), serve()
    engine.put([3], [_prompt(40, 3)], max_new_tokens=7)
    serve(until_idle=True)
    serve()
    return steps, tokens


def test_the_record_adds_up_to_the_steps_taken_and_the_tokens_emitted(devices):
    engine = _engine(metric_labels={"engine": "step-record"})
    before = dict(engine._hub.snapshot()["counters"])
    steps, tokens = _run(engine)
    st = engine.stats
    by_kind = {k: st[f"steps_{k}"] for k in STEP_KINDS}
    assert by_kind == {"prefill": 1, "mixed": 2, "burst": 3, "lone": 0,
                       "empty": 1}
    assert sum(by_kind.values()) == steps == engine._step_id
    assert {k: st[f"step_tokens_{k}"] for k in STEP_KINDS} == {
        "prefill": 2, "mixed": 5, "burst": 14, "lone": 0, "empty": 0}
    assert sum(st[f"step_tokens_{k}"] for k in STEP_KINDS) == tokens == 21 \
        == sum(st[k] for k in st if k.startswith("tokens_"))
    for k in STEP_KINDS:
        assert 0.0 <= st[f"step_wait_s_{k}"] <= st[f"step_s_{k}"], k
        assert (st[f"step_s_{k}"] > 0) == (st[f"steps_{k}"] > 0), k
    # a step that ran a program waited for it; an empty one for nothing
    assert st["step_wait_s_mixed"] > 0 and st["step_wait_s_empty"] == 0
    # the hub's token counter moved once a step, by the step's tokens
    name = 'serve.tokens_emitted{engine="step-record"}'
    after = engine._hub.snapshot()["counters"]
    assert after[name] - before.get(name, 0) == tokens
    engine.close()


def test_a_lone_step_is_a_single_decode_call(devices):
    """A budget of two: the prompt's step gives one token and the one left
    is no burst (``burst_refused_budget``), so a ``decode`` call gives it."""
    engine = _engine()
    engine.put([1], [_prompt(6, 1)], max_new_tokens=2)
    engine.generate_all()
    st = engine.stats
    assert (st["steps_prefill"], st["steps_lone"], st["steps_burst"]) == \
        (1, 1, 0)
    assert st["step_tokens_lone"] == 1 == st["burst_refused_budget"]
    engine.close()


def test_the_flight_recorders_row_of_a_step_is_its_record(devices):
    engine = _engine()
    mark = len(engine._flight.events())
    steps, tokens = _run(engine)
    rows = [f for _, kind, f in engine._flight.events()[mark:]
            if kind == "serve_step"]
    # one a step that read a call: the empty step leaves none
    assert [r["step_kind"] for r in rows] == [
        "prefill", "burst", "mixed", "mixed", "burst", "burst"]
    assert sum(r["emitted"] for r in rows) == tokens
    for r in rows:
        assert set(r) == {"step_kind", "tokens", "emitted", "wall_ms",
                          "wait_ms"} | {p + "_ms" for p in PHASES}
        assert r["wait_ms"] == r["fetch_ms"] <= r["wall_ms"]
        assert sum(r[p + "_ms"] for p in PHASES) <= r["wall_ms"] + 0.01
    # the rows a step's calls carried: two chunks; a burst of 4 x 2 rows;
    # two token rows beside what the budget of 32 leaves a chunk, then
    # beside the prompt's last 10
    assert [r["tokens"] for r in rows[:4]] == [25, 8, 2 + 30, 2 + 10]
    engine.close()


def test_a_burst_read_outside_a_step_counts_in_the_step_that_delivers_it(
        devices):
    """A full batch keeps a burst in flight; ``snapshot`` reads it between
    two steps (_drain): its call, rows and tokens wait on the record, and
    the next step, which hands the tokens out, books them."""
    engine = _engine(max_seqs_per_step=1)
    engine.put([1], [_prompt(6, 1)], max_new_tokens=14)
    engine.serve_step()                     # the prompt's step: one token
    first = engine.serve_step()             # issues two bursts, reads one
    assert len(first[1]) == K and engine._inflight is not None
    bursts = engine.stats["steps_burst"]
    engine.snapshot()
    assert engine._inflight is None
    assert engine._rec.calls == [("multi_decode", K)] and \
        engine._rec.tokens == K
    assert engine.stats["steps_burst"] == bursts        # no step closed
    out = engine.serve_step()
    assert len(out[1]) == 2 * K             # the drained burst's and its own
    assert engine.stats["steps_burst"] == bursts + 1
    engine.generate_all()
    st = engine.stats
    assert sum(st[f"step_tokens_{k}"] for k in STEP_KINDS) == 14
    engine.close()


def test_phases_do_not_nest(devices):
    engine = _engine()
    with engine._open_step():
        with engine._phase("admit"):
            with pytest.raises(RuntimeError, match="do not nest"):
                engine._phase("schedule")
    assert engine.stats["steps_empty"] == 1
    engine.close()


# -- the plan says why ---------------------------------------------------------

REASONS = (["burst_refused_" + r for r in BURST_REFUSALS]
           + ["ahead_refused_" + r for r in AHEAD_REFUSALS]
           + ["bursts_planned"])


def _decoding(n, max_new=12, prompt=7):
    """An engine of four slots with ``n`` sequences in decode, each one
    token into its answer, and the call that would be in flight."""
    engine = _engine()
    engine.put(list(range(n)), [_prompt(prompt, i) for i in range(n)],
               max_new_tokens=max_new)
    engine.step()
    live = list(engine.state.seqs.values())
    assert len(live) == n and all(s.in_decode for s in live)
    flight = _BurstInFlight(live, K, None, None, None, {}, None)
    return engine, flight


def _pending(engine, flight):
    engine.put([9], [_prompt(5, 9)], max_new_tokens=4)


def _no_pool(engine, flight):
    engine.kv_cache.allocator.allocate(engine.kv_cache.free_blocks)


def _seq_cap(engine, flight):
    engine.state.max_blocks_per_seq = 1


def _drafter(engine, flight):
    engine._drafter = object()


def _queued(engine, flight):
    engine._queue.append(object())


def _reordered(engine, flight):
    flight.live.reverse()


# reason -> (sequences live of four slots, new tokens asked, ahead of a call
# in flight?, what the test does to the engine first)
CASES = {
    "burst_refused_prefill_pending": (2, 12, False, _pending),
    "burst_refused_budget": (2, 2, False, None),
    "burst_refused_seq_cap": (2, 12, False, _seq_cap),
    "burst_refused_pool": (2, 12, False, _no_pool),
    "ahead_refused_drafter": (4, 12, True, _drafter),
    "ahead_refused_queue": (4, 12, True, _queued),
    "ahead_refused_free_slot": (2, 12, True, None),
    "ahead_refused_batch_changed": (4, 12, True, _reordered),
    "ahead_refused_budget": (4, 2 + K, True, None),
    "ahead_refused_seq_cap": (4, 12, True, _seq_cap),
    "ahead_refused_pool": (4, 12, True, _no_pool),
}


@pytest.mark.parametrize("reason", REASONS)
def test_each_return_of_the_plan_moves_exactly_one_reason(devices, reason):
    n, max_new, ahead, prepare = CASES.get(reason, (4, 12, False, None))
    engine, flight = _decoding(n, max_new)
    if prepare is not None:
        prepare(engine, flight)
    before = {r: engine.stats[r] for r in REASONS}
    got = engine._plan_decode_burst(flight if ahead else None)
    moved = {r: engine.stats[r] - before[r] for r in REASONS
             if engine.stats[r] != before[r]}
    assert moved == {reason: 1}
    assert (got is None) == (reason != "bursts_planned")
    assert engine.stats["burst_steps_clamped"] == 0
    if reason == "bursts_planned":
        # ... ahead of the call in flight too, clamped to the budget left
        assert got == K and engine._plan_decode_burst(flight) == K
        assert engine._plan_decode_burst(_BurstInFlight(
            flight.live, 2 * K, None, None, None, {}, None)) == 12 - 1 - 2 * K
        assert engine.stats["bursts_planned"] == before[reason] + 3
        assert engine.stats["burst_steps_clamped"] == K - 3
    engine._queue.clear()
    engine._drafter = None
    engine.close()


def test_a_plan_that_does_not_arise_counts_nothing(devices):
    engine = _engine()
    assert engine._plan_decode_burst() is None            # nothing live
    single, flight = _decoding(2)
    single.decode_steps = 1                               # bursts off
    assert single._plan_decode_burst() is None
    assert single._plan_decode_burst(flight) is None
    for e in (engine, single):
        assert not any(e.stats[r] for r in REASONS)
        e.close()


# -- nothing here touches a program ----------------------------------------------

DIGESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "step_program_digests.json")


def step_program_digests():
    """sha256 of the lowered text of the four step programs of the file's
    engine. To write the file anew after a change that means to change a
    program: ``python -c "import tests.conftest, tests.test_step_record
    as t; t.write_digests()"`` from the root (``tests/conftest.py`` first:
    the suite's environment, the CPU with 8 devices)."""
    engine = _engine()
    pool, S = engine.kv_cache.kv_state, engine.max_seqs
    i32 = jnp.int32
    rows = jnp.zeros(S, i32)
    table = jnp.zeros((S, engine.max_blocks_per_seq), i32)
    flat = jnp.zeros(engine.max_tokens, i32)
    args = {
        "gather": (engine._step_fn, (flat, flat, flat, table,
                                     jnp.asarray(0, i32)), {}),
        "decode": (engine._decode_fn, (rows, rows, table, rows), {}),
        "prefill": (engine._prefill_fn, (
            jnp.zeros((2, 32), i32), rows[:2], rows[:2], table[:2]), {}),
        "multi_decode": (engine._multi_decode_fn,
                         (rows, rows, table, rows), {"steps": K}),
    }
    out = {}
    with engine.mesh:
        for name, (fn, rest, kw) in args.items():
            text = fn.lower(engine.params, pool, *rest, **kw,
                            **engine.kv_cache.step_args([], S)).as_text()
            out[name] = hashlib.sha256(text.encode()).hexdigest()
    engine.close()
    return out


def write_digests():
    with open(DIGESTS, "w") as f:
        json.dump(step_program_digests(), f, indent=1)
        f.write("\n")


def test_the_step_programs_lowered_text_is_the_pinned_one(devices):
    """The record, the plan's counters and the hub's provider are host
    code: the four programs lower to the text they lowered to before them,
    byte for byte (the digests were written from the parent of the PR that
    added the record)."""
    with open(DIGESTS) as f:
        assert step_program_digests() == json.load(f)

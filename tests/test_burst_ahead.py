"""A full batch's decode burst is dispatched one call ahead
(``InferenceEngineV2._burst_step``): the tokens are those of an engine that
reads every call before it issues the next, an end of sequence found late
costs only thrown-away rows, the engine stays one call deep wherever an
arrival could be admitted, and whatever touches the engine from outside a
step reads the call in flight first."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2, _BurstInFlight
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

F32 = jnp.float32
K = 4                       # decode_steps of every engine here
KINDS = ["tiny", "tiny-hybrid"]
_MODELS = {}


def _model(kind):
    if kind not in _MODELS:
        m = get_model(kind, param_dtype=F32, dtype=F32)
        _MODELS[kind] = (m, m.init(jax.random.PRNGKey(0)))
    return _MODELS[kind]


def _engine(kind, **kw):
    model, params = _model(kind)
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    args = dict(kv_blocks=64, kv_block_size=16, max_tokens_per_step=32,
                max_seqs_per_step=2, max_blocks_per_seq=8, decode_steps=K,
                prefix_cache=False)
    if kind != "tiny":
        args["state_slots"] = 2
    args.update(kw)
    return InferenceEngineV2(model, mesh=mesh, params=params, dtype=F32,
                             **args)


def _one_call_deep(engine):
    """The same engine, never running ahead: its rule says no."""
    plan = engine._plan_decode_burst
    engine._plan_decode_burst = \
        lambda after=None: None if after is not None else plan()
    return engine


def _prompts(n=2, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 200, 5 + 3 * i).astype(np.int32)
            for i in range(n)]


def _serve(engine, max_new, uids=(1, 2), eos=None):
    engine.put(list(uids), _prompts(len(uids)), max_new_tokens=max_new)
    return engine.generate_all(eos_token_id=eos)


@pytest.mark.parametrize("kind", KINDS)
def test_full_batch_tokens_are_those_of_one_call_deep_and_of_single_steps(
        devices, kind):
    ahead = _engine(kind)
    got = _serve(ahead, 22)
    deep = _one_call_deep(_engine(kind))
    assert got == _serve(deep, 22)
    assert got == _serve(_engine(kind, decode_steps=1), 22)
    assert {u: len(t) for u, t in got.items()} == {1: 22, 2: 22}
    # 1 token from the prompt's step, then 5 bursts of 4 and a single step:
    # every burst but the first follows one that was still unread
    st = ahead.stats
    assert (st["calls_multi_decode"], st["calls_issued_ahead"]) == (5, 4)
    assert deep.stats["calls_multi_decode"] == 5
    assert deep.stats["calls_issued_ahead"] == 0
    assert st["ahead_rows_discarded"] == 0 and ahead._inflight is None
    for k in ("tokens_multi_decode", "token_steps_multi_decode",
              "rows_multi_decode", "decode_kernel_steps", "burst_steps"):
        assert st[k] == deep.stats[k], k
    if kind != "tiny":          # each call's own counters, all of them
        assert st["moe_token_layers"] == deep.stats["moe_token_layers"] > 0
        assert st["moe_local_pairs_decode"] == \
            deep.stats["moe_local_pairs_decode"] > 0
    ahead.close(), deep.close()


@pytest.mark.parametrize("kind", KINDS)
def test_a_steps_counters_move_with_the_tokens_it_returns(devices, kind):
    """The step after an admission issues two calls and returns one: its
    counters show one. ``token_steps / calls`` of the bursts is K."""
    engine = _engine(kind)
    engine.put([1, 2], _prompts(), max_new_tokens=40)
    keys = ("decode_kernel_steps", "burst_steps", "calls_multi_decode",
            "token_steps_multi_decode", "tokens_multi_decode",
            "calls_issued_ahead")
    steps = []
    for _ in range(6):
        before = {k: engine.stats[k] for k in keys}
        out = engine.serve_step()
        steps.append(({k: engine.stats[k] - before[k] for k in keys},
                      {u: len(t) for u, t in out.items()}))
    assert steps[0][1] == {1: 1, 2: 1}                 # the prompts' step
    for delta, out in steps[1:]:
        assert out == {1: K, 2: K}
        assert delta == dict(decode_kernel_steps=K, burst_steps=1,
                             calls_multi_decode=1, token_steps_multi_decode=K,
                             tokens_multi_decode=2 * K, calls_issued_ahead=1)
    # six bursts issued, five read
    assert engine._inflight is not None
    assert engine._calls_issued == engine.stats["calls_prefill"] + 6
    engine.close()
    assert engine._inflight is None
    assert {u: len(t) for u, t in engine.take_undelivered().items()} == \
        {1: K, 2: K}


@pytest.mark.parametrize("kind", KINDS)
def test_an_end_of_sequence_found_late_throws_rows_away_and_nothing_else(
        devices, kind):
    base = _serve(_engine(kind), 30)
    # an id of request 1's that a burst after the first emits, at a place
    # inside the call, and that request 1 has not emitted before
    at = next(j for j in range(K + 2, 30) if (j - 1) % K not in (0, K - 1)
              and base[1][j] not in base[1][:j] + base[2])
    eos = base[1][at]
    ahead, deep = _engine(kind), _one_call_deep(_engine(kind))
    for engine in (ahead, deep):
        engine.put([1, 2], _prompts(), max_new_tokens=30)
    got = {1: [], 2: []}
    while len(got[1]) <= at:
        for uid, toks in ahead.serve_step(eos_token_id=eos).items():
            got[uid].extend(toks)
    assert got[1] == base[1][:at + 1]                  # ends at that id
    # the call after is in flight, with rows of request 1's, which holds its
    # pages and its slot until that call is read
    assert ahead._inflight is not None and 1 in ahead.state.seqs
    newcomer = _prompts(3)[2]
    for engine in (ahead, deep):
        engine.put([3], [newcomer], max_new_tokens=9)
    for uid, toks in ahead.generate_all(eos_token_id=eos).items():
        got.setdefault(uid, []).extend(toks)
    assert got == deep.generate_all(eos_token_id=eos)
    assert ahead.stats["ahead_rows_discarded"] == K
    assert deep.stats["ahead_rows_discarded"] == 0
    # into the freed pages and slot, behind the stray rows: as if alone
    alone = _engine(kind)
    alone.put([3], [newcomer], max_new_tokens=9)
    assert got[3] == alone.generate_all(eos_token_id=eos)[3]
    assert ahead.kv_cache.free_blocks == alone.kv_cache.free_blocks


@pytest.mark.parametrize("max_new,calls,ahead", [
    (1 + K + 1, 1, 0),        # the follow-on would be one step: not a burst
    (1 + K + 2, 2, 1),        # cut to the budget, and nothing after it
    (1 + 2 * K, 2, 1),        # ends with the call in flight: none after it
    (1 + 2 * K + 2, 3, 2)])
def test_a_budget_that_ends_in_the_call_in_flight_stops_the_engine(
        devices, max_new, calls, ahead):
    engine = _engine("tiny")
    got = _serve(engine, max_new)
    assert got == _serve(_engine("tiny", decode_steps=1), max_new)
    assert {u: len(t) for u, t in got.items()} == {1: max_new, 2: max_new}
    st = engine.stats
    assert (st["calls_multi_decode"], st["calls_issued_ahead"]) == \
        (calls, ahead)
    assert st["ahead_rows_discarded"] == 0 and not engine.state.seqs


def test_a_free_slot_or_a_queued_request_keeps_the_engine_one_call_deep(
        devices):
    roomy = _engine("tiny", max_seqs_per_step=3)
    _serve(roomy, 22)
    assert roomy.stats["calls_issued_ahead"] == 0
    assert roomy.stats["calls_multi_decode"] == 5
    # three requests for two slots: the third waits, and while it does no
    # call is issued ahead; once it is in, the batch is full again
    queued = _engine("tiny")
    first, *others = _prompts(3)
    queued.put([1], [first], max_new_tokens=10)
    queued.put([2, 3], others, max_new_tokens=22)
    third = []
    while queued._queue:
        third += queued.serve_step().get(3, [])
        assert queued._inflight is None
        assert queued.stats["calls_issued_ahead"] == 0
    assert len(third + queued.generate_all()[3]) == 22
    assert queued.stats["calls_issued_ahead"] > 0


OPS = ["flush", "page_out", "snapshot", "migrate_out_session",
       "reload_params", "close", "step"]


@pytest.mark.parametrize("op", OPS)
def test_what_touches_the_engine_between_steps_reads_the_call_in_flight(
        devices, op):
    kw = {"host_kv_tier": True, "host_tier_mb": 8} if op == "page_out" else {}
    base = _serve(_engine("tiny", **kw), 22)
    engine = _engine("tiny", **kw)
    engine.put([1, 2], _prompts(), max_new_tokens=22)
    got = {1: [], 2: []}

    def take(out):
        for uid, toks in out.items():
            got[uid].extend(toks)

    for _ in range(3):
        take(engine.serve_step())
    assert engine._inflight is not None
    assert [len(t) for t in got.values()] == [1 + 2 * K] * 2
    calls = engine.stats["calls_multi_decode"]
    gone = ()
    if op == "flush":
        engine.flush([1])
        gone = (1,)
    elif op == "page_out":
        assert engine.page_out(1)
    elif op == "snapshot":
        assert engine.snapshot()["stats"]["calls_multi_decode"] == calls + 1
    elif op == "migrate_out_session":
        cap = engine.migrate_out_session(1)
        assert cap["generated"] == base[1][:1 + 3 * K]
        gone = (1,)
    elif op == "reload_params":
        engine.reload_params(_model("tiny")[1])
    elif op == "close":
        engine.close()
    elif op == "step":
        single = engine.step()                      # one token each, after
        take(engine.take_undelivered())             # the burst's
        take({uid: [tok] for uid, tok in single.items()})
        assert [len(t) for t in got.values()] == [1 + 3 * K + 1] * 2
    assert engine._inflight is None
    assert engine.stats["calls_multi_decode"] == calls + 1
    if op == "close":
        take(engine.take_undelivered())
        assert got == {u: t[:1 + 3 * K] for u, t in base.items()}
        return
    take(engine.generate_all())
    for uid in (1, 2):
        if uid not in gone:
            assert got[uid] == base[uid], uid
    if op == "migrate_out_session":     # what it had emitted, it delivered
        assert got[1] == base[1][:1 + 3 * K]
    assert not engine.state.seqs and not engine._undelivered


# -- a burst is planned against what the pool can give ---------------------------
#
# Engines of 8-token blocks with the prefix cache on. A finished request leaves
# its full prompt blocks idle in the cache, so the allocator's free list drains
# while the pool does not: the burst plan counts the idle entries
# (``kv_cache.available_blocks``) and takes what the free list lacks at once.

PLAN = ("bursts_planned", "bursts_reclaiming", "burst_blocks_reclaimed",
        "burst_refused_pool", "ahead_refused_pool")


def _pooled(**kw):
    args = dict(kv_block_size=8, prefix_cache=True, max_blocks_per_seq=8)
    args.update(kw)
    return _engine("tiny", **args)


def _doc(seed, n=17):
    """A prompt of two full blocks and a token: two idle entries once its
    request has finished."""
    return np.random.default_rng(100 + seed).integers(0, 200, n) \
        .astype(np.int32)


def _finish(engine, uid, prompt, max_new=2):
    engine.put([uid], [prompt], max_new_tokens=max_new)
    return engine.generate_all()[uid]


def _drained(engine):
    """Seven finished requests' prompt blocks idle in the cache; the free
    list holds what is left."""
    for i in range(7):
        _finish(engine, 100 + i, _doc(i))
    cache = engine.kv_cache.prefix_cache
    assert cache.evictable_blocks == 14 == cache.cached_blocks


@pytest.mark.parametrize("path", ["blocking", "ahead"])
def test_a_free_list_drained_by_idle_prompt_blocks_still_plans_full_bursts(
        devices, path):
    """Sixteen usable blocks: fourteen idle entries of seven finished
    requests and two for the prompts of two answers of 22 tokens, which end
    in their fourth blocks. The bursts take six idle entries; the tokens are
    those of single steps."""
    def serve(**kw):
        engine = _pooled(kv_blocks=17, **kw)
        if path == "blocking":
            _one_call_deep(engine)
        _drained(engine)
        assert engine.kv_cache.free_blocks == 2
        return engine, _serve(engine, 22)

    engine, got = serve()
    single, want = serve(decode_steps=1)
    assert got == want
    assert {u: len(t) for u, t in got.items()} == {1: 22, 2: 22}
    st = engine.stats
    # five bursts of K, each a full one, none refused for the pool
    assert st["bursts_planned"] == st["calls_multi_decode"] == 5
    assert st["burst_steps_clamped"] == 0
    assert st["burst_refused_pool"] == st["ahead_refused_pool"] == 0
    assert st["calls_issued_ahead"] == (4 if path == "ahead" else 0)
    # every block past the prompts' own comes from the idle entries, a
    # burst's at once: a border a sequence every other burst
    assert st["burst_blocks_reclaimed"] == 6
    assert st["bursts_reclaiming"] == 3
    # ... and the second prompt is a full block, cached in its turn
    assert engine.kv_cache.prefix_cache.cached_blocks == 14 - 6 + 1
    assert st["preempted"] == single.stats["preempted"] == 0
    assert not any(single.stats[k] for k in PLAN)
    engine.close(), single.close()


def _two_decoding(engine, max_new=12):
    """A full batch in decode, one token into its answers, a block border
    inside the next burst and none in the next token; and the call that
    would be in flight."""
    engine.put([1, 2], [_doc(9, 7), _doc(8, 7)], max_new_tokens=max_new)
    engine.step()
    live = list(engine.state.seqs.values())
    assert [s.seen_tokens for s in live] == [7, 7]
    return _BurstInFlight(live, K, None, None, None, {}, None)


@pytest.mark.parametrize("ahead", [False, True], ids=["blocking", "ahead"])
@pytest.mark.parametrize("idle,free,planned", [
    (2, 0, True),       # the free list empty, the idle entries are enough
    (1, 1, True),       # one block of each
    (0, 0, False),      # nothing anywhere (``_no_pool`` of test_step_record)
    (1, 0, False),      # one short with every idle entry counted
])
def test_the_pool_refuses_only_what_free_and_idle_blocks_cannot_hold(
        devices, ahead, idle, free, planned):
    engine = _pooled(kv_blocks=33)
    if idle:
        _finish(engine, 100, _doc(0, 1 + 8 * idle))
    flight = _two_decoding(engine)
    # a burst of K, on top of the call in flight too, crosses one border a
    # sequence: 7 + K and 7 + 2 K tokens end in the second block
    need = 2
    cache, pool = engine.kv_cache.prefix_cache, engine.kv_cache
    pool.allocator.allocate(pool.free_blocks - free)
    assert (pool.free_blocks, cache.evictable_blocks) == (free, idle)
    assert pool.available_blocks == free + idle
    before = {k: engine.stats[k] for k in PLAN}
    got = engine._plan_decode_burst(flight if ahead else None)
    moved = {k: engine.stats[k] - before[k] for k in PLAN
             if engine.stats[k] != before[k]}
    if planned:
        assert got == K
        assert moved == {"bursts_planned": 1, "bursts_reclaiming": 1,
                         "burst_blocks_reclaimed": need - free}
        assert (pool.free_blocks, cache.evictable_blocks) == \
            (0, idle - (need - free))
    else:
        assert got is None
        assert moved == {("ahead" if ahead else "burst") + "_refused_pool": 1}
        # the refusal took nothing, and the single step it leaves to run
        # needs no block: nobody is preempted
        assert (pool.free_blocks, cache.evictable_blocks) == (free, idle)
        engine.step()
        assert engine.stats["preempted"] == 0 and len(engine.state.seqs) == 2
    engine.close()


def test_a_burst_plan_never_takes_a_prefix_a_live_sequence_holds(devices):
    """Two requests on one document, one finished; beside them the idle
    entries of two other documents. The bursts of the one that goes on take
    the idle entries and leave the document it reads; a later request on an
    evicted document computes it again. Every answer is that of an engine
    without the cache."""
    doc, old = _doc(0, 16), [_doc(1), _doc(2)]
    asks = [np.concatenate([doc, _doc(10 + i, 3)]) for i in range(3)]

    def serve(**kw):
        engine = _one_call_deep(_pooled(**kw))
        out = [_finish(engine, 100 + i, p) for i, p in enumerate(old)]
        out.append(_finish(engine, 1, asks[0]))
        engine.put([2], [asks[1]], max_new_tokens=2)
        engine.put([3], [asks[2]], max_new_tokens=36)
        got = engine.generate_all()
        out += [got[2], got[3]]
        held = [engine.holds_prefix_blocks(p) for p in asks[:1] + old]
        out.append(_finish(engine, 4, old[0], 6))
        return engine, out, held

    # 9 usable blocks: the document's 2, the others' 4, and the 5 the long
    # answer ends with beside its document: its bursts have to evict 2
    engine, got, held = serve(kv_blocks=10)
    plain, want, _ = serve(kv_blocks=64, prefix_cache=False)
    assert got == want
    st = engine.stats
    assert st["prefix_hit_tokens"] >= 3 * 16 - 16     # both later asks hit
    assert st["bursts_reclaiming"] >= 1 and st["burst_refused_pool"] == 0
    assert st["burst_blocks_reclaimed"] == 2
    # the least recently idle went, the live document never
    assert held == [2, 0, 2]
    assert st["preempted"] == 0
    engine.close(), plain.close()


def test_under_a_host_tier_only_the_blocking_plan_pages_out(devices):
    """``reclaim`` under a host tier reads the pool, which would wait for
    the call in flight: the plan ahead keeps to the free list, the blocking
    plan pages the idle chain out, and a request that returns to it pages
    it back in, with the tokens of an engine that never evicted."""
    def fresh():
        return _pooled(kv_blocks=33, host_kv_tier=True, host_tier_mb=8)

    engine, roomy = fresh(), fresh()
    pool, tier = engine.kv_cache, engine.kv_cache.host_tier
    first = _finish(engine, 100, _doc(0), 4)
    flight = _two_decoding(engine, max_new=22)
    held = pool.allocator.allocate(pool.free_blocks)
    assert pool.available_blocks == pool.prefix_cache.evictable_blocks == 2
    assert engine._plan_decode_burst(flight) is None
    assert engine.stats["ahead_refused_pool"] == 1
    assert tier.stats["chain_blocks_out"] == 0
    assert pool.prefix_cache.evictable_blocks == 2
    got = engine.serve_step()                   # a blocking plan: pages out
    assert engine.stats["burst_blocks_reclaimed"] == 2
    assert engine.stats["burst_refused_pool"] == 0
    assert tier.stats["chain_blocks_out"] == 2
    assert pool.prefix_cache.cached_blocks == 0
    assert engine.holds_prefix_blocks(_doc(0)) == 2      # in the tier
    pool.free(held)
    for uid, toks in engine.generate_all().items():
        got[uid] = got[uid] + toks
    again = _finish(engine, 101, _doc(0), 4)
    assert tier.stats["chain_blocks_in"] == 2
    assert first == again == _finish(roomy, 100, _doc(0), 4)
    _two_decoding(roomy, max_new=22)
    want = roomy.generate_all()
    assert want == got
    assert roomy.kv_cache.host_tier.stats["chain_blocks_out"] == 0
    assert engine.stats["preempted"] == roomy.stats["preempted"] == 0
    engine.close(), roomy.close()

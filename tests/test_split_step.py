"""A step split by program, against the gather program: a dense model's and
that of a recurrent model that has a gather program (``tiny-hybrid``: gated
DeltaNet, full attention and experts).

On the kernel path ``InferenceEngineV2._split_by_program`` sends the rows
that advance one token through the decode program and the chunks through
the prefill program over their own sequences' pages (plain products a
segment, ``model_runner._segment_attention``, for both runners), several
chunks a call while the call's padded layout stays within twice the step's
budget. Here, on the CPU with interpreter kernels, the same schedule runs
both ways (``_use_paged_kernel`` flipped, as ``tests/test_kv_in_place.py``
does): the same tokens come out, and the logits rows they were picked from
agree.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.runners.serve import LogitsTap
from deepspeed_tpu.inference import engine_v2
from deepspeed_tpu.inference.engine_v2 import PROGRAMS
from deepspeed_tpu.inference.hybrid_runner import COUNTERS
from deepspeed_tpu.models.zoo import get_model

F32, BF16 = jnp.float32, jnp.bfloat16

MODELS = {
    # multi-head, learned positions, LayerNorm, GELU (the preset as it is)
    "mha": {},
    # grouped queries, rotary, RMSNorm, SwiGLU, untied head (Mistral's form)
    "gqa": dict(num_kv_heads=2, pos_emb="rope", norm="rmsnorm",
                activation="swiglu", tie_embeddings=False),
    # one KV head, biases, Falcon's parallel block
    "mqa": dict(num_kv_heads=1, pos_emb="rope", use_biases=True,
                parallel_block=True),
}


def _model(name, dtype=F32, seed=3):
    model = get_model("tiny", dtype=dtype, param_dtype=dtype, **MODELS[name])
    return model, model.init(jax.random.PRNGKey(seed))


def _engine(model, params, kernel, dtype=F32, **kw):
    args = dict(kv_blocks=96, kv_block_size=8, max_tokens_per_step=32,
                max_seqs_per_step=4, max_blocks_per_seq=8, decode_steps=1,
                prefix_cache=False, dtype=dtype)
    args.update(kw)
    eng = engine_v2.InferenceEngineV2(model, params=params, **args)
    eng._use_paged_kernel = kernel
    return eng


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 250, n).astype(np.int32)


class Drive:
    """One engine under a script; per step what was scheduled ((uid, new
    tokens, start) a row), the tokens emitted, and the logits row behind
    each."""

    def __init__(self, eng):
        self.eng, self.log = eng, []

    def put(self, uids, prompts, max_new):
        self.eng.put(list(uids), list(prompts), max_new_tokens=max_new)

    def step(self, n=1):
        for _ in range(n):
            with LogitsTap(self.eng) as tap:
                schedule = self.eng.scheduler.schedule

                def scheduled():
                    out = schedule()
                    self._rows = [(s.uid, len(nt), sp) for s, nt, sp in out]
                    return out

                self.eng.scheduler.schedule = scheduled
                emitted = self.eng.step()
            rows = {uid: tap.rows[-1][tap.slots[-1].index(uid)]
                    for uid in emitted}
            self.log.append((self._rows, emitted, rows))

    def finish(self, limit=64):
        while self.eng.state.seqs or self.eng._queue:
            self.step()
            limit -= 1
            assert limit > 0, "the engine does not finish"


def _same(split: Drive, gather: Drive, atol, rel=None, rows_at_least=0):
    """Step by step: one schedule, the same tokens, the rows they were
    picked from equal to ``atol`` (float32) or within ``rel`` of the row's
    norm (bf16). In bf16 the two programs round in another order and may
    pick another token at a near-tie: the histories part there, so the
    comparison ends, and ``rows_at_least`` rows must have been compared."""
    compared = 0
    for (rows_s, out_s, lg_s), (rows_g, out_g, lg_g) in zip(split.log,
                                                           gather.log):
        assert rows_s == rows_g                 # one schedule
        for uid in out_g:
            a, b = lg_s[uid], lg_g[uid]
            if rel is None:
                np.testing.assert_allclose(a, b, atol=atol, rtol=atol)
            else:
                bound = rel * np.linalg.norm(b)
                assert np.linalg.norm(a - b) <= bound, uid
                assert b.max() - b[out_s[uid]] <= bound, uid    # a near-tie
            compared += 1
        if rel is not None and out_s != out_g:
            break
        assert out_s == out_g
    else:
        assert len(split.log) == len(gather.log)
    assert compared >= rows_at_least, compared
    assert split.eng.stats["tokens_gather"] == 0
    assert gather.eng.stats["tokens_gather"] > 0
    split.eng.close(), gather.eng.close()


def _chunk_script(d: Drive):
    """Two and four chunks a step, three that do not pad into one call, a
    chunk over a page border, one that ends its prompt and one that does
    not, a chunk behind a prefix hit, and a dead slot between live ones.
    Pages of 8, 32 tokens and 4 sequences a step (so a call's padded
    layout may hold 64 rows)."""
    # two chunks: 13 tokens cross the border between pages 0 and 1
    d.put([1, 2], [_prompt(13, 1), _prompt(5, 2)], 3)
    d.step(2)
    # two token rows and two chunks, then four sequences in decode
    d.put([3, 4], [_prompt(9, 3), _prompt(6, 4)], 4)
    d.finish()
    # four chunks in one step
    d.put([5, 6, 7, 8], [_prompt(9, 5), _prompt(3, 6), _prompt(9, 7),
                         _prompt(6, 8)], 2)
    d.step()
    assert sorted(n for _, n, _ in d.log[-1][0]) == [3, 6, 9, 9]
    d.finish()
    # three chunks, two calls: 20 and 9 pad to 2 x 32 rows, a third
    # sequence would make that 4 x 32
    d.put([30, 31, 32], [_prompt(20, 30), _prompt(9, 31), _prompt(3, 32)], 2)
    d.step()
    assert [n for _, n, _ in d.log[-1][0]] == [20, 9, 3]
    d.finish()
    # a prompt over the step's budget: 32 that do not end it, 13 that do
    d.put([9], [_prompt(45, 9)], 2)
    d.step(2)
    assert [r[0] for r in d.log[-2:]] == [[(9, 32, 0)], [(9, 13, 32)]]
    assert not d.log[-2][1] and list(d.log[-1][1]) == [9]
    d.finish()
    # behind a prefix hit: the second prompt shares two whole pages
    if d.eng.kv_cache.prefix_cache is not None:
        doc = _prompt(16, 10)
        d.put([10], [np.concatenate([doc, _prompt(7, 11)])], 2)
        d.finish()
        d.put([11], [np.concatenate([doc, _prompt(5, 12)])], 2)
        d.step()
        assert d.log[-1][0] == [(11, 5, 16)]
        d.finish()
    # a dead slot between live ones: the middle sequence ends first, a
    # prompt arrives, and the step holds two token rows and a chunk
    d.put([20], [_prompt(4, 20)], 8)
    d.put([21], [_prompt(4, 21)], 2)
    d.put([22], [_prompt(4, 22)], 8)
    d.step(3)
    assert 21 not in d.eng.state.seqs
    d.put([23], [_prompt(11, 23)], 2)
    d.step()
    assert d.log[-1][0] == [(20, 1, 6), (22, 1, 6), (23, 11, 0)]
    d.finish()


def _calls_beyond_one_a_step(stats):
    """Program calls over one a step: what steps split by program add."""
    return sum(stats[f"calls_{p}"] for p in PROGRAMS) - stats[
        "steps_dispatched"]


def _both(model, params, script, **kw):
    split, gather = (Drive(_engine(model, params, kernel, **kw))
                     for kernel in (True, False))
    script(split), script(gather)
    return split, gather


@pytest.mark.parametrize("name", ["mha", "gqa", "mqa"])
def test_split_step_matches_the_gather_program(name):
    model, params = _model(name)
    split, gather = _both(model, params, _chunk_script, prefix_cache=True)
    st = split.eng.stats
    assert st["prefix_hit_tokens"] == 16 == gather.eng.stats[
        "prefix_hit_tokens"]
    # every step's chunks were one call of the prefill program, but for
    # the three that do not pad into one: two
    steps_with_chunk = sum(any(n > 1 for _, n, _ in rows)
                           for rows, _, _ in split.log)
    assert steps_with_chunk == 10
    assert st["prefill_chunk_calls"] == steps_with_chunk + 1
    assert st["prefill_gather_fallbacks"] == 0
    assert st["prefill_kernel_steps"] == steps_with_chunk
    # more than one call: token rows beside a chunk (twice), and those two
    assert _calls_beyond_one_a_step(st) == 3 == 1 + sum(
        len({n == 1 for _, n, _ in rows}) == 2 for rows, _, _ in split.log)
    assert st["calls_prefill"] == st["prefill_chunk_calls"]
    assert st["calls_gather"] == 0 < st["calls_decode"]
    got = gather.eng.stats
    assert _calls_beyond_one_a_step(got) == 0 == got["prefill_chunk_calls"]
    assert got["calls_prefill"] == 0 == got["calls_decode"]
    assert got["calls_gather"] > 0
    _same(split, gather, 1e-5)


@pytest.mark.parametrize("lens,calls", [
    # one call while S x tq (each bucketed to a power of two, tq from 8)
    # stays within 2 x 32 rows
    ([9, 3, 9, 6], [[0, 1, 2, 3]]),               # 4 x 16
    ([20, 9, 3], [[0, 1], [2]]),                  # 2 x 32, not 4 x 32
    ([17, 2, 2, 2], [[0, 1], [2, 3]]),
    ([30, 30], [[0, 1]]),
    # token rows first, all of them one call of the decode program
    ([1, 20, 1, 9], [[0, 2], [1, 3]]),
    ([1, 1, 1], [[0, 1, 2]]),
])
def test_chunks_share_a_call_while_its_padding_stays_in_budget(lens, calls):
    model, params = _model("mha")
    scheduled = [(None, [0] * n, 0) for n in lens]
    eng = _engine(model, params, True)
    assert eng._split_by_program(scheduled) == calls
    eng._use_paged_kernel = False           # the gather program: one part
    assert eng._split_by_program(scheduled) == [list(range(len(lens)))]
    eng.close()


@pytest.mark.parametrize("chunks", [[20], [5, 6, 9]])
def test_token_rows_beside_chunks(chunks):
    """31 (29) sequences in decode and one prompt (three) arriving: one
    call of the decode program, one of the prefill program."""
    model, params = _model("gqa")
    rows_before = 32 - len(chunks)

    def script(d):
        d.put(range(rows_before), [_prompt(1, i) for i in range(rows_before)],
              6)
        d.step(2)
        d.put(range(90, 90 + len(chunks)),
              [_prompt(n, 90 + i) for i, n in enumerate(chunks)], 3)
        d.step()
        rows = d.log[-1][0]
        assert sorted(n for _, n, _ in rows) == [1] * rows_before + sorted(
            chunks)
        assert len(d.log[-1][1]) == 32            # every row emits
        d.finish()

    split, gather = _both(model, params, script, max_seqs_per_step=32,
                          max_tokens_per_step=64, kv_blocks=160)
    st = split.eng.stats
    assert (_calls_beyond_one_a_step(st), st["calls_prefill"]) == (1, 1)
    assert st["prefill_chunk_calls"] == 1
    # the call carried the chunks' rows, padded to S x tq
    S = 1 if len(chunks) == 1 else 4
    assert st["rows_prefill"] == sum(chunks)
    assert st["padded_rows_prefill"] == S * (32 if max(chunks) > 16 else 16)
    assert st["tokens_prefill_kernel"] == len(chunks)
    assert st["tokens_decode"] == rows_before * 6 + len(chunks) * 2
    _same(split, gather, 1e-5)


@pytest.mark.parametrize("pool", ["int8", "bf16"])
def test_split_step_on_an_int8_pool_and_in_bf16(pool):
    """An int8 pool is dequantized on read by both programs, from the same
    payload: float32 agreement. In bf16 the two programs round in another
    order: the bf16 bound (2 % of the row's norm)."""
    if pool == "int8":
        model, params = _model("gqa")
        split, gather = _both(model, params, _chunk_script, kv_quant_bits=8)
        _same(split, gather, 1e-5)
    else:
        # a draw at which no pick of the script is a near-tie (of eight
        # tried, three: a toy model's logits are flat)
        model, params = _model("gqa", BF16, seed=0)
        split, gather = _both(model, params, _chunk_script, dtype=BF16)
        _same(split, gather, None, rel=2e-2, rows_at_least=44)


@pytest.mark.parametrize("name", ["gqa", "mha"])
def test_split_step_under_tp2(name, devices):
    from deepspeed_tpu.parallel import topology as topo
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    model, params = _model(name)
    runs = []
    for kernel in (True, False):
        topo._GLOBAL_MESH = None
        mesh = build_mesh(TopologyConfig(dp=1, tp=2), devices=devices[:2])
        d = Drive(_engine(model, params, kernel, mesh=mesh))
        assert d.eng._tp == 2
        _chunk_script(d)
        runs.append(d)
    _same(*runs, 1e-5)


def test_speculation_still_verifies_through_the_gather_program():
    model, params = _model("mha")
    prompt = np.tile(np.arange(6, dtype=np.int32), 4)   # lookup finds drafts
    spec = _engine(model, params, True, spec_decode=True, spec_k=3,
                   decode_steps=8)
    plain = _engine(model, params, True, decode_steps=8)
    for eng in (spec, plain):
        eng.put([1], [prompt], max_new_tokens=12)
    assert spec.generate_all() == plain.generate_all()
    st = spec.stats
    assert st["spec_steps"] > 0 and st["tokens_gather"] > 0
    assert plain.stats["tokens_gather"] == 0
    # the prompt itself went through the prefill program in both
    assert st["prefill_chunk_calls"] == plain.stats["prefill_chunk_calls"] == 1
    spec.close(), plain.close()


# -- a recurrent model that has a gather program -----------------------------

# chunks bucket from the recurrence's 64: a call may hold 4 x 64 or 2 x 128
HYBRID = dict(kv_blocks=128, kv_block_size=16, max_tokens_per_step=128,
              max_seqs_per_step=8, max_blocks_per_seq=16, state_slots=8)


@pytest.fixture(scope="module")
def hybrid():
    model = get_model("tiny-hybrid", dtype=F32, param_dtype=F32)
    return model, model.init(jax.random.PRNGKey(3))


def _pools(eng):
    """Each live sequence's recurrent state and convolution tail, by uid."""
    pool = eng.kv_cache.state_pool
    state, conv = np.array(pool.state), np.array(pool.conv)
    return {uid: (state[:, s.held["state"]], conv[:, s.held["state"]])
            for uid, s in eng.state.seqs.items()}


def _hybrid_script(chunks):
    """Three sequences decoding, then ``chunks`` arrive in one step beside
    them; the pools are compared right after that step."""
    def script(d):
        d.put([1, 2, 3], [_prompt(n, n) for n in (5, 70, 17)], 12)
        d.step(3)
        d.put(range(10, 10 + len(chunks)),
              [_prompt(n, 40 + i) for i, n in enumerate(chunks)], 4)
        dispatch, d.shapes = d.eng._dispatch, []

        def recorded(program, *args, **shape):
            if program == "prefill":
                d.shapes.append((shape["S"], shape["tq"]))
            return dispatch(program, *args, **shape)

        d.eng._dispatch = recorded
        d.step()
        d.eng._dispatch = dispatch
        assert sorted(n for _, n, _ in d.log[-1][0]) == sorted(
            [1, 1, 1] + chunks)
        d.pools = _pools(d.eng)
        d.finish()
    return script


@pytest.mark.parametrize("chunks,shapes", [
    ([20], [(1, 64)]),                      # a chunk beside token rows
    ([100], [(1, 128)]),
    ([30, 64], [(2, 64)]),                  # two chunks a call
    ([9, 33, 5, 64], [(4, 64)]),            # four
    ([70, 30], [(2, 128)]),
    # 4 x 128 rows would not fit (the scheduler's scan of the waiting
    # prompts starts at the second arrival: 9, 9, 70)
    ([70, 9, 9], [(2, 64), (1, 128)]),
])
def test_a_recurrent_models_step_is_split_like_a_dense_ones(hybrid, chunks,
                                                            shapes):
    """The decode program for the token rows, the prefill program for the
    chunks over their own rows: the same tokens and rows as the gather
    program gives, and after the mixed step every sequence's recurrent
    state and convolution tail are the gather path's."""
    split, gather = _both(*hybrid, _hybrid_script(chunks), **HYBRID)
    for uid, (state, conv) in gather.pools.items():
        np.testing.assert_allclose(split.pools[uid][0], state, atol=1e-4)
        np.testing.assert_allclose(split.pools[uid][1], conv, atol=1e-4)
    assert len(gather.pools) == 3 + len(chunks)
    st = split.eng.stats
    assert st["calls_gather"] == 0 == st["prefill_gather_fallbacks"]
    assert st["calls_prefill"] == st["prefill_chunk_calls"]
    # the first step's three prompts (5, 70, 17: 4 x 128 rows, two calls)
    # and then the arrivals' calls, each within twice the step's budget
    assert split.shapes == shapes and gather.shapes == []
    assert all(S * tq <= 2 * HYBRID["max_tokens_per_step"]
               for S, tq in shapes)
    assert st["calls_prefill"] == 2 + len(shapes)
    assert st["padded_rows_prefill"] == 2 * 128 + 64 + sum(
        S * tq for S, tq in shapes)
    assert st["rows_prefill"] == 5 + 70 + 17 + sum(chunks)
    assert gather.eng.stats["calls_prefill"] == 0
    _same(split, gather, 1e-4)


def test_a_recurrent_models_split_streams_are_the_full_forwards(hybrid):
    """Token streams of the split engine, a prompt over the step's budget
    and several a step among them, against ``argmax`` of the model's own
    forward over prompt and answer (one padded batch: the model is causal,
    what follows a sequence's end does not reach back)."""
    model, params = hybrid
    eng = _engine(model, params, True, **HYBRID)
    prompts = {1: _prompt(150, 1), 2: _prompt(64, 2), 3: _prompt(7, 3)}
    eng.put(list(prompts), list(prompts.values()), max_new_tokens=6)
    out = eng.generate_all()
    seqs = np.zeros((len(prompts), 150 + 5), np.int32)
    for row, (uid, prompt) in enumerate(prompts.items()):
        seqs[row, :len(prompt) + 5] = np.concatenate([prompt, out[uid][:-1]])
    logits = np.asarray(model.apply(params, jnp.asarray(seqs)))
    for row, (uid, prompt) in enumerate(prompts.items()):
        want = np.argmax(logits[row, len(prompt) - 1:len(prompt) + 5], -1)
        assert out[uid] == want.tolist(), uid
    st = eng.stats
    assert st["calls_gather"] == 0 == st["tokens_gather"]
    # 128 of the long prompt (with the 7: 2 x 128), then its 22 beside
    # the 64 (2 x 64): two steps, a call each
    assert (st["prefill_kernel_steps"], st["calls_prefill"]) == (2, 2)
    eng.close()


def test_each_calls_counters_are_booked_once_under_its_own_program(
        hybrid, monkeypatch):
    """Steps of one, two and three program calls: what each call counted
    (read here as the call returns, which the engine must not do) is added
    to ``stats`` once, a decode call's also under ``_decode``, and the
    engine reads them once a step, inside ``fetch``, with no program call
    after it in the step."""
    model, params = hybrid
    eng = _engine(model, params, True, **HYBRID)
    open_spans, events, per_call = [], [], []

    real_span = engine_v2.span

    @contextlib.contextmanager
    def span(name, **ids):
        open_spans.append(name)
        try:
            with real_span(name, **ids):
                yield
        finally:
            open_spans.pop()

    monkeypatch.setattr(engine_v2, "span", span)

    def counting(program):
        fn = getattr(eng, f"_{program}_fn")

        def call(*args, **kw):
            out = fn(*args, **kw)
            per_call.append((program, np.array(out[1]["counters"])))
            events.append(program)
            return out
        setattr(eng, f"_{program}_fn", call)

    counting("decode"), counting("prefill")
    fetch = eng._fetch_counters

    def fetching(calls):
        events.append(("fetch", tuple(open_spans), len(calls)))
        return fetch(calls)

    eng._fetch_counters = fetching

    def step():
        events.clear()
        eng.step()
        return list(events)

    eng.put([1, 2, 3], [_prompt(n, n) for n in (5, 30, 17)], 12)
    one = step()                                    # three chunks, one call
    assert one == ["prefill", ("fetch", ("serve_step", "fetch"), 1)]
    assert step()[0] == "decode"
    eng.put([4], [_prompt(40, 4)], 4)
    two = step()                                    # token rows and a chunk
    assert two == ["decode", "prefill",
                   ("fetch", ("serve_step", "fetch"), 2)]
    eng.put([5, 6, 7], [_prompt(n, n) for n in (70, 9, 9)], 4)
    three = step()                                  # and chunks of two calls
    assert three == ["decode", "prefill", "prefill",
                     ("fetch", ("serve_step", "fetch"), 3)]
    eng.generate_all()

    names = list(COUNTERS)
    total = sum(v for _, v in per_call)
    of_decode = sum(v for program, v in per_call if program == "decode")
    assert {n: eng.stats[n] for n in names} == dict(zip(names,
                                                        total.tolist()))
    for n in ("moe_local_pairs", "moe_experts_hit", "moe_work_items"):
        assert eng.stats[n + "_decode"] == of_decode[names.index(n)] > 0
        assert eng.stats[n] > eng.stats[n + "_decode"]
    # a decode call's three rows hit fewer experts than a chunk's forty
    hit = names.index("moe_experts_hit")
    chunk_hits = [v[hit] for program, v in per_call if program == "prefill"]
    assert max(v[hit] for p, v in per_call if p == "decode") < min(chunk_hits)
    eng.close()

"""Generators: deterministic in the seed, the same work for every seed,
and open-loop requests timed from their scheduled arrival."""

import collections
import json
import os

import numpy as np
import pytest

from benchmarks.generators import closed_loop, open_bursts, train_stream
from benchmarks.generators.requests import Served
from benchmarks.harness import manifest, stats

SEEDS = [0, 7, 2**31 + 11]


def _traffic(name):
    return manifest.load_json("traffic", name)


@pytest.mark.parametrize("seed", SEEDS)
def test_train_stream_is_deterministic_and_fresh(seed):
    t = _traffic("train-stream-2k")
    a = train_stream.batches(t, seed, 32000, 4, 2048)
    b = train_stream.batches(t, seed, 32000, 4, 2048)
    first, second = next(a)["input_ids"], next(a)["input_ids"]
    assert first.shape == (4, 2049) and first.dtype == np.int32
    assert np.array_equal(first, next(b)["input_ids"])
    tiled, rows = train_stream.check_batch(t, seed, 32000, 4, 2048, 2)
    assert tiled.shape == (4, 2049) and rows.shape == (2, 2049)
    # each distinct row fills one contiguous part of the batch
    assert np.array_equal(tiled[0], tiled[1]) and np.array_equal(tiled[2], rows[1])
    assert not np.array_equal(tiled[0], tiled[2])
    whole, own = train_stream.check_batch(t, seed, 32000, 4, 2048, 4)
    assert np.array_equal(whole, own)                   # a row for every chip
    assert len({r.tobytes() for r in whole}) == 4
    assert not np.array_equal(tiled[0], first[0])       # not a timed batch
    assert not np.array_equal(first, second)
    assert 0 <= first.min() and first.max() < 32000


def test_train_stream_differs_by_seed():
    t = _traffic("train-stream-2k")
    assert not np.array_equal(train_stream.check_batch(t, 1, 32000, 4, 64, 1)[0],
                              train_stream.check_batch(t, 2, 32000, 4, 64, 1)[0])
    a = next(train_stream.batches(t, 1, 32000, 4, 64))["input_ids"]
    b = next(train_stream.batches(t, 2, 32000, 4, 64))["input_ids"]
    assert not np.array_equal(a, b)


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_keeps_its_multiset_of_lengths(seed):
    t = _traffic("gen-closed-32")
    s = closed_loop.stream(t, seed, 32000)
    reqs = [next(s) for _ in range(64)]
    want = closed_loop.describe(t)
    assert sorted(len(r.prompt) for r in reqs) == want["prompt_tokens"]
    assert sorted(r.max_new for r in reqs) == want["answer_tokens"]
    assert min(want["prompt_tokens"]) >= 64 and max(want["prompt_tokens"]) <= 256
    assert min(want["answer_tokens"]) >= 128 and max(want["answer_tokens"]) <= 384
    again = closed_loop.stream(t, seed, 32000)
    assert all(np.array_equal(r.prompt, next(again).prompt) for r in reqs[:5])
    # no shared prefixes: first blocks are all distinct
    assert len({r.prompt[:16].tobytes() for r in reqs}) == 64


def _tiny_bursts():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "fixtures", "traffic", "tiny-rag-burst.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", SEEDS)
def test_open_bursts_keep_one_timeline_across_seeds(seed):
    t = dict(_traffic("rag-burst-hotcold"), bursts_per_s=0.8, min_gap_s=0)
    ref = open_bursts.plan(t, 12345, 32000, 30.0)
    got = open_bursts.plan(t, seed, 32000, 30.0)
    d = open_bursts.describe(t, 30.0)
    assert len(got) == len(ref) == d["requests"]
    def sizes(plan):
        return sorted(collections.Counter(r.group for r in plan).values())
    assert sizes(got) == d["burst_sizes"]
    # the same arrivals, lengths and hot/fresh flags; other contents
    assert [(r.scheduled, r.group, r.tag, len(r.prompt), r.max_new) for r in got] \
        == [(r.scheduled, r.group, r.tag, len(r.prompt), r.max_new) for r in ref]
    assert not any(np.array_equal(a.prompt, b.prompt) for a, b in zip(got, ref))
    assert all(32 <= len(r.prompt) - 640 <= 192 and 32 <= r.max_new <= 64
               and len(r.prompt) + r.max_new <= 896 for r in got)
    # the requests of a burst arrive within burst_within_s, about one document
    for g in {r.group for r in got}:
        burst = [r for r in got if r.group == g]
        assert burst[-1].scheduled - burst[0].scheduled <= t["burst_within_s"]
        assert len({r.prompt[:640].tobytes() for r in burst}) == 1
    # arrivals are sorted, start at 0 and end inside the window
    times = [r.scheduled for r in got]
    assert times == sorted(times) and times[0] == 0.0 and times[-1] < 0.75 * 30.0 + 0.1
    # deterministic
    again = open_bursts.plan(t, seed, 32000, 30.0)
    assert all(np.array_equal(a.prompt, b.prompt) and a.scheduled == b.scheduled
               for a, b in zip(got, again))


def test_the_burst_cell_runs_the_timeline_its_notes_state():
    """What BENCHMARK.json, the traffic file and PERF.md say of the cell is
    what the generator sends in a window of ``run_seconds``."""
    t = _traffic("rag-burst-hotcold")
    seconds = manifest.load_manifest()["run_seconds"]
    plan = open_bursts.plan(t, 1, 32000, float(seconds))
    sizes = collections.Counter(r.group for r in plan)
    assert len(plan) == 42 and len(sizes) == 19           # PERF.md section 4
    assert len(plan) >= 40 and len(sizes) >= 16           # ISSUE 40
    assert sorted(set(t["burst_sizes"])) == [1, 2, 3, 4]
    assert collections.Counter(sizes.values()) == {1: 6, 2: 5, 3: 6, 4: 2}
    # what the mix's users send is what it was (PR 23), but for the share
    # of bursts on a hot document (0.5 until PR 40: PERF.md section 2)
    assert (t["document_tokens"], t["hot_documents"], t["hot_burst_share"],
            t["arrival_span"]) == (640, 8, 0.2, 0.75)
    assert t["burst_sizes"] == [1, 1, 2, 2, 3, 3, 3, 4]
    assert (t["question_tokens"]["lo"], t["question_tokens"]["hi"],
            t["answer_tokens"]["lo"], t["answer_tokens"]["hi"]) == (32, 192, 32, 64)
    # a burst is due at one instant, and none within min_gap_s of the last
    due = collections.defaultdict(set)
    for r in plan:
        due[r.group].add(r.scheduled)
    assert all(len(v) == 1 for v in due.values())
    at = sorted(v.pop() for v in due.values())
    gaps = [b - a for a, b in zip(at, at[1:])]
    assert min(gaps) == pytest.approx(t["min_gap_s"]) == 1.4
    assert max(gaps) == pytest.approx(1.899, abs=0.001)
    assert at[0] == 0.0 and at[-1] == pytest.approx(28.616, abs=0.001)
    hot = {g for g in sizes if next(r for r in plan if r.group == g).tag == "hot"}
    assert sorted(sizes[g] for g in hot) == [1, 2, 3, 3]
    assert sum(r.tag == "hot" for r in plan) == 9
    # the hot questions and the lone fresh prompts, whose first token
    # comes in under 75 ms, are a third of the requests: the median lies
    # among the requests of fresh bursts of two to four, the slower mode
    # (PERF.md section 2), with 6 requests of such bursts below it
    fresh_multi = sum(n for g, n in sizes.items() if n > 1 and g not in hot)
    assert fresh_multi == 28 and len(plan) - fresh_multi == 14
    lo, hi = t["trace_window_s"]
    traced = [r for r in plan if lo <= r.scheduled < hi]
    assert {r.tag for r in traced} == {"hot", "fresh"} and len(traced) == 11
    assert max(sizes[r.group] for r in traced) == 4        # the packing's burst
    # opens and closes in a gap: no burst due in the 0.8 s before either
    # (the longest burst keeps the engine 0.9 s: the file's ``trace_note``)
    assert not any(b - 0.8 < r.scheduled < b for r in plan for b in (lo, hi))


def test_open_bursts_hot_documents_are_few_and_fresh_ones_unique():
    t = dict(_traffic("rag-burst-hotcold"), bursts_per_s=0.8, min_gap_s=0,
             hot_burst_share=0.5)
    plan = open_bursts.plan(t, 3, 32000, 30.0)
    hot = {d.tobytes() for d in open_bursts.hot_documents(t, 3, 32000)}
    assert len(hot) == 8
    for r in plan:
        assert (r.prompt[:640].tobytes() in hot) == (r.tag == "hot")
    share = open_bursts.offered_prefix_share(t, 30.0)
    assert 0.6 < share < 0.8           # ISSUE 23: about 70% offered


class _StalledEngine:
    """Emits nothing for the first ``stall`` seconds, then one token per
    request per step: TTFT must count the stall for requests due in it."""

    def __init__(self, stall):
        import time
        self.t0, self.stall, self.live, self.clock = time.perf_counter(), stall, {}, time
        self.stats = {"decode_kernel_steps": 0}
        self.scheduler = type("S", (), {"last_scheduled_seqs": 0})()

    def put(self, uids, toks, max_new_tokens):
        self.live[uids[0]] = max_new_tokens

    def serve_step(self):
        self.clock.sleep(0.01)
        if self.clock.perf_counter() - self.t0 < self.stall:
            return {}
        out = {u: [1] for u in list(self.live)}
        for u in list(self.live):
            self.live[u] -= 1
            if not self.live[u]:
                del self.live[u]
        return out


def test_open_loop_times_from_the_scheduled_arrival():
    t = _tiny_bursts()
    eng = _StalledEngine(stall=0.6)
    served = Served(eng)
    eng.t0 = served.t0
    win = open_bursts.drive(served, t, 5, 512, 2.0)
    tt = stats.ttfts(served.deliveries, win["scheduled"], win["t0"], win["t1"])
    early = [r for r, s in win["scheduled"].items() if s < 0.3]
    first = stats.first_token_times(served.deliveries)
    assert early and all(first[r] - win["scheduled"][r] > 0.25 for r in early)
    assert max(tt) >= 0.55                      # the stall is counted
    assert max(win["generator_lag_s"]) < 0.2    # and is not the generator's
    assert win["sent"] == len(win["scheduled"]) or win["t1"] >= 2.0


@pytest.mark.parametrize("stall,drain_s,late,fails", [
    (0.0, 1.0, False, False),      # in time
    (2.0, 2.0, True, False),       # pushed past the close, finished after it
    (9.0, 0.3, True, True),        # never finished: the engine is stuck
])
def test_open_loop_late_requests_are_late_and_unfinished_ones_fail(
        stall, drain_s, late, fails):
    """A system that pushes requests past the window must not read as a
    fast one: they are attempted and among the TTFT samples at their wait
    so far. They have *failed* only if the engine does not finish them in
    the drain after the close — which leaves every ledger as the close
    left it (one stall of the machine's inside a step made five requests
    of serve-chat-steady late; PERF.md section 2)."""
    from benchmarks.runners import serve

    t = _tiny_bursts()
    eng = _StalledEngine(stall=stall)
    served = Served(eng)
    eng.t0 = served.t0
    win = open_bursts.drive(served, t, 5, 512, 1.5)
    ledgers = [list(served.deliveries), list(served.busy), list(served.steps)]
    attempted, n_late, failed = serve.open_loop_outcome(served, win, drain_s)
    assert [served.deliveries, served.busy, served.steps] == ledgers
    tt = stats.ttfts(served.deliveries, win["scheduled"], win["t0"], win["t1"])
    assert attempted == len(win["scheduled"]) == len(tt) > 0
    if late:
        assert n_late == attempted and not served.deliveries
        assert min(tt) > 1.5 - max(win["scheduled"].values()) - 0.05
    else:
        assert n_late == 0 and max(tt) < 0.3
    assert failed == (attempted if fails else 0)
    assert served.outstanding == (len(served.asked) if fails else 0)


def test_a_request_never_sent_is_put_after_the_close_and_not_failed():
    """An engine that sits in one step from before an arrival to the close
    leaves that request unsent: due in the window, so attempted and late,
    and served in the drain like the others."""
    from benchmarks.runners import serve

    class OneLongStep(_StalledEngine):
        def serve_step(self):
            if self.clock.perf_counter() - self.t0 < 2.0:
                self.clock.sleep(2.0)           # holds the loop to the close
            return super().serve_step()

    t = _tiny_bursts()                          # two bursts in 2 s
    served = Served(OneLongStep(stall=0.0))
    win = open_bursts.drive(served, t, 5, 512, 2.0)
    assert win["unsent"] and win["sent"] + len(win["unsent"]) == len(
        win["scheduled"])
    attempted, n_late, failed = serve.open_loop_outcome(served, win, 2.0)
    assert (attempted, n_late, failed) == (len(win["scheduled"]), attempted, 0)


STEPS = [{"t": 1.0, "dt": 0.5}, {"t": 1.5, "dt": 0.5}, {"t": 2.4, "dt": 0.2},
         {"t": 3.0, "dt": 1.0}]


@pytest.mark.parametrize("at,want", [
    ((0.5, 2.9), (0.5, 2.9, (0, 3))),       # both ends between steps: kept
    ((1.2, 3.5), (1.5, 3.0, (1, 3))),       # both inside a step: cut back
    ((2.1, 2.3), (2.1, 2.3, (0, 0))),       # an idle gap: no step, no cut
    ((1.7, 1.9), (2.0, 1.5, (0, 0))),       # inside one step: nothing left
])
def test_a_traced_slice_is_cut_back_to_the_steps_it_holds_whole(at, want):
    """The capture is opened and closed from a thread, wherever the loop
    then is; the readers divide the trace's work by the steps' counts, so
    the slice ends where its first and last whole steps do."""
    from benchmarks.runners import serve

    assert serve.slice_of_whole_steps(STEPS, *at) == want


@pytest.mark.parametrize("mix", ["gen-closed-32", "rag-burst-hotcold"])
def test_the_cells_mixes_give_every_seed_the_same_schedule(mix):
    """Lengths and arrivals are the mix's, the seed's are the contents."""
    t = _traffic(mix)
    assert "order" not in t
    if t["generator"] == "closed_loop":
        a, b = closed_loop.stream(t, 1, 32000), closed_loop.stream(t, 2, 32000)
        ra, rb = [next(a) for _ in range(70)], [next(b) for _ in range(70)]
    else:
        ra, rb = (open_bursts.plan(t, s, 32000, 40.0) for s in (1, 2))
        assert [r.scheduled for r in ra] == [r.scheduled for r in rb]
        assert [r.tag for r in ra] == [r.tag for r in rb]
    assert [(len(r.prompt), r.max_new) for r in ra] == \
        [(len(r.prompt), r.max_new) for r in rb]
    assert not np.array_equal(ra[0].prompt, rb[0].prompt)
    lens = [len(r.prompt) for r in ra]
    assert lens != sorted(lens)              # a mixed order, not a ramp


CHAT_REQUESTS_A_RUN = 19      # PERF.md section 4: serve-chat-steady at 40 s


@pytest.mark.parametrize("seed", SEEDS)
def test_bursts_of_one_with_nothing_hot_share_no_document(seed):
    """``chat-steady-unshared``: ``open_bursts`` as it is with
    ``burst_sizes`` [1] and ``hot_burst_share`` 0: every request brings a
    document of its own, none is the hot one, and the timeline is one for
    every seed."""
    t = _traffic("chat-steady-unshared")
    assert t["burst_sizes"] == [1] and t["hot_burst_share"] == 0.0
    # four fifths of the knee over the window, offered in its first three
    # quarters: at the knee while requests arrive (the file's ``rate_note``)
    assert t["bursts_per_s"] * t["arrival_span"] == pytest.approx(
        0.8 * t["knee_bursts_per_s"])
    seconds = float(manifest.load_manifest()["run_seconds"])
    got = open_bursts.plan(t, seed, 32000, seconds)
    ref = open_bursts.plan(t, 12345, 32000, seconds)
    assert len(got) == CHAT_REQUESTS_A_RUN == open_bursts.describe(t, seconds)["requests"]
    assert [(r.scheduled, len(r.prompt), r.max_new) for r in got] \
        == [(r.scheduled, len(r.prompt), r.max_new) for r in ref]
    docs = [r.prompt[:640].tobytes() for r in got]
    assert len(set(docs)) == len(docs)                       # nothing shared
    hot = {d.tobytes() for d in open_bursts.hot_documents(t, seed, 32000)}
    assert len(hot) == 1 and not hot & set(docs)
    assert {r.tag for r in got} == {"fresh"} and len({r.group for r in got}) == len(got)
    # no two prompts share even their first block of 16 tokens
    assert len({r.prompt[:16].tobytes() for r in got}) == len(got)
    assert open_bursts.offered_prefix_share(t, seconds) == 0.0
    assert all(32 <= len(r.prompt) - 640 <= 192 and 32 <= r.max_new <= 64 for r in got)
    times = [r.scheduled for r in got]
    assert times == sorted(times) and times[0] == 0.0 and times[-1] < 0.75 * seconds
    # the check's sample is of this mix and of documents the window never sends
    sample = open_bursts.sample(t, seed, 32000, 3)
    assert len(sample) == 3 and not {r.prompt[:640].tobytes() for r in sample} & set(docs)


# -- a floor on the gap between bursts, and bursts that arrive at one instant --

@pytest.mark.parametrize("floor", [0.25, 1.0, 1.3, 30.0 / 18])
def test_floored_gaps_keep_count_sum_and_order(floor):
    gaps = stats.exponential_gaps(30.0 / 18, 18)
    got = stats.floored_gaps(gaps, floor)
    assert len(got) == len(gaps) and sum(got) == pytest.approx(30.0, abs=1e-9)
    assert min(got) >= floor - 1e-12
    assert got == sorted(got)                        # quantiles stay in order
    # only what lay under the floor was raised, only the longest were cut,
    # and those to one ceiling
    cut = [g for g, o in zip(got, gaps) if g < o - 1e-12]
    assert all(g == pytest.approx(max(got)) for g in cut)
    assert all(g == pytest.approx(o) for g, o in zip(got, gaps)
               if floor <= o and g < max(got) - 1e-9)


def test_floored_gaps_refuse_a_floor_that_does_not_fit():
    with pytest.raises(ValueError, match="do not fit"):
        stats.floored_gaps([1.0, 2.0, 3.0], 2.5)
    assert stats.floored_gaps([1.0, 2.0, 3.0], 0.5) == [1.0, 2.0, 3.0]


@pytest.mark.parametrize("floor", [0.5, 1.0, 1.4])
def test_the_skeleton_with_a_gap_floor_keeps_bursts_span_and_sizes(floor):
    t = dict(_traffic("rag-burst-hotcold"), bursts_per_s=0.6)
    free = open_bursts._skeleton(dict(t, min_gap_s=0), 40.0)
    held = open_bursts._skeleton(dict(t, min_gap_s=floor), 40.0)
    assert len(held) == len(free) == 18
    assert min(b["gap"] for b in held) >= floor - 1e-12
    assert min(b["gap"] for b in free) < 0.1
    assert sum(b["gap"] for b in held) == pytest.approx(
        sum(b["gap"] for b in free), abs=1e-9)
    assert [(b["size"], b["hot"]) for b in held] == \
        [(b["size"], b["hot"]) for b in free]
    # the same permutation places the gaps: the longest stay where they were
    order = sorted(range(18), key=lambda i: free[i]["gap"])
    assert [held[i]["gap"] for i in order] == sorted(b["gap"] for b in held)


class _CountingEngine(_StalledEngine):
    """Remembers how many requests were known at every ``serve_step``."""

    def __init__(self):
        super().__init__(stall=0.0)
        self.known_at_step, self.put_uids = [], []

    def put(self, uids, toks, max_new_tokens):
        super().put(uids, toks, max_new_tokens)
        self.put_uids.append(uids[0])

    def serve_step(self):
        self.known_at_step.append(len(self.put_uids))
        return super().serve_step()


@pytest.mark.parametrize("within_s,whole", [(0.0, True), (0.2, False)])
def test_a_burst_due_at_one_instant_is_put_whole_before_one_step(within_s,
                                                                 whole):
    """``burst_within_s`` 0: every request of a burst has one due time, so
    the generator puts them all before the engine's next step, and no step
    ever sees a part of a burst. Spread over 0.2 s, the followers arrive
    between the leader's steps."""
    t = dict(_tiny_bursts(), burst_within_s=within_s, bursts_per_s=3.0,
             min_gap_s=0.25)
    plan = open_bursts.plan(t, 5, 512, 2.0)
    by_burst = collections.Counter(r.group for r in plan)
    assert max(by_burst.values()) >= 3
    due = collections.defaultdict(set)
    for r in plan:
        due[r.group].add(r.scheduled)
    assert all(len(v) == 1 for v in due.values()) == whole
    eng = _CountingEngine()
    served = Served(eng)
    win = open_bursts.drive(served, t, 5, 512, 2.0)
    assert win["sent"] == len(plan) and eng.put_uids == [r.rid for r in plan]
    borders, n = set(), 0
    for g in sorted(by_burst):
        n += by_burst[g]
        borders.add(n)                      # requests known after each burst
    assert (set(eng.known_at_step) <= borders) == whole
    if whole:
        assert max(win["generator_lag_s"]) < 0.1


CHAT_PLAN = {       # serve-chat-steady's timeline as PR 27 froze it
    "due": [0.0, 0.2245, 2.9165, 8.7052, 10.1844, 10.6145, 11.1584, 14.3858,
            15.1848, 17.1576, 18.4377, 20.1446, 20.2755, 21.2192, 25.2596,
            27.5517, 27.5942, 28.6972, 29.0212],
    "question": [48, 38, 76, 85, 160, 104, 68, 35, 136, 43, 172, 148, 114, 54,
                 125, 61, 32, 185, 94],
    "answer": [55, 59, 32, 63, 47, 49, 33, 57, 42, 43, 61, 51, 40, 36, 53, 38,
               34, 45, 37]}


@pytest.mark.parametrize("what", sorted(CHAT_PLAN))
def test_the_steady_cells_timeline_is_what_it_was(what):
    """``chat-steady-unshared`` has no ``min_gap_s`` and bursts of one:
    nothing this generator learned since PR 27 may move its 19 requests."""
    t = _traffic("chat-steady-unshared")
    assert "min_gap_s" not in t
    plan = open_bursts.plan(t, 3, 32000, 40.0)
    got = {"due": [round(r.scheduled, 4) for r in plan],
           "question": [len(r.prompt) - 640 for r in plan],
           "answer": [r.max_new for r in plan]}
    assert got[what] == CHAT_PLAN[what]


def test_an_idle_generator_sleeps_to_the_next_due_time(monkeypatch):
    """Every open-loop mix waits the same way: one sleep that is asked to
    end at the next due time (no key of a mix changes that, so
    ``ttft_p50_ms`` counts the host's wake-up in every cell alike), and
    nothing is put before it is due."""
    t = dict(_tiny_bursts(), burst_within_s=0.0, bursts_per_s=3.0,
             min_gap_s=0.25)
    import types

    eng = _StalledEngine(stall=0.0)
    served = Served(eng)
    asked, real_sleep = [], open_bursts.time.sleep
    # the engine keeps the real clock: only the generator's sleeps are read
    eng.clock = types.SimpleNamespace(sleep=real_sleep,
                                      perf_counter=eng.clock.perf_counter)

    def sleep(seconds):
        asked.append(served.now() + seconds)
        real_sleep(seconds)

    monkeypatch.setattr(open_bursts.time, "sleep", sleep)
    win = open_bursts.drive(served, t, 5, 512, 2.0)
    assert win["sent"] == len(win["scheduled"])
    assert min(win["generator_lag_s"]) >= 0.0      # never before it is due
    due = sorted(set(win["scheduled"].values())) + [2.0]
    ends = [min(d for d in due if d > a - 1e-3) - a for a in asked]
    assert len(asked) >= len(due) - 2
    assert all(e == pytest.approx(0.0, abs=1e-3) for e in ends), ends


@pytest.mark.parametrize("mix", ["rag-burst-hotcold", "chat-steady-unshared"])
def test_no_open_loop_mix_has_a_key_for_how_the_generator_waits(mix):
    assert not {"wake_spin_s", "spin_s"} & set(_traffic(mix))

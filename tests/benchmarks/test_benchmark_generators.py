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
    t = dict(_traffic("rag-burst-hotcold"), bursts_per_s=0.8)
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
        assert burst[-1].scheduled - burst[0].scheduled < t["burst_within_s"]
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
    assert len(plan) == 19 and len(sizes) == 8
    assert sorted(sizes.values()) == sorted(t["burst_sizes"]) == [1, 1, 2, 2, 3, 3, 3, 4]
    assert plan[-1].scheduled == pytest.approx(25.51, abs=0.01)
    assert plan[3].scheduled == pytest.approx(0.241, abs=0.001)
    assert plan[4].scheduled == pytest.approx(10.584, abs=0.001)
    lo, hi = t["trace_window_s"]
    traced = [r for r in plan if lo <= r.scheduled < hi]
    assert {r.tag for r in traced} == {"hot", "fresh"} and len(traced) == 9
    assert not any(lo - 0.5 < r.scheduled < lo for r in plan)   # opens in a gap


def test_open_bursts_hot_documents_are_few_and_fresh_ones_unique():
    t = dict(_traffic("rag-burst-hotcold"), bursts_per_s=0.8)
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


def test_an_idle_open_loop_wakes_when_its_caller_asks():
    """``while_open`` may name a time to be called again at (the traced
    slice opens in an idle gap); an idle generator sleeps no longer."""
    t = dict(_tiny_bursts(), bursts_per_s=0.5)       # one burst, at 0
    served = Served(_StalledEngine(stall=0.0))
    calls = []

    def while_open():
        calls.append(served.now())
        return 1.0 if served.now() < 1.0 else None

    open_bursts.drive(served, t, 5, 512, 2.0, while_open=while_open)
    assert any(0.99 <= c < 1.1 for c in calls), calls


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

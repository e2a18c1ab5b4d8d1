"""Open loop with bursts: requests are sent on a schedule whether or not
earlier ones have finished. A request is one document, a question and an
answer; the requests of a burst are about one document and arrive within
``burst_within_s`` (0: at one instant, all of them put before the engine's
next step), no burst sooner than ``min_gap_s`` after the one before it
(so each finds the engine idle, where the mix's rate allows);
``hot_burst_share`` of the bursts ask about one of a few *hot*
documents (in the prefix cache since set-up), the others each about a
*fresh* document that nothing has seen.

So that every run does equal work at equal times, the schedule is one
fixed timeline for every seed — burst gaps (the quantiles of an
exponential), burst sizes, hot/fresh flags and the question and answer
lengths (fixed multisets), all in one fixed order — and the seed chooses
only documents and token ids (and, in the runner, the weights). What a
run's tail and median then measure is the system on this timeline, not
the law the timeline was drawn from (PERF.md section 4).
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmarks.generators.requests import Request, Served, lengths, mixed
from benchmarks.harness import stats, trace


_TIMELINE = 20230923      # the one draw every seed replays


def _skeleton(traffic: Dict, seconds: float) -> List[Dict]:
    # arrivals fill the first ``arrival_span`` of the window; the rest lets
    # what was sent finish, so a run does the whole work of a fixed set of
    # requests and not a seed-dependent part of it
    n = max(1, int(round(traffic["bursts_per_s"] * seconds
                         * traffic.get("arrival_span", 1.0))))
    fixed = np.random.default_rng(_TIMELINE)
    gaps = stats.exponential_gaps(1.0 / traffic["bursts_per_s"], n)
    if traffic.get("min_gap_s"):
        # a burst never arrives at a working engine (PERF.md section 4)
        gaps = stats.floored_gaps(gaps, traffic["min_gap_s"])
    gaps = [gaps[i] for i in fixed.permutation(n)]
    sizes = traffic["burst_sizes"]
    sizes = [sizes[i % len(sizes)] for i in range(n)]
    sizes = [sizes[i] for i in fixed.permutation(n)]
    n_hot = int(round(traffic["hot_burst_share"] * n))
    hot = [i < n_hot for i in fixed.permutation(n)]
    return [{"gap": g, "size": s, "hot": h}
            for g, s, h in zip(gaps, sizes, hot)]


def hot_documents(traffic: Dict, seed: int, vocab: int) -> List[np.ndarray]:
    rng = np.random.default_rng([int(seed), 0x686F74])
    return [rng.integers(0, vocab, traffic["document_tokens"]).astype(np.int32)
            for _ in range(traffic["hot_documents"])]


def plan(traffic: Dict, seed: int, vocab: int, seconds: float,
         salt: int = 0) -> List[Request]:
    rng = np.random.default_rng([int(seed), 0x62757273, salt])
    bursts = mixed(_skeleton(traffic, seconds))
    n_req = sum(b["size"] for b in bursts)
    # as many lengths as requests, so every seed sends the whole multiset
    q_all = lengths(dict(traffic["question_tokens"], count=n_req))
    a_all = lengths(dict(traffic["answer_tokens"], count=n_req))
    fixed = np.random.default_rng(_TIMELINE + 1)
    q_all = [q_all[i] for i in fixed.permutation(len(q_all))]
    a_all = [a_all[i] for i in fixed.permutation(len(a_all))]
    questions, answers = mixed(q_all), mixed(a_all)
    hot = hot_documents(traffic, seed, vocab)
    out: List[Request] = []
    t = 0.0
    for g, b in enumerate(bursts):
        doc = (hot[int(rng.integers(len(hot)))] if b["hot"] else
               rng.integers(0, vocab, traffic["document_tokens"])
               .astype(np.int32))
        for k in range(b["size"]):
            i = len(out)
            q = rng.integers(0, vocab, questions[i % len(questions)])
            out.append(Request(
                rid=salt * 1_000_000 + i,
                prompt=np.concatenate([doc, q.astype(np.int32)]),
                max_new=int(answers[i % len(answers)]),
                scheduled=t + k * traffic["burst_within_s"] / b["size"],
                tag="hot" if b["hot"] else "fresh", group=g))
        t += b["gap"]
    assert len(out) == n_req
    return out


def describe(traffic: Dict, seconds: float) -> Dict:
    sk = _skeleton(traffic, seconds)
    n_req = sum(b["size"] for b in sk)
    return {"bursts": len(sk), "requests": n_req,
            "hot_bursts": int(sum(b["hot"] for b in sk)),
            "burst_sizes": sorted(b["size"] for b in sk),
            "question_tokens": lengths(dict(traffic["question_tokens"],
                                            count=n_req)),
            "answer_tokens": lengths(dict(traffic["answer_tokens"],
                                          count=n_req)),
            "offered_prefix_share": offered_prefix_share(traffic, seconds)}


def offered_prefix_share(traffic: Dict, seconds: float) -> float:
    """Share of prompt tokens that an ideal cache could serve: every
    document of a hot burst, and all but the first of a fresh one."""
    sk = _skeleton(traffic, seconds)
    d = traffic["document_tokens"]
    q = sum(lengths(traffic["question_tokens"])) / traffic[
        "question_tokens"]["count"]
    reusable = sum(b["size"] * d if b["hot"] else (b["size"] - 1) * d
                   for b in sk)
    return reusable / sum(b["size"] * (d + q) for b in sk)


def sample(traffic: Dict, seed: int, vocab: int, n: int):
    """``n`` requests of this mix about documents the window never sends:
    the first requests of a plan under another salt, fresh bursts only."""
    reqs = [r for r in plan(traffic, seed, vocab, 30.0, salt=7)
            if r.tag == "fresh"]
    return reqs[:n]


def prewarm(served: Served, traffic: Dict, seed: int, vocab: int) -> None:
    """One pass over the hot documents, so that the window finds them in
    the prefix cache as a long-running server would."""
    for i, doc in enumerate(hot_documents(traffic, seed, vocab)):
        served.put(Request(rid=8_000_000 + i, prompt=np.concatenate(
            [doc, np.asarray([i + 1], np.int32)]), max_new=1))
    while served.outstanding:
        served.step()


def drive(served: Served, traffic: Dict, seed: int, vocab: int,
          seconds: float, on_window_open=None, salt: int = 0) -> Dict:
    reqs = plan(traffic, seed, vocab, seconds, salt)
    served.rebase()
    if on_window_open:
        on_window_open()
    lag, i = [], 0
    sched = {r.rid: r.scheduled for r in reqs}
    while True:
        now = served.now()
        if now >= seconds:
            break
        while i < len(reqs) and reqs[i].scheduled <= now:
            served.put(reqs[i])
            lag.append(served.now() - reqs[i].scheduled)
            i += 1
        if served.outstanding == 0:
            nxt = min(reqs[i].scheduled if i < len(reqs) else seconds,
                      seconds)
            with trace.span("generator_sleep"):
                time.sleep(max(0.0, nxt - served.now()))
            continue
        served.step()
    t1 = served.now()
    return {"t0": 0.0, "t1": t1, "scheduled": sched,
            "generator_lag_s": lag, "sent": i,
            # due in the window and never put: the engine sat in one step
            # from before their arrival to the close
            "unsent": [r for r in reqs[i:] if r.scheduled < t1],
            "tags": {r.rid: r.tag for r in reqs},
            "groups": {r.rid: r.group for r in reqs}}

"""Closed loop: ``clients`` callers that each send their next request
when the last one completes, so the load follows the system. Lengths come
from fixed multisets (the quantiles of the stated distributions) in one
order for every seed; token ids are the seed's; no two prompts share a prefix.
The window opens once every client is decoding."""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np

from benchmarks.generators.requests import Request, Served, lengths, mixed


def stream(traffic: Dict, seed: int, vocab: int, salt: int = 0
           ) -> Iterator[Request]:
    rng = np.random.default_rng([int(seed), 0x636C6F73, salt])
    prompts = mixed(lengths(traffic["prompt_tokens"]))
    answers = mixed(lengths(traffic["answer_tokens"]))
    i = 0
    while True:
        p, a = prompts[i % len(prompts)], answers[i % len(answers)]
        yield Request(rid=salt * 1_000_000 + i,
                      prompt=rng.integers(0, vocab, p).astype(np.int32),
                      max_new=int(a))
        i += 1


def describe(traffic: Dict, seconds: float = 0.0) -> Dict:
    return {"prompt_tokens": sorted(lengths(traffic["prompt_tokens"])),
            "answer_tokens": sorted(lengths(traffic["answer_tokens"])),
            "clients": traffic["clients"]}


def sample(traffic: Dict, seed: int, vocab: int, n: int):
    """``n`` requests of this mix that the window never sends (own salt)."""
    s = stream(traffic, seed, vocab, salt=7)
    return [next(s) for _ in range(n)]


def drive(served: Served, traffic: Dict, seed: int, vocab: int,
          seconds: float, on_window_open=None) -> Dict:
    """Returns the window ``[0, seconds]`` on the served clock (rebased
    when the window opens), exactly: the ``serve_step`` that straddles the
    close counts for the share of its duration inside
    (``stats.tokens_prorated``). ``t_end`` is when that step ended."""
    reqs = stream(traffic, seed, vocab)
    for _ in range(traffic["clients"]):
        served.put(next(reqs))
    decoding, replaced = set(), set()

    def pump():
        for rid, toks in served.step().items():
            if toks:
                decoding.add(rid)
            if rid in served.done_at and rid not in replaced:
                replaced.add(rid)
                decoding.discard(rid)
                served.put(next(reqs))

    # ramp: until every client's request is decoding at once (or, on a
    # mix too short for that, until as many requests as clients are done)
    while (len(decoding) < traffic["clients"]
           and len(replaced) < traffic["clients"]):
        pump()
    served.rebase()
    if on_window_open:
        on_window_open()
    while served.now() < seconds:
        pump()
    return {"t0": 0.0, "t1": float(seconds), "t_end": served.now()}

"""What the serving generators share: a request, the engine seen through
three calls, and the delivery log every serving metric is reduced from."""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.harness import stats, trace


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    scheduled: Optional[float] = None   # open loop: seconds after the window opens
    tag: str = ""                       # e.g. "hot" / "fresh"
    group: int = 0                      # open loop: which burst


def mixed(values: List) -> List:
    """A fixed multiset in the mix's one order: the same permutation for
    every seed, so that every run does the same work at the same times.
    The seed decides contents only (token ids, documents, weights): with
    the seed shifting even the phase of the schedule, engine time per
    request differed by 4% between two seeds (PERF.md section 4)."""
    perm = np.random.default_rng(20230923).permutation(len(values))
    return [values[i] for i in perm]


def lengths(spec: Dict) -> List[int]:
    return stats.quantile_multiset(spec["lo"], spec["hi"], spec["count"],
                                   spec["shape"])


class Served:
    """The engine through ``put`` and ``serve_step`` only, with the
    benchmark's clock and spans around each call and its own ledger:
    deliveries (time, request, tokens), seconds inside ``serve_step``, and
    per ``serve_step`` its start, duration and the tokens it handed out,
    the sequences it advanced and the contexts a decode step read (from
    the public counters' deltas)."""

    def __init__(self, engine):
        self.engine = engine
        self.t0 = time.perf_counter()
        self.deliveries: List[Tuple[float, int, int]] = []
        self.busy: List[Tuple[float, float]] = []       # (start, seconds)
        self.steps: List[Dict] = []
        self.asked: Dict[int, int] = {}
        self.got: Dict[int, int] = {}
        self.ctx: Dict[int, int] = {}
        self.tokens: Dict[int, List[int]] = {}
        self.done_at: Dict[int, float] = {}
        self.closed = set()          # finished before the window opened

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def rebase(self) -> None:
        """Open the window: times count from here, the ledger starts empty
        (requests in flight stay known)."""
        self.t0 = time.perf_counter()
        self.deliveries, self.busy, self.steps = [], [], []
        self.closed = set(self.done_at)

    def completed(self) -> List[int]:
        """Requests that finished since the window opened."""
        return [rid for rid in self.done_at if rid not in self.closed]

    @property
    def outstanding(self) -> int:
        return len(self.asked) - len(self.done_at)

    def drain(self, limit_s: float) -> None:
        """After a window has closed: let what it left in flight finish,
        for at most ``limit_s``. The ledgers the metrics are reduced from
        (deliveries, busy, steps) stay as the close left them; only what
        each request got moves, so that a late request can be told from
        one the engine never finished."""
        kept = len(self.deliveries), len(self.busy), len(self.steps)
        until = self.now() + limit_s
        while self.outstanding and self.now() < until:
            self.step()
        del self.deliveries[kept[0]:], self.busy[kept[1]:], self.steps[kept[2]:]

    def put(self, req: Request) -> None:
        with trace.span("put"):
            self.engine.put([req.rid], [req.prompt], max_new_tokens=req.max_new)
        self.asked[req.rid] = req.max_new
        self.got[req.rid] = 0
        self.ctx[req.rid] = len(req.prompt)
        self.tokens[req.rid] = []

    def step(self) -> Dict[int, List[int]]:
        eng = self.engine
        k0 = eng.stats["decode_kernel_steps"]
        b0 = eng.stats.get("burst_steps", 0)
        t = self.now()
        with trace.span("serve_step"):
            out = eng.serve_step()
        t1 = self.now()
        self.busy.append((t, t1 - t))
        dk = eng.stats["decode_kernel_steps"] - k0
        burst = eng.stats.get("burst_steps", 0) - b0 > 0
        live = [rid for rid, toks in out.items() if toks]
        rec = {"t": t, "dt": t1 - t, "decode_kernel_steps": dk,
               "tokens": sum(len(toks) for toks in out.values()),
               "device_steps": dk if burst else 1,
               "seqs": len(live) if burst
               else eng.scheduler.last_scheduled_seqs}
        if dk:
            rec["decode_contexts"] = [self.ctx[rid] + self.got[rid] + j + 1
                                      for rid in live for j in range(dk)]
        self.steps.append(rec)
        for rid, toks in out.items():
            if not toks:
                continue
            self.deliveries.append((t1, rid, len(toks)))
            self.got[rid] += len(toks)
            self.tokens[rid].extend(int(x) for x in toks)
            if self.got[rid] >= self.asked[rid]:
                self.done_at[rid] = t1
        return out

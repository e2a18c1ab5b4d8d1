"""The program call as the unit of a serving trace: each ``dstpu/dispatch``
span of the traced slice joined to the execution of its program on the
device's module line, and the window's per-program counters.

The engine describes every call it issues on the span it issues it under
(``program``, ``step_id``, ``call``, ``seqs``, ``tokens``, ``padded_rows``,
``token_steps``, ``chunks`` and, for the prefill program, ``S`` and ``tq``:
docs/observability.md, "Profiler spans and names") and counts the same
numbers a program in ``engine.stats``. The spans are on the clock of the
device's events (``harness/program_trace.py``), so a call's device time can
be put beside what the call carried.

**The join.** The device runs one program at a time, in the order the host
issued them. So within a slice the serving programs' executions, in start
order, are the dispatch spans' calls, in start order, but for the slice's
borders: executions at the head whose dispatch lies before the slice, and
spans at the tail whose execution does not end inside it. The head's length
is the smallest for which every pair holds to order: the execution is of the
span's own program (the n-th span of a program meets the n-th execution of
its module) and starts after its span starts (to ``SLACK_S``: the two
planes' stamps are a run's constant apart, which ``least_lead_ms`` shows).
What pairs with nothing is dropped and counted, a program. A pair is *not*
held to start before the program's next dispatch span does: the host runs
ahead of the device wherever a step has no token to fetch (the chunk steps
of a lone prompt are issued a millisecond apart and take 14 ms each), which
is what ``lead_ms`` shows.

A program without these spans or counters (the parent of the PR that added
them) or a run without a profile yields nothing: every reader returns None.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from benchmarks.harness import program_trace as P

# what a dispatch span names as its ``program`` -> the module that runs it
# (a speculative round's verification is a call of the gather program)
MODULE_OF = {"gather": P.SERVE_GATHER, "spec": P.SERVE_GATHER,
             "prefill": P.SERVE_PREFILL,
             "decode": "jit_dstpu_serve_decode",
             "multi_decode": "jit_dstpu_serve_multi_decode"}
PROGRAMS = tuple(MODULE_OF)
# "after", on two planes of one profile: the device plane's stamps read up
# to a millisecond ahead of the host plane's, by a shift that holds for a
# run (PERF.md section 6, PR 41: the least lead of a run's calls read -0.08,
# -0.19 and -0.90 ms with 16-20 calls within a little of it), so an
# execution may read this much before its span; a call takes 11 ms or more
SLACK_S = 2e-3


class Pair(NamedTuple):
    span: P.Span
    start_s: float              # the execution's
    end_s: float

    @property
    def device_s(self) -> float:
        return self.end_s - self.start_s

    @property
    def lead_s(self) -> float:
        """From the host opening the dispatch span to the device starting."""
        return self.start_s - self.span.start_s


class Joined(NamedTuple):
    pairs: List[Pair]
    dropped: Dict[str, int]     # program (or module, at the head) -> calls


def join(spans: Sequence[P.Span],
         executions: Sequence[Tuple[float, float, str]]) -> Joined:
    """``spans``: the slice's dispatch spans; ``executions``: ``(start, end,
    module)`` of the serving programs' executions inside it. Both in start
    order. A profile that lost an event in the middle lines up for no head
    and pairs nothing (better than a shifted join); ``dropped`` shows it."""
    spans = [s for s in spans if s.ids.get("program") in MODULE_OF]
    # the head: an execution beyond the spans' count has no span either
    head = max(0, len(executions) - len(spans))
    while not all(MODULE_OF[s.ids["program"]] == module
                  and start >= s.start_s - SLACK_S
                  for s, (start, _, module) in zip(spans, executions[head:])):
        head += 1
    rest = executions[head:]
    dropped: Dict[str, int] = {}
    for name in [module for _, _, module in executions[:head]] + [
            s.ids["program"] for s in spans[len(rest):]]:
        dropped[name] = dropped.get(name, 0) + 1
    return Joined([Pair(s, start, end)
                   for s, (start, end, _) in zip(spans, rest)], dropped)


def join_run(pt: P.ProgramTrace) -> Optional[Joined]:
    """The join over a run's traced slice, first chip."""
    spans = pt.named("dispatch")
    executions = sorted((start, end, module)
                        for module in set(MODULE_OF.values())
                        for start, end in pt.executions(module))
    if not spans or not executions:
        return None
    return join(spans, executions)


def _mean_ms(values: Sequence[float]) -> float:
    return 1e3 * sum(values) / len(values)


def summary(joined: Joined) -> Dict:
    """What the pairs say beyond one number: by program the calls paired,
    the mean device milliseconds of one and the mean (and least) lead from
    its dispatch span's start to the device's; the prefill program's calls
    by ``(S, tq)``; and the calls dropped at the slice's borders."""
    by_program: Dict[str, List[Pair]] = {}
    by_shape: Dict[str, List[Pair]] = {}
    for p in joined.pairs:
        by_program.setdefault(p.span.ids["program"], []).append(p)
        if "S" in p.span.ids and "tq" in p.span.ids:
            by_shape.setdefault(f"{p.span.ids['S']}x{p.span.ids['tq']}",
                                []).append(p)

    def calls(ps):
        return {"calls": len(ps),
                "device_ms": _mean_ms([p.device_s for p in ps]),
                "rows": sum(p.span.ids.get("tokens", 0) for p in ps) / len(ps)}

    return {"by_program": {
                k: dict(calls(ps), lead_ms=_mean_ms([p.lead_s for p in ps]),
                        least_lead_ms=1e3 * min(p.lead_s for p in ps))
                for k, ps in sorted(by_program.items())},
            "prefill_by_S_x_tq": {k: calls(ps) for k, ps in sorted(
                by_shape.items(), key=lambda kv: -len(kv[1]))},
            "dropped": dict(joined.dropped)}


def us_per_row(joined: Joined, program: str) -> Optional[float]:
    """Device microseconds of the paired calls of ``program`` over the
    token rows those calls really carried (their spans' ``tokens``)."""
    mine = [p for p in joined.pairs if p.span.ids["program"] == program
            and "tokens" in p.span.ids]
    rows = sum(p.span.ids["tokens"] for p in mine)
    return 1e6 * sum(p.device_s for p in mine) / rows if rows else None


# --------------------------------------------------------------------------
# the window's counters
# --------------------------------------------------------------------------

def counted(result, keys: Sequence[str]) -> Optional[float]:
    """The window's delta of the engine's counters ``keys``, added up; None
    where the program has not all of them."""
    c = result.get("counters", {}).get("engine", {})
    if any(k not in c for k in keys):
        return None
    return sum(c[k] for k in keys)


def per_program(what: str, programs: Sequence[str] = PROGRAMS) -> List[str]:
    """``calls`` -> ``calls_gather``, ``calls_spec``, ..."""
    return [f"{what}_{p}" for p in programs]


def ratio(result, num: Sequence[str], den: Sequence[str],
          scale: float = 1.0) -> Optional[float]:
    """``scale * sum(num) / sum(den)`` over the window; None where a
    counter is missing or the denominator is 0."""
    n, d = counted(result, num), counted(result, den)
    return scale * n / d if n is not None and d else None

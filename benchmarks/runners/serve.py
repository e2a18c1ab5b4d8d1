"""One serving cell: ``InferenceEngineV2.put`` -> ``serve_step`` under a
load generator, every time taken by the benchmark's own clock.

Set-up, in order (each part's seconds are printed): weights made on the
device from the seed in one jitted call, in the type they are served in;
the engine; one warm-up request set per program shape (every
(sequences, chunk) bucket of a mixed step, every burst length of the
multi-step decode); the output check against the plain reference; the
traffic's own set-up (hot documents into the prefix cache). Then the
window; nothing may compile inside it.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from benchmarks.generators.requests import Request, Served
from benchmarks.harness import compare, compiles, manifest, stats, trace, weights


def build_engine(cfg: Dict, arch, seed: int):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.zoo import get_model
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    e = cfg["engine"]
    model = get_model(cfg["preset"], num_layers=arch.num_hidden_layers,
                      max_seq_len=e["max_blocks_per_seq"] * e["kv_block_size"],
                      param_dtype=jnp.bfloat16, remat=False,
                      **cfg.get("preset_overrides", {}))
    t = time.perf_counter()
    params = weights.make_program_params(arch, seed, jnp.bfloat16)
    jax.block_until_ready(params)
    weights_s = time.perf_counter() - t
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    engine = InferenceEngineV2(model, mesh=mesh, params=params,
                               dtype=jnp.bfloat16,
                               seed=int(seed) & 0x7FFFFFFF, **e)
    return engine, weights_s


def _pow2_upto(n: int, start: int = 1) -> List[int]:
    out, v = [], start
    while v <= n:
        out.append(v)
        v *= 2
    return out


def warm_up(served: Served, cfg: Dict, vocab: int) -> Dict:
    """Every program shape the engine can pick, once each, through ``put``
    and ``serve_step``: a mixed step for every (sequence bucket, longest
    chunk bucket) that fits a step's token budget — whichever program the
    engine's policy gives that bucket — and a lone sequence for every
    burst length of the multi-step decode (1 is the single decode step).
    """
    e = cfg["engine"]
    rng = np.random.default_rng(12345)
    rid = [9_000_000]
    parts = {}

    def send(lens, max_new):
        for n in lens:
            rid[0] += 1
            served.put(Request(rid[0], rng.integers(0, vocab, n)
                               .astype(np.int32), max_new))
        while served.outstanding:
            served.step()

    t = time.perf_counter()
    for tq in _pow2_upto(e["max_tokens_per_step"], 8):
        for s in _pow2_upto(e["max_seqs_per_step"]):
            n = 1 if s == 1 else s // 2 + 1
            rest = [min(8, tq)] * (n - 1)
            # the bucket's longest chunk, or where that leaves the others
            # no room in the step's budget, its shortest
            for longest in (tq, tq // 2 + 1):
                if longest + sum(rest) <= e["max_tokens_per_step"]:
                    send([longest] + rest, 1)
                    break
    parts["mixed_steps_s"] = time.perf_counter() - t
    t = time.perf_counter()
    for k in range(1, cfg.get("decode_steps", 8) + 1):
        send([8], 1 + k)
    parts["decode_bursts_s"] = time.perf_counter() - t
    return parts


class LogitsTap:
    """Reads the rows the engine samples from, for the check pass only.
    The engine has no public way to hand out logits (PERF.md, Open
    questions), so the tap stands in for two attributes of the *objects*
    (not of the program): the instance's greedy pick
    (``engine._pick_greedy``), to keep the rows it was about to reduce to
    ids, and the scheduler's ``schedule``, to know which request sits in
    which slot of that step. (Telling rows apart by their arg-max does not
    do: with random weights two requests often emit the same token.)"""

    def __init__(self, engine):
        self.engine, self.rows, self.slots = engine, [], []
        self._pick = engine._pick_greedy
        self._schedule = engine.scheduler.schedule

    def __enter__(self):
        def pick(logits, idx):
            self.rows.append(np.asarray(
                self.engine._take_rows(logits, idx), np.float32))
            return self._pick(logits, idx)

        def schedule():
            out = self._schedule()
            self.slots.append([seq.uid for seq, _, _ in out])
            return out

        self.engine._pick_greedy = pick
        self.engine.scheduler.schedule = schedule
        return self

    def __exit__(self, *exc):
        self.engine._pick_greedy = self._pick
        self.engine.scheduler.schedule = self._schedule
        return False


def engine_rows(served: Served, reqs: List[Request],
                tapped_tokens: int = 5) -> Dict:
    """Serve ``reqs`` together; return each one's tokens and, for every
    step that went through the tapped pick, the logits row behind each
    token it emitted: {rid: {token index: row}}. Each request's first
    ``tapped_tokens`` come from single steps (``decode_steps`` 1, the
    ``decode`` program and its paged kernel, whose rows the tap sees);
    the rest come in the multi-step bursts the window uses, which hand
    out ids only and are judged by the margin of each id."""
    rows: Dict[int, Dict[int, np.ndarray]] = {r.rid: {} for r in reqs}
    eng = served.engine
    burst_len, eng.decode_steps = eng.decode_steps, 1
    with LogitsTap(eng) as tap:
        for r in reqs:
            served.put(r)
        while served.outstanding:
            if eng.decode_steps == 1 and all(
                    served.got[r.rid] >= min(tapped_tokens, r.max_new)
                    for r in reqs):
                eng.decode_steps = burst_len     # the rest in bursts
            seen = len(tap.rows)
            before = {r.rid: served.got[r.rid] for r in reqs}
            out = served.step()
            if len(tap.rows) == seen:
                continue                       # a burst: ids only
            picked, slots = tap.rows[-1], tap.slots[-1]
            for rid, toks in out.items():
                if rid in rows and len(toks) == 1 and rid in slots:
                    row = picked[slots.index(rid)]
                    assert int(row.argmax()) == toks[0], (rid, toks)
                    rows[rid][before[rid]] = row
    eng.decode_steps = burst_len
    return {"rows": rows, "tokens": {r.rid: list(served.tokens[r.rid])
                                     for r in reqs}}


def reference_rows(ref, arch, cfg, seed, reqs, tokens, numerics="float32"):
    """Reference logits at every position that predicted a generated
    token: for request r, rows ``len(prompt) - 1 + j`` for j < generated."""
    import jax.numpy as jnp

    e = cfg["engine"]
    ceiling = e["max_blocks_per_seq"] * e["kv_block_size"]
    n_rows = cfg["check"]["max_new_tokens"]
    seqs, rows = [], []
    for r in reqs:
        s = np.concatenate([r.prompt, np.asarray(tokens[r.rid][:-1], np.int32)])
        # one shape for every request and seed (so one compiled program):
        # pad the sequence to the context ceiling — causal attention never
        # looks ahead, so the padding changes no row that is read — and the
        # rows to the check's answer length
        first = len(r.prompt) - 1
        rows.append(np.minimum(first + np.arange(n_rows), len(s) - 1))
        seqs.append(np.pad(s, (0, ceiling - len(s))))
    out = ref.forward_logits(
        arch, seqs, rows, weights.reference_layer_fn(arch, seed, jnp.bfloat16),
        weights.reference_top(arch, seed, jnp.bfloat16), numerics)
    return {r.rid: np.asarray(o)[:len(tokens[r.rid])]
            for r, o in zip(reqs, out)}


def margin(ref_row: np.ndarray, token: int) -> float:
    """How far below the reference's best logit the chosen token lies, in
    standard deviations of the row."""
    return float((ref_row.max() - ref_row[token]) / (ref_row.std() + 1e-30))


def serve_numbers(reqs, got_rows, got_tokens, want) -> Dict:
    """The three numbers: relative L2 of the rows at the last prompt
    position, of the rows at decode positions, and the mean margin of
    every generated token under the reference's logits."""
    pre_g, pre_w, dec_g, dec_w, margins, per_row = [], [], [], [], [], []
    for r in reqs:
        for j, tok in enumerate(got_tokens[r.rid]):
            margins.append(margin(want[r.rid][j], tok))
        for j, row in got_rows[r.rid].items():
            (pre_g if j == 0 else dec_g).append(row)
            (pre_w if j == 0 else dec_w).append(want[r.rid][j])
            per_row.append([r.rid, j, len(r.prompt) + j,
                            round(compare.rel_l2(row, want[r.rid][j]), 4)])
    return {"logits_prefill": compare.rel_l2(np.stack(pre_g), np.stack(pre_w))
            if pre_g else float("nan"),
            "logits_decode": compare.rel_l2(np.stack(dec_g), np.stack(dec_w))
            if dec_g else float("nan"),
            "token_margin": float(np.mean(margins)),
            "rows": {"prefill": len(pre_g), "decode": len(dec_g),
                     "tokens": len(margins)},
            "worst_rows": sorted(per_row, key=lambda x: -x[3])[:4]}


def sample_requests(cfg, traffic, seed, arch, gen) -> List[Request]:
    """A seeded sample of the mix's own requests (prompts as the mix
    draws them), their answers cut to the check's length: the check costs
    set-up in every run."""
    reqs = gen.sample(traffic, seed, arch.vocab_size,
                      cfg["check"]["sample_requests"])
    for r in reqs:
        r.max_new = min(r.max_new, cfg["check"]["max_new_tokens"])
    return reqs


def check(ctx, served: Served, ref, arch, gen) -> Dict:
    cfg = ctx.config
    reqs = sample_requests(cfg, ctx.traffic, ctx.seed, arch, gen)
    got = engine_rows(served, reqs)
    want = reference_rows(ref, arch, cfg, ctx.seed, reqs, got["tokens"])
    numbers = serve_numbers(reqs, got["rows"], got["tokens"], want)
    for k, limit in cfg["check"]["limits"].items():
        ctx.verdict.hold(f"serve.{k}", numbers[k], limit)
    ctx.verdict.require("serve.every_token_delivered", all(
        len(got["tokens"][r.rid]) == r.max_new for r in reqs))
    return numbers


def open_loop_outcome(served: Served, window: Dict, drain_s: float):
    """(attempted, late, failed) of an open-loop window. Every request due
    in it was asked for in it. One that has not delivered all its tokens
    when the window closes is *late*: its wait so far stands among the
    TTFT samples (``stats.ttfts``), and that is where lateness is judged.
    The engine then gets ``drain_s`` more, outside every ledger, for what
    the close left in flight or unsent; a request still short of its
    tokens after that — dropped, cut short, stuck — has *failed*."""
    due = [rid for rid, at in window["scheduled"].items()
           if window["t0"] <= at < window["t1"]]
    late = sum(rid not in served.done_at for rid in due)
    for req in window.get("unsent", []):
        served.put(req)
    served.drain(drain_s)
    return len(due), late, sum(rid not in served.done_at for rid in due)


def where_the_close_fell(steps, t0: float, t1: float) -> Dict:
    """So that a run says where its window closed: the tokens of the step
    that straddles the close (0: it closed between steps) and the window's
    seconds outside any ``serve_step`` (a run that stalls between steps
    names itself; one that stalls inside a step shows in ``tokens``)."""
    inside = sum(d * stats.step_share_inside(s, d, t0, t1) for s, d, _ in steps)
    return {"straddling_step_tokens": sum(n for s, d, n in steps
                                          if s < t1 < s + d),
            "outside_serve_step_s": (t1 - t0) - inside}


def slice_of_whole_steps(steps: List[Dict], lo: float, hi: float):
    """A capture opened and closed from a thread begins and ends wherever
    the generator's loop then is. A step that an end of ``[lo, hi]`` falls
    into is left out and the slice cut back to that step's border, so that
    the trace holds the work of whole steps, which the readers divide it
    by; an end that falls between steps stays where it fell. Returns the
    slice and the range of the steps inside it."""
    lo = max([lo] + [s["t"] + s["dt"] for s in steps
                     if s["t"] < lo < s["t"] + s["dt"]])
    hi = min([hi] + [s["t"] for s in steps if s["t"] < hi < s["t"] + s["dt"]])
    inside = [i for i, s in enumerate(steps)
              if lo <= s["t"] and s["t"] + s["dt"] <= hi]
    return lo, hi, (inside[0], inside[-1] + 1) if inside else (0, 0)


def run(ctx) -> Dict:
    cfg, traffic = ctx.config, ctx.traffic
    ref = manifest.reference_of(cfg, ctx.bench_dir)
    arch = ref.Arch.from_model(cfg)
    gen = manifest.load_module("generators", traffic["generator"],
                               ctx.bench_dir)
    parts = {}
    t = time.perf_counter()
    engine, parts["weights_s"] = build_engine(cfg, arch, ctx.seed)
    parts["engine_s"] = time.perf_counter() - t - parts["weights_s"]
    served = Served(engine)
    parts.update(warm_up(served, cfg, arch.vocab_size))
    t = time.perf_counter()
    numbers = check(ctx, served, ref, arch, gen)
    parts["check_s"] = time.perf_counter() - t
    t = time.perf_counter()
    if hasattr(gen, "prewarm"):
        gen.prewarm(served, traffic, ctx.seed, arch.vocab_size)
    parts["traffic_setup_s"] = time.perf_counter() - t
    ctx.note({"setup_parts": parts, "numbers": numbers,
              "traffic": gen.describe(traffic, ctx.seconds)})

    counters0 = {}
    cap = {"trace": None, "steps": (0, 0)}
    # the traced slice of the window, on the served clock: the mix's own
    # (``trace_window_s``, for a timeline whose start is not typical of
    # it), or the first ``trace_seconds``
    t_from, t_to = traffic.get(
        "trace_window_s", [0.0, cfg.get("trace_seconds", 4.0)])
    if t_to > ctx.seconds:                # a short trial run: its first half
        t_from, t_to = 0.0, ctx.seconds / 2

    def snapshot():
        return {"engine": {k: v for k, v in engine.stats.items()
                           if isinstance(v, (int, float))},
                "fallback_reasons": dict(engine.stats["fallback_reasons"]),
                "scheduler": dict(engine.scheduler.stats)}

    def on_open():
        counters0.update(snapshot())
        ctx.compiles0 = compiles.count()
        ctx.mark_setup_done()
        if ctx.trace:
            cap["thread"] = threading.Thread(target=capture_slice,
                                             daemon=True)
            cap["thread"].start()

    def capture_slice():
        """The slice is opened and closed from a thread beside the
        generator's loop: stopping the profiler takes 13 s on the chip
        (starting, 0.05 s) and reading the file back seconds more, and a
        loop that waited for them put the requests due meanwhile 2-3 s
        late (``generator_lag_p90_ms`` 2,751-2,970 under the profiler,
        ledger, PR 38 and 39; 0.6 from the thread, PERF.md section 6)."""
        try:
            time.sleep(max(0.0, t_from - served.now()))
            c = trace.Capture(ctx.trace_dir, read_on_exit=False)
            c.__enter__()
            cap["at"] = [served.now(), None]
            time.sleep(max(0.0, t_to - served.now()))
            cap["at"][1] = served.now()
            c.__exit__(None, None, None)
            cap["c"], cap["stop_s"] = c, served.now() - cap["at"][1]
        except BaseException as e:        # raised again where it is joined
            cap["error"] = e

    window = gen.drive(served, traffic, ctx.seed, arch.vocab_size,
                       ctx.seconds, on_open)
    if "thread" in cap:
        cap["thread"].join()
        if "error" in cap:
            raise cap["error"]
        whole = cap["c"].read()
        lo, hi, cap["steps"] = slice_of_whole_steps(served.steps, *cap["at"])
        if whole is not None:
            on_trace_clock = whole.t0 - cap["at"][0]
            cap["trace"] = whole.between(lo + on_trace_clock,
                                         hi + on_trace_clock)
        ctx.note({"traced_slice_s": [lo, hi], "profiler_open_s": cap["at"],
                  "profiler_stop_s": cap["stop_s"]})
    in_window = compiles.count() - ctx.compiles0
    ctx.verdict.require("no_compile_in_window", in_window == 0,
                        f"{in_window} compilations inside the window: "
                        f"{compiles.SEEN[-in_window:] if in_window else []}")
    now = snapshot()
    delta = {g: {k: now[g][k] - counters0[g].get(k, 0) for k in now[g]}
             for g in now}

    t0, t1 = window["t0"], window["t1"]
    span = t1 - t0
    steps = [(s["t"], s["dt"], s["tokens"]) for s in served.steps]
    tokens = stats.tokens_prorated(steps, t0, t1)
    done = served.completed()
    truncated = delta["engine"].get("truncated", 0)
    busy_s = sum(d for _, d in served.busy)
    e2e = {"serve_tokens_per_s": stats.closed_loop_rate(steps, t0, t1),
           "serve_busy_ms_per_req": 1e3 * busy_s / max(1, len(done))}
    # the gaps a client sees keep their whole deliveries: up to the end of
    # the last step, which a closed loop's window cuts (``t_end``)
    gaps = stats.token_gaps(served.deliveries, t0, window.get("t_end", t1))
    if gaps:
        e2e["tpot_p90_ms"] = 1e3 * stats.percentile(gaps, 90)
    samples = {"token_gaps": len(gaps), "requests_completed": len(done)}
    if "scheduled" in window:
        tt = stats.ttfts(served.deliveries, window["scheduled"], t0, t1)
        attempted, late, failed = open_loop_outcome(served, window,
                                                    ctx.seconds)
        samples.update(ttft=len(tt), unfinished_at_close=late)
        # in timeline order: [request, burst, due, put after due, first
        # token after due, last token after due], so a run says which
        # request moved and how long a burst kept the engine (PERF.md 2)
        lag = dict(zip(window["scheduled"], window["generator_lag_s"]))
        groups = window.get("groups", {})
        due_in = [(r, d) for r, d in window["scheduled"].items()
                  if t0 <= d < t1]                   # ``stats.ttfts``'s order
        samples["ttft_by_request_ms"] = [
            [rid, groups.get(rid), round(1e3 * due, 1),
             round(1e3 * lag[rid], 2) if rid in lag else None,
             round(1e3 * x, 2),
             round(1e3 * (served.done_at[rid] - due), 1)
             if rid in served.done_at else None]
            for (rid, due), x in zip(due_in, tt)]
        if tt:
            e2e["ttft_p50_ms"] = 1e3 * stats.percentile(tt, 50)
            e2e["ttft_p90_ms"] = 1e3 * stats.percentile(tt, 90)
    else:
        # closed loop: the requests in flight at the close are the load
        attempted, failed = len(done) + served.outstanding, truncated
    ctx.note({"window_s": span, "tokens": tokens, "samples": samples,
              "close": where_the_close_fell(steps, t0, t1),
              "serve_step_share": busy_s / span,
              "end_to_end": e2e, "counters": delta})
    return {"attempted": attempted, "failed": failed,
            "end_to_end": e2e, "trace": cap["trace"],
            "counters": delta, "served": served, "window": window,
            "facts": {"arch": arch, "traced_steps": cap["steps"],
                      "traffic": traffic, "samples": samples}}

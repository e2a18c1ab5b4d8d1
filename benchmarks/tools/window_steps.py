"""What a serving cell's window was made of, step by step: for a run that
reads slow (PERF.md section 2, "Stalls"). One process, one engine,
``--windows`` windows of the cell's own traffic, each under another seed.
For each window one line: the rate and the tokens, seconds by the tokens a
step handed out (32 is a gather step of ``gen-closed-32``, 64-256 a decode
burst), the last five steps, every step that took over three times the
median of its kind (a stall names itself), the seconds outside any
``serve_step``, the host's CPU seconds and collections inside the window
(a stall with neither raised is the machine's or the device runtime's)
and, for an open-loop mix, the sorted times to first token. ``--out``
keeps every window's timeline as [start, seconds, tokens].

``--watch DIR`` is for catching a stall and saying whose it is. Three
clocks tick beside the engine's thread: a child process that never touches
JAX (a gap in *its* ticks is the machine's: every process on it stood
still), a thread of this process (a gap in its ticks and none in the
child's: this process was frozen, or a C call held the interpreter), and
``serve_step`` itself (slow with both ticking: the step waits, for the
device or a lock). A step that has run ``--slow-s`` seconds has every
thread's Python stack, name and state written to ``DIR/dumps.txt`` while
it still hangs, and once more 3 s later. The last line lists every slow
step with the gaps that overlap it. Watching costs: the rate read 458-474
tokens/s watched against 510-512 (my chip run, PR 27), so this is a tool
and no part of a run. What it found: PERF.md section 2, "Stalls".

    python3 benchmarks/tools/window_steps.py --workload serve-gen-closed \
        --windows 3 --seconds 40 --seed 5 [--out FILE] [--manifest FILE] \
        [--watch DIR [--slow-s 1.5]]
"""

import argparse
import gc
import inspect
import json
import os
import resource
import statistics
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import manifest as mf  # noqa: E402


def host_counters():
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"cpu_user_s": ru.ru_utime, "cpu_sys_s": ru.ru_stime,
            "gc_collections": sum(g["collections"] for g in gc.get_stats())}


_TICKER = r"""
import json, os, sys, time
out = open(sys.argv[1], "a", buffering=1)
parent = int(sys.argv[2])
while os.getppid() == parent:           # never outlives the tool
    t = time.monotonic()
    time.sleep(0.02)
    gap = time.monotonic() - t
    if gap > 0.25:
        out.write(json.dumps({"gap": [t, gap]}) + "\n")
"""


def _read(path, limit=2000):
    try:
        with open(path) as f:
            return f.read(limit).strip()
    except OSError as e:
        return f"<{e.strerror}>"


class Watch(threading.Thread):
    """The ticking thread, the child that ticks, and the dumps."""

    def __init__(self, out_dir, slow_s):
        super().__init__(daemon=True)
        os.makedirs(out_dir, exist_ok=True)
        self.dir, self.slow_s = out_dir, slow_s
        self.step_t = None               # monotonic start of the running step
        self.gaps, self.slow, self.dumps = [], [], 0
        self.child_log = os.path.join(out_dir, "child_ticks.jsonl")
        open(self.child_log, "w").close()
        self.child = subprocess.Popen(
            [sys.executable, "-c", _TICKER, self.child_log, str(os.getpid())],
            stdin=subprocess.DEVNULL, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        self.halt = False

    def wrap(self, engine):
        step = engine.serve_step

        def watched():
            self.step_t = t = time.monotonic()
            try:
                return step()
            finally:
                self.step_t = None
                if time.monotonic() - t > self.slow_s:
                    self.slow.append((t, time.monotonic() - t))

        engine.serve_step = watched

    def run(self):
        dumped = {}
        while not self.halt:
            t = time.monotonic()
            time.sleep(0.02)
            now = time.monotonic()
            if now - t > 0.25:
                self.gaps.append((t, now - t))
            s = self.step_t
            if s is None:
                continue
            for after in (self.slow_s, self.slow_s + 3.0):
                if now - s > after and dumped.get(s, -1.0) < after:
                    dumped[s] = after
                    self.dump(s, now)

    def dump(self, s, now):
        import faulthandler

        self.dumps += 1
        with open(os.path.join(self.dir, "dumps.txt"), "a") as f:
            f.write(f"\n===== serve_step begun at {s:.3f} has run "
                    f"{now - s:.2f} s (monotonic {now:.3f})\n")
            f.flush()
            faulthandler.dump_traceback(f, all_threads=True)
            for tid in sorted(os.listdir("/proc/self/task"), key=int):
                base = f"/proc/self/task/{tid}/"
                stat = _read(base + "stat").rsplit(")", 1)[-1].split()
                f.write(f"--- tid {tid} {_read(base + 'comm')!r} state "
                        f"{stat[0] if stat else '?'}\n")

    def close(self):
        """Stops both tickers; returns every slow step with what ticked
        through it."""
        self.halt = True
        self.join(2.0)
        self.child.terminate()
        self.child.wait()
        with open(self.child_log) as f:
            child_gaps = [json.loads(line)["gap"] for line in f]

        def over(gaps, a, b):
            return [[round(t, 3), round(g, 3)] for t, g in gaps
                    if t < b and t + g > a]

        return {"slow_steps": [{"step_at": round(t, 3), "seconds": round(d, 3),
                                "thread_gaps": over(self.gaps, t, t + d),
                                "child_gaps": over(child_gaps, t, t + d)}
                               for t, d in self.slow],
                "dumps": self.dumps,
                "thread_gaps_all": over(self.gaps, 0.0, float("inf")),
                "child_gaps_all": over(child_gaps, 0.0, float("inf"))}


def report(steps, t0, t1):
    """``steps`` are (start, seconds, tokens) of one window ``[t0, t1]``."""
    from benchmarks.harness import stats

    kinds = {}
    for s, d, n in steps:
        share = stats.step_share_inside(s, d, t0, t1)
        if share > 0.0:
            kinds.setdefault(n, []).append(d)
    medians = {n: statistics.median(ds) for n, ds in kinds.items()}
    inside = sum(d * stats.step_share_inside(s, d, t0, t1) for s, d, _ in steps)
    return {"tokens": stats.tokens_prorated(steps, t0, t1),
            "serve_tokens_per_s": stats.closed_loop_rate(steps, t0, t1),
            "one_close_tokens_per_s": stats.tokens_prorated(steps, t0, t1)
            / (t1 - t0),
            "steps_by_tokens": {str(n): [len(ds), round(sum(ds), 4)]
                                for n, ds in sorted(kinds.items())},
            "slow_steps": [[round(s, 4), round(d, 4), n] for s, d, n in steps
                           if n in medians and d > 3 * medians[n]],
            "last_steps": [[round(s, 4), round(d, 4), n]
                           for s, d, n in steps if t0 <= s < t1][-5:],
            "outside_serve_step_s": (t1 - t0) - inside}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--manifest", default=os.path.join(mf.ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--out", default=None)
    ap.add_argument("--watch", default=None, metavar="DIR")
    ap.add_argument("--slow-s", type=float, default=1.5)
    args = ap.parse_args()
    # the child that ticks starts before JAX does and never touches it
    watch = Watch(args.watch, args.slow_s) if args.watch else None
    man, bench_dir, cell, cfg, traffic = mf.resolve(args.manifest,
                                                    args.workload)
    mf.program_logs_to_stderr()
    from benchmarks.generators.requests import Served
    from benchmarks.harness import cache, compiles, stats
    cache.enable()
    compiles.install()
    runner = mf.load_module("runners", "serve", bench_dir)
    gen = mf.load_module("generators", traffic["generator"], bench_dir)
    arch = mf.reference_of(cfg, bench_dir).Arch.from_model(cfg)
    engine, _ = runner.build_engine(cfg, arch, args.seed)
    served = Served(engine)
    runner.warm_up(served, cfg, arch.vocab_size)
    if hasattr(gen, "prewarm"):
        gen.prewarm(served, traffic, args.seed, arch.vocab_size)
    if watch:
        watch.wrap(engine)
        watch.start()
    timelines = []
    # an open-loop plan numbers its requests from its salt
    salted = "salt" in inspect.signature(gen.drive).parameters
    for i in range(args.windows):
        host0, before = {}, compiles.count()
        served = Served(engine)             # a ledger of its own a window
        win = gen.drive(served, traffic, args.seed + i, arch.vocab_size,
                        args.seconds,
                        on_window_open=lambda: host0.update(host_counters()),
                        **({"salt": 10 + i} if salted else {}))
        host = {k: v - host0[k] for k, v in host_counters().items()}
        steps = [(s["t"], s["dt"], s["tokens"]) for s in served.steps]
        row = dict(report(steps, win["t0"], win["t1"]), window=i,
                   seed=args.seed + i, host_in_window=host,
                   compiles=compiles.count() - before)
        if "scheduled" in win:
            row["ttfts_ms"] = [round(1e3 * x, 1) for x in sorted(stats.ttfts(
                served.deliveries, win["scheduled"], win["t0"], win["t1"]))]
        timelines.append([[round(s, 6), round(d, 6), n] for s, d, n in steps])
        print(json.dumps(row), flush=True)
        while served.outstanding:           # drain before the next window
            served.step()
    if watch:
        print(json.dumps({"watch": watch.close()}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(timelines, f)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)

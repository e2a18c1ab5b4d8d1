"""Find the knee of an open-loop mix once: the highest rate the system
sustains without a growing backlog. One process, one engine; each rate
runs ``--seconds`` of the cell's own traffic and reports what was sent,
what completed, the backlog left at the end, the share of the time spent
inside ``serve_step``, and time to first token. The cell then runs at a
fixed 0.8 of the knee, written into its traffic file as a number.

    python3 benchmarks/tools/sweep_rate.py --workload serve-rag-burst \
        --rates 0.4,0.6,0.8,1.0,1.2 --seconds 30 --seed 5 [--out FILE]

``--rates`` are bursts per second (a burst is 3 requests on average).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import manifest as mf  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--manifest", default=os.path.join(mf.ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--arrival-span", type=float, default=None,
                    help="share of each window in which requests arrive "
                         "(default: the traffic file's); 1.0 leaves a rate "
                         "above the knee no time to catch up")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
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
    gen.prewarm(served, traffic, args.seed, arch.vocab_size)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t = dict(traffic, bursts_per_s=rate)
        if args.arrival_span is not None:
            t["arrival_span"] = args.arrival_span
        before = compiles.count()
        win = gen.drive(served, t, args.seed + i, arch.vocab_size,
                        args.seconds, salt=10 + i)
        backlog = served.outstanding
        tt = stats.ttfts(served.deliveries, win["scheduled"], 0.0, win["t1"])
        busy = sum(d for _, d in served.busy)
        row = {"bursts_per_s": rate, "arrival_span": t.get("arrival_span", 1.0),
               "planned": len(win["scheduled"]), "sent": win["sent"],
               "requests_per_s": win["sent"] / args.seconds,
               "completed": len(served.completed()), "backlog": backlog,
               "compiles": compiles.count() - before,
               "busy_share": busy / win["t1"],
               "ttft_p50_ms": 1e3 * stats.percentile(tt, 50) if tt else None,
               "ttft_p90_ms": 1e3 * stats.percentile(tt, 90) if tt else None,
               "ttfts_ms": [round(1e3 * x, 1) for x in sorted(tt)],
               "first_tokens": sum(r in stats.first_token_times(
                   served.deliveries) for r in win["scheduled"]),
               "lag_p90_ms": 1e3 * stats.percentile(win["generator_lag_s"], 90)
               if win["generator_lag_s"] else None}
        # TTFT of the last third against the first third: a growing queue
        third = args.seconds / 3
        first = stats.first_token_times(served.deliveries)
        early = [first[r] - s for r, s in win["scheduled"].items()
                 if r in first and s < third]
        late = [first[r] - s for r, s in win["scheduled"].items()
                if r in first and s >= 2 * third]
        row["ttft_p50_first_third_ms"] = 1e3 * stats.percentile(early, 50) \
            if early else None
        row["ttft_p50_last_third_ms"] = 1e3 * stats.percentile(late, 50) \
            if late else None
        rows.append(row)
        print(json.dumps(row), flush=True)
        while served.outstanding:           # drain before the next rate
            served.step()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)

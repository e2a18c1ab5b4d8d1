"""Read, on many seeds in one process, the numbers ``correct`` rests on:
for the program (sound runs) and for the control — the plain reference put
in the program's place and computed in the precision below the one the
configuration states. A limit is set only from these two readings (PERF.md
section 2); the benchmark's own runs never run the control.

    python3 benchmarks/tools/check_seeds.py --workload train-z3-2k \
        --seeds 101,102,103 --control-seeds 101,102,103 [--out FILE]

One JSON line per seed and side, then a summary: the program's largest,
the control's smallest, and their ratio, for every number compared.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import manifest as mf  # noqa: E402


def _train(cfg, traffic, cell, seeds, control_seeds, control, emit, bench_dir):
    import jax

    from benchmarks.harness import compare
    runner = mf.load_module("runners", "train", bench_dir)
    gen = mf.load_module("generators", traffic["generator"], bench_dir)
    ref_mod = mf.reference_of(cfg, bench_dir)
    arch = ref_mod.Arch.from_model(cfg)
    chips, seq = cell["chips"], cfg["seq_len"]
    gb = cfg["job"]["train_micro_batch_size_per_chip"] * chips
    for seed in sorted(set(seeds) | set(control_seeds)):
        batch0, distinct = gen.check_batch(
            traffic, seed, arch.vocab_size, gb, seq,
            cfg["check"]["sample_sequences"])
        t = time.perf_counter()
        ref = runner.reference_numbers(ref_mod, arch, cfg, distinct, seed)
        ref_s = time.perf_counter() - t
        if seed in control_seeds:
            low = runner.reference_numbers(ref_mod, arch, cfg, distinct, seed,
                                           control)
            emit({"seed": seed, "side": "control", "numerics": control,
                  "loss": compare.rel_abs(low["loss"], ref["loss"]),
                  "grad_norm": compare.rel_abs(low["grad_norm"],
                                               ref["grad_norm"]),
                  "grad_leaves": max(compare.rel_l2(low["kept"][k],
                                                    ref["kept"][k])
                                     for k in ref["plan"])})
        if seed in seeds:
            _, engine = runner.build_engine(cfg, arch, seed, chips)
            data = gen.batches(traffic, seed, arch.vocab_size, gb, seq)
            loss0 = float(engine.train_batch(iter([{"input_ids": batch0}])))
            engine.synchronize()
            rows = runner.engine_gradient_rows(engine, arch, ref["plan"])
            gnorm = rows.pop("_norm")
            v = compare.Verdict()
            num = runner.compare_to_reference(
                v, ref, loss0, gnorm, rows,
                {k: float("inf") for k in ("loss", "grad_norm",
                                           "grad_leaves")})
            emit(dict(num, seed=seed, side="program", reference_s=ref_s))
            engine.params = engine.opt_state = None
            del engine, rows
            gc.collect()
            jax.clear_caches()


def _serve(cfg, traffic, cell, seeds, control_seeds, control, emit, bench_dir):
    import jax
    import jax.numpy as jnp

    from benchmarks.generators.requests import Served
    from benchmarks.harness import weights
    runner = mf.load_module("runners", "serve", bench_dir)
    gen = mf.load_module("generators", traffic["generator"], bench_dir)
    ref_mod = mf.reference_of(cfg, bench_dir)
    arch = ref_mod.Arch.from_model(cfg)
    n = cfg["check"]["sample_requests"]
    engine = served = None
    for seed in sorted(set(seeds) | set(control_seeds)):
        if engine is None:
            engine, _ = runner.build_engine(cfg, arch, seed)
            served = Served(engine)
        else:
            engine.params = None
            gc.collect()
            engine._v1.params = None        # both references to the old
            gc.collect()                    # weights go before the new come
            engine.reload_params(weights.make_program_params(
                arch, seed, jnp.bfloat16))
        reqs = runner.sample_requests(cfg, traffic, seed, arch, gen)
        for r in reqs:          # one engine serves every seed: fresh ids
            r.rid += seed * 10
        t = time.perf_counter()
        got = runner.engine_rows(served, reqs)
        ref = runner.reference_rows(ref_mod, arch, cfg, seed, reqs,
                                    got["tokens"])
        if seed in seeds:
            num = runner.serve_numbers(reqs, got["rows"], got["tokens"], ref)
            emit(dict(num, seed=seed, side="program",
                      check_s=time.perf_counter() - t))
        if seed in control_seeds:
            low = runner.reference_rows(ref_mod, arch, cfg, seed, reqs,
                                        got["tokens"], control)
            rows = {r.rid: {j: low[r.rid][j] for j in got["rows"][r.rid]}
                    for r in reqs}
            toks = {r.rid: [int(x) for x in low[r.rid].argmax(-1)]
                    for r in reqs}
            emit(dict(runner.serve_numbers(reqs, rows, toks, ref), seed=seed,
                      side="control", numerics=control))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default=None)
    ap.add_argument("--manifest", default=os.path.join(mf.ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    man, bench_dir, cell, cfg, traffic = mf.resolve(args.manifest,
                                                    args.workload)
    mf.program_logs_to_stderr()
    from benchmarks.harness import cache
    cache.enable()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    (_train if cfg["kind"] == "train" else _serve)(
        cfg, traffic, cell, seeds, cseeds,
        args.control or cfg["check"]["control"], emit, bench_dir)
    summary = {}
    for key in cfg["check"]["limits"]:
        prog = [r[key] for r in rows if r["side"] == "program"]
        ctrl = [r[key] for r in rows if r["side"] == "control"]
        summary[key] = {"program_max": max(prog, default=None),
                        "program_min": min(prog, default=None),
                        "control_min": min(ctrl, default=None),
                        "ratio": (min(ctrl) / max(prog))
                        if prog and ctrl and max(prog) > 0 else None}
    print(json.dumps({"summary": summary, "workload": args.workload}),
          flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)

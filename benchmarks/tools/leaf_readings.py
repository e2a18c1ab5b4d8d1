"""Per-leaf readings of a training cell's check, on many seeds in one
process: for every gradient leaf in the plan, the relative L2 distance of
the program's rows and of the control's (the reference in the precision
below the configuration's) from the float32 reference's — what
``tools/check_seeds.py`` folds into one maximum. Where the reference gives
``routed_pairs``, also the share of (token, expert) pairs that the
reference computed in ``--flip-numerics`` (bf16: the program's operand
type) routes differently from the float32 reference.

    python3 benchmarks/tools/leaf_readings.py --workload W --seeds 1,2 \
        [--control-seeds 1] [--manifest M] [--rehearse] [--out FILE]

``--job`` and ``--preset`` merge a JSON object into the configuration's job
and into its preset's overrides, ``--matmul-precision`` sets JAX's default
for the program: the witness that a gap between program and reference is the
precision's is the program run in the reference's (``--job '{"bf16":
{"enabled": false}, "train_micro_batch_size_per_chip": 1, "kernels":
{"flash_block_q": 512, "flash_block_k": 512}}' --preset '{"dtype":
"float32"}' --matmul-precision highest``: the check's one sequence is then
the whole batch, and float32 blocks of 512 are what VMEM holds).
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import manifest as mf  # noqa: E402


def per_leaf(rows, ref):
    from benchmarks.harness import compare

    return {k: compare.rel_l2(rows[k], ref["kept"][k]) for k in ref["plan"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--flip-numerics", default="bf16")
    ap.add_argument("--manifest", default=os.path.join(mf.ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--job", default="{}")
    ap.add_argument("--preset", default="{}")
    ap.add_argument("--matmul-precision")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    _, bench_dir, cell, cfg, traffic = mf.resolve(args.manifest, args.workload)
    cfg = dict(cfg, job={**cfg["job"], **json.loads(args.job)},
               preset_overrides={**cfg.get("preset_overrides", {}),
                                 **json.loads(args.preset)})
    mf.program_logs_to_stderr()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import cache, device, weights

    cache.enable()
    device.require(cell["chips"], args.rehearse)
    runner = mf.load_module("runners", "train", bench_dir)
    gen = mf.load_module("generators", traffic["generator"], bench_dir)
    ref_mod = mf.reference_of(cfg, bench_dir)
    arch = ref_mod.Arch.from_model(cfg)
    chips, seq = cell["chips"], cfg["seq_len"]
    gb = cfg["job"]["train_micro_batch_size_per_chip"] * chips
    out = open(args.out, "w") if args.out else None

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        if out:
            print(line, file=out, flush=True)

    for seed in sorted(set(seeds) | set(control_seeds)):
        batch0, distinct = gen.check_batch(traffic, seed, arch.vocab_size, gb,
                                           seq, cfg["check"]["sample_sequences"])
        ref = runner.reference_numbers(ref_mod, arch, cfg, distinct, seed)
        if seed in control_seeds:
            low = runner.reference_numbers(ref_mod, arch, cfg, distinct, seed,
                                           cfg["check"]["control"])
            emit({"seed": seed, "side": "control",
                  "leaves": per_leaf(low["kept"], ref)})
            del low
        if hasattr(ref_mod, "routed_pairs"):
            def pairs(numerics):
                return np.asarray(ref_mod.routed_pairs(
                    arch, jnp.asarray(distinct[0, :-1]),
                    weights.reference_layer_fn(arch, seed, jnp.float32),
                    weights.reference_top(arch, seed, jnp.float32), numerics))
            a, b = pairs("float32"), pairs(args.flip_numerics)
            # sorted sets of k a token: pairs of a not in b
            same = (a[..., :, None] == b[..., None, :]).any(-1)
            emit({"seed": seed, "side": "flips", "numerics": args.flip_numerics,
                  "pairs": int(same.size),
                  "flipped_share": float(1.0 - same.mean()),
                  "by_expert_layer": [float(1.0 - s.mean()) for s in same]})
        if seed in seeds:
            with jax.default_matmul_precision(args.matmul_precision):
                _, engine = runner.build_engine(cfg, arch, seed, chips)
                loss0 = float(engine.train_batch(
                    iter([{"input_ids": batch0}])))
                engine.synchronize()
            rows = runner.engine_gradient_rows(engine, arch, ref["plan"])
            gnorm = rows.pop("_norm")
            hub_row = {}
            try:
                from deepspeed_tpu.observability.hub import peek_hub
                hub_row = dict(peek_hub().step_history[-1].extras)
            except Exception:
                pass
            emit({"seed": seed, "side": "program", "loss": [loss0, ref["loss"]],
                  "grad_norm": [gnorm, ref["grad_norm"]],
                  "leaves": per_leaf(rows, ref), "counted": hub_row,
                  "job": json.loads(args.job),
                  "preset": json.loads(args.preset),
                  "matmul_precision": args.matmul_precision})
            engine.params = engine.opt_state = None
            del engine, rows
            gc.collect()
            jax.clear_caches()
        del ref
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)

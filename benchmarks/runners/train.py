"""One training cell: ``dstpu.initialize`` -> ``engine.train_batch`` on a
stream of fresh batches, the whole optimizer step timed by the host clock
around steps that end in ``block_until_ready``.

Order of a run: the plain reference first, on an empty chip (loss, global
gradient norm and sampled gradient rows of the check batch: a seeded
sample of sequences, one of its own for every chip, repeated to the
batch's shape); then the engine, built
on weights from the same seed; its first step, on that batch, gives the
loss before any update and, through AdamW's first moment (mu = 0.1 g after
one step from zero, no clipping), the gradient it applied; a second
warm-up step; then the window. All of that is set-up.
"""

from __future__ import annotations

import math
import time
from typing import Dict

import numpy as np

from benchmarks.harness import compare, compiles, manifest, trace, weights


def _sample_rows(n_rows: int, want: int, seed: int, salt: int):
    rng = np.random.default_rng([int(seed), salt])
    return np.sort(rng.choice(n_rows, size=min(want, n_rows), replace=False))


def _leaf_plan(ref, arch, seed: int, rows: int) -> Dict[str, np.ndarray]:
    """Which gradient leaves are compared, and which rows of each (axis
    0): of one seeded layer the leaves the reference lists
    (``CHECK_LAYER_LEAVES``) and of the top its ``CHECK_TOP_LEAVES``; a
    matrix gives a seeded sample of its rows, a vector (a gain) all of
    itself."""
    layer = int(np.random.default_rng([int(seed), 7]).integers(
        arch.num_hidden_layers))
    plan = {}
    for names, prefix, salt in ((ref.CHECK_LAYER_LEAVES, f"layers.{layer}.", 100),
                                (ref.CHECK_TOP_LEAVES, "", 200)):
        matrices = 0
        for name in names:
            shape = weights.leaf_of(arch, name).shape
            if len(shape) == 1:
                plan[prefix + name] = np.arange(shape[0])
            else:
                plan[prefix + name] = _sample_rows(shape[0], rows, seed,
                                                   salt + matrices)
                matrices += 1
    return plan


def reference_numbers(ref, arch, cfg, batch, seed, numerics="float32") -> Dict:
    import jax.numpy as jnp

    plan = _leaf_plan(ref, arch, seed, cfg["check"]["sample_rows"])

    def keep(name, g):
        rows = plan.get(name)
        return None if rows is None else np.asarray(g[jnp.asarray(rows)])

    out = ref.loss_and_grads(
        arch, batch, weights.reference_layer_fn(arch, seed, jnp.float32),
        weights.reference_top(arch, seed, jnp.float32), keep, numerics)
    out["plan"] = plan
    return out


def _tree_get(tree, dotted: str):
    for part in dotted.split("."):
        tree = tree[part]
    return tree


def engine_gradient_rows(engine, arch, plan, b1: float = 0.9
                         ) -> Dict[str, np.ndarray]:
    """The gradient of the step just taken, read from AdamW's first moment
    (mu_1 = (1 - b1) g_1 from a zero start; the job clips nothing)."""
    import jax
    import jax.numpy as jnp
    import optax

    mu = optax.tree_utils.tree_get(engine.opt_state.inner, "mu")
    out = {"_norm": float(jax.jit(optax.global_norm)(mu)) / (1.0 - b1)}
    for name, rows in plan.items():
        leaf = _tree_get(mu, weights.program_leaf_name(arch, name))
        if name.startswith("layers."):
            leaf = leaf[int(name.split(".")[1])]
        out[name] = np.asarray(leaf[jnp.asarray(rows)]) / (1.0 - b1)
    return out


def compare_to_reference(verdict, ref: Dict, loss0: float, gnorm: float,
                         rows: Dict[str, np.ndarray], limits: Dict) -> Dict:
    numbers = {"loss": compare.rel_abs(loss0, ref["loss"]),
               "grad_norm": compare.rel_abs(gnorm, ref["grad_norm"]),
               "grad_leaves": max(compare.rel_l2(rows[k], ref["kept"][k])
                                  for k in ref["plan"])}
    # only a number that separates the program from the control carries a
    # limit (the gradient rows; the loss and the norm are averages that an
    # fp8 reference reproduces as closely as the bf16 program does); the
    # others are printed in the run's notes
    for k, limit in limits.items():
        verdict.hold(f"train.{k}", numbers[k], limit)
    return numbers


def build_engine(cfg: Dict, arch, seed: int, cell_chips: int):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.zoo import get_model
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    base = get_model(cfg["preset"], num_layers=arch.num_hidden_layers,
                     max_seq_len=cfg["seq_len"], **cfg.get("preset_overrides", {}))

    class SeededLM(type(base)):
        """The zoo model with its weights drawn by the benchmark."""

        def init(self, rng):
            # the engine's own key, PRNGKey(config seed) = base_key(seed),
            # an argument of its jitted init: one program for every seed
            return weights.program_params(arch, rng, jnp.float32)

    model = SeededLM(base.config)
    mesh = build_mesh(TopologyConfig(**cfg.get("mesh", {})),
                      devices=jax.devices()[:cell_chips])
    engine, _, _, _ = dstpu.initialize(
        model=model, config=dict(cfg["job"], seed=int(seed)),
        mesh=mesh)
    return model, engine


def run(ctx) -> Dict:
    import jax

    cfg, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    ref_mod = manifest.reference_of(cfg, ctx.bench_dir)
    arch = ref_mod.Arch.from_model(cfg)
    seq, chips = cfg["seq_len"], ctx.cell["chips"]
    gen = manifest.load_module("generators", traffic["generator"], ctx.bench_dir)
    global_batch = cfg["job"]["train_micro_batch_size_per_chip"] * chips \
        * cfg["job"].get("gradient_accumulation_steps", 1)
    parts = {}
    if cfg["check"]["sample_sequences"] % chips:
        # one sequence on every chip would leave the mean over chips equal
        # to each chip's own gradient, and the reduction unchecked
        raise ValueError(f"check.sample_sequences = "
                         f"{cfg['check']['sample_sequences']} gives the "
                         f"{chips} chips no sequence each of their own")

    t = time.perf_counter()
    batch0, distinct = gen.check_batch(traffic, seed, arch.vocab_size,
                                       global_batch, seq,
                                       cfg["check"]["sample_sequences"])
    ref = reference_numbers(ref_mod, arch, cfg, distinct, seed)
    parts["reference_s"] = time.perf_counter() - t

    t = time.perf_counter()
    model, engine = build_engine(cfg, arch, seed, chips)
    assert engine.train_batch_size == global_batch, engine.train_batch_size
    data = gen.batches(traffic, seed, arch.vocab_size, global_batch, seq)
    parts["engine_s"] = time.perf_counter() - t

    t = time.perf_counter()
    loss0 = float(engine.train_batch(iter([{"input_ids": batch0}])))
    engine.synchronize()
    rows = engine_gradient_rows(engine, arch, ref["plan"])
    gnorm = rows.pop("_norm")
    parts["first_step_s"] = time.perf_counter() - t
    numbers = compare_to_reference(ctx.verdict, ref, loss0, gnorm, rows,
                                   cfg["check"]["limits"])
    ctx.note({"reference": {"loss": ref["loss"], "grad_norm": ref["grad_norm"]},
              "engine": {"loss": loss0, "grad_norm": gnorm}, "numbers": numbers})
    del ref, rows
    t = time.perf_counter()
    jax.block_until_ready(engine.train_batch(data))
    engine.synchronize()
    parts["second_step_s"] = time.perf_counter() - t
    ctx.note({"setup_parts": parts})

    def steps(seconds: float):
        """Run steps for ``seconds``; all the work and all the time."""
        losses = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with trace.span("train_batch"):
                losses.append(engine.train_batch(data))
        with trace.span("result_fetch"):
            engine.synchronize()
            jax.block_until_ready(losses)
        return losses, time.perf_counter() - t0

    compiles0 = compiles.count()
    ctx.mark_setup_done()
    traced, cap = [], None
    if ctx.trace:
        with trace.Capture(ctx.trace_dir) as cap:
            traced, _ = steps(min(cfg.get("trace_seconds", 3.0),
                                  ctx.seconds / 2))
        ctx.seconds_left = ctx.seconds - cap.wall_s
    losses, elapsed = steps(ctx.seconds_left)
    in_window = compiles.count() - compiles0
    ctx.verdict.require("no_compile_in_window", in_window == 0,
                        f"{in_window} compilations inside the window: "
                        f"{compiles.SEEN[-in_window:] if in_window else []}")

    every = [float(x) for x in traced + losses]
    failed = sum(not math.isfinite(x) for x in every)
    tokens_per_s_chip = len(losses) * global_batch * seq / elapsed / chips
    ctx.note({"steps": len(losses), "elapsed_s": elapsed,
              "step_ms": elapsed / len(losses) * 1e3,
              "loss_first_last": [every[0], every[-1]]})
    return {
        "attempted": len(every), "failed": failed,
        "end_to_end": {"train_tokens_per_s_chip": tokens_per_s_chip},
        "trace": cap.trace if cap else None,
        "facts": {"arch": arch, "seq": seq, "chips": chips,
                  "micro_per_chip": cfg["job"]["train_micro_batch_size_per_chip"],
                  "traced_steps": len(traced),
                  "flops_per_token": ref_mod.train_flops_per_token(arch, seq)},
    }

"""Grouped-GEMM vs capacity-einsum MoE dispatch under imbalanced routing
(VERDICT r3 #1 'measured flops win at zipf-imbalanced routing').

Mixtral-8x7B layer geometry on one chip, bf16, three routing regimes:
uniform, zipf(1.2)-biased, and hot-expert (80% of mass on one expert).
The einsum path runs dropless (capacity = tokens — the only setting
that matches the grouped path's zero-drop semantics under imbalance),
so its cost is E× the balanced FFN cost regardless of routing; the
grouped path pays exactly top_k FFNs per token.

Run: python tools/moe_zipf_bench.py   (TPU host)
Prints one JSON line per (impl, regime).
"""

from __future__ import annotations

import functools
import json
import os, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.parallel import topology as topo
from deepspeed_tpu.parallel.moe import GateConfig, moe_ffn
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

B, S, H, F, E, K = 4, 2048, 4096, 14336, 8, 2
DT = jnp.bfloat16


def run():
    enable_compile_cache()
    topo._GLOBAL_MESH = None
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (B, S, H), DT)
    params = {
        "wi": jax.random.normal(jax.random.fold_in(rng, 1), (E, H, F), DT) * 0.02,
        "wo": jax.random.normal(jax.random.fold_in(rng, 2), (E, F, H), DT) * 0.02,
        "wg": jax.random.normal(jax.random.fold_in(rng, 3), (E, H, F), DT) * 0.02,
    }
    routers = {
        "uniform": jax.random.normal(jax.random.fold_in(rng, 4), (H, E),
                                     DT) * 0.02,
        # zipf-weighted bias: expert e gets bias ∝ 1/(e+1)^1.2
        "zipf": (jax.random.normal(jax.random.fold_in(rng, 5), (H, E), DT)
                 * 0.02 + jnp.asarray(
                     2.0 / (np.arange(1, E + 1) ** 1.2), DT)[None, :]),
        "hot": jnp.zeros((H, E), DT).at[:, 0].set(0.05),
    }
    # exact top-k flops per token for the grouped path; E per token for
    # dropless einsum (capacity = S)
    ffn_flops = 3 * 2 * H * F  # swiglu: wg, wi, wo matmul-pairs
    results = []
    for impl, cfg in (
            ("grouped", GateConfig(num_experts=E, top_k=K,
                                   drop_tokens=False)),
            ("einsum", GateConfig(num_experts=E, top_k=K,
                                  drop_tokens=False))):
        fn = jax.jit(functools.partial(
            moe_ffn, cfg=cfg, activation="swiglu", impl=impl))
        for regime, router in routers.items():
            out, aux = fn(x, router_w=router, expert_params=params)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(10):
                out, aux = fn(x, router_w=router, expert_params=params)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / 10
            tokens = B * S
            useful = tokens * K * ffn_flops  # what a perfect engine pays
            results.append({
                "impl": impl, "routing": regime,
                "ms_per_layer": round(dt * 1e3, 3),
                "useful_tflops_per_s": round(useful / dt / 1e12, 1),
                "load_top_expert": round(
                    float(aux["expert_load"][0]), 3),
            })
            print(json.dumps(results[-1]))
    g = {r["routing"]: r["ms_per_layer"] for r in results
         if r["impl"] == "grouped"}
    e = {r["routing"]: r["ms_per_layer"] for r in results
         if r["impl"] == "einsum"}
    print(json.dumps({"speedup_grouped_vs_einsum":
                      {k: round(e[k] / g[k], 2) for k in g}}))


if __name__ == "__main__":
    run()

"""Subprocess worker for multi-host ZenFlow tests.

Runs the same ZenFlow training either as ONE process with 8 CPU-sim
devices or as one of TWO jax.distributed processes with 4 devices each
(gloo cross-process collectives) — the loss streams must match: the
device math is identical SPMD, and the per-shard host optimizers are
elementwise, so sharding the masters across processes changes nothing.

Usage:
  python zenflow_worker.py single
  python zenflow_worker.py multi <process_id>   (ZF_PORT env for rendezvous)

ZF_NDEV sets the GLOBAL device count (default 8; the multi mode gives
each of the two processes half). Smaller counts matter on starved CI
hosts: every per-leaf jit dispatch is a gloo rendezvous, and with 8
virtual devices on one core the inter-collective host gaps can exceed
gloo's pair timeout mid-run.

Prints one JSON line {"losses": [...]} on success.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

mode = sys.argv[1]
pid = int(sys.argv[2]) if len(sys.argv) > 2 else 0
ndev_global = int(os.environ.get("ZF_NDEV", "8"))
ndev = ndev_global if mode == "single" else ndev_global // 2

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={ndev}")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", ndev)
if os.environ.get("ZF_CACHE"):
    # persistent compile cache: on single-core CI hosts the two
    # processes' first-run compiles drift by minutes while gloo's pair
    # timeout is ~30s; a warm cache collapses the drift (the test
    # retries once after populating it). The directory follows the one
    # rule (utils/compile_cache.py): the environment's, else the repo's.
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
if mode == "multi":
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    port = os.environ.get("ZF_PORT", "29751")
    jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                               num_processes=2, process_id=pid)

import numpy as np  # noqa: E402

import deepspeed_tpu as dstpu  # noqa: E402
from deepspeed_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, TransformerLM)

CFG = TransformerConfig(
    vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
    max_seq_len=32, pos_emb="learned", norm="layernorm",
    activation="gelu", tie_embeddings=False, remat=False)

ds_cfg = {
    "train_micro_batch_size_per_chip": 1,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
    "zero_optimization": {
        "stage": 2,
        "offload_optimizer": {"device": "cpu"},
        "zenflow": {"topk_ratio": 0.05, "update_interval": 2,
                    "select_interval": 4, "overlap_step": False},
    },
    "steps_per_print": 1000,
}

engine, *_ = dstpu.initialize(model=TransformerLM(CFG), config=ds_cfg,
                              topology={"dp": 1, "fsdp": -1})
assert engine._zenflow is not None, "zenflow must be active"

rng = np.random.default_rng(0)
B_global = ndev_global  # micro=1 x all global devices
fixed = [rng.integers(0, 64, (B_global, 17)).astype(np.int32)
         for _ in range(2)]


def local_slice(x):
    if mode == "single":
        return x
    half = x.shape[0] // 2
    return x[pid * half:(pid + 1) * half]


def it():
    i = 0
    while True:
        yield {"input_ids": local_slice(fixed[i % 2])}
        i += 1


stream = it()
losses = [float(engine.train_batch(stream)) for _ in range(8)]
engine._zenflow.finalize()
print(json.dumps({"losses": losses}), flush=True)

"""Subprocess worker for stage-3 qwZ execute-tests.

XLA CPU's thunk executor runs independent while-loops concurrently and
their collective rendezvous can interleave across devices (4 threads
stuck at channel A, 4 at channel B -> abort). This is a CPU-simulator
runtime race, not a program bug — on TPU each core executes one program
stream in schedule order. The reference CI isolates the same hazard
with ``pytest --forked`` (.github/workflows/cpu-torch-latest.yml); here
the affected tests run this worker in a fresh process, where the race
window has never been observed to close.

Usage: python qwz_worker.py <mode>   (mode: exact | quant | tp | hpz)
Prints one JSON line with losses.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402

import deepspeed_tpu as dstpu  # noqa: E402
from deepspeed_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, TransformerLM)
from deepspeed_tpu.parallel import topology as topo  # noqa: E402

UNTIED = TransformerConfig(
    vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
    max_seq_len=32, pos_emb="learned", norm="layernorm",
    activation="gelu", tie_embeddings=False, remat=False)


def run(extra, topology, steps=6):
    topo._GLOBAL_MESH = None
    cfg = {"train_micro_batch_size_per_chip": 2,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
           "steps_per_print": 1000}
    cfg.update(extra)
    engine, *_ = dstpu.initialize(model=TransformerLM(UNTIED), config=cfg,
                                  topology=topology)
    assert (engine._qwz_stage3
            == bool(extra["zero_optimization"].get("zero_quantized_weights")))
    rng = np.random.default_rng(0)
    fixed = [{"input_ids": rng.integers(
        0, 64, (engine.micro_batch_size * engine.dp_world_size, 17))
        .astype(np.int32)} for _ in range(2)]

    def it():
        i = 0
        while True:
            yield fixed[i % 2]
            i += 1

    data = it()
    return [float(engine.train_batch(data)) for _ in range(steps)]


def main():
    # one engine per process: even exact-then-quant in one process trips
    # the CPU-sim collective race (each engine gets a fresh process)
    mode = sys.argv[1]
    if mode == "exact":
        losses = run({"zero_optimization": {"stage": 3}},
                     {"dp": 1, "fsdp": -1})
    elif mode == "quant":
        losses = run({"zero_optimization": {
            "stage": 3, "zero_quantized_weights": True}},
            {"dp": 1, "fsdp": -1})
    elif mode == "tp":
        losses = run({"zero_optimization": {
            "stage": 3, "zero_quantized_weights": True}},
            {"dp": 1, "fsdp": 4, "tp": 2})
    elif mode == "hpz":
        # hpZ mesh: params shard over fsdp only (gathers stay in-group),
        # replicated across dp — the quantized gather must compose
        losses = run({"zero_optimization": {
            "stage": 3, "zero_quantized_weights": True,
            "zero_hpz_partition_size": 4}},
            {"dp": 2, "fsdp": 4})
    else:
        raise SystemExit(f"unknown mode {mode}")
    print(json.dumps({"losses": losses}))


if __name__ == "__main__":
    main()

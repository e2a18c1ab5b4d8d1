"""Training stream: a fresh batch of full sequences every step, drawn
from the seed. ``batches`` is what the engine's ``train_batch`` pulls from;
``check_batch`` is the one batch the output check compares on."""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def _rng(seed: int):
    return np.random.default_rng([int(seed), 0x7261696E])


def batches(traffic: Dict, seed: int, vocab: int, global_batch: int,
            seq: int) -> Iterator[Dict[str, np.ndarray]]:
    if traffic.get("tokens", "uniform") != "uniform":
        raise ValueError(f"train_stream: unknown token law {traffic['tokens']!r}")
    rng = _rng(seed)
    while True:
        yield {"input_ids": rng.integers(
            0, vocab, (global_batch, seq + 1)).astype(np.int32)}


def check_batch(traffic: Dict, seed: int, vocab: int, global_batch: int,
                seq: int, distinct: int):
    """The batch of the output check (a warm-up step, never a timed one):
    ``distinct`` seeded sequences of the stream's own law, each repeated
    to fill one contiguous ``1/distinct`` of the global batch, so that the
    plain reference has ``distinct`` sequences to compute and the mean over
    the batch is the mean over them. With as many sequences as chips every
    chip's micro-batch holds a sequence of its own, and the gradient
    compared is right only if the reduction across chips is. Returns (the
    batch ``[global_batch, seq + 1]``, the distinct rows)."""
    if global_batch % distinct:
        raise ValueError(f"{distinct} sequences do not tile a batch of "
                         f"{global_batch}")
    rng = np.random.default_rng([int(seed), 0x63686B])
    rows = rng.integers(0, vocab, (distinct, seq + 1)).astype(np.int32)
    return np.repeat(rows, global_batch // distinct, axis=0), rows

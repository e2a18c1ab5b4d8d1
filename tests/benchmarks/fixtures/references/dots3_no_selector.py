"""``benchmarks/references/dots3_note.py`` with the selector dropped: every
full layer attends over its whole causal context, what a program that
skipped the indexer would compute. A configuration that names it is judged
not ``correct``."""

import jax.numpy as jnp

import benchmarks.references.dots3_note as d
from benchmarks.references.dots3_note import *  # noqa: F401,F403
from benchmarks.references.dots3_note import Arch, forward_logits  # noqa: F401


def _select_everything(a, scores, positions):
    return jnp.arange(scores.shape[1])[None, :] <= positions[:, None]


d.select = _select_everything

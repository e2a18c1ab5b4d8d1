"""``benchmarks/references/ouro.py`` with one K/V slot a layer shared by all
passes: a layer's keys and values are made once, in the first pass, and every
later pass of that layer attends over them (its queries are its own), what a
program whose pool had ``num_hidden_layers`` slots for ``cache_layers`` would
serve. A configuration that names it is judged not ``correct``."""

import jax.numpy as jnp

import benchmarks.references.ouro as m
from benchmarks.references.ouro import *  # noqa: F401,F403
from benchmarks.references.ouro import Arch  # noqa: F401


def forward_logits(arch, tokens, rows, layer_weights, top,
                   numerics="float32"):
    xs = [top["embed_tokens"][jnp.asarray(t)] for t in tokens]
    pos = [jnp.arange(len(t)) for t in tokens]
    slot = {}                       # (layer, sequence) -> keys and values
    for t in m.passes(arch):
        for l in range(arch.num_hidden_layers):
            w = layer_weights(l)
            for i, (x, ps) in enumerate(zip(xs, pos)):
                xs[i], made = m.layer(arch, numerics, x, w, ps,
                                      kv=slot.get((l, i)))
                slot.setdefault((l, i), made)
        xs = [m.pass_norm(arch, x, top["norm"]) for x in xs]
    return [m.head_logits(arch, numerics, x[jnp.asarray(r)], top["lm_head"])
            for x, r in zip(xs, rows)]

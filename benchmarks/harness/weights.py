"""Weights from ``--seed``: one function, read by the program and by the
plain reference alike, so neither depends on anything the other made.

Which leaves an architecture has is data: its reference module's
``Arch.leaf_table()`` gives a ``Leaf`` for each (path in the program's
tree, published name, shape, scale, whether it is one per layer), and
nothing here knows an architecture. Every leaf is a normal draw from its
own key (seed, leaf path, layer), so one layer can be made alone — the
reference holds one layer in float32 at a time — and equals, bit for bit,
that layer's slice of the stacked tree the program is given (a test pins
it). A scale of None is a norm gain, drawn around one so that a dropped
gain would show.

The stacked tree has the layout the program takes (``layers.attn.wq [L,
H, n, D]`` ...): that layout is the interface between benchmark and
program. ``reference_layer_fn`` / ``reference_top`` rename the same arrays
to the published names the reference uses.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    path: str                   # in the program's tree: "attn.wq"
    published: str              # in the reference: "q_proj"
    shape: Tuple[int, ...]      # of one layer's leaf, or of a top leaf
    scale: Optional[float]      # of the normal draw; None: 1 + 0.1 n
    per_layer: bool             # stacked on axis 0 under "layers"


def base_key(seed: int):
    """``jax.random.PRNGKey(seed)`` for any whole number up to 2**32: the
    key the training engine makes from its config's ``seed`` and hands to
    ``model.init``. Every function below takes the key as an *argument* of
    its jitted program and never as a constant in it, so the compiled
    program is the same for every seed and the persistent cache hits."""
    return jax.random.PRNGKey(int(seed))


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, shape, scale, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.1 * x if scale is None else x * scale
    return x.astype(dtype)


def draw_leaf(leaf: Leaf, key, layer, dtype):
    """One leaf from ``key = base_key(seed)``; ``layer`` (traced or not)
    for a per-layer leaf, None for a top leaf."""
    k = _leaf_key(key, leaf.path)
    if leaf.per_layer:
        k = jax.random.fold_in(k, layer)
    return _draw(k, leaf.shape, leaf.scale, dtype)


def _put(tree: Dict, path: str, x) -> None:
    *outer, inner = path.split(".")
    for part in outer:
        tree = tree.setdefault(part, {})
    tree[inner] = x


def program_params(a, key, dtype) -> Dict:
    """The whole tree in the program's layout, layers stacked on axis 0,
    from ``key = base_key(seed)``. Pure and traceable: call it under
    ``jit`` with the key as an argument, so that the weights are made on
    the device, in the type they are used in, by a program that does not
    depend on the seed."""
    layers = jnp.arange(a.num_hidden_layers)
    tree: Dict = {"layers": {}}
    for leaf in a.leaf_table():
        if leaf.per_layer:
            _put(tree["layers"], leaf.path, jax.vmap(
                lambda l, leaf=leaf: draw_leaf(leaf, key, l, dtype))(layers))
    for leaf in a.leaf_table():
        if not leaf.per_layer:
            _put(tree, leaf.path, draw_leaf(leaf, key, None, dtype))
    return tree


def reference_layer_fn(a, seed: int, dtype):
    """``layer -> {published name: float32 array}``: the same draws, in
    the program's storage type first (so the values are the ones the
    program holds), then widened."""
    key = base_key(seed)
    make = _layer_program(a, jnp.dtype(dtype).name)
    return lambda l: make(key, jnp.int32(l))


@functools.lru_cache(maxsize=None)
def _layer_program(a, dtype: str):
    return jax.jit(lambda key, layer: {
        leaf.published: draw_leaf(leaf, key, layer, jnp.dtype(dtype))
        .astype(jnp.float32) for leaf in a.leaf_table() if leaf.per_layer})


@functools.lru_cache(maxsize=None)
def _top_program(a, dtype: str):
    return jax.jit(lambda key: {
        leaf.published: draw_leaf(leaf, key, None, jnp.dtype(dtype))
        .astype(jnp.float32) for leaf in a.leaf_table()
        if not leaf.per_layer})


def reference_top(a, seed: int, dtype) -> Dict:
    return _top_program(a, jnp.dtype(dtype).name)(base_key(seed))


@functools.lru_cache(maxsize=None)
def _params_program(a, dtype: str):
    return jax.jit(lambda key: program_params(a, key, jnp.dtype(dtype)))


def make_program_params(a, seed: int, dtype) -> Dict:
    """The program's tree, made on the device in one jitted call."""
    return _params_program(a, jnp.dtype(dtype).name)(base_key(seed))


def leaf_of(a, published: str) -> Leaf:
    """The leaf behind a published name, with or without its layer:
    ``"layers.3.q_proj"`` or ``"lm_head"``."""
    name = published.split(".")[-1]
    return next(leaf for leaf in a.leaf_table() if leaf.published == name)


def program_leaf_name(a, published: str) -> str:
    """``"layers.3.q_proj"`` -> the path of the same leaf in the program's
    tree (``"layers.attn.wq"``) — for reading the program's gradient."""
    leaf = leaf_of(a, published)
    return ("layers." if leaf.per_layer else "") + leaf.path

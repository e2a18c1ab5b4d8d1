"""Weights from ``--seed``: one function, read by the program and by the
plain reference alike, so neither depends on anything the other made.

Every leaf is a normal draw from its own key (seed, leaf name, layer), so
one layer can be made alone — the reference holds one layer in float32 at
a time — and equals, bit for bit, that layer's slice of the stacked tree
the program is given (a test pins it). Scales follow the usual fan-in rule
so that activations stay of order one through the depth; norm gains are
drawn around one so that a dropped gain would show.

The stacked tree has the layout ``deepspeed_tpu.models.transformer``
takes (``layers.attn.wq [L, H, n, D]`` ...): that layout is the interface
between benchmark and program. ``reference_layer`` / ``reference_top``
rename the same arrays to the published names the reference uses.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Dict

import jax
import jax.numpy as jnp

from benchmarks.references.mistral import Arch

# leaf name -> (path in the program's tree, published name or None)
_LAYER_LEAVES = {
    "attn.wq": "q_proj", "attn.wk": "k_proj", "attn.wv": "v_proj",
    "attn.wo": "o_proj", "mlp.wg": "gate_proj", "mlp.wi": "up_proj",
    "mlp.wo": "down_proj", "ln1.scale": "input_layernorm",
    "ln2.scale": "post_attention_layernorm",
}
_TOP_LEAVES = {"embed.tokens": "embed_tokens", "final_norm.scale": "norm",
               "unembed.kernel": "lm_head"}


def _shapes(a: Arch) -> Dict[str, tuple]:
    h, nq, nkv, d, f, v = (a.hidden_size, a.num_attention_heads,
                           a.num_key_value_heads, a.head_dim,
                           a.intermediate_size, a.vocab_size)
    fan = 1.0 / math.sqrt(h)
    return {
        "attn.wq": ((h, nq, d), fan), "attn.wk": ((h, nkv, d), fan),
        "attn.wv": ((h, nkv, d), fan),
        "attn.wo": ((nq, d, h), 1.0 / math.sqrt(nq * d)),
        "mlp.wg": ((h, f), fan), "mlp.wi": ((h, f), fan),
        "mlp.wo": ((f, h), 1.0 / math.sqrt(f)),
        "ln1.scale": ((h,), None), "ln2.scale": ((h,), None),
        "embed.tokens": ((v, h), 0.02), "final_norm.scale": ((h,), None),
        "unembed.kernel": ((h, v), 0.02),
    }


def base_key(seed: int):
    """``jax.random.PRNGKey(seed)`` for any whole number up to 2**32: the
    key the training engine makes from its config's ``seed`` and hands to
    ``model.init``. Every function below takes the key as an *argument* of
    its jitted program and never as a constant in it, so the compiled
    program is the same for every seed and the persistent cache hits."""
    return jax.random.PRNGKey(int(seed))


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _draw(key, shape, scale, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.1 * x if scale is None else x * scale
    return x.astype(dtype)


def layer_leaf(a: Arch, key, name: str, layer, dtype):
    shape, scale = _shapes(a)[name]
    return _draw(jax.random.fold_in(_leaf_key(key, name), layer), shape,
                 scale, dtype)


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    tree: Dict = {}
    for name, x in flat.items():
        outer, inner = name.split(".")
        tree.setdefault(outer, {})[inner] = x
    return tree


def program_params(a: Arch, key, dtype) -> Dict:
    """The whole tree in the program's layout, layers stacked on axis 0,
    from ``key = base_key(seed)``. Pure and traceable: call it under
    ``jit`` with the key as an argument, so that the weights are made on
    the device, in the type they are used in, by a program that does not
    depend on the seed."""
    layers = jnp.arange(a.num_hidden_layers)
    flat = {name: jax.vmap(lambda l, n=name: layer_leaf(a, key, n, l, dtype))(
        layers) for name in _LAYER_LEAVES}
    tree = {"layers": _nest(flat)}
    for name in _TOP_LEAVES:
        shape, scale = _shapes(a)[name]
        outer, inner = name.split(".")
        tree.setdefault(outer, {})[inner] = _draw(_leaf_key(key, name),
                                                  shape, scale, dtype)
    return tree


def reference_layer_fn(a: Arch, seed: int, dtype):
    """``layer -> {published name: float32 array}``: the same draws, in
    the program's storage type first (so the values are the ones the
    program holds), then widened."""
    key = base_key(seed)
    make = _layer_program(a, jnp.dtype(dtype).name)
    return lambda l: make(key, jnp.int32(l))


@functools.lru_cache(maxsize=None)
def _layer_program(a: Arch, dtype: str):
    return jax.jit(lambda key, layer: {
        pub: layer_leaf(a, key, name, layer, jnp.dtype(dtype))
        .astype(jnp.float32) for name, pub in _LAYER_LEAVES.items()})


@functools.lru_cache(maxsize=None)
def _top_program(a: Arch, dtype: str):
    def make(key):
        out = {}
        for name, pub in _TOP_LEAVES.items():
            shape, scale = _shapes(a)[name]
            out[pub] = _draw(_leaf_key(key, name), shape, scale,
                             jnp.dtype(dtype)).astype(jnp.float32)
        return out

    return jax.jit(make)


def reference_top(a: Arch, seed: int, dtype) -> Dict:
    return _top_program(a, jnp.dtype(dtype).name)(base_key(seed))


@functools.lru_cache(maxsize=None)
def _params_program(a: Arch, dtype: str):
    return jax.jit(lambda key: program_params(a, key, jnp.dtype(dtype)))


def make_program_params(a: Arch, seed: int, dtype) -> Dict:
    """The program's tree, made on the device in one jitted call."""
    return _params_program(a, jnp.dtype(dtype).name)(base_key(seed))


def program_leaf_name(published: str) -> str:
    """``"layers.3.q_proj"`` -> the path of the same leaf in the program's
    tree (``"layers.attn.wq"``) — for reading the program's gradient."""
    inv = {v: k for k, v in {**_LAYER_LEAVES, **_TOP_LEAVES}.items()}
    parts = published.split(".")
    if parts[0] == "layers":
        return "layers." + inv[parts[2]]
    return inv[published]

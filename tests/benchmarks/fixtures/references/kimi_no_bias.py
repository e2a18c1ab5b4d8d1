"""``benchmarks/references/kimi_k2.py`` with ``e_score_correction_bias``
dropped from the router's choice: what a program that ignored the bias would
compute. A configuration that names it is judged not ``correct``."""

import jax
import jax.numpy as jnp

import benchmarks.references.kimi_k2 as k
from benchmarks.references.kimi_k2 import *  # noqa: F401,F403
from benchmarks.references.kimi_k2 import Arch, forward_logits  # noqa: F401


def _route_without_bias(a, y, w):
    return _route(a, y, dict(w, e_score_correction_bias=jnp.zeros_like(
        w["e_score_correction_bias"])))


_route, k.route = k.route, _route_without_bias

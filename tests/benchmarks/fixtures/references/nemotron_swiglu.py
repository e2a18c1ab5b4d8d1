"""``benchmarks/references/nemotron_h.py`` with a SwiGLU in place of the
squared ReLU (``silu(u) * u``, the gate tied to the up-projection: the
experts hold no gate matrix): what a program that kept the gated activation
would compute. The cell judges the program as it is against this, and must
not call it correct."""

import jax

from benchmarks.references import nemotron_h as _n
from benchmarks.references.nemotron_h import *  # noqa: F401,F403


class Arch(_n.Arch):
    def activation(self, u):
        return jax.nn.silu(u) * u

"""``benchmarks/references/nemotron_h.py`` with the Mamba-2 gate applied after the grouped norm (norm(y) * silu(z)) instead of before it:
what a program that did so would compute. The cell judges the program as it
is against this, and must not call it correct."""

from benchmarks.references import nemotron_h as _n
from benchmarks.references.nemotron_h import *  # noqa: F401,F403


class Arch(_n.Arch):
    def gate_before_norm(self):
        return False

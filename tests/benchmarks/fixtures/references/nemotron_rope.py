"""``benchmarks/references/nemotron_h.py`` with rotary applied to the attention mixer's queries and keys, which the family leaves without positions:
what a program that did so would compute. The cell judges the program as it
is against this, and must not call it correct."""

from benchmarks.references import nemotron_h as _n
from benchmarks.references.nemotron_h import *  # noqa: F401,F403


class Arch(_n.Arch):
    def rotates(self):
        return True

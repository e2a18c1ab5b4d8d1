"""``benchmarks/references/nemotron_h.py`` with the gated norm's mean squares taken over all the inner channels at once instead of over each group:
what a program that did so would compute. The cell judges the program as it
is against this, and must not call it correct."""

from benchmarks.references import nemotron_h as _n
from benchmarks.references.nemotron_h import *  # noqa: F401,F403


class Arch(_n.Arch):
    def norm_groups(self):
        return 1

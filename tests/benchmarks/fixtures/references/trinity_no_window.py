"""``benchmarks/references/trinity.py`` with the sliding window dropped: every layer sees every earlier key (rotary still by layer kind): what a program that did so would compute. The cell
judges the program as it is against this, and must not call it correct."""

from benchmarks.references import trinity as _t
from benchmarks.references.trinity import *  # noqa: F401,F403


class Arch(_t.Arch):
    def window_of(self, layer):
        return None

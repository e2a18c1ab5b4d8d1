"""``benchmarks/references/trinity.py`` with expert_bias left out of the router's choice: what a program that did so would compute. The cell
judges the program as it is against this, and must not call it correct."""

from benchmarks.references import trinity as _t
from benchmarks.references.trinity import *  # noqa: F401,F403


class Arch(_t.Arch):
    def choice_scores(self, scores, bias):
        return scores

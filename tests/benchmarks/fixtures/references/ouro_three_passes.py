"""``benchmarks/references/ouro.py`` with three passes for four: what a
program that dropped the last pass of the loop would compute. A configuration
that names it is judged not ``correct``."""

import benchmarks.references.ouro as m
from benchmarks.references.ouro import *  # noqa: F401,F403
from benchmarks.references.ouro import Arch, forward_logits  # noqa: F401

m.passes = lambda arch: range(arch.total_ut_steps - 1)

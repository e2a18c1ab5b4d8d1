"""``benchmarks/references/ouro.py`` with the norm between passes dropped:
what a pass left enters the next as it is (the head still reads the last
pass's norm), what a program that looped over the layers alone would compute.
A configuration that names it is judged not ``correct``."""

import benchmarks.references.ouro as m
from benchmarks.references.ouro import *  # noqa: F401,F403
from benchmarks.references.ouro import Arch, forward_logits  # noqa: F401

m.next_pass_input = lambda x, normed: x

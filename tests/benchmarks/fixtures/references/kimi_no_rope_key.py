"""``benchmarks/references/kimi_k2.py`` with the shared rotary key dropped
from the scores (``q_r . k_r`` left out): what a program that cached the
compressed vector alone would compute. A configuration that names it is
judged not ``correct``."""

import benchmarks.references.kimi_k2 as k
from benchmarks.references.kimi_k2 import *  # noqa: F401,F403
from benchmarks.references.kimi_k2 import Arch, forward_logits  # noqa: F401


def _scores_without_the_rotary_key(a, numerics, q_n, q_r, lat, w):
    return _scores(a, numerics, q_n, q_r * 0.0, lat, w)


_scores, k.expanded_scores = k.expanded_scores, _scores_without_the_rotary_key

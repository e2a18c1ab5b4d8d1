"""``benchmarks/references/trinity.py`` with the routed experts' gradients
reported as zero: what the check would be comparing against if a backward
dropped them. The cell judges the program as it is against this, and must
not call it correct."""

from benchmarks.references import trinity as _t
from benchmarks.references.trinity import *  # noqa: F401,F403


def loss_and_grads(arch, batch, layer_weights, top, keep, numerics="float32"):
    def keep_but_experts(name, g):
        return keep(name, g * 0.0 if name in _t.EXPERT_LEAVES else g)
    return _t.loss_and_grads(arch, batch, layer_weights, top,
                             keep_but_experts, numerics)

"""Operations the forward and backward passes of a dense GQA transformer
require per trained token. Recomputation is not counted: this is the
numerator of model-FLOP/s utilization, not of hardware utilization."""

from __future__ import annotations

from benchmarks.references.mistral import Arch


def matmul_params(a: Arch) -> int:
    """Weights that multiply every token: the blocks and the head (the
    embedding is a lookup)."""
    h, d = a.hidden_size, a.head_dim
    attn = h * d * (2 * a.num_attention_heads + 2 * a.num_key_value_heads)
    mlp = 3 * h * a.intermediate_size
    return a.num_hidden_layers * (attn + mlp) + h * a.vocab_size


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Forward 2 FLOP per weight; causal attention, averaged over the
    positions of a full sequence, 2 products of seq/2 keys by head_dim per
    query head: 2 * 2 * (seq / 2) * head_dim * heads per layer. Backward
    twice the forward."""
    fwd = 2.0 * matmul_params(a) + (a.num_hidden_layers * 2.0 * seq
                                    * a.head_dim * a.num_attention_heads)
    return 3.0 * fwd


def mfu(tokens_per_s_chip: float, flops_per_token: float,
        peak_flops: float) -> float:
    return tokens_per_s_chip * flops_per_token / peak_flops

"""Operations and bytes one token step of latent (MLA) decode attention
needs in one layer, in its absorbed form: every head's absorbed query against
each context token's latent (the compressed vector and the one rotary key),
the values the compressed vector itself.

A context token costs its latent's ``latent_dim * itemsize`` bytes **once**:
keys and values are the same bytes of a page (a kernel that fetches a page
twice, or rows padded beyond the latent's width, reads more and shows a lower
share), and ``2 * heads * (latent_dim + value_dim)`` operations (a score over
the whole latent, a weighted sum over its compressed part). Queries in
(``heads * latent_dim``) and outputs out (``heads * value_dim``) a sequence
besides. The absorb products (``q W_kvb^K``, ``o W_kvb^V``) are not the
kernel's. At 121 operations a byte against the chip's ridge of 240 the floor
is memory's, within a factor of two of compute's.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmarks.harness.trace import kernel_name


def classify(event_name: str):
    """"decode" for the ``mla_decode`` kernel's device events, else None."""
    return "decode" if kernel_name(event_name) == "mla_decode" else None


def call(context_lens: Iterable[int], heads: int, latent_dim: int,
         value_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    flops = bytes_ = 0.0
    for n in (int(c) for c in context_lens if c > 0):
        flops += 2.0 * heads * (latent_dim + value_dim) * n
        bytes_ += n * latent_dim * itemsize
        bytes_ += heads * (latent_dim + value_dim) * itemsize
    return flops, float(bytes_)


def sizes(arch) -> Tuple[int, int, int]:
    """(heads, latent_dim, value_dim) of an architecture with latent
    attention."""
    return (arch.num_attention_heads,
            arch.kv_lora_rank + arch.qk_rope_head_dim, arch.kv_lora_rank)

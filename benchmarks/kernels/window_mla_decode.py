"""Operations and bytes one token step of windowed latent attention needs in
one sliding layer, in its absorbed form.

A context of ``n`` tokens attends over its last ``min(n, window)`` (513, the
query's own position counted). A token in the window costs its latent's
``latent_dim * itemsize`` bytes **once** (1,088 values: not the pool's
lane-padded row of 1,152, and not the whole pages at the window's two ends)
and ``2 * heads * (latent_dim + value_dim)`` operations. Queries in and
outputs out a sequence besides. At 513 rows a sequence the step is short:
the kernel's fixed cost a sequence shows in the share.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmarks.kernels import mla_decode


def rows_read(n: int, window: int) -> int:
    return min(int(n), window)


def call(context_lens: Iterable[int], window: int, heads: int,
         latent_dim: int, value_dim: int, itemsize: int = 2
         ) -> Tuple[float, float]:
    return mla_decode.call([rows_read(c, window) for c in context_lens],
                           heads, latent_dim, value_dim, itemsize)


def sizes(arch) -> Tuple[int, int, int, int]:
    """(window, heads, latent_dim, value_dim) of the sliding layers."""
    return (arch.sliding_window_size, arch.swa_num_attention_heads,
            arch.swa_kv_lora_rank + arch.swa_qk_rope_head_dim,
            arch.swa_kv_lora_rank)


def sliding_layers(arch) -> int:
    return sum(not arch.is_full(l) for l in range(arch.num_hidden_layers))

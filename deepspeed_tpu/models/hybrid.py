"""Hybrid recurrent / attention LM: one stack for the ``qwen3_next`` family
(Qwen3-Next-80B-A3B), the ``minicpm_sala`` family (MiniCPM-SALA) and the
``kimi_k2`` / ``deepseek_v3`` family (multi-head latent attention in every
layer: ``attention_kind`` "mla", no recurrent layer; a prologue of
``first_k_dense`` dense layers before the expert layers; sigmoid routing).

The stack is not one scanned block. Each layer is **recurrent**, **full** or
**windowed** (:attr:`HybridConfig.layer_kinds`: Qwen3-Next's "every
``full_attention_interval``-th layer is full", or a published list of mixers
cut to the layers held here). A windowed layer ("w") is the full layer's
gated softmax attention under a ``sliding_window`` (key j visible to query i
iff 0 <= i - j < window), with its own share of rotary dims
(``window_rotary_factor``), so a stack can rotate its windowed layers and
leave its full layers without positions; or, in a latent stack
(``window_attention_kind`` "mla": the ``dots3_note`` family), a latent mixer
of its own (``wmla``: its own ranks, head count, key width and rotary base)
under the window, beside full latent layers whose context a learned selector
picks (``index_topk``: ``index_project``, ``index_scores``,
``block_sparse.topk_mask``),
both with a sigmoid gate a head (``mla_head_gate``) and rescaled latents
(``mla_lora_rescale``). :attr:`HybridConfig.mixer_kinds` says which mixer's
leaves each layer reads. ``post_norms`` adds a norm after
each branch (four a layer). A recurrent layer is gated DeltaNet or lightning
attention (``recurrent_kind``; ``ops/pallas/gated_delta.py``); a full layer is
gated softmax attention with QK-norm, rotary on a part of each head (none of
it at ``partial_rotary_factor`` 0) and, with ``sparse_topk``, the block-sparse
rule of ``ops/block_sparse.py``, or with ``msa_topk`` that file's learned
block selector (two projections of the layer's input a KV group, ``wsq`` and
``wsk``; the ``minimax_m3`` family, whose full mixer has no output gate,
``attn_output_gate`` False, a QK-norm gain a head, ``qk_norm_per_head``, and
the clamped ``swigluoai`` activation in every feed-forward). Every layer ends
in an expert block
(``parallel/moe.py::moe_ffn_share``: softmax routing over all the experts, the
top-k renormalised, the experts held here, and a shared expert behind a
sigmoid gate) or, at ``num_experts`` 0, a dense SwiGLU. ``scale_emb``,
``residual_scale`` and ``logit_divisor`` are the muP constants (1: none).
Norms, rotary and the embedding are ``models/transformer.py``'s.

The parameter tree stacks every per-layer leaf over *all* layers under
``"layers"`` (both mixers' leaves for every layer: the layout a checkpoint
loader or the benchmark's weight table hands over); :func:`serving_params`
keeps, of each mixer, the layers that use it, and that is what the forward
passes and the serving runner (``inference/hybrid_runner.py``) take:

    layers  ln1, ln2, moe.{router, shared, shared_gate} | mlp   [L, ...]
    experts wg, wi, wo (none with a dense feed-forward)    [L, E_held, ...]
    gdn | lightning  the recurrent layers' mixer           [recurrent, ...]
    attn | mla       the full layers' mixer                [full, ...]
    wmla             the windowed latent layers' mixer     [windowed, ...]
    dense   wg, wi, wo: the prologue's feed-forward        [first_k_dense, ...]

(with a prologue ``experts`` holds the expert layers alone, ``[L -
first_k_dense, ...]``; the other per-layer leaves keep a slot a layer).

A stack of **one-mixer blocks** (the ``nemotron_h`` family: ``layer_pattern``
over "M" | "E" | "*", :attr:`HybridConfig.one_mixer`) is *norm -> one mixer ->
residual* a block, the mixer a Mamba-2 state-space mixer (``recurrent_kind``
"mamba2": ``mamba2_mixer``, ``ops/pallas/mamba2.py``), the expert feed-forward
(here with ungated squared-ReLU experts, ``activation`` "relu2") or full
attention (here without positions, QK-norm or gate). Its tree holds no leaf
for a part a block lacks: ``layers`` keeps the one norm a block, and
``mamba2``, ``attn``, ``moe`` and ``experts`` lie at the top, each over the
blocks of its own kind. It trains; the serving runner refuses it.

``experts_held`` / ``expert_offset`` give the chip's share of the routed
experts (None: all of them); the router always has ``num_experts`` outputs.
With ``experts_apart`` the stacked tree itself keeps ``experts`` at the top,
over the expert layers alone (a training state then holds no dead expert
slot for a dense layer).

Training (``loss_fn`` through ``runtime/engine.py``): each layer under the
engine's checkpoint policy, softmax attention through
``ops/attention.py::multi_head_attention`` (the flash kernel on a chip, its
band grid under a window), the experts through the differentiable grouped
product on a row buffer of :func:`share_capacity` rows (a pair beyond it
stops the engine's step: ``HybridLM.fatal_counters``), the loss in
``tiled_logits`` tiles of the sequence. With ``bias_update_rate`` the loss's
aux carries ``param_deltas``: what the step adds to ``router_bias`` from the
tokens each output got (``parallel/moe.py::bias_update``), which the engine
applies after the optimizer, no gradient involved.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models.transformer import (TransformerConfig, _norm, _rope,
                                              yarn_inv_freq, yarn_mscale)
from deepspeed_tpu.ops import block_sparse
from deepspeed_tpu.ops.attention import multi_head_attention
from deepspeed_tpu.ops.pallas.gated_delta import gdn_chunk, lightning_chunk
from deepspeed_tpu.ops.pallas.mamba2 import ssd_chunk
from deepspeed_tpu.parallel.moe import (ROUTING_NAME, UNGATED, GateConfig,
                                        Glu, bias_update, moe_ffn_share)
from deepspeed_tpu.runtime.sharding import (effective_dtype,
                                            vocab_parallel_lookup)


class StateSpaceUnsupported(NotImplementedError):
    """A path with no decode rule, state slot or carried prefill for the
    scalar-decay state-space mixer (``recurrent_kind`` "mamba2") was asked to
    run one: it refuses rather than run another model."""


class OneMixerStackUnsupported(NotImplementedError):
    """A path that counts a mixer and a feed-forward a layer (a KV or state
    slot a layer) was asked to run a stack of one-mixer blocks
    (``layer_pattern`` over "M" | "E" | "*")."""


class MlaSizes(NamedTuple):
    """One latent mixer's sizes (``HybridConfig.mla_sizes``): heads, the
    two ranks, a head's ``nope`` / ``rope`` / value widths, the rotary base
    (``inv_freq``: YaRN's frequencies in its place), the softmax scale and
    what the normed latents are multiplied by."""

    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    inv_freq: Any
    scale: float
    q_rescale: float
    kv_rescale: float

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope


@dataclasses.dataclass(frozen=True)
class HybridConfig(TransformerConfig):
    """``TransformerConfig`` (hidden, heads, KV heads, vocabulary, rope,
    norm) plus the layer kinds, the recurrent mixer, the attention's extras
    and the feed-forward."""

    attn_head_dim: int = 256
    partial_rotary_factor: float = 0.25
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512              # the router's outputs
    top_k: int = 10
    moe_ffn_size: int = 512
    shared_ffn_size: int = 512
    experts_held: Optional[int] = None  # None: all of them
    expert_offset: int = 0
    # the published model's mixers, one letter a layer ("m": full, "w":
    # windowed softmax attention, "l": recurrent), of which this stack
    # holds ``num_layers`` from ``first_layer`` on; None: every
    # ``full_attention_interval``-th is full
    layer_pattern: Optional[str] = None
    first_layer: int = 0
    # the "w" layers: their window, and the share of each head they rotate
    # (``partial_rotary_factor`` is the full layers')
    sliding_window: Optional[int] = None
    window_rotary_factor: float = 1.0
    # a norm after each branch too (``ln1_post``, ``ln2_post``)
    post_norms: bool = False
    recurrent_kind: str = "gdn"         # gdn | lightning
    # muP: the embedding's scale, the scale of every residual branch, and
    # what the final hidden state is divided by before the head
    scale_emb: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    # the block-sparse rule of the full layers (ops/block_sparse.py);
    # sparse_topk 0: plain causal attention
    sparse_topk: int = 0
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    # the learned block selector of the full layers (ops/block_sparse.py,
    # ``msa_select``; msa_topk 0: none): ``msa_index_heads`` indexer queries
    # a KV group and one indexer key a KV head, ``msa_index_dim`` wide
    msa_topk: int = 0
    msa_block_size: int = 128
    msa_index_heads: int = 4
    msa_index_dim: int = 128
    msa_local_blocks: int = 2
    # the "gated" full mixer's extras: the sigmoid gate on its output (read
    # from a second half of ``wq``), and a QK-norm gain a head (else one
    # gain for all heads)
    attn_output_gate: bool = True
    qk_norm_per_head: bool = False
    # the constants of ``activation`` "swigluoai" (parallel/moe.py::Glu)
    swiglu_alpha: float = 1.702
    swiglu_limit: float = 7.0
    # the full layers' mixer: "gated" (QK-norm, output gate, K and V a KV
    # head) or "mla": multi-head latent attention, one ``kv_lora_rank +
    # qk_rope_head_dim`` vector a token in the cache, queries through a
    # ``q_lora_rank`` bottleneck, keys ``qk_nope + qk_rope`` wide, values
    # ``v_head_dim``
    attention_kind: str = "gated"
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # the "w" layers' mixer: "gated" (the full layers' gated softmax
    # attention under the window) or "mla": a latent mixer of its own, with
    # its own ranks, head count, key width and rotary base, over the last
    # ``sliding_window`` tokens (the query's own position counted)
    window_attention_kind: str = "gated"
    window_num_heads: int = 64
    window_q_lora_rank: int = 1024
    window_kv_lora_rank: int = 1024
    window_qk_nope_head_dim: int = 192
    window_qk_rope_head_dim: int = 64
    window_v_head_dim: int = 128
    window_rope_theta: float = 50000.0
    # a latent mixer's extras: the normed latents times ``sqrt(hidden /
    # rank)`` (queries' and keys' alike), and one sigmoid gate a head on the
    # attention's output, read from the layer's normed input
    mla_lora_rescale: bool = False
    mla_head_gate: bool = False
    # the full latent layers' learned selector (0: none, dense attention):
    # ``index_n_heads`` query heads of ``index_head_dim`` against one key a
    # token, cached beside the latent; a query attends over the
    # ``index_topk`` tokens that score highest (all of them below that)
    index_topk: int = 0
    index_n_heads: int = 64
    index_head_dim: int = 128
    # YaRN (factor 1: plain rotary): models/transformer.py::yarn_inv_freq,
    # and ``yarn_mscale(factor, mscale_all_dim)**2`` on the softmax scale
    rope_yarn_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # the published model's first layers have the dense SwiGLU of
    # ``ffn_size`` as their feed-forward, the experts start after them; a
    # stack that starts at ``first_layer`` holds ``dense_layers`` of them
    first_k_dense: int = 0
    # the stacked tree keeps ``experts`` at the top, over the expert
    # layers alone (else under ``layers.moe``, a slot for every layer)
    experts_apart: bool = False
    # what a training step moves a sigmoid router's bias by, towards equal
    # load (the published trainer's rule; 0: the bias is held)
    bias_update_rate: float = 0.0
    # the router's rule (parallel/moe.py::route) and whether the shared
    # expert sits behind a sigmoid gate
    router_scoring: str = "softmax"
    routed_scale: float = 1.0
    shared_gate: bool = True
    # QK-norm in the "gated" full mixer (False: no ``q_norm`` / ``k_norm``)
    qk_norm: bool = True
    # Mamba-2 (``recurrent_kind`` "mamba2"; ops/pallas/mamba2.py): heads of
    # ``mamba_head_dim`` (the inner width is their product, whatever the
    # hidden size), ``B`` and ``C`` of ``mamba_state_size`` shared by the
    # heads of each of ``mamba_n_groups`` groups, a causal convolution with
    # a bias over ``mamba_conv_kernel`` taps, the scan in chunks of
    # ``mamba_chunk`` tokens
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    mamba_n_groups: int = 8
    mamba_state_size: int = 128
    mamba_conv_kernel: int = 4
    mamba_chunk: int = 128

    def __post_init__(self):
        super().__post_init__()
        if self.attention_kind not in ("gated", "mla"):
            raise ValueError(f"attention_kind {self.attention_kind!r}")
        if not 0 <= self.first_k_dense <= self.first_layer + self.num_layers:
            raise ValueError(f"first_k_dense={self.first_k_dense} of layers "
                             f"{self.first_layer}.."
                             f"{self.first_layer + self.num_layers}")
        if self.layer_pattern is None:
            if self.num_layers % self.full_attention_interval:
                raise ValueError(
                    f"num_layers={self.num_layers} is not a whole number of "
                    f"periods of {self.full_attention_interval} layers")
        elif (set(self.layer_pattern) - set("mwlME*") or self.first_layer
              + self.num_layers > len(self.layer_pattern)):
            raise ValueError(
                f"layers {self.first_layer}..{self.first_layer + self.num_layers}"
                f" lie outside the pattern {self.layer_pattern!r} (m | w | l, "
                f"or M | E | * for blocks of one mixer)")
        elif set(self.layer_pattern) & set("ME*"):
            if set(self.layer_pattern) & set("mwl"):
                raise ValueError(
                    f"the pattern {self.layer_pattern!r} mixes layers of a "
                    f"mixer and a feed-forward (m | w | l) with blocks of "
                    f"one mixer (M | E | *)")
            if (self.attention_kind != "gated" or self.post_norms
                    or self.first_k_dense or self.sparse_topk
                    or self.msa_topk):
                raise ValueError(
                    "a block of one mixer is norm -> mixer -> residual: no "
                    "post-branch norm, no dense prologue, and its attention "
                    "the gated kind without a block-selecting rule")
            if "E" in self.layer_pattern and not self.num_experts:
                raise ValueError("an expert block (E) needs num_experts")
        if self.window_attention_kind not in ("gated", "mla"):
            raise ValueError(
                f"window_attention_kind {self.window_attention_kind!r}")
        if any(self.layer_windows) and (self.attention_kind == "mla") != (
                self.window_attention_kind == "mla"):
            raise ValueError(
                "a windowed layer is of the full layers' kind: gated softmax "
                "attention beside gated, a latent mixer beside latent "
                f"(attention_kind {self.attention_kind!r}, "
                f"window_attention_kind {self.window_attention_kind!r})")
        if self.index_topk and self.attention_kind != "mla":
            raise ValueError("the selector (index_topk) is the latent "
                             "layers'")
        if self.recurrent_kind not in ("gdn", "lightning", "mamba2"):
            raise ValueError(f"recurrent_kind {self.recurrent_kind!r} "
                             f"(gdn | lightning | mamba2)")
        if self.mamba_num_heads % self.mamba_n_groups:
            raise ValueError("mamba heads must be a multiple of the groups")
        if self.activation in UNGATED and (not self.num_experts
                                           or self.first_k_dense):
            raise ValueError(
                f"activation {self.activation!r} is the experts' own: the "
                f"dense feed-forwards are gated")
        self.sparse                     # the sizes check themselves
        self.glu                        # and the activation
        if self.msa_topk and (self.sparse_topk
                              or self.attention_kind != "gated"):
            raise ValueError("the learned block selector (msa_topk) is the "
                             "gated full layers' own, and their only rule")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear value heads must be a multiple of the "
                             "key heads")
        if self.held + self.expert_offset > self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + self.held}"
                f" lie outside the router's {self.num_experts} outputs")

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def latent_dim(self) -> int:
        """Values a token keeps in a latent cache (0: keys and values)."""
        return (self.kv_lora_rank + self.qk_rope_head_dim
                if self.attention_kind == "mla" else 0)

    @property
    def mla_scale(self) -> float:
        """The softmax scale of latent attention: ``(nope + rope)^-1/2``
        times YaRN's ``m**2``."""
        return self.mla_sizes().scale

    def mla_sizes(self, windowed: bool = False) -> "MlaSizes":
        """The sizes of a latent mixer: the full layers', or with
        ``windowed`` the "w" layers' own."""
        h = self.hidden_size

        def rescale(rank):
            return math.sqrt(h / rank) if self.mla_lora_rescale else 1.0

        if windowed:
            dn, r = self.window_qk_nope_head_dim, self.window_qk_rope_head_dim
            return MlaSizes(
                self.window_num_heads, self.window_q_lora_rank,
                self.window_kv_lora_rank, dn, r, self.window_v_head_dim,
                self.window_rope_theta, None, 1.0 / math.sqrt(dn + r),
                rescale(self.window_q_lora_rank),
                rescale(self.window_kv_lora_rank))
        m = yarn_mscale(self.rope_yarn_factor, self.rope_mscale_all_dim)
        dn, r = self.qk_nope_head_dim, self.qk_rope_head_dim
        return MlaSizes(self.num_heads, self.q_lora_rank, self.kv_lora_rank,
                        dn, r, self.v_head_dim, self.rope_theta,
                        self.mla_inv_freq(), m * m / math.sqrt(dn + r),
                        rescale(self.q_lora_rank), rescale(self.kv_lora_rank))

    @property
    def window_latent_dim(self) -> int:
        """Values a token keeps in a windowed latent layer's cache (0: the
        stack has no such layer)."""
        if self.window_attention_kind != "mla" or "w" not in self._held_pattern:
            return 0
        return self.window_kv_lora_rank + self.window_qk_rope_head_dim

    @property
    def index_key_dim(self) -> int:
        """Values a token keeps for the selector beside its latent (0: no
        selector)."""
        return self.index_head_dim if self.index_topk else 0

    def mla_inv_freq(self):
        """The rotary part's inverse frequencies (None: plain rotary)."""
        if self.rope_yarn_factor <= 1.0:
            return None
        return yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                             self.rope_yarn_factor, self.rope_original_max,
                             self.rope_beta_fast, self.rope_beta_slow)

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def _held_pattern(self) -> str:
        if self.layer_pattern is None:
            per = self.full_attention_interval
            return "".join("m" if (l + 1) % per == 0 else "l"
                           for l in range(self.num_layers))
        return self.layer_pattern[self.first_layer:
                                  self.first_layer + self.num_layers]

    @property
    def one_mixer(self) -> bool:
        """Whether a block is *norm -> one mixer -> residual* (the pattern's
        "M": the recurrent mixer, "E": the expert feed-forward, "*": full
        attention), not a mixer and a feed-forward with a norm each."""
        return bool(set(self.layer_pattern or "") & set("ME*"))

    @property
    def layer_kinds(self) -> Tuple[bool, ...]:
        """For each layer held here, whether it is softmax attention over
        keys and values (full or windowed), not recurrent (nor, a block of
        one mixer, the experts alone)."""
        return tuple(c in "mw*" for c in self._held_pattern)

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        """For each layer held here, its sliding window (None: none)."""
        if "w" in self._held_pattern and not self.sliding_window:
            raise ValueError("a windowed layer needs sliding_window")
        return tuple(self.sliding_window if c == "w" else None
                     for c in self._held_pattern)

    @property
    def mixer_kinds(self) -> Tuple[Any, ...]:
        """For each layer held here, which mixer's leaves it reads: True the
        full layers' (a gated windowed layer shares them), False the
        recurrent one's, "w" the windowed latent mixer's own, None an expert
        block's (a block of one mixer that is the feed-forward: no mixer)."""
        own = self.window_attention_kind == "mla"
        return tuple(None if c == "E" else "w" if c == "w" and own
                     else c in "mw*" for c in self._held_pattern)

    @property
    def window_layers(self) -> int:
        """Layers with a mixer and a cache of their own behind a window."""
        return sum(k == "w" for k in self.mixer_kinds)

    @property
    def dense_layers(self) -> int:
        """Layers held here whose feed-forward is the dense SwiGLU of the
        published model's first ``first_k_dense``."""
        if not self.num_experts:
            return 0
        return min(max(self.first_k_dense - self.first_layer, 0),
                   self.num_layers)

    @property
    def expert_layers(self) -> int:
        """Layers held here that end in (or, blocks of one mixer, are) the
        expert feed-forward."""
        if not self.num_experts:
            return 0
        if self.one_mixer:
            return sum(k is None for k in self.mixer_kinds)
        return self.num_layers - self.dense_layers

    @property
    def stack_plan(self) -> Tuple[int, Tuple[Tuple[Any, int], ...]]:
        """``(repeats, runs)``: the layers' mixers (``mixer_kinds``; after
        the prologue of ``first_k_dense`` layers, which the runner calls one
        by one before the scan) as ``repeats`` copies of the shortest
        pattern that tiles them, the pattern as runs ``(kind, layers)`` of
        one mixer (of blocks of one mixer, ``kind`` None: expert blocks).
        The serving runner scans the repeats and, inside, each run: one
        layer body a run, whatever the depth."""
        kinds = self.mixer_kinds[self.dense_layers:]    # after the prologue
        L = len(kinds)
        p = next(p for p in range(1, L + 1)
                 if L % p == 0 and kinds == kinds[:p] * (L // p))
        runs = []
        for full in kinds[:p]:
            if runs and runs[-1][0] == full:
                runs[-1][1] += 1
            else:
                runs.append([full, 1])
        return L // p, tuple((f, n) for f, n in runs)

    @property
    def kv_layers(self) -> int:
        """Layers that hold keys and values (or latents) in the paged pool:
        the full ones, and the windowed ones that share their mixer."""
        return sum(k is True for k in self.mixer_kinds)

    periods = kv_layers                 # Qwen3-Next: one full layer a period

    @property
    def recurrent_layers(self) -> int:
        return sum(k is False for k in self.mixer_kinds)

    @property
    def mamba_inner(self) -> int:
        """The Mamba-2 mixer's inner width: heads x head size."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        if self.recurrent_kind == "mamba2":     # x | B | C
            return self.mamba_inner + 2 * self.mamba_n_groups \
                * self.mamba_state_size
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def conv_taps(self) -> int:
        """Taps of the recurrent mixer's convolution (lightning: none)."""
        return {"gdn": self.linear_conv_kernel_dim,
                "mamba2": self.mamba_conv_kernel}.get(self.recurrent_kind, 1)

    @property
    def sparse(self) -> Optional[block_sparse.SparseSizes]:
        if not self.sparse_topk:
            return None
        return block_sparse.SparseSizes(
            kernel=self.sparse_kernel_size, stride=self.sparse_kernel_stride,
            block=self.sparse_block_size, init_blocks=self.sparse_init_blocks,
            window=self.sparse_window_size, topk=self.sparse_topk,
            dense_len=self.sparse_dense_len)

    @property
    def msa(self) -> Optional[block_sparse.MsaSizes]:
        if not self.msa_topk:
            return None
        return block_sparse.MsaSizes(
            block=self.msa_block_size, topk=self.msa_topk,
            index_heads=self.msa_index_heads, index_dim=self.msa_index_dim,
            local_blocks=self.msa_local_blocks)

    @property
    def glu(self) -> Glu:
        """The gated feed-forwards' activation (dense, routed and shared)."""
        if self.activation == "swigluoai":
            return Glu("swigluoai", self.swiglu_alpha, self.swiglu_limit)
        return Glu()

    @property
    def expert_activation(self):
        """What the routed and the shared experts apply: :attr:`glu`, or the
        name of an ungated activation (``parallel/moe.py::UNGATED``: two
        matrices an expert, no ``wg``)."""
        return self.activation if self.activation in UNGATED else self.glu

    def lightning_decay(self) -> jax.Array:
        """``log a`` [recurrent layers, heads] float32, a constant of the
        head and of the layer's *published* index ``l``: ``-s_j (1 - l /
        (layers - 1) + 1e-5)``, ``s_j = 2^(-8 (j + 1) / heads)`` (the
        lightning-attention convention)."""
        n = self.linear_num_value_heads
        total = len(self.layer_pattern or "") or self.num_layers
        slope = 2.0 ** (-8.0 * (jnp.arange(n, dtype=jnp.float32) + 1.0) / n)
        ls = jnp.asarray([self.first_layer + l for l, kind in
                          enumerate(self.mixer_kinds) if kind is False],
                         jnp.float32)
        return -slope[None, :] * (1.0 - ls[:, None] / max(total - 1, 1) + 1e-5)

    @property
    def gate(self) -> GateConfig:
        return GateConfig(num_experts=self.num_experts, top_k=self.top_k,
                          drop_tokens=False, scoring=self.router_scoring,
                          routed_scale=self.routed_scale)

    def is_dense(self, layer: int) -> bool:
        """Whether the layer's feed-forward is the dense SwiGLU."""
        return not self.num_experts or layer < self.dense_layers

    def is_full(self, layer: int) -> bool:
        return self.layer_kinds[layer]

    def num_params(self) -> int:
        return sum(math.prod(s) for s in jax.tree.leaves(
            _shapes(self), is_leaf=lambda x: isinstance(x, tuple)))

    def flops_per_token(self) -> float:
        """Forward and backward, 6 a weight a token touches (the experts:
        ``top_k`` and the shared one, three matrices each or, ungated, two)."""
        h = self.hidden_size
        if not self.num_experts:
            return 6.0 * self.num_params()
        mats = 2 if self.activation in UNGATED else 3
        active = mats * h * (self.top_k * self.moe_ffn_size
                             + self.shared_ffn_size)
        held_all = mats * h * self.moe_ffn_size * self.held
        if self.one_mixer:      # every leaf is read by a block of its kind
            return 6.0 * (self.num_params() + self.expert_layers * (
                active - held_all - mats * h * self.shared_ffn_size))
        # (the prologue's slots of the expert leaves are never read)
        K = self.dense_layers
        slots = self.num_layers - K if self.experts_apart else self.num_layers
        return 6.0 * (self.num_params() - slots * held_all
                      + (self.num_layers - K) * active
                      - K * 3 * h * self.shared_ffn_size)


def _layer_norms(cfg: HybridConfig) -> Tuple[str, ...]:
    """A layer's norms: before each branch and, with ``post_norms``, after;
    of a block of one mixer, the one before it."""
    if cfg.one_mixer:
        return ("ln1",)
    return ("ln1", "ln2") + (("ln1_post", "ln2_post") if cfg.post_norms
                             else ())


def _shapes(cfg: HybridConfig) -> Dict[str, Any]:
    """Every leaf's shape, in the tree's own nesting."""
    h, v, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    nq, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    e, f, fs = cfg.held, cfg.moe_ffn_size, cfg.shared_ffn_size
    # the slots of each group of leaves: one a layer (``serving_params`` cuts
    # a mixer to the layers that use it) or, of a stack of one-mixer blocks,
    # one a block of the group's kind, the group at the top of the tree
    one = cfg.one_mixer
    La, Lr, Lm = (cfg.kv_layers, cfg.recurrent_layers, cfg.expert_layers) \
        if one else (L, L, L)
    if cfg.recurrent_kind == "gdn":
        rec = {"wq": (Lr, h, nk, dk), "wk": (Lr, h, nk, dk),
               "wv": (Lr, h, nv, dv), "wz": (Lr, h, nv, dv),
               "wb": (Lr, h, nv), "wa": (Lr, h, nv),
               "conv": (Lr, cfg.linear_conv_kernel_dim, cfg.conv_channels),
               "A_log": (Lr, nv), "dt_bias": (Lr, nv), "norm": (Lr, dv),
               "wo": (Lr, nv, dv, h)}
    elif cfg.recurrent_kind == "mamba2":
        n, di, cc = cfg.mamba_num_heads, cfg.mamba_inner, cfg.conv_channels
        rec = {"w_in": (Lr, h, di + cc + n),            # z | x B C | dt
               "conv": (Lr, cfg.mamba_conv_kernel, cc), "conv_bias": (Lr, cc),
               "A_log": (Lr, n), "dt_bias": (Lr, n), "D": (Lr, n),
               "norm": (Lr, di), "w_out": (Lr, di, h)}
    else:
        rec = {"wq": (Lr, h, nv, dk), "wk": (Lr, h, nv, dk),
               "wv": (Lr, h, nv, dv), "wz": (Lr, h, nv, dv),
               "q_norm": (Lr, dk), "k_norm": (Lr, dk), "norm": (Lr, dv),
               "wo": (Lr, nv, dv, h)}
    top = {}
    if cfg.num_experts:
        K, F = cfg.dense_layers, cfg.ffn_size
        Le = cfg.expert_layers if cfg.experts_apart or one else L
        gated = cfg.activation not in UNGATED
        experts = {"wi": (Le, e, h, f), "wo": (Le, e, f, h)}
        moe = {"router": (Lm, h, cfg.num_experts),
               "shared": {"wi": (Lm, h, fs), "wo": (Lm, fs, h)}}
        if gated:
            experts["wg"], moe["shared"]["wg"] = (Le, e, h, f), (Lm, h, fs)
        if cfg.experts_apart or one:
            top["experts"] = experts
        else:
            moe["experts"] = experts
        if cfg.shared_gate:
            moe["shared_gate"] = (Lm, h)
        if cfg.router_scoring == "sigmoid":
            moe["router_bias"] = (Lm, cfg.num_experts)
        ffn = {"moe": moe}
        if K:
            top["dense"] = {"wg": (K, h, F), "wi": (K, h, F), "wo": (K, F, h)}
    else:
        ffn = {"mlp": {"wg": (L, h, cfg.ffn_size), "wi": (L, h, cfg.ffn_size),
                       "wo": (L, cfg.ffn_size, h)}}
    if cfg.attention_kind == "mla":
        mixers = {"mla": _mla_shapes(cfg, False)}
        if cfg.window_layers:
            mixers["wmla"] = _mla_shapes(cfg, True)
    else:
        per_head = cfg.qk_norm_per_head
        attn = {"wq": (La, h, nq, (2 if cfg.attn_output_gate else 1) * d),
                "wk": (La, h, nkv, d), "wv": (La, h, nkv, d),
                "wo": (La, nq, d, h)}
        if cfg.qk_norm:
            attn.update(q_norm=(La, nq, d) if per_head else (La, d),
                        k_norm=(La, nkv, d) if per_head else (La, d))
        if cfg.msa:
            hi, di = cfg.msa.index_heads, cfg.msa.index_dim
            attn.update(wsq=(L, h, nkv, hi, di), wsk=(L, h, nkv, di))
        mixers = {"attn": attn} if La else {}
    if cfg.recurrent_layers:
        mixers[cfg.recurrent_kind] = rec
    return _placed(cfg, {"embed": {"tokens": (v, h)},
                         "final_norm": {"scale": (h,)},
                         "unembed": {"kernel": (h, v)}, **top},
                   {n: {"scale": (L, h)} for n in _layer_norms(cfg)},
                   mixers, ffn)


def _placed(cfg: HybridConfig, top, norms, mixers, ffn) -> Dict[str, Any]:
    """The tree's nesting: the norms under ``layers``; the mixers and the
    feed-forward's leaves there too, a slot a layer, or of a stack of
    one-mixer blocks at the top, each over its own blocks."""
    if cfg.one_mixer:
        return {**top, **mixers, **ffn, "layers": norms}
    return {**top, "layers": {**norms, **mixers, **ffn}}


def _mla_shapes(cfg: HybridConfig, windowed: bool) -> Dict[str, Tuple]:
    """A latent mixer's leaves, a slot a layer: the full layers' (with the
    selector's three projections and its key norm) or the windowed ones'."""
    h, L, z = cfg.hidden_size, cfg.num_layers, cfg.mla_sizes(windowed)
    out = {"wqa": (L, h, z.q_rank), "q_norm": (L, z.q_rank),
           "wqb": (L, z.q_rank, z.heads, z.nope + z.rope),
           "wkva": (L, h, z.kv_rank + z.rope), "kv_norm": (L, z.kv_rank),
           "wkvb": (L, z.kv_rank, z.heads, z.nope + z.v),
           "wo": (L, z.heads, z.v, h)}
    if cfg.mla_head_gate:
        out["wgate"] = (L, h, z.heads)
    if cfg.index_topk and not windowed:
        ni, di = cfg.index_n_heads, cfg.index_head_dim
        out.update(wiq=(L, z.q_rank, ni, di), wik=(L, h, di),
                   ik_norm=(L, di), ik_bias=(L, di), wiw=(L, h, ni))
    return out


# the selector's key norm is a LayerNorm with bias at its own eps
INDEX_NORM_EPS = 1e-6

_GAINS = ("scale", "q_norm", "k_norm", "kv_norm", "norm", "ik_norm")
_MIXERS = ("attn", "mla", "wmla", "gdn", "lightning", "mamba2")


def init_params(cfg: HybridConfig, rng: jax.Array) -> Dict[str, Any]:
    """Fan-in normal draws, gains (and Mamba-2's skip ``D``) one, ``A_log``
    and the biases zero, ``dt_bias`` spread."""
    shapes = _shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, (path, shape) in zip(keys, leaves):
        name = path[-1].key
        group = path[-2].key if len(path) > 1 else ""
        if name in _GAINS or name == "D":
            x = jnp.ones(shape, cfg.param_dtype)
        elif name in ("A_log", "ik_bias", "conv_bias"):
            x = jnp.zeros(shape, cfg.param_dtype)
        elif name == "dt_bias":
            x = jax.random.normal(key, shape, cfg.param_dtype) * 2.0
        elif name in ("tokens", "kernel"):
            x = jax.random.normal(key, shape, cfg.param_dtype) * 0.02
        elif name == "conv":
            x = jax.random.normal(key, shape, cfg.param_dtype) * 0.5
        else:
            if name == "wo":   # contracts everything but the last axis
                fan = math.prod(shape[2:-1]) if group in _MIXERS \
                    else shape[-2]
            elif name == "w_out":
                fan = shape[-2]
            elif name in ("wqb", "wkvb", "wiq"):   # [L, rank, heads, d]
                # (of the input as it arrives: the rescaled latent's)
                z = cfg.mla_sizes(group == "wmla")
                fan = shape[1] * {"wqb": z.q_rescale, "wkvb": z.kv_rescale,
                                  "wiq": 1.0}[name] ** 2
            else:              # [L, (E,) h, ...]: contracts h
                fan = cfg.hidden_size
            x = jax.random.normal(key, shape, cfg.param_dtype) / math.sqrt(fan)
        out.append(x)
    return jax.tree.unflatten(treedef, out)


def logical_axes(cfg: HybridConfig) -> Dict[str, Any]:
    L = "layers"
    rec = {"wq": (L, "embed", None, None), "wk": (L, "embed", None, None),
           "wv": (L, "embed", None, None), "wz": (L, "embed", None, None),
           "norm": (L, None), "wo": (L, None, None, "embed")}
    if cfg.recurrent_kind == "gdn":
        rec.update({"wb": (L, "embed", None), "wa": (L, "embed", None),
                    "conv": (L, None, None), "A_log": (L, None),
                    "dt_bias": (L, None)})
    elif cfg.recurrent_kind == "mamba2":
        rec = {"w_in": (L, "embed", None), "conv": (L, None, None),
               "conv_bias": (L, None), "A_log": (L, None),
               "dt_bias": (L, None), "D": (L, None), "norm": (L, None),
               "w_out": (L, None, "embed")}
    else:
        rec.update({"q_norm": (L, None), "k_norm": (L, None)})
    top = {}
    if cfg.num_experts:
        experts = {"wi": (L, "expert", "embed", None),
                   "wo": (L, "expert", None, "embed")}
        moe = {"router": (L, "embed", None),
               "shared": {"wi": (L, "embed", None),
                          "wo": (L, None, "embed")}}
        if cfg.activation not in UNGATED:
            experts["wg"] = (L, "expert", "embed", None)
            moe["shared"]["wg"] = (L, "embed", None)
        if cfg.experts_apart or cfg.one_mixer:
            top["experts"] = experts
        else:
            moe["experts"] = experts
        if cfg.shared_gate:
            moe["shared_gate"] = (L, "embed")
        if cfg.router_scoring == "sigmoid":
            moe["router_bias"] = (L, None)
        ffn = {"moe": moe}
        if cfg.dense_layers:
            top["dense"] = {"wg": (L, "embed", "mlp"), "wi": (L, "embed", "mlp"),
                            "wo": (L, "mlp", "embed")}
    else:
        ffn = {"mlp": {"wg": (L, "embed", "mlp"), "wi": (L, "embed", "mlp"),
                       "wo": (L, "mlp", "embed")}}
    if cfg.attention_kind == "mla":
        # replicated: a latent cache has no head axis to shard
        def latent(windowed):
            return {name: (L, "embed") + (None,) * (len(shape) - 2)
                    if name in ("wqa", "wkva", "wgate", "wik", "wiw")
                    else (L,) + (None,) * (len(shape) - 2) + ("embed",)
                    if name == "wo" else (L,) + (None,) * (len(shape) - 1)
                    for name, shape in _mla_shapes(cfg, windowed).items()}

        mixers = {"mla": latent(False)}
        if cfg.window_layers:
            mixers["wmla"] = latent(True)
    else:
        per_head = cfg.qk_norm_per_head
        attn = {"wq": (L, "embed", "heads", "head_dim"),
                "wk": (L, "embed", "kv_heads", "head_dim"),
                "wv": (L, "embed", "kv_heads", "head_dim"),
                "wo": (L, "heads", "head_dim", "embed")}
        if cfg.qk_norm:
            attn.update(q_norm=(L, "heads", "head_dim") if per_head
                        else (L, "head_dim"),
                        k_norm=(L, "kv_heads", "head_dim") if per_head
                        else (L, "head_dim"))
        if cfg.msa:
            attn.update(wsq=(L, "embed", "kv_heads", None, None),
                        wsk=(L, "embed", "kv_heads", None))
        mixers = {"attn": attn} if cfg.kv_layers or not cfg.one_mixer else {}
    if cfg.recurrent_layers:
        # the recurrent mixer is replicated: its state pool is per
        # sequence, not per head shard
        mixers[cfg.recurrent_kind] = rec
    return _placed(cfg, {"embed": {"tokens": ("vocab", "embed")},
                         "final_norm": {"scale": ("embed",)},
                         "unembed": {"kernel": ("embed", "vocab")}, **top},
                   {n: {"scale": (L, "embed")} for n in _layer_norms(cfg)},
                   mixers, ffn)


def axes_for(cfg: HybridConfig, params: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`logical_axes` in the layout of ``params``: a mixer handed over
    at the top of the tree (over its own layers, as :func:`serving_params`
    takes it) has its axes there."""
    axes = logical_axes(cfg)
    layers = dict(axes["layers"])
    top = {m: layers.pop(m) for m in _MIXERS if m in params and m in layers}
    return dict(axes, layers=layers, **top)


def serving_params(cfg: HybridConfig, params: Dict[str, Any],
                   donate: bool = False) -> Dict[str, Any]:
    """The tree the forward passes take: of each mixer only the layers that
    use it (the other slices are dropped with the tree handed in), the
    routed experts apart from the other per-layer leaves (the grouped
    product reads them by layer, inside the kernel) and without the
    prologue's slots. Idempotent. ``donate``: the caller gives the stacked
    tree up, and each stacked leaf that is cut is deleted as soon as its cut
    exists, so that at most one leaf is held twice (a chip that the cut
    tree nearly fills cannot hold both trees)."""
    mixer = "mla" if cfg.attention_kind == "mla" else "attn"
    if "experts" in params and "layers" in params and not any(
            m in params["layers"] for m in _MIXERS):
        return params

    def cut(tree, keep):
        def one(x):
            if len(keep) == x.shape[0]:
                return x
            out = x[keep[0]:keep[-1] + 1] if keep == list(
                range(keep[0], keep[-1] + 1)) else x[jnp.asarray(keep)]
            if donate:
                jax.block_until_ready(out)
                x.delete()
            return out
        return jax.tree.map(one, tree)

    L = cfg.num_layers
    layers = dict(params["layers"])
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "unembed": params["unembed"]}
    # (a mixer handed over at the top is over its own layers already: the
    # benchmark's weight table draws it so, and holds no dead slot)
    for name, kind in ((mixer, True), (cfg.recurrent_kind, False),
                       ("wmla", "w")):
        uses = [l for l in range(L) if cfg.mixer_kinds[l] == kind]
        if uses:
            out[name] = params[name] if name in params else \
                cut(layers.pop(name), uses)
    experts = params.get("experts", {})     # (there with experts_apart)
    if cfg.num_experts and not experts:
        moe = dict(layers["moe"])
        experts = cut(moe.pop("experts"), list(range(cfg.dense_layers, L)))
        layers["moe"] = moe
    if "dense" in params:
        out["dense"] = params["dense"]
    return dict(out, layers=layers, experts=experts)


# ---------------------------------------------------------------------------
# the layer's pieces, shared by the full forward and the serving runner
# ---------------------------------------------------------------------------


def _rms(x, gain, eps):
    return _norm(x, {"scale": gain}, "rmsnorm", eps)


def residual(cfg: "HybridConfig", x, out):
    """``x + c * out``, ``c`` the muP scale of a residual branch."""
    c = cfg.residual_scale
    return x + out if c == 1.0 else x + jnp.asarray(c, out.dtype) * out


def attn_project(cfg: HybridConfig, ap, y, positions, windowed: bool = False):
    """Queries and keys normed and rotated (a full layer's share of each
    head, or with ``windowed`` a windowed layer's), values, and the output
    gate. y [..., H]; positions [...]. Returns q [..., nq, d], k, v
    [..., nkv, d], gate [..., nq, d] (None: the mixer has no output gate)."""
    dt, d = y.dtype, cfg.head_dim
    qg = jnp.einsum("...h,hnd->...nd", y, ap["wq"].astype(dt))
    q, gate = (qg[..., :d], qg[..., d:]) if cfg.attn_output_gate \
        else (qg, None)
    k = jnp.einsum("...h,hnd->...nd", y, ap["wk"].astype(dt))
    v = jnp.einsum("...h,hnd->...nd", y, ap["wv"].astype(dt))
    if cfg.qk_norm:
        q = _rms(q, ap["q_norm"], cfg.norm_eps)
        k = _rms(k, ap["k_norm"], cfg.norm_eps)
    rot = int(d * (cfg.window_rotary_factor if windowed
                   else cfg.partial_rotary_factor))

    def rope(x):
        if not rot:
            return x
        return jnp.concatenate(
            [_rope(x[..., :rot], positions, cfg.rope_theta), x[..., rot:]], -1)

    return rope(q), rope(k), v, gate


def mla_project(cfg: HybridConfig, mp, y, positions, windowed: bool = False,
                query_latent: bool = False):
    """Latent attention's projections of y [..., H] at positions [...]:
    queries through their bottleneck, ``q_n [..., n, nope]`` and ``q_r
    [..., n, rope]`` (rotated), and the token's latent ``[..., c + rope]``:
    the normed compressed vector ``c_kv`` and the one rotated key ``k_r``
    all heads share. That vector is what the cache holds. ``windowed``: the
    "w" layers' mixer, with its own sizes and rotary base. With
    ``mla_lora_rescale`` both normed latents are scaled (``sqrt(hidden /
    rank)``). ``query_latent``: a fourth value, the normed query latent
    *before* that scale, which the selector's queries read."""
    z = cfg.mla_sizes(windowed)
    dt, c, dn = y.dtype, z.kv_rank, z.nope
    cq0 = _rms(y @ mp["wqa"].astype(dt), mp["q_norm"], cfg.norm_eps)
    cq = cq0 if z.q_rescale == 1.0 else cq0 * jnp.asarray(z.q_rescale, dt)
    q = jnp.einsum("...q,qnd->...nd", cq, mp["wqb"].astype(dt))
    kva = y @ mp["wkva"].astype(dt)
    c_kv = _rms(kva[..., :c], mp["kv_norm"], cfg.norm_eps)
    if z.kv_rescale != 1.0:
        c_kv = c_kv * jnp.asarray(z.kv_rescale, dt)
    q_r = _rope(q[..., dn:], positions, z.theta, z.inv_freq)
    k_r = _rope(kva[..., None, c:], positions, z.theta, z.inv_freq)[..., 0, :]
    out = q[..., :dn], q_r, jnp.concatenate([c_kv, k_r], axis=-1)
    return out + (cq0,) if query_latent else out


def mla_absorb_q(cfg: HybridConfig, mp, q_n):
    """The absorbed query ``q_n W_kvb^K`` [..., n, c]: a head's query in the
    latent's own coordinates, so that its score against a cached token is a
    product with the latent itself. (``nope`` is read off ``q_n``: either
    latent mixer's.)"""
    wk = mp["wkvb"][..., :q_n.shape[-1]].astype(q_n.dtype)
    return jnp.einsum("...nd,cnd->...nc", q_n, wk)


def mla_absorb_o(cfg: HybridConfig, mp, o, windowed: bool = False):
    """``o W_kvb^V``: attention's output over latents [..., n, c] to a
    head's values [..., n, v]."""
    wv = mp["wkvb"][..., cfg.mla_sizes(windowed).nope:].astype(o.dtype)
    return jnp.einsum("...nc,cnd->...nd", o, wv)


def mla_expand(cfg: HybridConfig, mp, latent, windowed: bool = False):
    """The expanded form's keys and values of cached latents [..., c +
    rope]: ``k_n [..., n, nope]``, ``v [..., n, v]`` and the shared rotary
    key ``k_r [..., rope]``."""
    z = cfg.mla_sizes(windowed)
    kv = jnp.einsum("...c,cnd->...nd", latent[..., :z.kv_rank],
                    mp["wkvb"].astype(latent.dtype))
    return (kv[..., :z.nope], kv[..., z.nope:],
            latent[..., z.kv_rank:z.kv_rank + z.rope])


def mla_output(mp, o, y=None):
    """``concat(o) W_o``; o [..., n, v]. With the head-wise gate (``wgate``;
    ``y`` the layer's normed input) each head's output times ``sigmoid(y
    W_g)`` first."""
    if "wgate" in mp:
        with jax.named_scope("attn_gate"):
            g = jax.nn.sigmoid((y @ mp["wgate"].astype(y.dtype)).astype(
                jnp.float32))
            o = o * g[..., None].astype(o.dtype)
    return jnp.einsum("...nd,ndh->...h", o, mp["wo"].astype(o.dtype))


def index_project(cfg: HybridConfig, mp, y, cq0, positions):
    """The selector's projections of the normed input y [..., H] and the
    normed query latent cq0 [..., q_rank]: queries ``qI [..., ni, di]`` and
    the token's key ``kI [..., di]`` (LayerNorm with bias), both with their
    last ``qk_rope_head_dim`` dims rotated at the full layers' base, and the
    heads' weights ``[..., ni]`` float32 with the score's constant ``ni^-1/2
    di^-1/2`` folded in."""
    dt, r = y.dtype, cfg.qk_rope_head_dim
    ni, di = cfg.index_n_heads, cfg.index_head_dim
    q = jnp.einsum("...q,qnd->...nd", cq0, mp["wiq"].astype(dt))
    k = _norm(y @ mp["wik"].astype(dt),
              {"scale": mp["ik_norm"], "bias": mp["ik_bias"]}, "layernorm",
              INDEX_NORM_EPS)

    def rope(x):
        return jnp.concatenate(
            [x[..., :di - r], _rope(x[..., di - r:], positions,
                                    cfg.rope_theta, cfg.mla_inv_freq())], -1)

    w = (y @ mp["wiw"].astype(dt)).astype(jnp.float32) / math.sqrt(ni * di)
    return rope(q), rope(k[..., None, :])[..., 0, :], w


def index_scores(q, w, keys):
    """``I(t, s) = sum_h w[t, h] relu(q[t, h] . keys[s])``: q [..., T, ni,
    di], w [..., T, ni] float32, keys [..., N, di]; float32 [..., T, N]."""
    s = jnp.einsum("...tnd,...sd->...tns", q, keys.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.einsum("...tns,...tn->...ts", jax.nn.relu(s), w)


def mla_attention(cfg: HybridConfig, mp, q_n, q_r, latent, windowed=False,
                  selected=None):
    """Causal latent attention of whole sequences in the expanded form, no
    cache: q_n, q_r [B, S, n, .]; latent [B, S, c + rope]. ``windowed``: the
    "w" layers' mixer under its window; ``selected`` bool [B, S, S]: the
    keys the selector kept for each query."""
    S, dt = q_n.shape[1], q_n.dtype
    k_n, v, k_r = mla_expand(cfg, mp, latent, windowed)
    s = (jnp.einsum("bsnd,btnd->bnst", q_n, k_n)
         + jnp.einsum("bsnd,btd->bnst", q_r, k_r)).astype(jnp.float32)
    back = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    ok = back >= 0
    if windowed:
        ok = ok & (back < cfg.sliding_window)
    ok = ok[None, None] if selected is None else (ok & selected)[:, None]
    pr = jax.nn.softmax(jnp.where(ok, s * cfg.mla_sizes(windowed).scale,
                                  -1e30), axis=-1)
    return jnp.einsum("bnst,btnd->bsnd", pr.astype(dt), v)


def select_tokens(cfg: HybridConfig, mp, y, cq0, positions):
    """The selector on whole sequences, no cache: bool [B, S, S], for each
    query the ``index_topk`` causally visible keys that score highest."""
    S = y.shape[1]
    q, k, w = index_project(cfg, mp, y, cq0, positions)
    visible = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    return block_sparse.topk_mask(index_scores(q, w, k), visible[None],
                                  cfg.index_topk)


def attn_output(ap, attn, gate):
    """``o_proj(attn * sigmoid(gate))`` under the scope ``attn_gate``; attn,
    gate [..., nq, d]. ``gate`` None (a mixer without the gate):
    ``o_proj(attn)``."""
    dt = attn.dtype
    if gate is None:
        return jnp.einsum("...nd,ndh->...h", attn, ap["wo"].astype(dt))
    with jax.named_scope("attn_gate"):
        o = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
        return jnp.einsum("...nd,ndh->...h", o, ap["wo"].astype(dt))


def msa_project(ap, y):
    """The learned block selector's projections of the normed input y [...,
    H]: the indexer queries ``qI [..., nkv, index_heads, di]`` and the
    token's indexer key a KV head ``kI [..., nkv, di]`` (no rotary, no norm)."""
    dt = y.dtype
    return (jnp.einsum("...h,hkid->...kid", y, ap["wsq"].astype(dt)),
            jnp.einsum("...h,hkd->...kd", y, ap["wsk"].astype(dt)))


def msa_mask(cfg: HybridConfig, ap, y, positions):
    """The selector on whole sequences, no cache: bool [B, S, nkv, blocks],
    the blocks each (query, KV head) reads. A block's pooled key is the
    maximum over all its tokens: the blocks that are scored lie wholly
    before the query's local ones."""
    sz = cfg.msa
    B, S = positions.shape
    qi, ki = msa_project(ap, y)
    pad = (-S) % sz.block
    ki = jnp.pad(ki, ((0, 0), (0, pad), (0, 0), (0, 0)),
                 constant_values=-jnp.inf)
    pooled = jnp.max(ki.reshape(B, -1, sz.block, *ki.shape[2:]), axis=2)
    return jax.vmap(lambda q, p, t: block_sparse.msa_select(sz, q, p, t)[0])(
        qi, pooled, positions)


def gdn_project(cfg: HybridConfig, gp, y):
    """The recurrent mixer's projections of y [..., H]: the convolution's
    input ``q|k|v`` [..., C], ``z`` [..., nv, dv], and float32 ``beta``,
    ``g`` [..., nv]."""
    dt = y.dtype
    lead = y.shape[:-1]
    q = jnp.einsum("...h,hnd->...nd", y, gp["wq"].astype(dt))
    k = jnp.einsum("...h,hnd->...nd", y, gp["wk"].astype(dt))
    v = jnp.einsum("...h,hnd->...nd", y, gp["wv"].astype(dt))
    z = jnp.einsum("...h,hnd->...nd", y, gp["wz"].astype(dt))
    b = jnp.einsum("...h,hn->...n", y, gp["wb"].astype(dt)).astype(jnp.float32)
    a = jnp.einsum("...h,hn->...n", y, gp["wa"].astype(dt)).astype(jnp.float32)
    mixed = jnp.concatenate([x.reshape(lead + (-1,)) for x in (q, k, v)], -1)
    g = -jnp.exp(gp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + gp["dt_bias"].astype(jnp.float32))
    return mixed, z, jax.nn.sigmoid(b), g


def gdn_heads(cfg: HybridConfig, conv_out):
    """After the convolution and SiLU: float32 q, k [..., nv, dk] (key heads
    repeated, L2-normalised, q scaled) and v [..., nv, dv]."""
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    lead = conv_out.shape[:-1]
    x = jax.nn.silu(conv_out.astype(jnp.float32))
    q = x[..., :nk * dk].reshape(lead + (nk, dk))
    k = x[..., nk * dk:2 * nk * dk].reshape(lead + (nk, dk))
    v = x[..., 2 * nk * dk:].reshape(lead + (nv, dv))

    def unit(t):
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q), nv // nk, axis=-2) / math.sqrt(dk)
    k = jnp.repeat(unit(k), nv // nk, axis=-2)
    return q, k, v


def gdn_output(cfg: HybridConfig, gp, o, z):
    """``out_proj(rmsnorm(o) * silu(z))``; o float32 [..., nv, dv]."""
    dt = z.dtype
    o = _rms(o, gp["norm"], cfg.norm_eps).astype(dt) * jax.nn.silu(z)
    return jnp.einsum("...nd,ndh->...h", o, gp["wo"].astype(dt))


def lightning_project(cfg: HybridConfig, mp, y, positions):
    """Lightning attention's projections of y [..., H]: float32 q (scaled),
    k [..., n, d] normed and rotated over the whole head, v, and the gate's
    input z [..., n, d]."""
    dt = y.dtype
    q = jnp.einsum("...h,hnd->...nd", y, mp["wq"].astype(dt))
    k = jnp.einsum("...h,hnd->...nd", y, mp["wk"].astype(dt))
    v = jnp.einsum("...h,hnd->...nd", y, mp["wv"].astype(dt))
    z = jnp.einsum("...h,hnd->...nd", y, mp["wz"].astype(dt))
    q = _rope(_rms(q, mp["q_norm"], cfg.norm_eps), positions, cfg.rope_theta)
    k = _rope(_rms(k, mp["k_norm"], cfg.norm_eps), positions, cfg.rope_theta)
    f32 = jnp.float32
    return (q.astype(f32) / math.sqrt(cfg.linear_key_head_dim), k.astype(f32),
            v.astype(f32), z)


def lightning_output(cfg: HybridConfig, mp, o, z):
    """``out_proj(sigmoid(z) * rmsnorm(o))``; o float32 [..., n, d]."""
    dt = z.dtype
    o = (_rms(o, mp["norm"], cfg.norm_eps).astype(dt)
         * jax.nn.sigmoid(z.astype(jnp.float32)).astype(dt))
    return jnp.einsum("...nd,ndh->...h", o, mp["wo"].astype(dt))


def mamba2_project(cfg: HybridConfig, mp, y):
    """The Mamba-2 mixer's input projection of y [..., H], split: the gate
    ``z`` [..., inner], the convolution's input ``x|B|C`` [..., C] and the
    step sizes' ``dt`` [..., heads] before their bias."""
    di, cc = cfg.mamba_inner, cfg.conv_channels
    with jax.named_scope("mamba2_proj"):
        zxbcdt = y @ mp["w_in"].astype(y.dtype)
    return zxbcdt[..., :di], zxbcdt[..., di:di + cc], zxbcdt[..., di + cc:]


@jax.named_scope("mamba2_conv")
def mamba2_conv(mp, xbc):
    """``silu(conv1d(x|B|C) + bias)`` of whole sequences [B, T, C] from a
    zero tail: causal, depthwise, ``taps [K, C]``; float32 inside."""
    taps, T = mp["conv"].astype(jnp.float32), xbc.shape[1]
    K = taps.shape[0]
    # (the window stays in its own type: widened tap by tap inside the sum)
    window = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(taps[i] * window[:, i:i + T] for i in range(K))
    return jax.nn.silu(out + mp["conv_bias"].astype(jnp.float32)).astype(
        xbc.dtype)


def mamba2_heads(cfg: HybridConfig, mp, conv_out, dt):
    """After the convolution: ``x`` [..., heads, head], ``B``, ``C`` [...,
    groups, state] (head ``h`` reads group ``h // (heads / groups)``), and
    float32 the step sizes ``softplus(dt + dt_bias)`` [..., heads] (no clamp:
    the family's ``time_step_limit`` is ``(0, inf)``) and ``A = -exp(A_log)``
    [heads]."""
    n, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N, di = cfg.mamba_n_groups, cfg.mamba_state_size, cfg.mamba_inner
    lead = conv_out.shape[:-1]
    x = conv_out[..., :di].reshape(lead + (n, P))
    B = conv_out[..., di:di + G * N].reshape(lead + (G, N))
    C = conv_out[..., di + G * N:].reshape(lead + (G, N))
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + mp["dt_bias"].astype(jnp.float32))
    return x, B, C, dt, -jnp.exp(mp["A_log"].astype(jnp.float32))


def mamba2_output(cfg: HybridConfig, mp, o, z):
    """``out_proj(norm(o * silu(z)))``: the gate first, then an RMSNorm over
    each of the ``mamba_n_groups`` groups of channels, one gain a channel; o
    float32 [..., heads, head], z [..., inner]."""
    G, di = cfg.mamba_n_groups, cfg.mamba_inner
    lead = z.shape[:-1]
    with jax.named_scope("mamba2_norm"):
        g = o.reshape(lead + (di,)) * jax.nn.silu(z.astype(jnp.float32))
        g = g.reshape(lead + (G, di // G))
        g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.norm_eps)
        g = (g.reshape(lead + (di,))
             * mp["norm"].astype(jnp.float32)).astype(z.dtype)
    with jax.named_scope("mamba2_proj"):
        return g @ mp["w_out"].astype(z.dtype)


@jax.named_scope("mamba2")
def mamba2_mixer(cfg: HybridConfig, mp, y):
    """The Mamba-2 mixer of whole sequences y [B, T, H] (normed), every
    sequence from an empty state: projection, convolution, the chunked scan
    (``ops/pallas/mamba2.py::ssd_chunk``), the gated grouped norm, the
    output projection."""
    z, xbc, dt = mamba2_project(cfg, mp, y)
    x, B, C, dt, A = mamba2_heads(cfg, mp, mamba2_conv(mp, xbc), dt)
    o, _ = ssd_chunk(x, dt, A, B, C, mp["D"], chunk=cfg.mamba_chunk)
    return mamba2_output(cfg, mp, o, z)


@jax.named_scope("gdn_conv")
def causal_conv(taps, tail, x):
    """Depthwise causal convolution over the token axis. taps [K, C]; x
    [B, T, C]; tail [B, K - 1, C], the inputs before x (zeros at a
    sequence's start). Returns (out [B, T, C], the window [B, K - 1 + T, C]
    of which the last K - 1 *real* rows are the next tail)."""
    K = taps.shape[0]
    window = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    T = x.shape[1]
    out = sum(taps[i].astype(x.dtype) * window[:, i:i + T] for i in range(K))
    return out, window


def _glu_ffn(cfg: HybridConfig, mp, y):
    dt = y.dtype
    a = cfg.glu(y @ mp["wg"].astype(dt), y @ mp["wi"].astype(dt))
    return a @ mp["wo"].astype(dt)


def branch(cfg: HybridConfig, lp, post: str, x, out):
    """``x + c * out`` (``c`` the residual scale), the branch's output
    through its own norm first where the stack has ``post_norms``."""
    if cfg.post_norms:
        out = _rms(out, lp[post]["scale"], cfg.norm_eps)
    return residual(cfg, x, out)


def expert_ffn(cfg: HybridConfig, moe, experts, y, layer, valid=None,
               capacity=None):
    """The expert feed-forward of normed flat tokens y [T, H] with its
    routing counts (``parallel/moe.py::moe_ffn_share``): ``moe`` the layer's
    router, bias and shared expert, ``experts`` and ``layer`` as
    :func:`expert_block` takes them."""
    shared = dict(moe["shared"])
    if cfg.shared_gate:
        shared["gate"] = moe["shared_gate"]
    return moe_ffn_share(
        y, moe["router"], experts, cfg.gate, offset=cfg.expert_offset,
        shared=shared, valid=valid, layer=layer,
        router_bias=moe.get("router_bias"), capacity=capacity,
        glu=cfg.expert_activation)


def expert_block(cfg: HybridConfig, lp, experts, x, layer, valid=None,
                 dense=None, capacity=None):
    """``x + c * ffn(norm(x))`` on flat tokens x [T, H] (``c`` the residual
    scale): the expert block, with its routing counts beside it, or the dense
    SwiGLU (no counts): for every layer of a stack without experts, and with
    ``dense`` (one prologue layer's ``wg``, ``wi``, ``wo``) for a layer of
    the prologue. ``layer`` indexes ``experts`` (the expert layers alone);
    with ``layer`` None ``experts`` is one layer's leaves, the products
    differentiate, and ``capacity`` bounds their row buffer."""
    y = _rms(x, lp["ln2"]["scale"], cfg.norm_eps)
    if dense is not None:
        with jax.named_scope("dense_ffn"):
            return branch(cfg, lp, "ln2_post", x,
                          _glu_ffn(cfg, dense, y)), None
    if not cfg.num_experts:
        with jax.named_scope("mlp"):
            return branch(cfg, lp, "ln2_post", x,
                          _glu_ffn(cfg, lp["mlp"], y)), None
    out, counts = expert_ffn(cfg, lp["moe"], experts, y, layer, valid,
                             capacity)
    return branch(cfg, lp, "ln2_post", x, out), counts


def embed_tokens(cfg: HybridConfig, params, ids):
    dt = effective_dtype(cfg.dtype)
    x = vocab_parallel_lookup(params["embed"]["tokens"].astype(dt), ids)
    return x if cfg.scale_emb == 1.0 else x * jnp.asarray(cfg.scale_emb, dt)


def head_logits(cfg: HybridConfig, params, x):
    """Final norm, the muP divisor, the untied head; float32 logits."""
    x = _rms(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.logit_divisor != 1.0:
        x = x / jnp.asarray(cfg.logit_divisor, x.dtype)
    return jnp.einsum("...h,hv->...v", x, params["unembed"]["kernel"].astype(
        x.dtype)).astype(jnp.float32)


def full_attention(cfg: HybridConfig, q, k, v, positions, blocks=None):
    """Causal softmax attention of whole sequences, no cache: q [B, S, nq,
    d]; k, v [B, S, nkv, d]. With the sparse rule, the masked-dense form:
    compressed keys from ``k``, each query's blocks, a mask; ``blocks``
    bool [B, S, nkv, blocks of ``msa_block_size``]: the learned selector's
    choice (:func:`msa_mask`), a mask likewise."""
    B, S = q.shape[:2]
    g_ = cfg.num_heads // cfg.kv_heads
    dt = q.dtype
    scale = 1.0 / math.sqrt(cfg.head_dim)
    qh = q.reshape(B, S, cfg.kv_heads, g_, cfg.head_dim)
    ok = jnp.broadcast_to(
        (jnp.arange(S)[:, None] >= jnp.arange(S)[None, :])[None, None],
        (B, cfg.kv_heads, S, S))
    sz = cfg.sparse
    if sz is not None:
        pad = (-S) % sz.block
        nblocks = (S + pad) // sz.block
        W = nblocks * sz.per_block
        kp = jnp.pad(k, ((0, 0), (0, pad + sz.kernel), (0, 0), (0, 0)))
        at = (jnp.arange(W) * sz.stride)[:, None] + jnp.arange(sz.kernel)
        ck = block_sparse.compress_windows(kp[:, at]).astype(dt)  # [B,W,k,d]

        def one(qs, cks, ts):
            idx, _, _ = block_sparse.select_blocks(sz, qs, cks, ts, scale)
            return block_sparse.block_mask(sz, idx, ts, nblocks)

        mask = jax.vmap(one)(qh, ck, positions)          # [B, S, nkv, blocks]
        mask = jnp.repeat(mask, sz.block, axis=-1)[..., :S]
        ok = ok & jnp.moveaxis(mask, 1, 2)
    if blocks is not None:
        mask = jnp.repeat(blocks, cfg.msa.block, axis=-1)[..., :S]
        ok = ok & jnp.moveaxis(mask, 1, 2)
    s = jnp.einsum("bskgd,btkd->bkgst", qh, k).astype(jnp.float32) * scale
    s = jnp.where(ok[:, :, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1).astype(dt)
    return jnp.einsum("bkgst,btkd->bskgd", pr, v).reshape(q.shape)


# ---------------------------------------------------------------------------
# full forward (no cache): the training engine's path, and the tests
# ---------------------------------------------------------------------------

# what an expert layer of the full forward counts, summed over the layers:
# (token, expert) pairs routed to held experts, held experts that got a row,
# the fullest held expert's rows, tokens through an expert layer, and pairs
# beyond the row buffer (``share_capacity``), which add nothing
MOE_COUNTERS = ("moe_local_pairs", "moe_experts_hit", "moe_max_expert_rows",
                "moe_token_layers", "moe_dropped_pairs")


def softmax_attention(cfg: HybridConfig, q, k, v, positions, window=None,
                      blocks=None):
    """Causal softmax attention of whole sequences, no cache: the kernel
    dispatch of ``ops/attention.py`` (flash on a chip, its band grid under
    ``window``), or with a block-selecting rule the masked-dense form."""
    if cfg.sparse is not None or blocks is not None:
        return full_attention(cfg, q, k, v, positions, blocks)
    return multi_head_attention(q, k, v, causal=True, impl=cfg.attn_impl,
                                window=window)


def share_capacity(cfg: HybridConfig, tokens: int) -> Optional[int]:
    """Rows of the training path's expert buffer for ``tokens`` tokens. A
    chip that holds every expert gives every pair a row (None). A share's
    pairs are the first ``top_k * held / num_experts`` of the sorted rows in
    expectation; the buffer is one and a half times that in whole 512-row
    tiles, and never more than every pair. (A router balanced by its bias
    sends a share within a few percent of its expectation; the slack is for
    a router before it is balanced, where the fullest share seen on drawn
    weights was 1.07 of it. A step that routes more stops:
    ``HybridLM.fatal_counters``.)"""
    if cfg.held == cfg.num_experts:
        return None
    expect = tokens * cfg.top_k * cfg.held / cfg.num_experts
    rows = int(math.ceil(1.5 * expect / 512.0)) * 512
    return min(rows, -(-tokens * cfg.top_k // 128) * 128)


def _layer(cfg: HybridConfig, l: int, x, positions, lp, mp, experts, dense):
    """Layer ``l`` of the full forward on x [B, S, H]: ``lp`` its per-layer
    leaves, ``mp`` its mixer's (an expert block's: its router, bias and
    shared expert), ``experts`` its routed experts' (or None), ``dense`` its
    prologue feed-forward's (or None). Returns (x, counts
    [len(MOE_COUNTERS)] int32, load [num_experts] int32: the tokens each of
    the router's outputs got, zeros for a layer without experts)."""
    B, S, H = x.shape
    y = _rms(x, lp["ln1"]["scale"], cfg.norm_eps)
    if cfg.one_mixer:           # norm -> one mixer -> residual
        if cfg.mixer_kinds[l] is None:
            out, c = expert_ffn(cfg, mp, experts, y.reshape(B * S, H), None,
                                capacity=share_capacity(cfg, B * S))
            out = out.reshape(B, S, H)
        else:
            out, c = _mixer(cfg, l, y, positions, mp), None
        return (residual(cfg, x, out), *_counted(cfg, c, B * S))
    x = branch(cfg, lp, "ln1_post", x, _mixer(cfg, l, y, positions, mp))
    x, c = expert_block(cfg, lp, experts, x.reshape(B * S, H), None,
                        dense=dense, capacity=share_capacity(cfg, B * S))
    counts, load = _counted(cfg, c, B * S)
    return x.reshape(B, S, H), counts, load


def _counted(cfg: HybridConfig, c, tokens: int):
    """An expert layer's counts in ``MOE_COUNTERS``' order and its load
    (``c`` None, no experts: zeros)."""
    if c is None:
        return (jnp.zeros((len(MOE_COUNTERS),), jnp.int32),
                jnp.zeros((cfg.num_experts,), jnp.int32))
    return (jnp.stack([c["pairs"], c["experts_hit"], c["max_rows"],
                       jnp.int32(tokens), c["dropped"]]), c["load"])


def _mixer(cfg: HybridConfig, l: int, y, positions, mp):
    """Layer ``l``'s mixer of the full forward on its normed input y [B, S,
    H], ``mp`` its leaves, every sequence from an empty state."""
    B, S, _ = y.shape
    dt = y.dtype
    nv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    state0 = jnp.zeros((B, nv, dk, dv), jnp.float32)    # (a recurrent layer's)
    if cfg.attention_kind == "mla":
        windowed = cfg.mixer_kinds[l] == "w"
        selects = bool(cfg.index_topk) and not windowed
        q_n, q_r, latent, *cq0 = mla_project(cfg, mp, y, positions, windowed,
                                             query_latent=selects)
        sel = select_tokens(cfg, mp, y, cq0[0], positions) if selects \
            else None
        out = mla_output(mp, mla_attention(cfg, mp, q_n, q_r, latent,
                                           windowed, sel), y)
    elif cfg.is_full(l):
        window = cfg.layer_windows[l]
        with jax.named_scope("attn_window" if window else "attn_full"):
            q, k, v, gate = attn_project(cfg, mp, y, positions,
                                         windowed=window is not None)
            blocks = msa_mask(cfg, mp, y, positions) if cfg.msa else None
            a = softmax_attention(cfg, q, k, v, positions, window, blocks)
            out = attn_output(mp, a, gate)
    elif cfg.recurrent_kind == "mamba2":
        out = mamba2_mixer(cfg, mp, y)
    elif cfg.recurrent_kind == "gdn":
        mixed, z, beta, g = gdn_project(cfg, mp, y)
        tail = jnp.zeros((B, cfg.linear_conv_kernel_dim - 1,
                          cfg.conv_channels), dt)
        conv, _ = causal_conv(mp["conv"], tail, mixed)
        qf, kf, vf = gdn_heads(cfg, conv)
        o, _ = gdn_chunk(qf, kf, vf, g, beta, state0)
        out = gdn_output(cfg, mp, o, z)
    else:
        decay = cfg.lightning_decay()[sum(
            kind is False for kind in cfg.mixer_kinds[:l])]
        qf, kf, vf, z = lightning_project(cfg, mp, y, positions)
        o, _ = lightning_chunk(qf, kf, vf, jnp.broadcast_to(decay, (B, S, nv)),
                               state0)
        out = lightning_output(cfg, mp, o, z)
    return out


def hidden_states(cfg: HybridConfig, params: Dict[str, Any], tokens: jax.Array,
                  positions: Optional[jax.Array] = None):
    """tokens [B, S] -> (the last layer's output [B, S, H] before the final
    norm, the expert layers' counts ``{name: int32}`` over MOE_COUNTERS, the
    router outputs' tokens a layer ``[num_layers, num_experts]`` int32):
    every sequence from an empty state, the recurrence in its chunked form,
    each layer under the checkpoint policy (``remat`` / ``remat_policy``:
    the engine's ``activation_checkpointing`` where the model names none),
    which decides about activations; an expert layer's routing integers
    (``parallel/moe.py::ROUTING_NAME``) cross the checkpoint under any."""
    from deepspeed_tpu.runtime.activation_checkpointing import \
        checkpoint_wrapper

    p = serving_params(cfg, params)
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = embed_tokens(cfg, p, tokens)
    K = cfg.dense_layers
    # (an expert block of a one-mixer stack reads ``moe`` as its mixer)
    names = {True: "mla" if cfg.attention_kind == "mla" else "attn",
             False: cfg.recurrent_kind, "w": "wmla", None: "moe"}
    seen = dict.fromkeys(names, 0)
    counts, loads = jnp.zeros((len(MOE_COUNTERS),), jnp.int32), []

    def at(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    for l in range(cfg.num_layers):
        full = cfg.mixer_kinds[l]
        fn = functools.partial(_layer, cfg, l)
        if cfg.remat:
            fn = checkpoint_wrapper(fn, policy=cfg.remat_policy,
                                    kept_names=(ROUTING_NAME,))
        lp, mp = at(p["layers"], l), at(p[names[full]], seen[full])
        if cfg.one_mixer:
            experts = at(p["experts"], seen[None]) if full is None else None
        else:
            experts = at(p["experts"], l - K) if cfg.num_experts and l >= K \
                else None
        x, c, load = fn(x, positions, lp, mp, experts,
                        at(p["dense"], l) if l < K else None)
        seen[full] += 1
        counts = counts + c
        loads.append(load)
    # the fullest expert is a maximum a layer; summed like the others it
    # stays "rows of the fullest expert, added over the expert layers"
    return x, dict(zip(MOE_COUNTERS, counts)), jnp.stack(loads)


def apply(cfg: HybridConfig, params: Dict[str, Any], tokens: jax.Array,
          positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] -> float32 logits [B, S, V]."""
    x, _, _ = hidden_states(cfg, params, tokens, positions)
    return head_logits(cfg, serving_params(cfg, params), x)


def loss_fn(cfg: HybridConfig, params, batch) -> Tuple[jax.Array, Dict]:
    """Mean next-token cross-entropy over ``batch["input_ids"]`` [B, S+1],
    as ``models/transformer.py`` takes it: with ``tiled_logits`` the final
    norm, the head and the loss a tile of the sequence at a time, so that no
    [B, S, V] array exists. The aux carries ``ntokens`` and, of a stack with
    experts, ``counters``: what its expert layers counted, and with
    ``bias_update_rate`` ``param_deltas``: what the step adds to the
    ``router_bias`` of its expert layers (a dense layer's slot gets zero)."""
    ids = batch["input_ids"]
    inputs, labels = ids[:, :-1], ids[:, 1:]
    x, counters, loads = hidden_states(cfg, params, inputs)
    with jax.named_scope("head_loss"):
        if cfg.tiled_logits > 1:
            from deepspeed_tpu.parallel.tiled_compute import tiled_logits_loss

            def fnorm_tile(h):
                h = _rms(h, params["final_norm"]["scale"], cfg.norm_eps)
                return h if cfg.logit_divisor == 1.0 else \
                    h / jnp.asarray(cfg.logit_divisor, h.dtype)

            nll_sum, total = tiled_logits_loss(
                x, params["unembed"]["kernel"].astype(x.dtype), labels, None,
                cfg.tiled_logits, tile_transform=fnorm_tile)
        else:
            logits = head_logits(cfg, params, x)
            nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, labels[..., None], axis=-1)[..., 0]
            nll_sum, total = jnp.sum(nll), jnp.float32(nll.size)
        loss = nll_sum / total
    aux = {"loss": loss, "ntokens": total}
    if cfg.num_experts:
        aux["counters"] = counters
        if cfg.bias_update_rate and cfg.router_scoring == "sigmoid":
            if cfg.one_mixer:   # the bias lies over the expert blocks alone
                blocks = jnp.asarray([l for l, kind in enumerate(
                    cfg.mixer_kinds) if kind is None])
                aux["param_deltas"] = {"moe": {"router_bias": bias_update(
                    loads[blocks], cfg.bias_update_rate)}}
            else:
                expert_layer = jnp.arange(cfg.num_layers) >= cfg.dense_layers
                aux["param_deltas"] = {"layers": {"moe": {
                    "router_bias": jnp.where(
                        expert_layer[:, None],
                        bias_update(loads, cfg.bias_update_rate), 0.0)}}}
    return loss, aux


class HybridLM:
    """(config, init, apply, loss, logical_axes): the model object the
    engines take, as ``TransformerLM`` is for the dense block."""

    # a step that counted one of these above zero computed something else
    # than the model (pairs beyond the experts' row buffer add nothing):
    # the engine stops there
    fatal_counters = ("moe_dropped_pairs",)

    def __init__(self, config: HybridConfig):
        self.config = config

    def init(self, rng) -> Dict[str, Any]:
        return init_params(self.config, rng)

    def logical_axes(self) -> Dict[str, Any]:
        return logical_axes(self.config)

    def axes_for(self, params) -> Dict[str, Any]:
        return axes_for(self.config, params)

    def apply(self, params, tokens, positions=None):
        return apply(self.config, params, tokens, positions)

    def loss(self, params, batch):
        return loss_fn(self.config, params, batch)

    def flops_per_token(self) -> float:
        return self.config.flops_per_token()

    def num_params(self) -> int:
        return self.config.num_params()

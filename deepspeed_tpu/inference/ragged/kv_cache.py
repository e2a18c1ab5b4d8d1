"""Blocked (paged) KV cache.

Reference: ``BlockedKVCache`` (inference/v2/ragged/kv_cache.py:40) backs a
paged KV pool consumed by CUDA blocked-flash kernels. TPU re-design: the
pool is ONE jax array per model,

    kv[L, num_blocks, block_size, 2, kv_heads, head_dim]

sharded over the tp axis on ``kv_heads``. Pages are appended inside the
compiled step via scatter (see inference/model_runner.py); the host only
manages block ids (blocked_allocator.py). Static pool shape keeps every
step the same compiled program — the XLA analog of the reference
preallocating the cache up front.

A model whose attention chooses the pages it reads (``ops/block_sparse.py``)
keeps a third kind of state, the **compressed keys** its choice is scored
against ("the cache for the indexer"): the mean of every window of keys, a
few windows a page. They live here, page-addressed beside the keys they
summarise,

    compressed[L, num_blocks, windows_per_block, kv_heads, head_dim]

(window ``j`` in the page where it starts), so the block table that
addresses a sequence's pages addresses its summaries, and a page that is
freed, preempted or recomputed takes them with it: there is no second
allocator and nothing to leak. The step programs write them
(``hybrid_runner._compress_new``) and carry the array with the pool.

A pool is of one of two **kinds** (``KVCacheConfig.kind``). ``"kv"`` is the
layout above. ``"latent"`` is the pool of a model with multi-head latent
attention, which keeps one compressed vector a token in place of keys and
values a head:

    kv[L, num_blocks, block_size, lanes(latent_dim)]

no K/V pair and no head axis; a token's row is its ``latent_dim`` values
(576: the compressed vector and the one rotary key) followed by zeros up to
whole 128-lane tiles (640): the device's tiled layout pads the last axis so
whatever the shape says, and the decode kernel's page fetch can slice a pool
only at whole tiles. The block table, the allocator, the prefix chain and
the host tier address pages and do not look inside one: a latent page is a
page. The quantized rungs are not built for it and refuse by name.

A latent pool whose model selects its context with a learned indexer keeps
the **indexer keys** too (``KVCacheConfig.index_key_dim`` values a token),
page-addressed beside the latents and kept for the whole context,

    index_keys[L, num_blocks, block_size, index_key_dim]

as the compressed keys above are: same block table, same allocator, a page
that is freed takes them with it.

A model with **windowed latent layers** keeps a third pool
(:class:`WindowedLatentPool`, ``BlockedKVCache.window_pool``): rows of
another width, for the last ``window`` tokens of a sequence only,

    wkv[window layers, window_blocks, block_size, lanes(row_dim)]

with an allocator of its own. A sequence holds a **ring** of at most
``ring_pages = ceil((window + block_size - 1) / block_size) + 1`` pages of
it, whatever its length: the token at position ``p`` lives in ring entry
``(p // block_size) % ring_pages``, so a page the window has passed is
written over by the tokens ``ring_pages`` pages later (the step programs
count each such reuse: ``window_pages_recycled``). Pages are taken one at a
time as a young sequence grows and all given back when it is released. What
would have to copy, share or restore a sequence's pages without knowing the
ring (the prefix cache, the host tier, migration, hand-off, speculation)
refuses by name (:class:`WindowedPoolUnsupported`).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.ragged.blocked_allocator import BlockedAllocator


class LatentPoolUnsupported(NotImplementedError):
    """The operation is not built for a latent pool (one vector a token, no
    K/V pair, no head axis)."""


class WindowedPoolUnsupported(NotImplementedError):
    """The operation is not built for a model with a windowed pool (a ring of
    pages a sequence writes over as its window moves on)."""


@dataclasses.dataclass(frozen=True)
class WindowPoolConfig:
    layers: int
    window: int             # tokens visible, the query's own counted
    row_dim: int            # values a token keeps in one layer
    block_size: int
    num_blocks: int         # the last one is scratch
    dtype: object = jnp.bfloat16

    @classmethod
    def for_sequences(cls, seqs: int, **sizes) -> "WindowPoolConfig":
        """A pool that gives each of ``seqs`` sequences its whole ring, and
        the scratch page."""
        ring = cls(num_blocks=1, **sizes).ring_pages
        return cls(num_blocks=seqs * ring + 1, **sizes)

    @property
    def ring_pages(self) -> int:
        """Pages a sequence holds at most: the window's span, a partial page
        at either end, and one page of room for a burst's new tokens."""
        bs = self.block_size
        return -(-(self.window + bs - 1) // bs) + 1

    @property
    def pool_shape(self):
        return (self.layers, self.num_blocks, self.block_size,
                -(-self.row_dim // 128) * 128)


class WindowedLatentPool:
    """The windowed latent layers' pool: the device array and an allocator
    of its own (the last block is scratch and never handed out)."""

    def __init__(self, config: WindowPoolConfig):
        self.config = config
        self.allocator = BlockedAllocator(config.num_blocks - 1)
        self.data = jnp.zeros(config.pool_shape, config.dtype)

    @property
    def scratch_block(self) -> int:
        return self.config.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    @property
    def pages_in_use(self) -> int:
        return self.allocator.total_blocks - self.allocator.free_blocks

    def pages_for(self, num_tokens: int) -> int:
        """Ring pages a sequence of ``num_tokens`` holds."""
        c = self.config
        return min(-(-num_tokens // c.block_size), c.ring_pages)

    def grow(self, blocks: np.ndarray, num_tokens: int):
        """``blocks`` grown to what ``num_tokens`` tokens hold, or None
        where the pool has no page left."""
        need = self.pages_for(num_tokens) - len(blocks)
        if need <= 0:
            return blocks
        if need > self.allocator.free_blocks:
            return None
        return np.concatenate([blocks, self.allocator.allocate(need)])

    def free(self, blocks) -> None:
        if len(blocks):
            self.allocator.free(blocks)


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    num_layers: int
    kv_heads: int
    head_dim: int
    block_size: int = 16
    num_blocks: int = 256
    dtype: object = jnp.bfloat16
    # None = bf16 pool (bit-exact legacy program); 8 = int8 payload with one
    # fp32 scale per (layer, block, row, k/v, head) vector; 4 = packed-nibble
    # uint8 payload (two values per byte, ~1.9x more sessions at head_dim
    # 128) with the same per-vector fp32 scale; "fp8" = e4m3 payload (the
    # quality midpoint between int8 and int4) with the same per-vector scale.
    quant_bits: Optional[object] = None
    # compressed keys a page (0: none): block_size / the windows' stride
    compressed_per_block: int = 0
    # "kv": keys and values a head; "latent": one vector of ``latent_dim``
    # values a token (multi-head latent attention)
    kind: str = "kv"
    latent_dim: int = 0
    # the selector's key a token, beside a latent pool (0: no selector)
    index_key_dim: int = 0

    def __post_init__(self):
        if self.kind not in ("kv", "latent"):
            raise ValueError(f"a KV pool is of kind 'kv' or 'latent', got "
                             f"{self.kind!r}")
        if self.kind == "latent":
            if self.latent_dim <= 0:
                raise ValueError("a latent pool needs latent_dim")
            if self.quant_bits is not None or self.compressed_per_block:
                raise LatentPoolUnsupported(
                    f"a latent pool holds one bf16/float vector a token: "
                    f"quant_bits={self.quant_bits!r} and compressed keys "
                    f"are not built for it (quantized latent pages: "
                    f"ROADMAP.md)")
        elif self.index_key_dim:
            raise ValueError("indexer keys live beside a latent pool")
        if self.quant_bits not in (None, 4, 8, "fp8"):
            raise ValueError(f"kv quant_bits must be None, 4, 8 or 'fp8', "
                             f"got {self.quant_bits}")
        if self.quant_bits == 4 and self.head_dim % 2:
            raise ValueError(
                f"int4 KV storage packs two values per byte and needs an "
                f"even head_dim, got {self.head_dim}")

    @property
    def payload_width(self) -> int:
        """Last-dim extent of the pool payload: head_dim values, packed
        two-per-byte under int4; a latent pool's row, whole lane tiles."""
        if self.kind == "latent":
            return -(-self.latent_dim // 128) * 128
        return self.head_dim // 2 if self.quant_bits == 4 else self.head_dim

    @property
    def pool_shape(self):
        page = (self.payload_width,) if self.kind == "latent" else (
            2, self.kv_heads, self.payload_width)
        return (self.num_layers, self.num_blocks, self.block_size) + page

    @property
    def bytes_per_block(self) -> int:
        if self.kind == "latent":
            return (self.num_layers * self.block_size
                    * (self.payload_width + self.index_key_dim)
                    * jnp.dtype(self.dtype).itemsize)
        vecs = self.num_layers * self.block_size * 2 * self.kv_heads
        if self.quant_bits is not None:
            # int8/fp8/packed-int4 payload + fp32 scale per head vector
            return vecs * (self.payload_width + 4)
        itemsize = jnp.dtype(self.dtype).itemsize
        summaries = self.num_layers * self.compressed_per_block * self.kv_heads
        return (vecs + summaries) * self.head_dim * itemsize


@partial(jax.jit, donate_argnums=(0,))
def dstpu_kv_write_blocks(pool, idx, rows):
    """``pool[:, idx] = rows`` in the pool's own buffer (donated): a
    restore touches the blocks it writes, not the whole pool."""
    return pool.at[:, idx].set(rows.astype(pool.dtype))


class BlockedKVCache:
    """Device pool + host allocator (reference kv_cache.py:40 contract:
    reserve/free by block count; here also owns the device buffer).

    When a :class:`~deepspeed_tpu.inference.ragged.prefix_cache.PrefixCache`
    is attached (``prefix_cache`` attr), idle cached blocks are parked
    outside the allocator free list; :meth:`reclaim` evicts them back
    under memory pressure, so shared-prefix reuse never shrinks the pool
    a live sequence can reach."""

    def __init__(self, config: KVCacheConfig, mesh=None, tp_axis: str = "tp"):
        self.config = config
        self.allocator = BlockedAllocator(config.num_blocks)
        self.prefix_cache = None  # Optional[PrefixCache], attached by owner
        self.host_tier = None     # Optional[HostKVTier], attached by owner
        # Optional[RecurrentStatePool] (ragged/state_pool.py), attached by
        # the owner for a model with recurrent layers: slot-addressed state
        # beside the blocks, handed to the step programs in one pytree
        self.state_pool = None
        # the compressed keys of a model that chooses its pages (None: no
        # such model); one dtype with the pool, handed out and taken back
        # with it (``kv_state``)
        self.compressed = None
        if config.compressed_per_block:
            if config.quant_bits is not None:
                raise ValueError("compressed keys beside a quantized pool "
                                 "are not wired")
            self.compressed = jnp.zeros(
                (config.num_layers, config.num_blocks,
                 config.compressed_per_block, config.kv_heads,
                 config.head_dim), config.dtype)
        # the selector's keys of a latent pool (None: no selector), and the
        # windowed latent layers' pool (None: no such layer; attached by the
        # owner); both handed out and taken back with the pool
        self.index_keys = None
        if config.index_key_dim:
            self.index_keys = jnp.zeros(
                config.pool_shape[:3] + (config.index_key_dim,), config.dtype)
        self.window_pool = None
        shape = config.pool_shape
        # a hybrid stack's step programs take the pools as a dict, also
        # where no recurrent-state pool stands beside this one (a stack
        # without recurrent layers): the engine says so
        self.pools_as_dict = False
        quantized = config.quant_bits is not None
        # int4 packs nibbles into uint8 (the runner infers the width from
        # the pool dtype at trace time: int8 → 8, uint8 → 4, e4m3 → fp8)
        pool_dtype = (jnp.uint8 if config.quant_bits == 4
                      else jnp.float8_e4m3fn if config.quant_bits == "fp8"
                      else jnp.int8 if quantized else config.dtype)
        self.scales = None
        if config.kind == "kv" and mesh is not None and (
                tp_axis in mesh.axis_names and mesh.shape[tp_axis] > 1):
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(
                mesh, P(None, None, None, None, tp_axis, None))
            self.data = jax.device_put(jnp.zeros(shape, pool_dtype), sharding)
            if quantized:
                s_sharding = NamedSharding(
                    mesh, P(None, None, None, None, tp_axis))
                self.scales = jax.device_put(
                    jnp.ones(shape[:-1], jnp.float32), s_sharding)
        else:
            self.data = jnp.zeros(shape, pool_dtype)
            if quantized:
                self.scales = jnp.ones(shape[:-1], jnp.float32)

    @property
    def quant_bits(self) -> Optional[int]:
        return self.config.quant_bits

    @property
    def kv_state(self):
        """Device pool as the pytree the ragged forwards consume: the bare
        bf16 array when unquantized (today's program, verbatim), or a
        (payload, fp32 scales) pair when ``quant_bits`` is set (int8
        payload, or packed-nibble uint8 for 4-bit storage). With a
        recurrent-state pool attached: the dict of both pools
        (``inference/hybrid_runner.py``). A hybrid stack's ``counters`` are
        no part of it: a step program hands its own vector out and takes
        none in, so the last call's is never donated to the next."""
        if self.state_pool is not None:
            sp = self.state_pool
            state = {"kv": self.data, "state": sp.state, "conv": sp.conv}
            if self.compressed is not None:
                state["ck"] = self.compressed
            return state
        if self.pools_as_dict:
            state = {"kv": self.data}
            if self.index_keys is not None:
                state["ik"] = self.index_keys
            if self.window_pool is not None:
                state["wkv"] = self.window_pool.data
            return state
        if self.scales is None:
            return self.data
        return (self.data, self.scales)

    def set_kv_state(self, state) -> None:
        """Store the pool returned by a compiled step (inverse of
        :attr:`kv_state`). The step programs donate the pool they are
        handed, so this is the only live handle afterwards: read
        ``data`` / ``kv_state`` afresh, never keep one across a step."""
        if self.state_pool is not None:
            sp = self.state_pool
            self.data, sp.state, sp.conv = (
                state["kv"], state["state"], state["conv"])
            self.compressed = state.get("ck")
        elif self.pools_as_dict:
            self.data = state["kv"]
            self.index_keys = state.get("ik")
            if self.window_pool is not None:
                self.window_pool.data = state["wkv"]
        elif self.scales is None:
            self.data = state
        else:
            self.data, self.scales = state

    def blocks_needed(self, num_tokens: int) -> int:
        bs = self.config.block_size
        return (num_tokens + bs - 1) // bs

    # -- host-tier block I/O (ragged/kv_tier.py) -----------------------

    def read_blocks_host(self, block_ids):
        """Device→host copy of the pool contents at ``block_ids``:
        ``(payload [L, n, bs, 2, H, W], scales [L, n, bs, 2, H] | None)``
        (a latent pool: ``payload [L, n, bs, W]``) in the pool's native
        storage format — for a quantized pool this IS the compact kv_pack
        wire format, so paging it out costs no
        conversion (the disagg serialize idiom applied to the tier)."""
        idx = np.asarray(block_ids, np.int64)
        payload = np.asarray(self.data[:, idx])
        scales = (np.asarray(self.scales[:, idx])
                  if self.scales is not None else None)
        return payload, scales

    def write_blocks(self, block_ids, payload, scales=None) -> None:
        """Host→device restore of pool contents at ``block_ids`` —
        the inverse of :meth:`read_blocks_host`, bit-exact when the
        payload is pool-native. The pool is updated in place: the
        handles read from ``data`` / ``scales`` before are dead after."""
        idx = jnp.asarray(np.asarray(block_ids, np.int32))
        self.data = dstpu_kv_write_blocks(self.data, idx,
                                          jnp.asarray(payload))
        if self.scales is not None and scales is not None:
            self.scales = dstpu_kv_write_blocks(self.scales, idx,
                                                jnp.asarray(scales))

    def free(self, blocks) -> None:
        if len(blocks):
            self.allocator.free(blocks)

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    @property
    def compressed_keys_in_use(self) -> int:
        """Window slots of the pages that sequences hold (a layer): they are
        taken and given back with their pages."""
        held = self.allocator.total_blocks - self.allocator.free_blocks
        return held * self.config.compressed_per_block

    @property
    def available_blocks(self) -> int:
        """Free blocks plus idle prefix-cached blocks reclaimable via
        :meth:`reclaim` — the admission-control capacity number."""
        extra = (self.prefix_cache.evictable_blocks
                 if self.prefix_cache is not None else 0)
        return self.allocator.free_blocks + extra

    def reclaim(self, n: int) -> int:
        """Evict up to ``n`` idle prefix-cached blocks back into the
        allocator free list; returns how many were reclaimed. With a
        host tier attached, cold chains page OUT (contents parked in
        host memory under the same chain keys) instead of being
        dropped — a returning session pages back in without
        re-prefill."""
        if n <= 0 or self.prefix_cache is None:
            return 0
        if self.host_tier is not None:
            entries = self.prefix_cache.evict_entries(n)
            if entries:
                keys = [k for k, _ in entries]
                blocks = [b for _, b in entries]
                payload, scales = self.read_blocks_host(blocks)
                self.host_tier.put_chain(keys, payload, scales)
                self.allocator.free(np.asarray(blocks, np.int64))
            return len(entries)
        evicted = self.prefix_cache.evict(n)
        if evicted:
            self.allocator.free(np.asarray(evicted, np.int64))
        return len(evicted)

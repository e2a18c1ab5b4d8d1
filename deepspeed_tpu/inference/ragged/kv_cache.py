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
(:class:`WindowedLatentPool`, the store named ``"wkv"``): rows of
another width, for the last ``window`` tokens of a sequence only,

    wkv[window layers, window_blocks, block_size, lanes(row_dim)]

with an allocator of its own. A sequence holds a **ring**
(``seq.held["wkv"]``) of at most
``ring_pages = ceil((window + block_size - 1) / block_size) + 1`` pages of
it, whatever its length: the token at position ``p`` lives in ring entry
``(p // block_size) % ring_pages``, so a page the window has passed is
written over by the tokens ``ring_pages`` pages later (the step programs
count each such reuse: ``window_pages_recycled``). Pages are taken one at a
time as a young sequence grows and all given back when it is released. What
would have to copy, share or restore a sequence's pages without knowing the
ring (the prefix cache, the host tier, migration, hand-off) refuses by name
(:class:`WindowedPoolUnsupported`). Speculation is not on its list: every
model with a ring has a latent pool, whose refusal is the one pinned to win
(``tests/test_dots3_model.py``) though the pages are asked last; whether a
rejected draft's rows would cost a ring rows its window still sees is not
established, so a ring beside a pool that speculates lists it first.

:class:`BlockedKVCache` is the paged pool and the list of the stores beside
it (``ragged/store.py``): what the engine asks of a model's per-sequence
state it asks here, of all of them.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.ragged.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.ragged.store import OPS, Store

_NO_BLOCKS = np.empty(0, dtype=np.int64)


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

    def build(self) -> "WindowedLatentPool":
        return WindowedLatentPool(self)


class WindowedLatentPool(Store):
    """The windowed latent layers' pool: the device array and an allocator
    of its own (the last block is scratch and never handed out)."""

    name = "wkv"
    unsupported = frozenset({"prefix_cache", "host_tier", "migration",
                             "handoff"})

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

    def free(self, blocks) -> None:
        if len(blocks):
            self.allocator.free(blocks)

    # -- the store interface (ragged/store.py) ---------------------------

    def error(self, what: str) -> WindowedPoolUnsupported:
        return WindowedPoolUnsupported(
            f"{what} is not built for a model with windowed latent "
            "layers (a ring of pages a sequence writes over as its "
            "window moves on: ragged/kv_cache.py)")

    def arrays(self):
        return {"wkv": self.data}

    def set_arrays(self, state) -> None:
        self.data = state["wkv"]

    def can_take(self) -> bool:
        """A sequence is admitted where its whole ring has room."""
        return self.free_blocks >= self.config.ring_pages

    def take(self, seq) -> None:
        seq.held["wkv"] = _NO_BLOCKS

    def grow(self, seq, num_tokens: int) -> bool:
        """The ring grown to what ``num_tokens`` tokens hold (nothing once
        it is whole); False where the pool has no page left."""
        c, ring = self.config, seq.held["wkv"]
        need = min(-(-num_tokens // c.block_size), c.ring_pages) - len(ring)
        if need > self.allocator.free_blocks:
            return False
        if need > 0:
            seq.held["wkv"] = np.concatenate(
                [ring, self.allocator.allocate(need)])
        return True

    def give_back(self, seq) -> None:
        self.free(seq.held.pop("wkv"))

    def host_args(self, seqs, rows: int):
        """``window_table``: each batch slot's ring of pages, the scratch
        page where a slot or an entry is empty."""
        table = np.full((rows, self.config.ring_pages), self.scratch_block,
                        np.int32)
        for i, s in enumerate(seqs):
            table[i, :len(s.held["wkv"])] = s.held["wkv"]
        return {"window_table": table}

    def in_use(self):
        return {"window_pages_in_use": self.pages_in_use}


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


class BlockedKVCache(Store):
    """Device pool + host allocator (reference kv_cache.py:40 contract:
    reserve/free by block count; here also owns the device buffer), and
    the list of the model's stores (``stores``: those handed in, then the
    pages themselves).

    When a :class:`~deepspeed_tpu.inference.ragged.prefix_cache.PrefixCache`
    is attached (``prefix_cache`` attr), idle cached blocks are parked
    outside the allocator free list; :meth:`reclaim` evicts them back
    under memory pressure, so shared-prefix reuse never shrinks the pool
    a live sequence can reach."""

    name = "kv"

    def __init__(self, config: KVCacheConfig, mesh=None, tp_axis: str = "tp",
                 stores: Sequence[Store] = ()):
        self.config = config
        self.allocator = BlockedAllocator(config.num_blocks)
        self.prefix_cache = None  # Optional[PrefixCache], attached by owner
        self.host_tier = None     # Optional[HostKVTier], attached by owner
        # the stores beside the pages first: where two refuse one operation,
        # the error is the one that names what is particular to the model;
        # and a sequence grows there first (a ring takes nothing once whole)
        self.stores = [*stores, self]
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
        # the selector's keys of a latent pool (None: no selector), handed
        # out and taken back with the pool
        self.index_keys = None
        if config.index_key_dim:
            self.index_keys = jnp.zeros(
                config.pool_shape[:3] + (config.index_key_dim,), config.dtype)
        shape = config.pool_shape
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

    # -- all the stores together -----------------------------------------

    @property
    def kv_state(self) -> Dict[str, Any]:
        """Every store's device arrays as the one pytree the step programs
        take, donate and hand back: ``kv`` (bf16, or a quantized pool's
        int8 / packed-nibble uint8 / e4m3 payload with its fp32 ``scales``),
        ``ck`` and ``ik`` where the pool has them, and the other stores'
        (``state``, ``conv``, ``wkv``). A hybrid stack's ``counters`` are no
        part of it: a step program hands its own vector out and takes none
        in, so the last call's is never donated to the next."""
        return {k: v for store in self.stores
                for k, v in store.arrays().items()}

    def set_kv_state(self, state: Dict[str, Any]) -> None:
        """Inverse of :attr:`kv_state`, for what a compiled step returned.
        The step programs donate what they are handed, so these are the only
        live handles afterwards: never keep one across a step."""
        for store in self.stores:
            store.set_arrays(state)

    def store(self, name: str) -> Optional[Store]:
        return next((s for s in self.stores if s.name == name), None)

    @property
    def state_pool(self):
        """The recurrent-state store (None: the model has none)."""
        return self.store("state")

    def supports(self, op: str) -> bool:
        return all(op not in store.unsupported for store in self.stores)

    def require(self, op: str, what: str) -> None:
        """Raise the named error of the first store that cannot do ``op``
        (of ``store.OPS``)."""
        assert op in OPS, op
        for store in self.stores:
            if op in store.unsupported:
                raise store.error(what)

    def admissible(self) -> bool:
        """Whether every store has room for one more sequence (the pages
        are the engine's own count: ``InferenceEngineV2.can_schedule``)."""
        return all(store.can_take() for store in self.stores)

    def step_args(self, seqs, rows: int) -> Dict[str, jax.Array]:
        """The step programs' keyword arguments beside the block table."""
        return {k: jnp.asarray(v) for store in self.stores
                for k, v in store.host_args(seqs, rows).items()}

    def occupancy(self) -> Dict[str, int]:
        return {k: v for store in self.stores
                for k, v in store.in_use().items()}

    # -- the pages as a store (ragged/store.py) --------------------------

    @property
    def unsupported(self):
        """What reads or writes a page by its K/V heads (the wires' codecs)
        is not built for a latent page, nor is speculation, which verifies
        through the gather program."""
        if self.config.kind == "latent":
            return frozenset({"migration", "handoff", "speculation"})
        return frozenset()

    def error(self, what: str) -> LatentPoolUnsupported:
        return LatentPoolUnsupported(
            f"{what} is not built for a latent pool (one vector a "
            "token, no K/V pair, no head axis: ragged/kv_cache.py)")

    def arrays(self):
        named = {"kv": self.data, "scales": self.scales,
                 "ck": self.compressed, "ik": self.index_keys}
        return {k: v for k, v in named.items() if v is not None}

    def set_arrays(self, state) -> None:
        self.data = state["kv"]
        self.scales = state.get("scales")
        self.compressed = state.get("ck")
        self.index_keys = state.get("ik")

    def grow(self, seq, num_tokens: int) -> bool:
        """After reclaiming idle prefix-cached blocks, if need be."""
        need = self.blocks_needed(num_tokens) - len(seq.kv_blocks)
        if need <= 0:
            return True
        if need > self.free_blocks:
            self.reclaim(need - self.free_blocks)
        if need > self.free_blocks:
            return False
        seq.kv_blocks = np.concatenate([seq.kv_blocks,
                                        self.allocator.allocate(need)])
        return True

    def give_back(self, seq) -> None:
        """The cache-managed head run is unref'd, the rest freed."""
        n_shared = len(seq.prefix_keys)
        if n_shared:
            self.prefix_cache.unref(seq.prefix_keys)
            seq.prefix_keys = []
        if len(seq.kv_blocks) > n_shared:
            self.free(seq.kv_blocks[n_shared:])
        seq.kv_blocks = _NO_BLOCKS

    def in_use(self):
        """The compressed keys' window slots (a layer) of the pages that
        sequences hold: taken and given back with their pages."""
        if self.compressed is None:
            return {}
        held = self.allocator.total_blocks - self.allocator.free_blocks
        return {"compressed_keys_in_use":
                held * self.config.compressed_per_block}

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

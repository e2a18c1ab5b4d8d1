"""Ragged-batching state management (reference: inference/v2/ragged/)."""

from deepspeed_tpu.inference.ragged.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.ragged.kv_cache import (
    BlockedKVCache, KVCacheConfig, LatentPoolUnsupported, WindowPoolConfig,
    WindowedLatentPool, WindowedPoolUnsupported)
from deepspeed_tpu.inference.ragged.kv_tier import HostKVTier, PagedSession
from deepspeed_tpu.inference.ragged.prefix_cache import PrefixCache
from deepspeed_tpu.inference.ragged.sequence import (
    SequenceDescriptor, StateManager)
from deepspeed_tpu.inference.ragged.ragged_batch import RaggedBatch
from deepspeed_tpu.inference.ragged.state_pool import (
    RecurrentStatePool, StatePoolConfig, StateSnapshotUnsupported)
from deepspeed_tpu.inference.ragged.store import OPS, Store

__all__ = [
    "BlockedAllocator",
    "BlockedKVCache",
    "HostKVTier",
    "KVCacheConfig",
    "LatentPoolUnsupported",
    "PagedSession",
    "PrefixCache",
    "SequenceDescriptor",
    "StateManager",
    "RaggedBatch",
    "RecurrentStatePool",
    "StatePoolConfig",
    "StateSnapshotUnsupported",
    "Store",
    "OPS",
    "WindowPoolConfig",
    "WindowedLatentPool",
    "WindowedPoolUnsupported",
]

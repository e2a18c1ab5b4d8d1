"""Recurrent-state pool: the second kind of per-sequence state.

A model with recurrent layers (``models/hybrid.py``) keeps, for every live
sequence and every recurrent layer, a state that is *replaced* at each token
and does not grow: the recurrence's matrix for each head (float32; the delta
rule's or lightning attention's) and the last ``K - 1`` inputs of the causal
convolution (none for a mixer without one: ``conv_taps`` 1, an empty array). It is not block-addressed:
a sequence owns one **slot** for its whole life (``seq.held["state"]``),

    state[layers, slots + 1, heads, key_dim, value_dim]   float32
    conv [layers, slots + 1, K - 1, channels]             the serving dtype

The last slot is scratch (rows of a step that hold no sequence read and write
it), as the paged pool's last block is. The pool is one of the stores
(``ragged/store.py``) of the object that owns the paged pool
(``BlockedKVCache.stores``) and travels with it: the step programs take both
in one donated pytree and update them in place.

A slot is taken and **zeroed** at admission and given back when the sequence
is released (finished, flushed, or preempted for recompute). There is no
snapshot of a slot yet: whatever would have to copy a sequence's state
(prefix cache, host tier, migration, hand-off, speculation's roll-back) is
switched off or refused for such a model (:class:`StateSnapshotUnsupported`:
every one of ``store.OPS``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.ragged.store import OPS, Store


class StateSnapshotUnsupported(NotImplementedError):
    """The operation would have to copy or roll back a sequence's recurrent
    state, which has no snapshot yet."""


@dataclasses.dataclass(frozen=True)
class StatePoolConfig:
    layers: int
    slots: int
    heads: int
    key_dim: int
    value_dim: int
    conv_taps: int
    conv_channels: int
    dtype: object = jnp.bfloat16

    @property
    def bytes_per_slot(self) -> int:
        return self.layers * (
            self.heads * self.key_dim * self.value_dim * 4
            + (self.conv_taps - 1) * self.conv_channels
            * jnp.dtype(self.dtype).itemsize)

    def build(self) -> "RecurrentStatePool":
        return RecurrentStatePool(self)


@partial(jax.jit, donate_argnums=(0, 1))
def dstpu_state_zero_slot(state, conv, slot):
    """Zero one slot of both arrays in their own buffers."""
    return (state.at[:, slot].set(0.0), conv.at[:, slot].set(0))


class RecurrentStatePool(Store):
    name = "state"
    unsupported = frozenset(OPS)

    def __init__(self, config: StatePoolConfig):
        c = self.config = config
        self.state = jnp.zeros((c.layers, c.slots + 1, c.heads, c.key_dim,
                                c.value_dim), jnp.float32)
        self.conv = jnp.zeros((c.layers, c.slots + 1, c.conv_taps - 1,
                               c.conv_channels), c.dtype)
        self._free: List[int] = list(range(c.slots - 1, -1, -1))

    @property
    def scratch_slot(self) -> int:
        return self.config.slots

    @property
    def total_slots(self) -> int:
        return self.config.slots

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def slots_in_use(self) -> int:
        return self.config.slots - len(self._free)

    def allocate(self) -> int:
        """A zeroed slot; MemoryError when none is free (admission counts
        slots first, as it counts blocks)."""
        if not self._free:
            raise MemoryError("no free recurrent-state slot")
        slot = self._free.pop()
        self.state, self.conv = dstpu_state_zero_slot(
            self.state, self.conv, jnp.int32(slot))
        return slot

    def free(self, slot: int) -> None:
        if not 0 <= slot < self.config.slots or slot in self._free:
            raise ValueError(f"slot {slot} is not in use")
        self._free.append(slot)

    # -- the store interface (ragged/store.py) ---------------------------

    def error(self, what: str) -> StateSnapshotUnsupported:
        return StateSnapshotUnsupported(
            f"{what} needs a snapshot of each sequence's recurrent "
            "state (ragged/state_pool.py), which does not exist yet: "
            "not available for a model with recurrent layers")

    def arrays(self):
        return {"state": self.state, "conv": self.conv}

    def set_arrays(self, state) -> None:
        self.state, self.conv = state["state"], state["conv"]

    def can_take(self) -> bool:
        return bool(self._free)

    def take(self, seq) -> None:
        seq.held[self.name] = self.allocate()

    def give_back(self, seq) -> None:
        slot = seq.held.pop(self.name, None)
        if slot is not None:
            self.free(slot)

    def host_args(self, seqs, rows: int):
        """``state_slots``: each batch slot's slot here, scratch where
        empty."""
        slots = np.full(rows, self.scratch_slot, np.int32)
        for i, s in enumerate(seqs):
            slots[i] = s.held[self.name]
        return {"state_slots": slots}

    def in_use(self):
        return {"state_slots": self.total_slots,
                "state_slots_in_use": self.slots_in_use}

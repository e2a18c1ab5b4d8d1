"""What one kind of layer keeps per sequence: the store interface.

Pages of keys and values or of latents (``kv_cache.BlockedKVCache``), a slot
of recurrent state (``state_pool.RecurrentStatePool``), a ring of pages for
the last ``window`` tokens (``kv_cache.WindowedLatentPool``). The runner says
which a configuration needs (``store_specs``: a spec is anything with
``build() -> Store``), ``BlockedKVCache.stores`` holds them, and the engine,
the scheduler and the sequences' manager ask each the same questions without
knowing its kind. A new kind of per-sequence state costs a store class, its
spec in the runner, and its rows in ``tests/test_store_interface.py``.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet

import numpy as np

# what copies, shares, restores or rolls back a sequence's state from outside
# a step: a prefix-cache hit, host-tier parking, the session-migration wire,
# the disagg hand-off wire, speculation's rejected drafts
OPS = ("prefix_cache", "host_tier", "migration", "handoff", "speculation")


class Store:
    """The questions, with the answers of a store that keeps nothing.

    ``arrays`` / ``set_arrays``: the device arrays, by the names the step
    programs know them under, into the programs' one donated pytree and out
    of what a program returned (then the only live handles). ``can_take``:
    whether one more sequence is admissible; ``take`` at admission; ``grow``
    to ``num_tokens`` tokens (False where no room is left; what was taken
    stays); ``give_back`` at release, all of it. ``host_args``: a step
    program's keyword arguments beside the block table (batch slot ``i`` of
    ``rows`` is ``seqs[i]``, the rest are empty). ``error``: the store's
    named error for a member of OPS it cannot do (``what`` names the
    operation as its caller knows it). ``in_use``: occupancy, under the keys
    ``stats`` shows it by."""

    #: key of what a sequence holds here, in ``SequenceDescriptor.held`` (the
    #: pages' blocks: ``seq.kv_blocks``, every program's block table)
    name: str = ""
    #: the members of OPS this store cannot do
    unsupported: FrozenSet[str] = frozenset()

    def error(self, what: str) -> Exception:
        raise NotImplementedError

    def arrays(self) -> Dict[str, Any]:
        return {}

    def set_arrays(self, state: Dict[str, Any]) -> None:
        pass

    def can_take(self) -> bool:
        return True

    def take(self, seq) -> None:
        pass

    def grow(self, seq, num_tokens: int) -> bool:
        return True

    def give_back(self, seq) -> None:
        pass

    def host_args(self, seqs, rows: int) -> Dict[str, np.ndarray]:
        return {}

    def in_use(self) -> Dict[str, int]:
        return {}

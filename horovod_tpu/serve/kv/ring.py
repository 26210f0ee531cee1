"""Window layers' allocator: a ring of blocks a slot.

A window layer keeps the last ``window`` positions of a request, so a
slot never needs more than ``window / block + 1`` blocks of the window
layers' own device pools, whatever the context.  :class:`RingPool`
hands a slot those blocks one by one as the request reaches them and
keeps the slot's row of the *ring table* (``[slots, ring + 1]``, the
last column the trash column, as in :mod:`.pool`): the block of
positions ``[i * block, (i + 1) * block)`` is column ``i mod ring``.
Once a row holds ``ring`` blocks it changes no more: block ``i`` is
written over block ``i - ring``, which lies wholly behind the window
(``blocks_given_back`` counts those — what a chain would have kept and
a ring does not; nothing moves on the free list).  A ring holds no
beginning another request could join, so nothing here is shared,
indexed or copied.

Host bookkeeping only, no jax; ``release`` may arrive from another
thread than the batcher's (a cancel), so every mutation holds
``_lock``.
"""

from __future__ import annotations

import collections
import threading
from typing import Dict

from .pool import KVPoolExhaustedError, TRASH_BLOCK


class RingPool:
    def __init__(self, num_blocks: int, table,
                 bytes_per_block: int) -> None:
        self.num_blocks = int(num_blocks)         # block 0 is the trash
        self.ring = table.shape[1] - 1
        self.bytes_per_block = int(bytes_per_block)
        self._table = table                       # guarded-by: _lock
        self._lock = threading.Lock()
        self._free: "collections.deque" = collections.deque(
            range(1, self.num_blocks))            # guarded-by: _lock
        # slot -> blocks of positions the slot's request has begun
        self._begun: Dict[int, int] = {}          # guarded-by: _lock
        self.blocks_given_back = 0                # guarded-by: _lock

    def begin(self, slot: int) -> None:
        """An empty ring for ``slot``'s new request."""
        with self._lock:
            if slot in self._begun:
                raise RuntimeError(f"slot {slot} already has a ring")
            self._begun[slot] = 0

    def reach(self, slot: int, last: int) -> None:
        """``slot``'s ring as far as the block of positions ``last``: a
        new block for each of the first ``ring`` blocks of positions;
        from then on the column's own block is written over.  Nothing
        for a slot without a request (released meanwhile)."""
        with self._lock:
            begun = self._begun.get(slot)
            if begun is None or last < begun:
                return
            for i in range(begun, min(last + 1, self.ring)):
                if not self._free:
                    raise KVPoolExhaustedError(
                        f"all {self.num_blocks - 1} window-layer blocks "
                        f"are held by active requests")
                self._table[slot, i] = self._free.popleft()
            self.blocks_given_back += max(
                0, last + 1 - max(begun, self.ring))
            self._begun[slot] = last + 1

    def release(self, slot: int) -> None:
        with self._lock:
            held = min(self._begun.pop(slot, 0), self.ring)
            self._free.extend(int(b) for b in self._table[slot, :held])
            self._table[slot, :] = TRASH_BLOCK

    def blocks_in_use(self) -> int:
        with self._lock:
            return sum(min(n, self.ring) for n in self._begun.values())

    def stats(self) -> Dict:
        with self._lock:
            return {
                "kv_window_blocks_total": self.num_blocks - 1,
                "kv_window_blocks_in_use": sum(
                    min(n, self.ring) for n in self._begun.values()),
                "kv_window_ring_blocks": self.ring,
                "kv_window_blocks_given_back": self.blocks_given_back,
                "kv_window_bytes_per_block": self.bytes_per_block,
            }

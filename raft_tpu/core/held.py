"""Values held with a device array.

An index computes some values once from an array that never changes (CAGRA's
seed constants, brute force's row norms) and reuses them in every search.
They are keyed by the array's identity and the array is held weakly: an array
that is replaced (moved to another device, reloaded, rebuilt by a compaction)
is a new key, and the values of an array are freed with it.
"""

from __future__ import annotations

import collections
import threading
import weakref

import jax


def concrete(x) -> bool:
    """A device array with values (not a tracer, not a host array that
    could be written in place): what a value may be held for."""
    return isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)


class HeldByArray:
    """Entries per array, each array's in insertion (or use) order. Callers
    read and fill :meth:`_entries` under ``self._lock``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._by_data: dict[int, tuple] = {}  # id -> (weakref, OrderedDict)

    def _forget(self, ident: int):
        def drop(ref):
            # runs as the array is freed, before its id can be reused
            slot = self._by_data.get(ident)
            if slot is not None and slot[0] is ref:
                self._by_data.pop(ident, None)

        return drop

    def _entries(self, array) -> collections.OrderedDict:
        """The entries held for ``array``, a new empty slot if it has
        none (call under ``self._lock``)."""
        ident = id(array)
        slot = self._by_data.get(ident)
        if slot is None or slot[0]() is not array:
            slot = (weakref.ref(array, self._forget(ident)),
                    collections.OrderedDict())
            self._by_data[ident] = slot
        return slot[1]

    def held(self, array) -> list:
        """The values held for ``array``, creating nothing."""
        with self._lock:
            slot = self._by_data.get(id(array))
            if slot is None or slot[0]() is not array:
                return []
            return list(slot[1].values())

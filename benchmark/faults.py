"""Faults of a served index's write path, for the checks that must read
``correct: false``: each wraps a built index (``indexes/<kind>.py``'s
``build`` result) so that one kind of write is acknowledged and never
applied. Reads go to the program's own search unchanged.

- ``insert_lost``: upserts return their ids and write nothing, so the
  delta stays empty and reads see the sealed index only;
- ``delete_lost``: deletes return and write nothing, so reads ignore the
  tombstones.

    python3 benchmark/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 --fault insert_lost
"""

from __future__ import annotations

import numpy as np


class _Writes:
    """A mutable's write path with ``drop`` ("upsert" or "delete")
    acknowledged and dropped; everything else passes through."""

    def __init__(self, mutable, drop: str):
        self._mutable, self._drop = mutable, drop

    def upsert(self, rows, ids=None, res=None):
        if self._drop == "upsert":
            return np.asarray(ids, np.int64)
        return self._mutable.upsert(rows, ids, res=res)

    def delete(self, ids) -> int:
        if self._drop == "delete":
            return 0
        return self._mutable.delete(ids)

    def __getattr__(self, name):
        return getattr(self._mutable, name)


def _wrap(built: dict, drop: str) -> dict:
    from raft_tpu.neighbors._hooks import make_hook

    hook = built["hook"]
    fn = make_hook(hook, hook.kind, hook.dim, hook.query_dtype)
    fn.mutable = _Writes(hook.mutable, drop)
    return dict(built, hook=fn)


def insert_lost(built: dict) -> dict:
    return _wrap(built, "upsert")


def delete_lost(built: dict) -> dict:
    return _wrap(built, "delete")


FAULTS = {"insert_lost": insert_lost, "delete_lost": delete_lost}

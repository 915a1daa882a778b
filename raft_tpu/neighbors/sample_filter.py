"""Candidate sample filters for ANN searches.

Re-design of raft::neighbors::filtering (cpp/include/raft/neighbors/
sample_filter_types.hpp — none_ivf_sample_filter, bitset_filter). The
reference evaluates a device predicate per (query, sample) inside the scan
kernels; the TPU formulation is a boolean keep-mask over global dataset ids,
gathered per candidate and fused into the score epilogue (masked-out
candidates score ±inf and can never win select_k).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..core import tracing

__all__ = ["NoFilter", "BitsetFilter", "resolve_filter", "apply_id_filter"]


class NoFilter:
    """Keep everything (ref: none_ivf_sample_filter)."""

    mask = None


class BitsetFilter:
    """Keep dataset row ``i`` iff ``bitset[i]`` (ref: bitset_filter,
    sample_filter_types.hpp — a packed bitset over dataset indices)."""

    def __init__(self, bitset):
        self.mask = jnp.asarray(bitset, bool)


def resolve_filter(f):
    """Normalize a filter argument to a keep-mask array or None."""
    if f is None or isinstance(f, NoFilter):
        return None
    if isinstance(f, BitsetFilter):
        return f.mask
    return jnp.asarray(f, bool)


def validate_filter_covers(index, keep_mask) -> None:
    """Check the keep-mask covers every stored id. IVF indexes hold explicit
    ``list_ids`` whose max needs a device reduction + host sync, so it is
    memoized on the index instance (invalidated by extend(), which returns a
    new index object); dense row-id indexes (cagra, whose stored ids ARE the
    dataset row offsets) cover ``[0, size)`` by construction."""
    from ..core.errors import expects

    max_id = getattr(index, "_max_id_cache", None)
    if max_id is None:
        ids = getattr(index, "list_ids", None)
        max_id = index.size - 1 if ids is None else int(jnp.max(ids))
        index._max_id_cache = max_id
    expects(
        keep_mask.shape[0] > max_id,
        "sample filter length %d must cover max stored id %d",
        keep_mask.shape[0],
        max_id,
    )


def apply_id_filter(scores, ids, keep_mask, select_min: bool):
    """Fused mask epilogue: invalidate scores whose candidate id is filtered.

    ``ids`` may contain −1 padding, which stays invalid. Device stage
    ``sample_filter``.
    """
    with tracing.scope("sample_filter"):
        bad = -jnp.inf if not select_min else jnp.inf
        valid = ids >= 0
        kept = jnp.take(keep_mask, jnp.clip(ids, 0), axis=0) & valid
        return jnp.where(kept, scores, bad)

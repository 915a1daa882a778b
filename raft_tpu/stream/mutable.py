"""MutableIndex: an LSM-style mutable lifecycle over any sealed ANN index.

Every index in :mod:`raft_tpu.neighbors` is immutable-at-best after build
(``extend`` appends; nothing deletes), yet a production corpus churns —
live traffic upserts and deletes rows continuously. The standard answer is
the fresh/sealed split of FreshDiskANN (Singh et al. 2021), which is the
memtable/compaction shape of LSM-trees (O'Neil et al. 1996) applied to ANN:

- **Delta memtable** — recent writes land in a fixed-capacity row buffer
  scanned by the exact fused-kNN at serve time. The buffer is exposed to
  the device at power-of-two *bucket* sizes (8, 16, ..., ``delta_capacity``
  — the same shape discipline as :mod:`raft_tpu.serve.batcher`'s query
  buckets), so delta growth never compiles on the hot path once
  :meth:`MutableIndex.warm` has touched the ladder.
- **Tombstones** — deletes flip per-slot alive bits. IVF kinds carry them
  in the index's own list layout: a per-epoch live view of the sealed
  index whose ``list_ids`` hold -1 at every dead slot, which the scan's
  padding mask (``ids < 0`` → ±inf) already drops — no per-candidate
  gather. Brute-force and CAGRA (stored ids are row offsets) filter the
  sealed index through its module's ``sample_filter=`` epilogue; the delta
  applies the alive mask to its exact scan. ``upsert`` =
  tombstone-the-old-slot + insert-new, so an id is live in exactly one
  physical slot at a time.
- **Unified search** — sealed(filtered) and delta scans merge through the
  existing ``select_k`` dispatch; slot-local ids translate to stable global
  ids through a device-resident id map. Results are indistinguishable from
  a fresh build over the live rows (bit-equal ids for exact sealed kinds,
  recall-parity for quantized ones — pinned by ``tests/test_stream.py``).
- **Compaction** (:mod:`raft_tpu.stream.compactor`) folds delta+tombstones
  into a new sealed index off the hot path — ``extend`` where the sealed
  kind supports it (IVF-Flat/IVF-PQ), full rebuild where it does not
  (CAGRA, brute-force) or when tombstones must actually be reclaimed — and
  swaps it in atomically. Writes that land during a fold are never lost:
  the fold consumes a snapshot *prefix* of the append-only delta, the
  remainder carries over, and the swap recomputes every alive bit from the
  live tombstone state.

Thread-safety: all mutations run under one lock; searches take a handle
snapshot and run lock-free (device arrays are immutable once published to a
state). :meth:`searcher` returns a serving hook pinned to the CURRENT state
object, which is exactly what :class:`raft_tpu.serve.IndexRegistry` leases:
after a compaction swap, in-flight flushes drain on the pinned (frozen)
pre-compaction state while new flushes pick up the published successor.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from dataclasses import dataclass
from typing import Callable

import numpy as np

from ..core.errors import RaftError, expects
from ..core.resources import default_resources
from ..distance.types import DistanceType, resolve_metric
from ..obs import dispatch as obs_dispatch
from ..obs import mem as obs_mem
from ..obs import metrics
from ..serve.errors import OverloadedError
from ..testing import faults
from .tiered import TieredStore, TierPolicy

__all__ = ["MutableIndex", "DeltaFullError", "DELTA_MIN_BUCKET",
           "delta_buckets", "save", "load"]

# NOTE on sharding: raft_tpu/stream/sharded.py composes S of these (one per
# mesh shard, device-pinned via ``device=`` with shard-owned global ids via
# ``ids=``) into a scatter-gather ShardedMutableIndex; the per-shard scan
# halves of :func:`_search_state` are exposed as :func:`_scan_state` so the
# sharded tier can merge ALL shards' sealed+delta candidates through ONE
# ``select_k`` dispatch instead of S per-shard merges.

# floor of the delta bucket ladder: an empty delta still scans one fully
# masked bucket of this size, so "delta empty" and "delta tiny" share a
# program instead of forking the hot path
DELTA_MIN_BUCKET = 8


class DeltaFullError(OverloadedError):
    """The delta memtable is at capacity — writes shed load exactly like the
    serve queue bound (same admission-control taxonomy: this IS an
    ``OverloadedError``). Compact, or attach a
    :class:`raft_tpu.stream.Compactor` whose delta-fill watermark folds the
    memtable before it fills."""


def delta_buckets(capacity: int) -> tuple[int, ...]:
    """The delta memtable's power-of-two device-shape ladder
    ``(8, 16, ..., capacity)``."""
    expects(capacity >= DELTA_MIN_BUCKET
            and (capacity & (capacity - 1)) == 0,
            "delta_capacity must be a power of two >= %d, got %d",
            DELTA_MIN_BUCKET, capacity)
    out, b = [], DELTA_MIN_BUCKET
    while b <= capacity:
        out.append(b)
        b *= 2
    return tuple(out)


def _bucket_for(n: int, capacity: int) -> int:
    b = DELTA_MIN_BUCKET
    while b < n:
        b *= 2
    return min(b, capacity)


def check_upsert_ids(ids, r: int) -> np.ndarray:
    """Validate caller-chosen upsert ids — the ONE id contract shared by
    the plain, sharded and replicated write paths (shape ``(r,)``,
    unique within the call, ``>= 0``, int32-representable for the device
    id maps). Returns the int64 array."""
    gids = np.asarray(ids, np.int64).reshape(-1)
    expects(gids.shape == (r,), "ids must match rows (%d)", r)
    expects(np.unique(gids).size == r,
            "upsert ids must be unique within one call")
    expects(int(gids.min()) >= 0, "ids must be >= 0")
    expects(int(gids.max()) < 2 ** 31 - 1,
            "ids must fit int32 (device id maps are int32)")
    return gids


# -- metrics (catalogue: docs/observability.md) ------------------------------

@functools.lru_cache(maxsize=None)
def _g_delta_fill():
    return metrics.gauge("raft_tpu_stream_delta_fill",
                         "delta memtable fill fraction (rows / capacity)")


@functools.lru_cache(maxsize=None)
def _g_delta_rows():
    return metrics.gauge("raft_tpu_stream_delta_rows",
                         "rows currently in the delta memtable")


@functools.lru_cache(maxsize=None)
def _g_tombstone():
    return metrics.gauge(
        "raft_tpu_stream_tombstone_ratio",
        "dead sealed slots / sealed slots (reclaimable by rebuild compaction)")


@functools.lru_cache(maxsize=None)
def _c_upserts():
    return metrics.counter("raft_tpu_stream_upserts_total",
                           "rows upserted into the delta memtable")


@functools.lru_cache(maxsize=None)
def _c_deletes():
    return metrics.counter("raft_tpu_stream_deletes_total",
                           "live rows tombstoned by delete/upsert")


@functools.lru_cache(maxsize=None)
def _c_delta_full():
    return metrics.counter("raft_tpu_stream_delta_full_total",
                           "writes refused because the delta memtable is full")


@functools.lru_cache(maxsize=None)
def _c_sealed_filter():
    return metrics.counter(
        "raft_tpu_stream_sealed_filter_total",
        "sealed scans by how tombstones were applied: layout=list (-1 ids "
        "in the live list view) or ids (keep-mask over slot ids)")


# -- per-kind dispatch -------------------------------------------------------

def _resolve_kind(sealed):
    from ..neighbors import brute_force, cagra, ivf_flat, ivf_pq

    for kind, mod, cls in (("brute_force", brute_force, brute_force.BruteForce),
                           ("ivf_flat", ivf_flat, ivf_flat.IvfFlatIndex),
                           ("ivf_pq", ivf_pq, ivf_pq.IvfPqIndex),
                           ("cagra", cagra, cagra.CagraIndex)):
        if isinstance(sealed, cls):
            return kind, mod
    raise RaftError(
        f"MutableIndex cannot wrap {type(sealed).__name__!r} (expected "
        "BruteForce, IvfFlatIndex, IvfPqIndex or CagraIndex)")


def _sealed_meta(kind, sealed):
    """(n_rows, dim, metric, metric_arg, data_kind) of a sealed index."""
    if kind == "brute_force":
        expects(sealed.dataset is not None, "sealed brute_force index is not built")
        n, d = sealed.dataset.shape
        dk = str(sealed.dataset.dtype)
        if dk not in ("int8", "uint8"):
            dk = "float32"
        return n, d, resolve_metric(sealed.metric), float(sealed.metric_arg), dk
    return (sealed.size, sealed.dim, sealed.metric, 2.0, sealed.data_kind)


def _store_rows(store) -> np.ndarray | None:
    """The raw rows of a retained store as a host array: a plain ``hbm``
    store IS the array, a :class:`~raft_tpu.stream.tiered.TieredStore`
    exposes its cold copy — compaction folds, drift sampling and
    serialization all read rows through this one seam."""
    if store is None:
        return None
    return store.host_view() if isinstance(store, TieredStore) else store


def _recover_store(kind, sealed, data_kind):
    """Raw live rows in the SERVING dtype, when the sealed kind stores them
    (brute-force/CAGRA keep the dataset; uint8 CAGRA holds it shifted into
    the s8 domain and is unshifted here). IVF kinds store lists/codes, not
    rows — their store must be supplied via ``dataset=``."""
    import jax

    if kind == "brute_force":
        return np.asarray(jax.device_get(sealed.dataset))
    if kind == "cagra":
        ds = np.asarray(jax.device_get(sealed.dataset))
        if data_kind == "uint8":
            return (ds.astype(np.int16) + 128).astype(np.uint8)
        return ds
    return None


def _sealed_search(cfg, view, queries, k, res=None):
    """Search the sealed side through its published view (see
    :func:`_publish_sealed`): ``(live list view, None)`` for IVF kinds,
    ``(sealed, slot keep-mask)`` otherwise. Each kind's module
    ``search`` reads what its index holds (brute force: its row norms)."""
    sealed, keep_mask = view
    if metrics._enabled:
        _c_sealed_filter().inc(1, name=cfg.name,
                               layout="list" if keep_mask is None else "ids")
    return cfg.module.search(cfg.search_params, sealed, queries, k,
                             sample_filter=keep_mask, res=res)


# -- jitted merge pieces -----------------------------------------------------

@functools.cache
def _jnp():
    import jax.numpy as jnp

    return jnp


@functools.cache
def _jits():
    import jax
    import jax.numpy as jnp

    from ..core import tracing
    from ..matrix.select_k import _select_k

    @jax.jit
    def map_ids(ids, id_map):
        with tracing.scope("stream.map_ids"):
            g = jnp.take(id_map, jnp.clip(ids, 0), axis=0)
            return jnp.where(ids >= 0, g, -1)

    @functools.partial(jax.jit, static_argnames=("k", "select_min"))
    def merge(sealed_d, sealed_i, delta_d, delta_i, k: int, select_min: bool):
        with tracing.scope("stream.merge"):
            d = jnp.concatenate([sealed_d, delta_d], axis=1)
            i = jnp.concatenate([sealed_i, delta_i], axis=1)
            dv, iv = _select_k(d, i, k, select_min)
            # underfilled slots keep the shared sentinel: id -1 at ±inf
            return dv, jnp.where(jnp.isinf(dv), -1, iv)

    return map_ids, merge


def _map_ids(ids, id_map):
    """Translate slot-local ids to global ids; -1 sentinels pass through."""
    return _jits()[0](ids, id_map)


def _merge(sealed_d, sealed_i, delta_d, delta_i, k, select_min):
    obs_dispatch.note(1)
    return _jits()[1](sealed_d, sealed_i, delta_d, delta_i, int(k),
                      bool(select_min))


# -- state ------------------------------------------------------------------

@dataclass(frozen=True)
class _Config:
    """Immutable wrap-time configuration shared by every state epoch."""

    kind: str
    module: object
    search_params: object
    metric: DistanceType
    metric_arg: float
    select_min: bool
    dim: int
    data_kind: str
    query_dtype: str
    name: str
    # optional device pin (the sharded tier places each shard's arrays —
    # and therefore its compute — on its own mesh device); None = default
    device: object = None


def _dev_put(cfg: "_Config", x):
    """Upload a host array to the config's device: COMMITTED when a device
    pin is set (committed inputs make every downstream program run on the
    shard's device — jax's placement-follows-committed-args rule is the
    whole scatter mechanism), plain ``jnp.asarray`` otherwise (identical to
    the pre-sharding behavior, bit for bit)."""
    if cfg.device is None:
        return _jnp().asarray(x)
    import jax

    return jax.device_put(x, cfg.device)


class _StreamState:
    """One epoch of mutable-index state. The big arrays (sealed index,
    id map) are frozen per epoch — compaction builds a successor and swaps —
    while the tombstone/delta device handles are REPLACED (never mutated in
    place) on every write, so a search that snapshots the handles is always
    internally consistent without holding the write lock."""

    __slots__ = ("cfg", "sealed", "id_map", "sealed_alive", "sealed_dead_n",
                 "store", "delta", "delta_ids", "delta_alive", "delta_n",
                 "delta_oldest_at", "epoch", "id_map_dev", "sealed_view",
                 "live_ids", "slot_pos", "delta_view", "store_dev", "mem",
                 "__weakref__")

    def __init__(self, cfg: _Config):
        self.cfg = cfg
        self.delta_n = 0
        self.delta_oldest_at = None
        self.epoch = 0
        # incremental dead-sealed-slot count (== n - sealed_alive.sum(),
        # maintained at tombstone/swap time): stats() and the gauge updates
        # on the write path must not scan the whole bitset per write — the
        # sharded tier aggregates stats across S shards on EVERY routed
        # upsert/delete
        self.sealed_dead_n = 0
        # device copy of the retained row store, built lazily on the first
        # exact_search of an epoch (the recall canary's shadow oracle) —
        # never on the serving hot path
        self.store_dev = None
        # obs.mem ledger token for this epoch's stream-owned arrays (delta
        # view, masks, id map, store) — auto-releases when the state is
        # collected, which is the retirement-audit hook for pre-compaction
        # epochs
        self.mem = None


def _np_dtype(query_dtype: str):
    return {"float32": np.float32, "int8": np.int8,
            "uint8": np.uint8}[query_dtype]


def _list_layout(sealed, n: int):
    """``(flat list_ids host copy, slot → flat position)`` of an IVF
    index — the per-epoch base of its live view — or None for kinds whose
    stored ids are row offsets (brute force, CAGRA). Sealed ids are the
    dense slot range ``0..n-1`` (the wrap contract), so the map is one
    O(n) scatter."""
    ids = getattr(sealed, "list_ids", None)
    if ids is None:
        return None
    import jax

    flat = np.array(jax.device_get(ids), np.int32).reshape(-1)
    valid = np.flatnonzero(flat >= 0)
    pos = np.empty(n, np.int64)
    pos[flat[valid]] = valid
    return flat, pos


def _init_sealed_view(st: _StreamState, layout) -> None:
    """Build and publish one epoch's sealed search view from ``st.sealed``
    and ``st.sealed_alive`` (wrap and compaction swap); ``layout`` is
    :func:`_list_layout` of ``st.sealed``, which the swap computes off the
    write lock."""
    if layout is None:
        st.live_ids = st.slot_pos = None
    else:
        st.live_ids, st.slot_pos = layout
        st.live_ids[st.slot_pos[~st.sealed_alive]] = -1
    _publish_sealed(st)


def _kill_listed(st: _StreamState, slots) -> None:
    """Write -1 at dead slots' positions of the host live ids. The array
    the published view was uploaded from is never written again (on the
    CPU an upload may alias it; on the chip a transfer may still read it):
    the kill lands in a fresh copy, which the caller publishes."""
    if st.live_ids is not None:
        st.live_ids = st.live_ids.copy()
        st.live_ids[st.slot_pos[slots]] = -1


def _publish_sealed(st: _StreamState) -> None:
    """Publish the sealed side as ONE handle, ``st.sealed_view``: for IVF
    kinds ``(live view, None)`` — the sealed index with ``list_ids``
    replaced by the live ids, a pytree of the same shapes, so it runs the
    sealed index's own compiled program and the scan's ``ids < 0`` mask
    drops the tombstones; otherwise ``(sealed, keep-mask over slots)``
    through the module's ``sample_filter=``. ``st.sealed`` itself stays
    untouched (compaction and serialization read it)."""
    cfg = st.cfg
    if st.live_ids is None:
        st.sealed_view = (st.sealed, _dev_put(cfg, st.sealed_alive))
        return
    ids = _dev_put(cfg, st.live_ids.reshape(st.sealed.list_ids.shape))
    st.sealed_view = (dataclasses.replace(st.sealed, list_ids=ids), None)


def _refresh_delta(st: _StreamState, capacity: int,
                   mask_only: bool = False) -> None:
    b = _bucket_for(st.delta_n, capacity)
    keep = st.delta_alive[:b] & (np.arange(b) < st.delta_n)
    # ONE attribute assignment: a lock-free reader snapshots rows, mask and
    # ids that always belong to the same bucket shape (per-field replacement
    # would let a grown rows array pair with a stale shorter mask).
    # Transfer economy: deletes (mask_only — rows/ids untouched, bucket
    # unchanged) reuse the published device arrays and re-upload just the
    # bool mask. Upserts re-upload the whole bucket: a device-side splice
    # of only the appended rows (lax.dynamic_update_slice) was considered
    # and REJECTED — its program is keyed on the caller's write batch size,
    # which warm() cannot enumerate, so it would put data-dependent
    # compiles on the write path and void the warmed-ladder zero-compile
    # guarantee the bucket discipline exists for. The memtable is small by
    # design (<= capacity rows), so the O(bucket) host upload is bounded,
    # value-independent, and compile-free.
    view = getattr(st, "delta_view", None)
    if mask_only and view is not None and view[3] == b:
        rows_dev, ids_dev = view[0], view[2]
    else:
        rows_dev = _dev_put(st.cfg, st.delta[:b])
        ids_dev = _dev_put(st.cfg, st.delta_ids[:b])
    st.delta_view = (rows_dev, _dev_put(st.cfg, keep), ids_dev, b)


def _build_loc(st: _StreamState) -> dict:
    """id → live-slot map, built from vectorized numpy passes (zip over
    materialized lists — ~10x a per-row Python loop with int() casts; at the
    bench's 100k scale this runs in single-digit ms, which matters because
    the compaction swap rebuilds it under the write lock)."""
    s_slots = np.nonzero(st.sealed_alive)[0]
    loc = dict(zip(st.id_map[s_slots].tolist(),
                   zip(("s",) * len(s_slots), s_slots.tolist())))
    d_slots = np.nonzero(st.delta_alive[:st.delta_n])[0]
    loc.update(zip(st.delta_ids[d_slots].tolist(),
                   zip(("d",) * len(d_slots), d_slots.tolist())))
    return loc


def _scan_state(st: _StreamState, queries, k: int, res=None,
                k_sealed: int | None = None):
    """The scatter half of a one-epoch search: sealed(filtered) scan +
    delta scan with slot-local ids mapped to the global space — everything
    BEFORE the select_k merge, so the sharded tier
    (:mod:`raft_tpu.stream.sharded`) can collect every shard's candidate
    sets and merge them through ONE dispatch (the knn_merge_parts contract
    generalized to mixed sealed+delta parts). All device handles are
    snapshotted up front, so a concurrent write (which replaces handles,
    never mutates them) cannot tear this call. The state epoch is noted on
    the request-log collector (the sharded tier prefixes it per shard); the
    stages' device time is in the profiler trace under their
    :func:`raft_tpu.core.tracing.scope` names (``stream.delta``,
    ``stream.map_ids``, the sealed index's own).

    Returns ``(sealed_d (m, k), sealed_i, delta_d (m, kd), delta_i)`` with
    global ids and the shared ``-1 / ±inf`` sentinel in unfillable slots.
    ``k_sealed`` (sharded tier only) narrows the sealed candidate width —
    a shard with fewer sealed rows than k contributes what it has and the
    merge pads the rest; the single-device path keeps its k-≤-rows
    contract untouched.
    """
    from ..obs import requestlog

    jnp = _jnp()
    cfg = st.cfg
    requestlog.annotate("stream_epoch", st.epoch)
    # handle snapshot — one consistent view (delta_view and sealed_view are
    # each assigned as one tuple, id_map is frozen per epoch, the sealed
    # view only changes VALUES within an epoch, never shape). ORDER
    # MATTERS: the delta view is read BEFORE the sealed view, pairing with
    # upsert's kill-then-reveal publish order (sealed view first, delta
    # second) — a reader that sees an upserted id's new delta copy is then
    # guaranteed to also see the old sealed copy's tombstone; the reverse
    # read order could surface BOTH copies of one id in a single result
    # row. (The benign anomaly — an id briefly absent — is the one the
    # design accepts, like any read racing a write.)
    delta, dkeep, dids, _ = st.delta_view
    sview, imap = st.sealed_view, st.id_map_dev

    queries = jnp.asarray(queries)
    expects(queries.ndim == 2 and queries.shape[1] == cfg.dim,
            "queries must be (rows, %d)", cfg.dim)
    if cfg.query_dtype == "float32":
        queries = queries.astype(jnp.float32)
    k = int(k)
    ks = k if k_sealed is None else int(k_sealed)
    sd, si = _sealed_search(cfg, sview, queries, ks, res=res)
    si = _map_ids(si, imap)
    dd, di = _delta_search(cfg, delta, queries, min(k, delta.shape[0]),
                           dkeep, res)
    di = _map_ids(di, dids)
    # the dispatch meter (obs/dispatch.py): sealed search + delta scan +
    # the two id maps = 4 instrumented sites per epoch scan
    obs_dispatch.note(4)
    return sd, si, dd, di


def _delta_search(cfg, delta, queries, kd: int, dkeep, res=None):
    """The delta memtable's exact scan, as the ``stream.delta`` stage. The
    scope names its operations where the search is traced inside a jit;
    called eagerly, the scan's own top-level program carries no scope, and
    ``benchmark/stages.py`` maps that program (``jit__bf_knn``) to this
    stage."""
    from ..core import tracing
    from ..neighbors import brute_force

    with tracing.scope("stream.delta"):
        return brute_force.knn(delta, queries, kd, cfg.metric,
                               cfg.metric_arg, sample_filter=dkeep, res=res)


def _search_state(st: _StreamState, queries, k: int, res=None):
    """Unified search over one state epoch: the sealed+delta scan
    (:func:`_scan_state`) merged through select_k (``stream.merge``)."""
    sd, si, dd, di = _scan_state(st, queries, k, res=res)
    return _merge(sd, si, dd, di, int(k), st.cfg.select_min)


# -- the mutable index -------------------------------------------------------

class MutableIndex:
    """Mutable lifecycle wrapper over a sealed index (see module docstring).

    ``sealed`` must be a freshly built (or loaded) index whose stored ids
    are the dense row range ``0..n-1`` — exactly what ``build()`` produces.
    ``search_params`` are baked in at wrap time (the serving-hook
    discipline); ``index_params`` are required only for rebuild compaction
    of IVF kinds. ``delta_capacity`` (power of two) bounds the memtable;
    ``retain_vectors`` keeps a host-side raw row store (required for
    rebuild compaction — auto-recovered from brute-force/CAGRA sealed
    datasets, supplied via ``dataset=`` for IVF kinds, whose codes cannot
    reconstruct rows). ``builder`` (optional) replaces the default
    ``module.build(index_params, rows)`` in rebuild compaction: any
    ``fn(rows, res=None) -> sealed-index-of-the-same-kind`` — the hook that
    lets compactions rebuild SHARDED over a mesh
    (:func:`raft_tpu.parallel.cagra.merged_builder`), shrinking the rebuild
    wall that bounds sustainable write churn. Like ``search_params`` it is
    runtime configuration: never serialized, supplied fresh to ``load``.
    ``ids`` (optional, length-n unique non-negative ints) assigns the
    sealed rows' GLOBAL ids — by default the dense row range the sealed
    build produced. The sharded tier uses this as its global-id offset
    map: each shard's sealed index stays a dense local build while its
    results surface the caller's global id space, and fresh ids continue
    past ``max(ids)``. ``device`` (optional) pins every device array (and
    therefore every search program — jax placement follows committed
    inputs) to one device: the scatter mechanism of
    :class:`raft_tpu.stream.sharded.ShardedMutableIndex`, where shard ``s``
    lives on mesh device ``s`` and only candidate tuples ever leave it.
    ``shard`` (optional) is the shard ordinal for obs.mem ledger
    attribution — the sharded tier passes its index so ``/debug/mem``
    breaks bytes down per shard. ``storage`` picks where the retained
    raw rows live: ``"hbm"`` (default — the pre-tiering behavior, a
    host array with a lazy full device copy for the oracle) or
    ``"tiered"`` (:class:`~raft_tpu.stream.tiered.TieredStore`: rows in
    host RAM or an mmap'd file per ``tier`` — a
    :class:`~raft_tpu.stream.tiered.TierPolicy` — with refine/oracle
    batches crossing to the device through a double-buffered gather;
    see :meth:`search_refined` and docs/streaming.md "Tiered storage").
    ``clock`` is injected for deterministic tests (the age watermark's
    time base).
    """

    def __init__(self, sealed, *, search_params=None, index_params=None,
                 delta_capacity: int = 1024, retain_vectors: bool | None = None,
                 dataset=None, builder: Callable | None = None,
                 ids=None, device=None, name: str = "default",
                 shard: int | None = None, wal=None,
                 snapshot_path: str | None = None,
                 storage: str = "hbm", tier: TierPolicy | None = None,
                 tier_residency: str | None = None,
                 clock: Callable[[], float] = time.monotonic):
        kind, module = _resolve_kind(sealed)
        n, d, metric, metric_arg, data_kind = _sealed_meta(kind, sealed)
        expects(n > 0, "cannot wrap an empty sealed index")
        if device is not None:
            import jax

            if kind == "brute_force":
                # BruteForce is not a pytree — move its dataset in place
                # (the wrap takes ownership of the sealed index anyway);
                # the moved array holds norms of its own
                sealed.dataset = jax.device_put(sealed.dataset, device)
                sealed.hold_norms()
            else:
                sealed = jax.device_put(sealed, device)
        if kind in ("ivf_flat", "ivf_pq"):
            # the id-map contract: internal ids are the dense row range
            import jax.numpy as jnp

            expects(int(jnp.max(sealed.list_ids)) == n - 1,
                    "sealed %s ids must be the dense row range 0..n-1 "
                    "(a fresh build); wrap before extending with custom ids",
                    kind)
        query_dtype = data_kind if data_kind in ("int8", "uint8") else "float32"
        if search_params is None and kind != "brute_force":
            # default params at WRAP time, not an AttributeError at first
            # search (which could land on a serving thread)
            search_params = module.SearchParams()
        if (kind == "ivf_pq"
                and getattr(search_params, "funnel_widen", 1) > 1):
            # fail the funnel/tier mismatch at WRAP time, not on a serving
            # thread at first search (same rationale as the default above)
            expects(sealed.has_fast_scan,
                    "search_params pins funnel_widen=%d but the sealed "
                    "index carries no fast-scan tier — build with "
                    "IndexParams.fast_scan='1bit'|'4bit'",
                    int(search_params.funnel_widen))
        cfg = _Config(kind=kind, module=module, search_params=search_params,
                      metric=metric, metric_arg=metric_arg,
                      select_min=metric != DistanceType.InnerProduct,
                      dim=d, data_kind=data_kind, query_dtype=query_dtype,
                      name=name, device=device)
        self._cfg = cfg
        # shard ordinal for obs.mem ledger attribution (the sharded tier
        # passes its shard index; None = unsharded)
        self._shard = None if shard is None else int(shard)
        self._index_params = index_params
        expects(builder is None or callable(builder),
                "builder must be a callable fn(rows, res=None) -> sealed index")
        self._builder = builder
        self.delta_capacity = int(delta_capacity)
        self._buckets = delta_buckets(self.delta_capacity)
        self._clock = clock
        self._lock = threading.RLock()
        self._compact_lock = threading.Lock()
        # durability (docs/streaming.md "Durability & replication"): a WAL
        # logs every upsert/delete at admission, BEFORE the memtable sees
        # it; snapshot_path couples compaction swaps to an atomic snapshot
        # + WAL truncation. A fresh wrap refuses a log that already holds
        # records — those belong to an earlier life of this index and must
        # be recovered through stream.load(wal=), not silently shadowed.
        if wal is not None and not hasattr(wal, "append_upsert"):
            from .wal import WriteAheadLog

            wal = WriteAheadLog(wal, name=name)
        self._wal = wal
        self._wal_seq = 0
        self._snapshot_path = snapshot_path
        if wal is not None:
            expects(wal.seq == 0,
                    "WAL %r already holds records (seq=%d) — a fresh wrap "
                    "would shadow them; recover with stream.load(wal=) or "
                    "point at a fresh log", getattr(wal, "path", "?"),
                    wal.seq)
        if ids is None:
            id_map = np.arange(n, dtype=np.int64)
        else:
            id_map = np.asarray(ids, np.int64).reshape(-1)
            expects(id_map.shape == (n,),
                    "ids= must assign one global id per sealed row (%d), "
                    "got %d", n, id_map.shape[0])
            expects(np.unique(id_map).size == n, "ids= must be unique")
            expects(int(id_map.min()) >= 0, "ids= must be >= 0")
            expects(int(id_map.max()) < 2 ** 31 - 1,
                    "ids= must fit int32 (device id maps are int32)")
        self._next_id = int(id_map.max()) + 1
        self._loc: dict[int, tuple[str, int]] = {}

        store = None
        if dataset is not None:
            from ..core import chunked

            # a ChunkedReader dataset (the out-of-core build's corpus)
            # contributes its BACKING array — for an np.memmap that keeps
            # the retained rows disk-backed end to end (TieredStore
            # adopts the mmap; see stream/tiered.py), the corpus is
            # never copied into RAM
            store = (dataset.host_view() if chunked.is_reader(dataset)
                     else np.asarray(dataset))
            expects(store.shape == (n, d),
                    "dataset= must be the sealed rows (%d, %d), got %s",
                    n, d, tuple(store.shape))
            if query_dtype == "float32":
                if store.dtype != np.float32:
                    store = np.asarray(store, np.float32)
            else:
                expects(str(store.dtype) == query_dtype,
                        "dataset= dtype %s must match the serving dtype %s",
                        store.dtype, query_dtype)
        elif retain_vectors is not False:
            store = _recover_store(kind, sealed, data_kind)
        if retain_vectors is True:
            expects(store is not None,
                    "retain_vectors=True needs dataset= for %s (stored codes "
                    "cannot reconstruct raw rows)", kind)
        # the beyond-HBM storage policy (docs/streaming.md "Tiered
        # storage"): "tiered" keeps the full-precision rows cold (host
        # RAM / disk mmap) behind a TieredStore — the refine epilogue and
        # the exact oracle then cross to the device per batch instead of
        # pinning a second full-precision copy in HBM
        expects(storage in ("hbm", "tiered"),
                "storage must be 'hbm' or 'tiered', got %r", storage)
        expects(tier is None or storage == "tiered",
                "tier= (a TierPolicy) applies to storage='tiered' only")
        expects(tier_residency is None or storage == "tiered",
                "tier_residency= applies to storage='tiered' only")
        if storage == "tiered":
            expects(store is not None,
                    "storage='tiered' stores the raw refine rows cold — "
                    "pass dataset= (IVF kinds) or retain_vectors=True")
        self._storage = storage
        self._tier = tier

        st = _StreamState(cfg)
        st.sealed = sealed
        st.id_map = id_map
        st.sealed_alive = np.ones(n, bool)
        # tier_residency (load()'s layout-restore seam) skips the
        # placement decision entirely — re-deciding here and correcting
        # later would pay a full wasted H2D for a cold-saved store
        st.store = self._make_store(store, epoch=0,
                                    residency=tier_residency)
        dt = _np_dtype(query_dtype)
        st.delta = np.zeros((self.delta_capacity, d), dt)
        st.delta_ids = np.zeros(self.delta_capacity, np.int32)
        st.delta_alive = np.zeros(self.delta_capacity, bool)
        st.id_map_dev = _dev_put(cfg, st.id_map.astype(np.int32))
        _init_sealed_view(st, _list_layout(sealed, n))
        _refresh_delta(st, self.delta_capacity)
        self._state = st
        self._loc = _build_loc(st)
        # ledger attribution: the sealed store re-attributes under the
        # serving name (idempotent per index object); the stream-owned
        # arrays get their own per-epoch entry
        self._sealed_mem = obs_mem.account_index(
            sealed, name=cfg.name, shard=self._shard, epoch=0)
        self._update_gauges(st)

    # -- introspection ------------------------------------------------------
    @property
    def kind(self) -> str:
        return self._cfg.kind

    @property
    def dim(self) -> int:
        return self._cfg.dim

    @property
    def name(self) -> str:
        return self._cfg.name

    @property
    def query_dtype(self) -> str:
        return self._cfg.query_dtype

    @property
    def can_rebuild(self) -> bool:
        """Whether rebuild compaction (the tombstone-reclaiming mode) is
        available: a raw row store, plus build params for IVF kinds."""
        st = self._state
        if st.store is None:
            return False
        return (self._cfg.kind in ("brute_force", "cagra")
                or self._index_params is not None
                or self._builder is not None)

    @property
    def size(self) -> int:
        """Live (searchable) rows."""
        with self._lock:
            st = self._state
            return int(len(st.sealed_alive) - st.sealed_dead_n
                       + st.delta_alive[:st.delta_n].sum())

    def _make_store(self, rows, epoch: int, residency: str | None = None):
        """Materialize the retained row store for one state epoch: the
        raw array under ``storage="hbm"``, a
        :class:`~raft_tpu.stream.tiered.TieredStore` under ``"tiered"``
        (per-epoch — a compaction successor's store re-places against the
        budget, carrying the predecessor's residency when asked, which is
        how tier residency migrates through the fold-and-swap)."""
        if rows is None or self._storage == "hbm":
            return rows
        # rows pass RAW: TieredStore adopts an np.memmap in place (zero
        # host bytes) — an asarray here would strip that provenance
        return TieredStore(
            rows, name=self._cfg.name, shard=self._shard,
            epoch=epoch, policy=self._tier, device=self._cfg.device,
            residency=residency, clock=self._clock)

    @property
    def storage(self) -> str:
        """The storage policy ("hbm" or "tiered")."""
        return self._storage

    @property
    def tiered_store(self) -> TieredStore | None:
        """The live epoch's :class:`TieredStore` (None under "hbm")."""
        st = self._state.store
        return st if isinstance(st, TieredStore) else None

    def _drift_store(self):
        """The retained raw-row store (or None) — what a
        :class:`~raft_tpu.stream.Compactor` feeds the corpus-side drift
        detector; the sharded tier overrides this with a cross-shard
        subsample."""
        return _store_rows(self._state.store)

    def stats(self) -> dict:
        with self._lock:
            st = self._state
            n_sealed = len(st.sealed_alive)
            dead = int(st.sealed_dead_n)
            return {
                "live": int(n_sealed - dead
                            + st.delta_alive[:st.delta_n].sum()),
                "sealed_rows": n_sealed,
                "sealed_dead": dead,
                "tombstone_ratio": dead / max(n_sealed, 1),
                "delta_rows": int(st.delta_n),
                "delta_fill": st.delta_n / self.delta_capacity,
                "delta_bucket": st.delta_view[3],
                "delta_oldest_at": st.delta_oldest_at,
                "epoch": st.epoch,
            }

    def _update_gauges(self, st: _StreamState) -> None:
        if not metrics._enabled:
            return
        name = self._cfg.name
        n_sealed = len(st.sealed_alive)
        dead = int(st.sealed_dead_n)
        _g_delta_fill().set(st.delta_n / self.delta_capacity, name=name)
        _g_delta_rows().set(st.delta_n, name=name)
        _g_tombstone().set(dead / max(n_sealed, 1), name=name)
        self._account_state(st)

    def _account_state(self, st: _StreamState) -> None:
        """(Re)account this epoch's stream-owned arrays in the obs.mem
        ledger: device = the published delta view + the sealed keep-mask
        or live list ids + id map (+ the lazy store copy), host = the
        preallocated memtable, bitsets, live-id layout and retained store.
        Keyed on the STATE object, so a compaction swap leaves the old
        epoch's entry to auto-release at drain — exactly what the
        retirement audit watches."""
        if not metrics._enabled:
            return
        sview, skeep = st.sealed_view
        dev = [st.id_map_dev, sview.list_ids if skeep is None else skeep,
               *st.delta_view[:3]]
        if st.store_dev is not None:
            dev.append(st.store_dev)
        host = [st.delta, st.delta_ids, st.delta_alive, st.sealed_alive,
                st.id_map]
        if st.live_ids is not None:
            host += [st.live_ids, st.slot_pos]
        # a TieredStore carries its own "tier" ledger entry (rows + mirror
        # + gather slots) — ONE attribution, not a second copy here
        if st.store is not None and not isinstance(st.store, TieredStore):
            host.append(st.store)
        if st.mem is None:
            st.mem = obs_mem.account(
                "stream", name=self._cfg.name, shard=self._shard,
                epoch=st.epoch, device=dev, host=host, owner=st)
        else:
            obs_mem.reaccount(st.mem, device=dev, host=host)

    def _growth_bytes(self, r: int) -> int:
        """Device bytes a write of ``r`` rows would newly allocate — the
        uniform admission surface the sharded/replicated tiers price their
        hoisted whole-or-nothing gate with."""
        return self._delta_growth_bytes(self._state, r)

    def _delta_rows_now(self) -> int:
        """Current delta occupancy for hoisted admission checks (reads a
        snapshot without the lock: concurrent folds only SHRINK a delta,
        so a stale read can only over-refuse, never admit past capacity)."""
        return int(self._state.delta_n)

    def _delta_growth_bytes(self, st: _StreamState, r: int) -> int:
        """Device bytes a write of ``r`` rows would newly allocate: the
        delta bucket ladder only grows in power-of-two steps, and a grown
        bucket re-uploads rows+ids+mask (the old bucket's arrays free)."""
        b0 = st.delta_view[3]
        b1 = _bucket_for(st.delta_n + r, self.delta_capacity)
        if b1 <= b0:
            return 0
        return (b1 - b0) * (self._cfg.dim * st.delta.dtype.itemsize + 4 + 1)

    # -- writes -------------------------------------------------------------
    def _coerce_rows(self, rows):
        rows = np.asarray(rows)
        expects(rows.ndim == 2 and rows.shape[1] == self._cfg.dim,
                "rows must be (r, %d)", self._cfg.dim)
        if self._cfg.query_dtype == "float32":
            return np.asarray(rows, np.float32)
        expects(str(rows.dtype) == self._cfg.query_dtype,
                "byte index %r takes %s rows, got %s", self._cfg.name,
                self._cfg.query_dtype, rows.dtype)
        return rows

    def upsert(self, rows, ids=None, res=None):
        """Insert rows (fresh ids assigned and returned) or upsert under
        caller-chosen ids: the previous live occurrence of each id is
        tombstoned and the new row becomes visible to the very next search
        (read-your-writes — no compaction needed). Raises
        :class:`DeltaFullError` (an ``OverloadedError``) at capacity, and
        :class:`~raft_tpu.serve.errors.MemoryBudgetError` (also an
        ``OverloadedError``) when growing the delta's device bucket would
        exceed ``res.memory_budget_bytes`` — both BEFORE any row lands
        (whole-or-nothing)."""
        rows = self._coerce_rows(rows)
        r = rows.shape[0]
        expects(r >= 1, "upsert needs at least one row")
        with self._lock:
            st = self._state
            obs_mem.gate(res or default_resources(),
                         lambda: self._delta_growth_bytes(st, r),
                         site="upsert", detail=f"stream {self._cfg.name!r}")
            if st.delta_n + r > self.delta_capacity:
                if metrics._enabled:
                    _c_delta_full().inc(1, name=self._cfg.name)
                raise DeltaFullError(
                    f"delta memtable at {st.delta_n}/{self.delta_capacity} "
                    f"rows; upsert of {r} refused — compact() (or attach a "
                    "stream.Compactor) to fold the delta into the sealed "
                    "index")
            if ids is None:
                gids = np.arange(self._next_id, self._next_id + r,
                                 dtype=np.int64)
            else:
                gids = check_upsert_ids(ids, r)
            expects(int(gids.max()) < 2 ** 31 - 1,
                    "ids must fit int32 (device id maps are int32)")
            self._next_id = max(self._next_id, int(gids.max()) + 1)
            if self._wal is not None:
                # write-ahead: the record is durable BEFORE the memtable
                # mutates; a crash in the window below replays it at load
                self._wal_seq = self._wal.append_upsert(rows, gids)
                faults.fire("stream/post-wal", name=self._cfg.name,
                            op="upsert")
            sealed_dirty = self._tombstone_locked(st, gids.tolist())
            p = st.delta_n
            st.delta[p:p + r] = rows
            st.delta_ids[p:p + r] = gids.astype(np.int32)
            st.delta_alive[p:p + r] = True
            for j, g in enumerate(gids.tolist()):
                self._loc[g] = ("d", p + j)
            if st.delta_n == 0:
                st.delta_oldest_at = self._clock()
            st.delta_n += r
            # tombstone-before-reveal: the old copy's tombstone lands first
            # so a lock-free reader can never see both copies of an
            # upserted id
            if sealed_dirty:
                _publish_sealed(st)
            _refresh_delta(st, self.delta_capacity)
            if metrics._enabled:
                _c_upserts().inc(r, name=self._cfg.name)
            self._update_gauges(st)
        return gids

    def _tombstone_locked(self, st, gids) -> bool:
        """Mark the live occurrence of each id dead; returns whether a
        SEALED slot changed (the caller publishes the sealed view)."""
        dead_sealed = []
        killed = 0
        for g in gids:
            loc = self._loc.pop(int(g), None)
            if loc is None:
                continue
            killed += 1
            if loc[0] == "s":
                st.sealed_alive[loc[1]] = False
                st.sealed_dead_n += 1
                dead_sealed.append(loc[1])
            else:
                st.delta_alive[loc[1]] = False
        if dead_sealed:
            _kill_listed(st, dead_sealed)
        if killed and metrics._enabled:
            _c_deletes().inc(killed, name=self._cfg.name)
        return bool(dead_sealed)

    def delete(self, ids) -> int:
        """Tombstone ids; returns how many were live. Deletes are visible to
        the very next search (the masks flip before this returns); unknown
        or already-dead ids are a counted no-op, not an error."""
        arr = np.asarray(ids).reshape(-1)
        with self._lock:
            st = self._state
            if self._wal is not None and arr.size:
                self._wal_seq = self._wal.append_delete(arr)
                faults.fire("stream/post-wal", name=self._cfg.name,
                            op="delete")
            before = len(self._loc)
            sealed_dirty = self._tombstone_locked(st, arr.tolist())
            n = before - len(self._loc)
            if sealed_dirty:
                _publish_sealed(st)
            # delta tombstones ride the keep mask; rows/ids are untouched
            # by a delete, so only the mask re-uploads
            _refresh_delta(st, self.delta_capacity, mask_only=True)
            self._update_gauges(st)
        return n

    # -- reads --------------------------------------------------------------
    def search(self, queries, k: int, res=None):
        """Unified search over (sealed − tombstones) + delta; returns
        ``(distances (m, k), global ids (m, k))`` with the shared
        ``id -1 / ±inf`` sentinel in slots the live rows cannot fill."""
        return _search_state(self._state, queries, k, res=res)

    def exact_search(self, queries, k: int, res=None):
        """EXACT fused kNN over the live corpus — the recall canary's
        shadow oracle (:func:`raft_tpu.obs.quality.exact_oracle`). The
        sealed side scans the retained raw row store through a keep-mask
        of the same tombstone bitset the serving path reads; the delta side
        is the usual exact bucket scan; both merge through ``select_k``
        and map to global ids. Needs the retained store (``dataset=`` /
        ``retain_vectors=True`` — PQ codes cannot reconstruct rows).

        Off the hot path by design: the store's device copy uploads
        lazily once per compaction epoch, and the brute-force program is
        keyed on the epoch's sealed row count — warm it per epoch
        (``RecallCanary.warm``; the churn bench covers epochs by
        rehearsal). Handle-snapshot ordering matches :meth:`search`, so a
        concurrent write cannot tear the view."""
        sd, si, dd, di = self._exact_scan(queries, k, res=res)
        return _merge(sd, si, dd, di, int(k), self._cfg.select_min)

    def _exact_scan(self, queries, k: int, res=None):
        """The scatter half of :meth:`exact_search` — exact store scan +
        delta scan with global ids, BEFORE the merge — so the sharded tier
        composes shard-local exact scans through the same one-dispatch
        merge as :meth:`search` (the RecallCanary's oracle then covers a
        whole mesh unchanged). Returns ``(sd (m, ks), si, dd (m, kd),
        di)``; ``ks``/``kd`` are clamped to the store/bucket rows."""
        from ..neighbors import brute_force

        jnp = _jnp()
        st = self._state
        cfg = self._cfg
        # same snapshot discipline and ORDER as _search_state: delta view
        # before the sealed view (pairs with upsert's kill-then-reveal)
        delta, dkeep, dids, _ = st.delta_view
        skeep, imap = st.sealed_view[1], st.id_map_dev
        queries = jnp.asarray(queries)
        expects(queries.ndim == 2 and queries.shape[1] == cfg.dim,
                "queries must be (rows, %d)", cfg.dim)
        if cfg.query_dtype == "float32":
            queries = queries.astype(jnp.float32)
        k = int(k)
        ts = st.store if isinstance(st.store, TieredStore) else None
        # mirror SNAPSHOT: a concurrent pressure spill nulls ts.mirror
        # from a writer thread — one read decides the branch AND supplies
        # the array, so a spill mid-query degrades to the chunked path's
        # next call instead of failing this one
        mirror = ts.mirror if ts is not None else None
        if ts is not None and mirror is None:
            # cold tiered store: chunked scan through the double-buffered
            # slot ring — the oracle covers the full corpus with ZERO net
            # device row bytes (satellite: the canary's shadow-rerank must
            # not duplicate the store on device). The keep-mask is COPIED
            # once here — sealed_alive mutates in place under writes, and
            # a per-chunk live read could miss an id in BOTH parts (delta
            # snapshot too old, sealed bit already killed); one copy taken
            # AFTER the delta view preserves the kill-then-reveal pairing
            # exactly like the resident path's frozen device mask
            alive = st.sealed_alive.copy()
            sd, si = self._chunked_store_scan(st, ts, queries, k,
                                              alive=alive, res=res)
            si = _map_ids(si, imap)
        else:
            store_dev = (mirror if mirror is not None
                         else self._store_device(st))
            if skeep is None:
                # a list layout publishes no slot mask: the store scan
                # uploads a copy of the bitset taken after the delta view
                # (off the serving path, like the store copy itself)
                skeep = _dev_put(cfg, st.sealed_alive.copy())
            ks = min(k, store_dev.shape[0])
            sd, si = brute_force.knn(store_dev, queries, ks, cfg.metric,
                                     cfg.metric_arg, sample_filter=skeep,
                                     res=res)
            si = _map_ids(si, imap)
        kd = min(k, delta.shape[0])
        dd, di = brute_force.knn(delta, queries, kd, cfg.metric,
                                 cfg.metric_arg, sample_filter=dkeep, res=res)
        di = _map_ids(di, dids)
        obs_dispatch.note(4)  # store scan + delta scan + two id maps
        return sd, si, dd, di

    def _chunked_store_scan(self, st: _StreamState, ts: TieredStore,
                            queries, k: int, *, alive=None, res=None,
                            max_chunks: int | None = None):
        """Exact scan of a COLD tiered store: fixed-shape chunks stream
        through the store's replacement slot ring (chunk N+1's upload overlaps
        chunk N's distance compute under async dispatch) and fold into a
        running top-k through the same ``_merge`` program the serving path
        uses. Every chunk shares one program set — (chunk, k) knn + shift
        + merge — so store size never compiles on the oracle path after
        :meth:`warm`. Returns ``(sd, si)`` in STORE-SLOT id space (the
        caller maps to global ids); the tombstone keep-mask rides each
        chunk's ``sample_filter`` exactly like the resident scan.
        ``max_chunks`` bounds the walk (the warm path compiles the
        program set with two chunks instead of scanning everything)."""
        from ..neighbors import brute_force
        from . import tiered as _tiered

        cfg = st.cfg
        chunk = ts.oracle_chunk
        kc = min(int(k), chunk)
        n_chunks = ts.n_oracle_chunks()
        if max_chunks is not None:
            n_chunks = min(n_chunks, int(max_chunks))
        if alive is None:  # warm path; real scans pass the caller's copy
            alive = st.sealed_alive.copy()
        acc_d = acc_i = None
        for ci in range(n_chunks):
            rows_dev, base, valid = ts.oracle_chunk_dev(ci)
            keep = np.zeros(chunk, bool)
            keep[:valid] = alive[base:base + valid]
            cd, cidx = brute_force.knn(
                rows_dev, queries, kc, cfg.metric, cfg.metric_arg,
                sample_filter=_dev_put(cfg, keep), res=res)
            cidx = _tiered.shift_slots(cidx, base)
            if acc_d is None:
                acc_d, acc_i = cd, cidx
            else:
                acc_d, acc_i = _merge(acc_d, acc_i, cd, cidx, kc,
                                      cfg.select_min)
        return acc_d, acc_i

    def _store_device(self, st: _StreamState):
        """The epoch-frozen device copy of the retained row store (lazy;
        a benign publication race uploads at most twice — the store array
        itself is never mutated within an epoch)."""
        expects(st.store is not None,
                "exact_search needs the retained row store "
                "(retain_vectors=True / dataset= at wrap time)")
        expects(not isinstance(st.store, TieredStore),
                "tiered stores never materialize a second full device "
                "copy — use the mirror or the chunked scan")
        dev = st.store_dev
        if dev is None:
            dev = _dev_put(st.cfg, st.store)
            st.store_dev = dev
            # the lazy oracle copy joins the epoch's ledger entry (off the
            # serving hot path by construction)
            self._account_state(st)
        return dev

    # -- the refine epilogue (tiered storage's serving path) -----------------
    def search_refined(self, queries, k: int, refine_ratio: int = 4,
                       res=None):
        """IVF-PQ search with the exact-refine epilogue restructured as a
        store gather: the sealed scan widens to ``k * refine_ratio`` PQ
        candidates, their full-precision rows gather from the retained
        store — under ``storage="tiered"`` a double-buffered host→device
        hop (:meth:`TieredStore.fetch`; batch N+1's H2D overlaps batch
        N's distance compute), under ``"hbm"`` a device-side gather from
        the resident copy — and :func:`raft_tpu.neighbors.refine
        .refine_gathered` re-ranks exactly; the delta memtable (already
        exact) merges at serving width. Identical ids/distances across
        the two storage modes (bit-parity pinned by the ``tiering``
        suite): tiering moves WHERE the rows live, never what a query
        answers. Returns ``(distances (m, k), global ids (m, k))``."""
        return self._search_refined_state(self._state, queries, k,
                                          refine_ratio, res=res)

    def _search_refined_state(self, st: _StreamState, queries, k: int,
                              refine_ratio: int, res=None):
        rd, ri, dd, di = self._refined_scan(queries, k, refine_ratio,
                                            res=res, st=st)
        return _merge(rd, ri, dd, di, int(k), self._cfg.select_min)

    def _refined_scan(self, queries, k: int, refine_ratio: int, res=None,
                      st: _StreamState | None = None):
        """The scatter half of :meth:`search_refined` — refined sealed
        part + exact delta part, global ids, BEFORE the merge — so the
        sharded tier composes per-shard refined scans through its one
        ``select_k`` dispatch. Snapshot order matches :func:`_scan_state`
        (delta view before the sealed view). ``st`` pins an explicit
        state epoch (the :meth:`refined_searcher` hook's lease-drain
        contract); None reads the live state."""
        from ..neighbors.refine import refine_gathered
        from ..obs import requestlog
        from . import tiered as _tiered

        if st is None:
            st = self._state
        cfg = self._cfg
        expects(cfg.kind == "ivf_pq",
                "search_refined is the IVF-PQ refine epilogue (kind=%r "
                "scores candidates exactly already — use search())",
                cfg.kind)
        expects(st.store is not None,
                "search_refined needs the retained raw rows (dataset= / "
                "retain_vectors=True at wrap time)")
        r = int(refine_ratio)
        expects(r >= 1, "refine_ratio must be >= 1, got %d", r)
        jnp = _jnp()
        requestlog.annotate("stream_epoch", st.epoch)
        delta, dkeep, dids, _ = st.delta_view
        sview, imap = st.sealed_view, st.id_map_dev
        queries = jnp.asarray(queries)
        expects(queries.ndim == 2 and queries.shape[1] == cfg.dim,
                "queries must be (rows, %d)", cfg.dim)
        if cfg.query_dtype == "float32":
            queries = queries.astype(jnp.float32)
        k = int(k)
        kr = min(k * r, st.id_map.shape[0])
        # PQ candidates at the widened width — approximate distances are
        # DISCARDED; only the slot ids feed the exact re-rank
        _, slots = _sealed_search(cfg, sview, queries, kr, res=res)
        ts = st.store if isinstance(st.store, TieredStore) else None
        if ts is not None:
            cand = ts.fetch(slots, res=res)
        else:
            cand = _tiered.mirror_gather(self._store_device(st), slots)
        ks = min(k, kr)
        rd, rslots = refine_gathered(cand, queries, slots, ks,
                                     metric=cfg.metric)
        ri = _map_ids(rslots, imap)
        dd, di = _delta_search(cfg, delta, queries, min(k, delta.shape[0]),
                               dkeep, res)
        di = _map_ids(di, dids)
        obs_dispatch.note(5)
        return rd, ri, dd, di

    def refined_searcher(self, refine_ratio: int = 4):
        """Serving hook over :meth:`search_refined` (the
        ``batched_searcher`` contract) — what a tiered IVF-PQ index
        publishes: PQ scan + store-gather refine as ONE hook, pinned to
        the current state epoch exactly like :meth:`searcher` (a
        compaction swap freezes the leased hook's view; the republish
        picks up the successor — the registry lease-drain contract)."""
        from ..neighbors._hooks import make_hook

        st = self._state
        fn = make_hook(
            lambda queries, k: self._search_refined_state(st, queries, k,
                                                          refine_ratio),
            f"stream/{self._cfg.kind}+refine", self._cfg.dim,
            self._cfg.data_kind)
        fn.mutable = self
        return fn

    def warm_refined(self, buckets, ks=(10,), refine_ratio: int = 4,
                     sample=None) -> dict:
        """Rehearse the refined serving path per (query bucket, k): one
        real :meth:`search_refined` per shape compiles the widened PQ
        scan, the gather slots (filling the double-buffer ring), the
        refine program and the merge — after which the ``tiering`` suite's
        zero-cold-compile contract holds across refine double-buffer
        cycles. Under tiered storage the chunked-oracle program set warms
        too (two chunks — the knn/shift/merge triple compiles, the full
        walk stays off the warm path). Returns per-(k, bucket) compile
        attribution like :meth:`warm`."""
        import jax

        from .._warmup import _random_queries
        from ..obs import compile as obs_compile

        cfg = self._cfg
        out: dict = {}
        key = jax.random.key(0)
        for kk in sorted(set(int(x) for x in ks)):
            out[kk] = {}
            for b in sorted(set(int(x) for x in buckets)):
                key, kq = jax.random.split(key)
                q = _random_queries(kq, b, cfg.dim, cfg.query_dtype,
                                    sample=sample)
                t0 = time.perf_counter()
                with obs_compile.attribution() as rec:
                    jax.block_until_ready(
                        self.search_refined(q, kk, refine_ratio)[0])
                    ts = self.tiered_store
                    if ts is not None:
                        # warm the CHUNKED oracle programs regardless of
                        # current residency: a promoted store can be
                        # pressure-spilled later, and its first post-
                        # spill exact_search must not cold-compile the
                        # chunk knn/shift/merge set mid-serve
                        st = self._state
                        jnp_q = _jnp().asarray(q)
                        if cfg.query_dtype == "float32":
                            jnp_q = jnp_q.astype(_jnp().float32)
                        jax.block_until_ready(self._chunked_store_scan(
                            st, ts, jnp_q, kk, max_chunks=2)[0])
                out[kk][b] = {"wall_s": round(time.perf_counter() - t0, 3),
                              **rec.summary()}
        return out

    def searcher(self):
        """Serving hook pinned to the CURRENT state epoch (the
        ``batched_searcher`` contract: ``fn(queries, k)`` with
        ``kind``/``dim``/``query_dtype``). Deletes/upserts remain visible
        through a pinned hook until a compaction swap freezes its epoch —
        from then on it serves the pre-compaction view, which is exactly
        the lease-drain semantics ``serve.IndexRegistry`` wants."""
        from ..neighbors._hooks import make_hook

        st = self._state
        fn = make_hook(lambda queries, k: _search_state(st, queries, k),
                       f"stream/{st.cfg.kind}", st.cfg.dim,
                       st.cfg.data_kind)
        # marker for the serve write path: lets SearchService.publish tell a
        # mutable's own hook (keep/retarget the upsert handle) from any
        # other bare hook (close the write path)
        fn.mutable = self
        return fn

    # -- warmup -------------------------------------------------------------
    def warm(self, buckets, ks=(10,), sample=None) -> dict:
        """Compile the delta-ladder program set: the exact delta scan at
        EVERY memtable bucket × every serving (query-bucket, k), plus the
        id-map and merge programs. These shapes are sealed-independent, so
        one warm covers every future compaction epoch; the sealed-side
        programs are warmed per epoch by ``registry.publish`` (which runs
        the full hook). Returns per-(k, bucket) compile attribution like
        :func:`raft_tpu._warmup.warm_buckets`."""
        import jax

        from .._warmup import _random_queries
        from ..obs import compile as obs_compile

        cfg = self._cfg
        out: dict = {}
        key = jax.random.key(0)
        dt = _np_dtype(cfg.query_dtype)
        from ..neighbors import brute_force

        for kk in sorted(set(int(x) for x in ks)):
            out[kk] = {}
            for b in sorted(set(int(x) for x in buckets)):
                key, kq = jax.random.split(key)
                q = _random_queries(kq, b, cfg.dim, cfg.query_dtype,
                                    sample=sample)
                t0 = time.perf_counter()
                with obs_compile.attribution() as rec:
                    for db in self._buckets:
                        # dummies ride _dev_put so a device-pinned shard
                        # warms programs at the SAME committed placement
                        # its serving path dispatches (placement is part of
                        # the executable key — an off-device warm would
                        # leave the hot path cold)
                        dummy = _dev_put(cfg, np.zeros((db, cfg.dim), dt))
                        keep = _dev_put(cfg, np.zeros((db,), bool))
                        kd = min(kk, db)
                        dd, di = brute_force.knn(
                            dummy, q, kd, cfg.metric, cfg.metric_arg,
                            sample_filter=keep)
                        di = _map_ids(di, _dev_put(
                            cfg, np.zeros((db,), np.int32)))
                        sd = _dev_put(cfg, np.zeros((b, kk), np.float32))
                        si = _dev_put(cfg, np.full((b, kk), -1, np.int32))
                        jax.block_until_ready(
                            _merge(sd, si, dd, di, kk, cfg.select_min))
                out[kk][b] = {"wall_s": round(time.perf_counter() - t0, 3),
                              **rec.summary()}
        return out

    # -- compaction ---------------------------------------------------------
    def compact(self, mode: str = "auto", res=None, *,
                ooc_chunk_rows: int | None = None) -> dict:
        """Fold the delta memtable (and, in rebuild mode, the tombstones)
        into a new sealed index and swap it in atomically.

        ``mode``: "extend" appends the live delta rows to the sealed lists
        (IVF kinds only; tombstoned sealed slots stay masked), "rebuild"
        rebuilds the sealed index from the raw live rows (drops tombstones
        entirely; needs the retained row store), "auto" picks extend for
        IVF kinds and rebuild otherwise. The heavy fold runs OFF the write
        lock — searches keep serving the old state, and writes landing
        mid-fold carry over: the fold consumes a snapshot prefix of the
        delta, and every alive bit is re-read from the live tombstone state
        at swap time. Returns a report dict (mode, rows folded/reclaimed,
        wall seconds).

        ``ooc_chunk_rows`` (rebuild mode only) routes the fold through
        the out-of-core build path: the live rows feed the builder as a
        ``core.chunked.ChunkedReader`` instead of one device-resident
        array, so a rebuild's device peak stays at index + two staged
        chunks — what lets a tiered/beyond-HBM index compact without
        transiently re-materializing its corpus in HBM. Bit-equal to the
        in-core fold (the streamed-build parity contract).
        """
        expects(mode in ("auto", "extend", "rebuild"),
                "mode must be 'auto', 'extend' or 'rebuild', got %r", mode)
        import jax
        import jax.numpy as jnp

        cfg = self._cfg
        with self._compact_lock:
            if mode == "auto":
                mode = ("extend" if cfg.kind in ("ivf_flat", "ivf_pq")
                        else "rebuild")
            expects(mode == "rebuild" or cfg.kind in ("ivf_flat", "ivf_pq"),
                    "%s has no extend(); use mode='rebuild'", cfg.kind)
            expects(ooc_chunk_rows is None or mode == "rebuild",
                    "ooc_chunk_rows= streams the REBUILD fold; extend "
                    "folds only the (small) delta — pass mode='rebuild'")
            t0 = time.perf_counter()
            with self._lock:
                st = self._state
                snap_n = st.delta_n
                d_src = np.nonzero(st.delta_alive[:snap_n])[0]
                fold_rows = st.delta[d_src].copy()
                fold_gids = st.delta_ids[d_src].astype(np.int64)
                if mode == "rebuild":
                    expects(st.store is not None,
                            "rebuild compaction needs the retained row store "
                            "(retain_vectors=True / dataset=)")
                    s_src = np.nonzero(st.sealed_alive)[0]

            # ---- heavy fold, off the hot path ----------------------------
            if mode == "extend":
                n_old = len(st.id_map)
                if len(d_src):
                    new_sealed = cfg.module.extend(
                        st.sealed, fold_rows,
                        new_ids=jnp.arange(n_old, n_old + len(d_src),
                                           dtype=jnp.int32),
                        res=res)
                else:
                    new_sealed = st.sealed
                new_id_map = np.concatenate([st.id_map, fold_gids])
                new_store = (np.concatenate([_store_rows(st.store),
                                             fold_rows])
                             if st.store is not None else None)
                reclaimed = 0
            else:
                live_rows = np.concatenate([_store_rows(st.store)[s_src],
                                            fold_rows])
                expects(live_rows.shape[0] > 0,
                        "compaction would leave an empty index")
                new_id_map = np.concatenate([st.id_map[s_src], fold_gids])
                new_store = live_rows
                reclaimed = len(st.id_map) - len(s_src)
                if ooc_chunk_rows is not None:
                    # out-of-core fold: the builder streams the live rows
                    # chunk by chunk (all four kinds take readers) — no
                    # whole-corpus device copy; a device pin is restored
                    # on the sealed result below like any off-device build
                    from ..core import chunked

                    x = chunked.ChunkedReader(
                        live_rows, chunk_rows=int(ooc_chunk_rows))
                else:
                    # committed input: a device-pinned shard rebuilds ON
                    # its own device (off the hot path either way)
                    x = _dev_put(cfg, live_rows)
                if self._builder is not None:
                    new_sealed = self._builder(x, res=res)
                    got_kind, _ = _resolve_kind(new_sealed)
                    expects(got_kind == cfg.kind,
                            "builder returned a %s index for a %s mutable "
                            "index", got_kind, cfg.kind)
                elif cfg.kind == "brute_force":
                    from ..neighbors import brute_force

                    new_sealed = brute_force.BruteForce(
                        cfg.metric, cfg.metric_arg).build(x)
                else:
                    ip = self._index_params
                    if cfg.kind == "cagra" and ip is None:
                        ip = cfg.module.IndexParams()
                    expects(ip is not None,
                            "rebuild compaction of %s needs index_params "
                            "(build configuration)", cfg.kind)
                    new_sealed = cfg.module.build(ip, x, res=res)
                if cfg.device is not None:
                    # a builder may construct off-device (e.g. a mesh-
                    # sharded build); the successor must land back on the
                    # shard's pin or the next search would mix committed
                    # devices in one program
                    if cfg.kind == "brute_force":
                        new_sealed.dataset = jax.device_put(
                            new_sealed.dataset, cfg.device)
                        new_sealed.hold_norms()
                    else:
                        new_sealed = jax.device_put(new_sealed, cfg.device)
            # materialize before the swap (BruteForce is not a pytree —
            # block on its dataset directly)
            if cfg.kind == "brute_force":
                jax.block_until_ready(new_sealed.dataset)
            else:
                jax.block_until_ready(jax.tree_util.tree_leaves(new_sealed))
            id_map_dev = _dev_put(cfg, new_id_map.astype(np.int32))
            # the successor's list layout (an O(n) host pass) off the lock
            layout = _list_layout(new_sealed, len(new_id_map))

            # ---- atomic swap ---------------------------------------------
            with self._lock:
                st = self._state
                nd = _StreamState(cfg)
                nd.sealed = new_sealed
                nd.id_map = new_id_map
                # tier residency MIGRATES through the fold-and-swap: the
                # successor epoch's store re-places with the predecessor's
                # residency (its promote still honors the budget — a
                # squeezed successor degrades to cold instead of failing
                # the compaction)
                nd.store = self._make_store(
                    new_store, epoch=st.epoch + 1,
                    residency=(st.store.residency
                               if isinstance(st.store, TieredStore)
                               else None))
                # alive bits re-read from the LIVE state: deletes that
                # landed mid-fold are preserved across the swap
                if mode == "extend":
                    nd.sealed_alive = np.concatenate(
                        [st.sealed_alive, st.delta_alive[d_src]])
                else:
                    nd.sealed_alive = np.concatenate(
                        [st.sealed_alive[s_src], st.delta_alive[d_src]])
                # re-based from the concatenated bitset (O(n) once per
                # fold, never per write)
                nd.sealed_dead_n = int(len(nd.sealed_alive)
                                       - nd.sealed_alive.sum())
                dt = _np_dtype(cfg.query_dtype)
                nd.delta = np.zeros((self.delta_capacity, cfg.dim), dt)
                nd.delta_ids = np.zeros(self.delta_capacity, np.int32)
                nd.delta_alive = np.zeros(self.delta_capacity, bool)
                rem = st.delta_n - snap_n
                if rem:
                    nd.delta[:rem] = st.delta[snap_n:st.delta_n]
                    nd.delta_ids[:rem] = st.delta_ids[snap_n:st.delta_n]
                    nd.delta_alive[:rem] = st.delta_alive[snap_n:st.delta_n]
                nd.delta_n = rem
                nd.delta_oldest_at = self._clock() if rem else None
                nd.epoch = st.epoch + 1
                nd.id_map_dev = id_map_dev
                _init_sealed_view(nd, layout)
                _refresh_delta(nd, self.delta_capacity)
                # location map: every live id points at its new slot
                self._loc = _build_loc(nd)
                old_state, self._state = st, nd
                # retirement audit: the pre-compaction epoch (and, when the
                # fold produced a successor index, the old sealed store)
                # SHOULD free once draining leases release it — a retired
                # entry still accounted is the leak obs.mem.audit() reports
                obs_mem.retire(old_state.mem)
                if isinstance(old_state.store, TieredStore):
                    # the pre-fold epoch's tier entry should free at drain
                    # like every other retired epoch allocation
                    old_state.store.retire()
                if nd.sealed is not old_state.sealed:
                    old_sealed_mem = self._sealed_mem
                    self._sealed_mem = obs_mem.account_index(
                        nd.sealed, name=cfg.name, shard=self._shard,
                        epoch=nd.epoch)
                    obs_mem.retire(old_sealed_mem)
                self._update_gauges(nd)
            report = {"mode": mode, "epoch": nd.epoch,
                      "folded": int(len(d_src)), "reclaimed": int(reclaimed),
                      "sealed_rows": int(len(nd.id_map)),
                      "delta_remaining": int(rem),
                      "wall_s": round(time.perf_counter() - t0, 3)}
            if self._wal is not None and self._snapshot_path is not None:
                # WAL truncation rides the compaction swap: the post-fold
                # state lands atomically at snapshot_path (save() also
                # resets the log once the rename is durable), so the WAL
                # never outgrows one epoch's writes
                save(self, self._snapshot_path)
                report["snapshot"] = self._snapshot_path
            return report


# -- serialization (raft_tpu/8 "stream" section) -----------------------------

def save(mutable: MutableIndex, path: str) -> None:
    """Serialize the FULL mutable state — sealed index, delta memtable,
    tombstone bitsets, id map — as one ``stream`` section (raft_tpu/10;
    /8 layout plus the WAL coordination seq). The sealed index rides
    embedded through its own module serializer (``write_index``), so its
    layout/back-compat rules are unchanged.

    ATOMIC: the bytes land in a same-directory temp file and replace
    ``path`` in one ``os.replace`` — a crash mid-save leaves the previous
    snapshot readable (:func:`raft_tpu.core.serialize.atomic_write`; the
    fault-injection suite pins it). When the index carries a WAL, the log
    is truncated AFTER the rename is durable: crash before the rename
    keeps old snapshot + full log, crash between rename and truncate keeps
    the new snapshot + a log whose records are all ≤ its ``wal_seq`` (and
    replay skips them) — no ordering loses an acknowledged write."""
    from ..core import serialize
    from ..core.serialize import (atomic_write, serialize_header,
                                  serialize_mdspan, serialize_scalar)

    with mutable._lock:
        st = mutable._state
        cfg = mutable._cfg
        with atomic_write(path) as f:
            serialize_header(f, "stream")
            serialize_scalar(f, cfg.kind)
            serialize_scalar(f, cfg.name)
            serialize_scalar(f, mutable.delta_capacity)
            serialize_scalar(f, int(mutable._next_id))
            if serialize.version_number(serialize.SERIALIZATION_VERSION) >= 10:
                serialize_scalar(f, int(mutable._wal_seq))
            serialize_scalar(f, int(st.delta_n))
            serialize_scalar(f, st.store is not None)
            if serialize.version_number(serialize.SERIALIZATION_VERSION) >= 12:
                # the decided tier layout (raft_tpu/12): storage policy +
                # the store's residency at save time, so load() restores
                # placement without re-deciding (TierPolicy itself is
                # runtime configuration, supplied fresh like search_params)
                serialize_scalar(f, mutable._storage)
                serialize_scalar(f, (st.store.residency
                                     if isinstance(st.store, TieredStore)
                                     else "device"))
            serialize_mdspan(f, st.id_map)
            serialize_mdspan(f, st.sealed_alive)
            serialize_mdspan(f, st.delta[:st.delta_n])
            serialize_mdspan(f, st.delta_ids[:st.delta_n])
            serialize_mdspan(f, st.delta_alive[:st.delta_n])
            if st.store is not None:
                serialize_mdspan(f, _store_rows(st.store))
            cfg.module.write_index(f, st.sealed)
        if mutable._wal is not None:
            mutable._wal.reset()


def load(path: str, *, search_params=None, index_params=None,
         builder: Callable | None = None, name: str | None = None,
         device=None, wal=None, snapshot_path: str | None = None,
         shard: int | None = None, tier: TierPolicy | None = None,
         clock: Callable[[], float] = time.monotonic) -> MutableIndex:
    """Load a :func:`save`d mutable index. ``search_params``/
    ``index_params``/``builder``/``device`` are runtime configuration (like
    every other index loader) and are supplied fresh here.

    ``wal`` (a path or :class:`~raft_tpu.stream.wal.WriteAheadLog`) is the
    crash-recovery entry: every intact record with ``seq`` past the
    snapshot's ``wal_seq`` replays through the ordinary write path (WAL
    appends suppressed — the records are already in the log), then the log
    re-attaches for new writes. ``m.last_recovery`` reports
    ``{replayed, skipped, torn}``; follow with ``warm()`` + a registry
    publish for the zero-cold-compile cold-start path (docs/streaming.md
    "Durability & replication"). ``snapshot_path`` re-arms the
    compaction-coupled snapshot+truncation (defaults to ``path`` whenever
    a WAL is given — recovering WITHOUT re-arming snapshots would let the
    log grow past what the next crash can afford to replay)."""
    from ..core.serialize import (check_header, deserialize_mdspan,
                                  deserialize_scalar, version_number)
    from ..neighbors import brute_force, cagra, ivf_flat, ivf_pq

    mods = {"brute_force": brute_force, "ivf_flat": ivf_flat,
            "ivf_pq": ivf_pq, "cagra": cagra}
    with open(path, "rb") as f:
        ver = check_header(f, "stream")
        kind = deserialize_scalar(f)
        saved_name = deserialize_scalar(f)
        capacity = int(deserialize_scalar(f))
        next_id = int(deserialize_scalar(f))
        wal_seq = (int(deserialize_scalar(f))
                   if version_number(ver) >= 10 else 0)
        delta_n = int(deserialize_scalar(f))
        has_store = bool(deserialize_scalar(f))
        storage, residency = "hbm", None
        if version_number(ver) >= 12:
            storage = deserialize_scalar(f)
            residency = deserialize_scalar(f)
        id_map = np.asarray(deserialize_mdspan(f))
        sealed_alive = np.asarray(deserialize_mdspan(f)).astype(bool)
        delta = np.asarray(deserialize_mdspan(f))
        delta_ids = np.asarray(deserialize_mdspan(f))
        delta_alive = np.asarray(deserialize_mdspan(f)).astype(bool)
        store = np.asarray(deserialize_mdspan(f)) if has_store else None
        sealed = mods[kind].read_index(f)

    if snapshot_path is None and wal is not None:
        snapshot_path = path
    # the SAVED placement threads into construction instead of being
    # re-decided: the layout is part of the snapshot (raft_tpu/12), so
    # load + WAL replay + warm() comes back exactly as placed — no
    # re-decision, no wasted upload-then-spill; a saved device residency
    # that no longer fits the budget degrades to cold (promote() never
    # raises), which the tier events make visible
    m = MutableIndex(sealed, search_params=search_params,
                     index_params=index_params, delta_capacity=capacity,
                     retain_vectors=has_store, dataset=store, builder=builder,
                     device=device, snapshot_path=snapshot_path, shard=shard,
                     storage=storage, tier=tier,
                     tier_residency=residency if storage == "tiered" else None,
                     name=saved_name if name is None else name, clock=clock)
    with m._lock:
        st = m._state
        st.id_map = id_map.astype(np.int64)
        st.sealed_alive = sealed_alive
        dead = np.flatnonzero(~sealed_alive)
        st.sealed_dead_n = int(dead.size)
        # the wrap built the all-alive view; the saved tombstones join it
        _kill_listed(st, dead)
        st.delta[:delta_n] = delta
        st.delta_ids[:delta_n] = delta_ids
        st.delta_alive[:delta_n] = delta_alive
        st.delta_n = delta_n
        # the restored delta's true write times are gone — age it from load
        # time (conservative: the Compactor's max_age_s watermark stays
        # armed for a restored non-empty delta instead of silently never
        # firing)
        st.delta_oldest_at = clock() if delta_n else None
        m._next_id = next_id
        st.id_map_dev = _dev_put(st.cfg, st.id_map.astype(np.int32))
        _publish_sealed(st)
        _refresh_delta(st, capacity)
        m._loc = _build_loc(st)
        m._update_gauges(st)
        m._wal_seq = wal_seq
    if wal is not None:
        if not hasattr(wal, "replay"):
            from .wal import WriteAheadLog

            wal = WriteAheadLog(wal, name=m.name)
        # replay through the ORDINARY write path (m._wal is still None, so
        # nothing re-appends): every acknowledged write past the snapshot
        # comes back with read-your-writes semantics intact
        replayed, last = 0, wal_seq
        for seq, op, rows, ids in wal.replay(after_seq=wal_seq):
            if op == "upsert":
                m.upsert(rows, ids=ids)
            else:
                m.delete(ids)
            replayed, last = replayed + 1, seq
        m.last_recovery = {
            "replayed": replayed,
            "skipped": wal.last_scan["records"] - replayed,
            "torn": wal.last_scan["torn"], "wal_seq": last}
        with m._lock:
            m._wal = wal
            m._wal_seq = last
    return m

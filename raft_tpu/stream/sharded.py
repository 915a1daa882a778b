"""ShardedMutableIndex: the mutable serve+stream lifecycle across a mesh.

Everything :class:`~raft_tpu.stream.MutableIndex` proved on one device —
delta memtable, tombstone bitsets, warm compaction swaps — composed S ways
into the production serving topology the distributed pieces already
justify: ``parallel/knn`` reproduces the reference's knn_merge_parts
contract (all_gather + select_k over per-shard candidates,
detail/knn_merge_parts.cuh), PR 3/6 measured shard-local graphs at zero
recall cost, and the FreshDiskANN lineage's fresh/sealed split shards
cleanly when compaction is staggered per shard. Three moving parts:

- **Hash-routed writes.** Every global id owns exactly one home shard
  (:func:`shard_of`, a stable SplitMix-style mix — independent of shard
  history, so a restart routes identically). Each shard is a full
  :class:`MutableIndex`: its own delta memtable, tombstone bitset, id map
  (``ids=`` carries the global ids, so shard-local sealed builds stay
  dense while results surface global ids) and — when a mesh is given —
  its own pinned device, which is what makes the scatter real: jax runs
  every per-shard program on the device its committed arrays live on.
- **Scatter-gather search.** A query batch fans to all shards (the
  per-shard scans dispatch WITHOUT materializing — jax's async dispatch
  overlaps them across devices), each shard contributes its sealed(k) and
  delta(≤k) candidate sets with global ids, and ALL ``2S`` parts merge
  through ONE ``select_k`` dispatch — the ``parallel/knn`` merge
  generalized to mixed sealed+delta parts. Candidates ride the
  interconnect; raw rows never do. Delta parts are padded to width k
  with the shared ``-1 / ±inf`` sentinel so the merge program is keyed on
  ``(m, 2S·k)`` alone — per-shard delta growth can never mint a new merge
  shape, which is what keeps the warmed ladder finite.
- **Staggered compaction.** :meth:`compact` folds ONE shard per call —
  the most-due one — through that shard's ordinary fold+swap; the other
  S−1 shards keep serving their current epochs untouched. A
  :class:`~raft_tpu.stream.Compactor` drives it unchanged (``stats()``
  reports the BINDING shard's watermarks: max fill, max tombstone ratio,
  oldest delta), so one ``run_once`` = one shard folded + one warm
  republish through the serve registry — there is never a global
  stop-the-world, and the publish warm covers the successor epoch's
  program set exactly like the single-device churn rows.

Serve integration is duck-typed end to end: ``serve.publish`` /
``make_searcher`` resolve this class exactly like a ``MutableIndex``
(``upsert``/``searcher`` attributes open the write path),
:meth:`exact_search` composes the shard-local exact scans through the same
one-dispatch merge so ``obs.quality.exact_oracle`` — and therefore the
RecallCanary and SLOTracker — work unchanged over the mesh, and
``obs.requestlog`` notes are prefixed ``stream/shard<i>/`` so a traced
flush names the epoch each shard answered from.

Consistency: per-shard reads/writes keep MutableIndex's guarantees
(read-your-writes, kill-then-reveal upserts); a cross-shard search
snapshots each shard's state independently, so a multi-row write that
spans shards may be half-visible to one racing read — the same anomaly
class as any read racing a write, documented in docs/streaming.md
("Sharded lifecycle").

Two late additions complete the availability axis (docs/streaming.md
"Elastic resharding" / "Durability & replication"):

- **Elastic resharding** (:meth:`ShardedMutableIndex.reshard`): online
  power-of-two split/merge. Because :func:`shard_of` routes by ``h % S``,
  doubling to ``2S`` sends every id homed on shard ``s`` to exactly ``s``
  or ``s + S`` — a split is a LOCAL fold of one donor shard into two
  successors (a merge the inverse), replayed shard-at-a-time through the
  same fold machinery compaction uses: donors keep serving (and accepting
  writes) while successors build off-lock, the new topology's whole
  program set warms BEFORE the flip (through the registry's pre-flip
  ``publish(warm_hook=)`` seam when a publisher drives it), writes that
  landed mid-migration carry over at the atomic id→shard-map swap exactly
  like compaction's mid-fold writes, and in-flight flushes finish on the
  topology they leased (retire-after-drain generalizes to whole donor
  shards).
- **Mesh-wide durability** (``wal_dir=``): one
  :class:`~raft_tpu.stream.wal.WriteAheadLog` per shard group, a
  per-shard atomic snapshot, and a topology MANIFEST (shard count,
  topology epoch, per-shard wal_seq) written through
  ``core.serialize.atomic_write`` — the manifest's rename is the durable
  commit point of both :meth:`ShardedMutableIndex.save` and a reshard, so
  recovery (:meth:`ShardedMutableIndex.load`) replays each shard's log
  against whichever topology the manifest committed. A crash between a
  successor swap and the manifest write recovers to the OLD topology with
  zero acknowledged-write loss (fault points ``reshard/split``,
  ``reshard/flip``, ``reshard/manifest``).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Callable, Sequence

import numpy as np

from ..core.errors import expects
from ..core.resources import default_resources
from ..obs import dispatch as obs_dispatch
from ..obs import events as obs_events
from ..obs import mem as obs_mem
from ..obs import metrics
from ..testing import faults
from . import mutable as _mut
from .mutable import DeltaFullError, MutableIndex
from .replicated import FencingPolicy, ReplicatedShard, _PinnedGroup

__all__ = ["ShardedMutableIndex", "shard_of"]

# the topology manifest's file name inside a mesh's wal_dir/save dir
_MANIFEST = "manifest"


# -- the one-dispatch merge --------------------------------------------------

@functools.cache
def _shard_jits():
    import jax
    import jax.numpy as jnp

    from ..core import tracing
    from ..matrix.select_k import _select_k

    @functools.partial(jax.jit, static_argnames=("k", "select_min"))
    def pad(d, i, k: int, select_min: bool):
        # widen a (m, kd<k) candidate set to width k with the shared
        # underfill sentinel (id -1 at ±inf): appended AFTER the real
        # candidates, so a stable select keeps the unpadded ordering —
        # the 1-shard bit-parity with MutableIndex's own merge rides on it
        m, kd = d.shape
        fill = jnp.inf if select_min else -jnp.inf
        return (jnp.concatenate([d, jnp.full((m, k - kd), fill, d.dtype)], 1),
                jnp.concatenate([i, jnp.full((m, k - kd), -1, i.dtype)], 1))

    @functools.partial(jax.jit, static_argnames=("k", "select_min"))
    def merge(ds: tuple, is_: tuple, k: int, select_min: bool):
        # the knn_merge_parts contract over 2S mixed sealed+delta parts,
        # every part pre-padded to width k so this program is keyed on
        # (m, 2S·k) alone — ONE _select_k dispatch per (bucket, k)
        with tracing.scope("stream.merge"):
            d = jnp.concatenate(ds, axis=1)
            i = jnp.concatenate(is_, axis=1)
            dv, iv = _select_k(d, i, k, select_min)
            return dv, jnp.where(jnp.isinf(dv), -1, iv)

    return pad, merge


def _pad_part(d, i, k: int, select_min: bool):
    obs_dispatch.note(1)
    return _shard_jits()[0](d, i, int(k), bool(select_min))


def _serving_scan(st, queries, k, res=None):
    """Per-shard serving scan: sealed width clamps to the shard's sealed
    rows (small shards contribute what they have; the merge pads)."""
    return _mut._scan_state(st, queries, k, res=res,
                            k_sealed=min(int(k), st.id_map.shape[0]))


def _view_scan(view, queries, k, res=None):
    """Per-shard scan over a pinned view: a plain shard's state runs the
    single-replica scan; a replica group's pinned view routes through its
    health-picked twin with same-flush failover."""
    if isinstance(view, _PinnedGroup):
        return view.scan_serving(queries, k, res=res)
    return _serving_scan(view, queries, k, res=res)


def _merge_parts(ds, is_, k: int, select_min: bool):
    obs_dispatch.note(1)
    return _shard_jits()[1](tuple(ds), tuple(is_), int(k), bool(select_min))


def _resident_on(x, device) -> bool:
    """Whether a candidate part already lives (committed) on ``device`` —
    the skip test of the fused gather. Anything that cannot prove
    residency moves (moving is always correct; skipping is the
    optimization)."""
    try:
        devs = x.devices()
        return len(devs) == 1 and next(iter(devs)) == device
    except Exception:  # non-jax arrays (host numpy parts) always move
        return False


def _gather_parts(parts_d, parts_i, device):
    """The one merge-device gather, shared by the serving scatter-gather
    and the warm ladder: move candidate parts onto ``device`` for the
    single cross-shard ``_select_k`` merge, SKIPPING parts already
    resident there (shard 0's candidates live on the merge device — the
    old per-call ``device_put`` of every part re-dispatched 4S transfers
    per flush, S of them no-ops) and batching the movers into ONE
    ``device_put`` call. Returns ``(parts_d, parts_i, moved)`` where
    ``moved`` counts the arrays that actually crossed devices."""
    if device is None:
        return parts_d, parts_i, 0
    import jax

    arrays = list(parts_d) + list(parts_i)
    move = [j for j, a in enumerate(arrays) if not _resident_on(a, device)]
    if move:
        placed = jax.device_put(tuple(arrays[j] for j in move), device)
        for j, a in zip(move, placed):
            arrays[j] = a
        obs_dispatch.note(len(move))
    s = len(parts_d)
    return arrays[:s], arrays[s:], len(move)


@functools.lru_cache(maxsize=None)
def _g_shards():
    return metrics.gauge(
        "raft_tpu_stream_shards",
        "shard count of a sharded mutable index (per-shard series report "
        "under name/shard<i>)")


@functools.lru_cache(maxsize=None)
def _c_migrations():
    return metrics.counter(
        "raft_tpu_reshard_migrations_total",
        "reshard migrations by action (split/merge) and phase "
        "(started/completed) — started without completed is an aborted "
        "or crashed migration, which recovery resolves to the old "
        "topology")


@functools.lru_cache(maxsize=None)
def _c_rows_moved():
    return metrics.counter(
        "raft_tpu_reshard_rows_moved_total",
        "live rows folded from donor shards into reshard successors",
        unit="rows")


@functools.lru_cache(maxsize=None)
def _h_reshard():
    return metrics.histogram(
        "raft_tpu_reshard_seconds",
        "one reshard step's wall seconds (fold + warm + carry-over + "
        "flip + manifest, off the serving hot path)", unit="seconds")


def shard_of(ids, n_shards: int):
    """Stable home shard of each global id: a SplitMix64-style avalanche
    mix mod the shard count — independent of insertion order or shard
    state, so routing is reproducible across processes and restarts
    (the contract a router in front of a real fleet would share)."""
    h = np.asarray(ids, np.uint64)
    h = (h + np.uint64(0x9E3779B97F4A7C15))
    h ^= h >> np.uint64(30)
    h *= np.uint64(0xBF58476D1CE4E5B9)
    h ^= h >> np.uint64(27)
    h *= np.uint64(0x94D049BB133111EB)
    h ^= h >> np.uint64(31)
    return (h % np.uint64(n_shards)).astype(np.int64)


class ShardedMutableIndex:
    """Mesh-wide mutable index (see module docstring).

    ``dataset`` (n, d) rows are routed to ``n_shards`` home shards by
    :func:`shard_of` over their global ids (``ids=``, default the dense
    row range) and each shard's sealed index is built by ``build`` — any
    ``fn(rows) -> sealed index`` (size per-shard knobs like ``n_lists`` /
    ``n_probes`` / ``itopk`` for rows/S shards, see docs/using_comms.md
    "Serving-tier sizing"). Every shard must own at least one row.

    ``devices`` pins shard ``s`` to ``devices[s]`` (pass ``comms=`` to take
    the mesh's devices) — candidates then gather onto ``devices[0]`` for
    the merge; without a pin everything stays on the default device and
    only the search-composition semantics remain (the 1-shard twin of a
    plain MutableIndex, bit-equal by the parity suite).

    ``search_params`` / ``index_params`` / ``builder`` / ``delta_capacity``
    (per shard) / ``retain_vectors`` / ``clock`` forward to every shard's
    :class:`MutableIndex`. The retained row store defaults ON (the
    constructor holds each shard's rows anyway), so rebuild compaction,
    :meth:`exact_search` AND :meth:`reshard` work out of the box; pass
    ``retain_vectors=False`` to drop it.

    ``wal_dir`` arms mesh-wide durability: one write-ahead log per shard
    group (``<wal_dir>/shard<i>.e<epoch>.wal``, logging every acknowledged
    write at admission), per-shard atomic snapshots, and the topology
    manifest — written at construction, so the mesh is recoverable
    (:meth:`load`) from its very first acknowledged write. Per-shard WAL
    truncation saw-tooths with each shard's compaction fold (the shards'
    ``snapshot_path`` is armed automatically), and a :meth:`reshard`
    commits durably through the manifest. The directory must be fresh or
    belong to this mesh's previous life recovered via :meth:`load` — a
    directory holding unrecovered records is refused (shadowing them
    would lose acknowledged writes).
    """

    def __init__(self, dataset, *, n_shards: int, build: Callable,
                 ids=None, search_params=None, index_params=None,
                 builder: Callable | None = None,
                 delta_capacity: int = 1024,
                 retain_vectors: bool | None = None,
                 devices: Sequence | None = None, comms=None,
                 replicas: int = 1,
                 fencing: FencingPolicy | None = None,
                 wal_dir: str | None = None,
                 name: str = "default",
                 storage: str = "hbm", tier=None,
                 clock: Callable[[], float] = time.monotonic):
        from ..core import chunked

        # a ChunkedReader corpus (the out-of-core build path) shards
        # WITHOUT a whole-corpus RAM copy: each shard gathers only its
        # own rows off the reader (memmap pages fault per shard)
        stream = chunked.is_reader(dataset)
        if not stream:
            dataset = np.asarray(dataset)
        expects(dataset.ndim == 2, "dataset must be (rows, d)")
        n = int(dataset.shape[0])
        n_shards = int(n_shards)
        expects(n_shards >= 1, "n_shards must be >= 1, got %d", n_shards)
        if ids is None:
            gids = np.arange(n, dtype=np.int64)
        else:
            gids = np.asarray(ids, np.int64).reshape(-1)
            expects(gids.shape == (n,), "ids= must match dataset rows (%d)", n)
        if comms is not None:
            expects(devices is None, "pass devices= or comms=, not both")
            devices = list(comms.mesh.devices.flat)
        if devices is not None:
            devices = list(devices)
            expects(len(devices) >= n_shards,
                    "%d shards need %d devices, got %d", n_shards, n_shards,
                    len(devices))
        owner = shard_of(gids, n_shards)
        self._name = name
        self._clock = clock  # Compactor inherits it (one age time base)
        self._lock = threading.RLock()
        self._compact_lock = threading.Lock()
        R = int(replicas)
        expects(R >= 1, "replicas must be >= 1, got %d", R)
        if R > 1 and devices is not None:
            # twins of one shard land on devices[(s*R + j) % D]: j1 and j2
            # collide iff D divides j1-j2, i.e. iff D < R — and co-located
            # twins silently void the device anti-affinity the replica
            # groups promise (pass devices=None for unpinned twins)
            expects(len(devices) >= R,
                    "replica anti-affinity needs >= %d devices so twins "
                    "of one shard land on different devices, got %d",
                    R, len(devices))
        # the shard build recipe, retained whole: reshard successors are
        # built with EXACTLY what the originals were
        self._build_fn = build
        self._search_params = search_params
        self._index_params = index_params
        self._builder = builder
        self._delta_capacity = int(delta_capacity)
        self._retain_vectors = retain_vectors
        # the beyond-HBM policy, per shard: every shard's MutableIndex gets
        # its own TieredStore, so mesh capacity = shards x (HBM + host)
        self._storage = storage
        self._tier = tier
        self._devices = devices
        self._replicas_n = R
        self._fencing = fencing
        self._topology_epoch = 0
        self._migration: dict | None = None
        self._wal_dir = os.fspath(wal_dir) if wal_dir is not None else None
        if self._wal_dir is not None:
            os.makedirs(self._wal_dir, exist_ok=True)
            # a directory with a committed manifest belongs to an earlier
            # life of a mesh — possibly at a DIFFERENT topology epoch, so
            # the per-shard WAL probe below would miss its files entirely
            # and the construction-time save() would orphan every
            # acknowledged write behind a fresh epoch-0 manifest
            expects(not os.path.exists(
                os.path.join(self._wal_dir, _MANIFEST)),
                "wal_dir %r already holds a mesh manifest — recover that "
                "mesh with ShardedMutableIndex.load() (a fresh mesh here "
                "would shadow its acknowledged writes) or point at a "
                "fresh directory", self._wal_dir)
        self._shards: list = []
        for s in range(n_shards):
            rows_idx = np.nonzero(owner == s)[0]
            expects(len(rows_idx) > 0,
                    "shard %d of %d owns no rows (n=%d) — use fewer shards",
                    s, n_shards, n)
            wal_path = snap_path = None
            if self._wal_dir is not None:
                snap_path, wal_path = self._shard_files(s)
            rows_s = (dataset.take(rows_idx) if stream
                      else dataset[rows_idx])
            self._shards.append(self._make_shard(
                rows_s, gids[rows_idx], s, n_shards,
                wal=wal_path, snapshot_path=snap_path))
        self._next_id = int(gids.max()) + 1 if n else 0
        self._finish_init()
        if self._wal_dir is not None:
            # durable by construction: the baseline snapshots + manifest
            # land before the first write can be acknowledged, so load()
            # works from the very first WAL record
            self.save()

    @staticmethod
    def _shard_names(s: int, e: int) -> tuple:
        """(snapshot, wal) FILE NAMES of shard ``s`` at topology epoch
        ``e`` — the one place the naming scheme lives: construction,
        save(), the manifest and the reshard commit all derive from here,
        so the manifest can never desynchronize from the files on disk."""
        return f"shard{s}.e{e}.idx", f"shard{s}.e{e}.wal"

    def _shard_files(self, s: int, epoch: int | None = None,
                     dir: str | None = None) -> tuple:
        """(snapshot, wal) paths of shard ``s`` at a topology epoch —
        epoch-keyed so a mid-reshard crash can never confuse the old
        topology's files with a half-written successor set."""
        e = self._topology_epoch if epoch is None else int(epoch)
        sn, wn = self._shard_names(s, e)
        d = self._wal_dir if dir is None else dir
        return os.path.join(d, sn), os.path.join(d, wn)

    def _make_shard(self, rows_s, gids_s, s: int, total: int, *,
                    wal=None, snapshot_path=None):
        """Build one home shard at ordinal ``s`` of a ``total``-shard
        topology — the ONE recipe shared by construction and resharding.
        Past the construction-time device floor, ordinals pin modulo the
        device list (a split beyond the mesh size co-locates successors,
        trading isolation for capacity — documented in streaming.md)."""
        sealed = self._build_fn(rows_s)
        devices = self._devices
        if self._replicas_n == 1:
            return MutableIndex(
                sealed, search_params=self._search_params,
                index_params=self._index_params,
                delta_capacity=self._delta_capacity,
                # the constructor holds the shard's raw rows either way,
                # so retention costs no extra recover pass; False opts out
                retain_vectors=self._retain_vectors,
                dataset=(None if self._retain_vectors is False else rows_s),
                builder=self._builder, ids=gids_s,
                device=(devices[s % len(devices)] if devices is not None
                        else None),
                wal=wal, snapshot_path=snapshot_path,
                storage=self._storage, tier=self._tier,
                name=f"{self._name}/shard{s}", shard=s, clock=self._clock)
        # replica j of shard s lands on devices[s*R + j] (mod the mesh):
        # twins of one shard live on DIFFERENT devices — the anti-affinity
        # that makes a group survive a device
        R = self._replicas_n
        return ReplicatedShard(
            sealed, n_replicas=R,
            devices=([devices[(s * R + j) % len(devices)]
                      for j in range(R)] if devices is not None else None),
            search_params=self._search_params,
            index_params=self._index_params,
            delta_capacity=self._delta_capacity,
            retain_vectors=self._retain_vectors,
            dataset=(None if self._retain_vectors is False else rows_s),
            builder=self._builder, ids=gids_s,
            policy=self._fencing or FencingPolicy(),
            wal=wal, snapshot_path=snapshot_path,
            storage=self._storage, tier=self._tier,
            name=f"{self._name}/shard{s}", shard=s, clock=self._clock)

    def _finish_init(self) -> None:
        """Shared tail of ``__init__`` and :meth:`load`: cross-shard
        config consistency, merge-device pin, gauge baseline."""
        cfg0 = self._shards[0]._cfg
        for s, sh in enumerate(self._shards[1:], 1):
            expects(sh._cfg.kind == cfg0.kind and sh._cfg.dim == cfg0.dim
                    and sh._cfg.query_dtype == cfg0.query_dtype,
                    "shard %d built a (%s, %d, %s) index but shard 0 is "
                    "(%s, %d, %s) — build must be deterministic in kind",
                    s, sh._cfg.kind, sh._cfg.dim, sh._cfg.query_dtype,
                    cfg0.kind, cfg0.dim, cfg0.query_dtype)
        self._select_min = cfg0.select_min
        self._merge_device = (self._devices[0]
                              if self._devices is not None else None)
        self._update_gauges()

    # -- introspection ------------------------------------------------------
    @property
    def kind(self) -> str:
        return self._shards[0].kind

    @property
    def dim(self) -> int:
        return self._shards[0].dim

    @property
    def name(self) -> str:
        return self._name

    @property
    def query_dtype(self) -> str:
        return self._shards[0].query_dtype

    @property
    def n_shards(self) -> int:
        return len(self._shards)

    @property
    def shards(self) -> tuple:
        """The per-shard :class:`MutableIndex` objects (read-only tuple —
        write through the sharded surface so routing stays consistent)."""
        return tuple(self._shards)

    @property
    def can_rebuild(self) -> bool:
        return all(sh.can_rebuild for sh in self._shards)

    @property
    def size(self) -> int:
        return sum(sh.size for sh in self._shards)

    def stats(self) -> dict:
        """Aggregated view + ``per_shard`` detail. The scalar watermarks a
        :class:`~raft_tpu.stream.Compactor` reads are the BINDING shard's:
        ``delta_fill`` / ``tombstone_ratio`` are maxima (the shard that
        will hit the wall first) and ``delta_oldest_at`` the minimum (the
        stalest write) — so an aggregate watermark trips exactly when some
        shard needs a fold, and :meth:`compact` folds that shard."""
        per = [sh.stats() for sh in self._shards]
        oldest = [p["delta_oldest_at"] for p in per
                  if p["delta_oldest_at"] is not None]
        return {
            "live": sum(p["live"] for p in per),
            "sealed_rows": sum(p["sealed_rows"] for p in per),
            "sealed_dead": sum(p["sealed_dead"] for p in per),
            "tombstone_ratio": max(p["tombstone_ratio"] for p in per),
            "delta_rows": sum(p["delta_rows"] for p in per),
            "delta_fill": max(p["delta_fill"] for p in per),
            "delta_oldest_at": min(oldest) if oldest else None,
            "epoch": sum(p["epoch"] for p in per),
            "shards": len(per),
            "per_shard": per,
            # replica-group detail (replicas=1: every shard is its own
            # single healthy "replica"): healthy is the WORST shard's
            # pickable-twin count — the availability binding constraint
            **({"replicas": sum(p.get("replicas", 1) for p in per),
                "healthy": min(p.get("healthy", 1) for p in per),
                "stale": sum(p.get("stale", 0) for p in per)}
               if any("replicas" in p for p in per) else {}),
        }

    def health(self) -> dict:
        """Per-shard replica-group health for ``/healthz``
        (``obs.start_http_exporter(replicas=...)``): each group's breaker
        detail plus the mesh verdict — a shard with ZERO pickable twins
        means queries to it fail, which is an outage, not degradation."""
        shards = [sh.health() if isinstance(sh, ReplicatedShard)
                  else {"name": sh.name, "replicas": [], "healthy": 1}
                  for sh in self._shards]
        with self._lock:
            migration = (dict(self._migration)
                         if self._migration is not None else None)
        return {"name": self._name, "shards": shards,
                "healthy_min": min(s["healthy"] for s in shards),
                # live topology-migration state (None outside a reshard):
                # folds into /healthz via obs.start_http_exporter(replicas=)
                "reshard": migration}

    def _update_gauges(self, st: dict | None = None) -> None:
        if not metrics._enabled:
            return
        st = self.stats() if st is None else st
        name = self._name
        _g_shards().set(st["shards"], name=name)
        # the aggregate rides the same stream gauges under the parent name
        # (per-shard series report under name/shard<i> already)
        _mut._g_delta_fill().set(st["delta_fill"], name=name)
        _mut._g_delta_rows().set(st["delta_rows"], name=name)
        _mut._g_tombstone().set(st["tombstone_ratio"], name=name)

    def _drift_store(self):
        """Cross-shard corpus sample for the drift detector: an interleave
        of every shard's retained rows (bounded — the classifier subsamples
        downstream anyway); None when any shard dropped its store."""
        stores = [sh._drift_store() for sh in self._shards]
        if any(s is None for s in stores):
            return None
        cap = max(4096 // len(stores), 256)
        return np.concatenate([s[:cap] for s in stores])

    # -- writes -------------------------------------------------------------
    def upsert(self, rows, ids=None, res=None):
        """Insert/upsert rows, each routed to its global id's home shard.
        Admission is checked across ALL touched shards BEFORE any row
        lands (writes go through this serialized surface, so the check is
        exact): one full home shard refuses the whole call with
        :class:`~raft_tpu.stream.DeltaFullError`, and the summed device
        growth of every touched shard's delta bucket is checked against
        ``res.memory_budget_bytes`` in the same hoisted pass
        (:class:`~raft_tpu.serve.errors.MemoryBudgetError`) — either way
        nothing is written, the same whole-or-nothing contract as a single
        shard's upsert."""
        # validate ONCE up front (dim + dtype through shard 0's rules): a
        # per-shard refusal after a sibling already accepted its group
        # would break the whole-or-nothing contract
        rows = self._shards[0]._coerce_rows(rows)
        r = rows.shape[0]
        expects(r >= 1, "upsert needs at least one row")
        with self._lock:
            if ids is None:
                gids = np.arange(self._next_id, self._next_id + r,
                                 dtype=np.int64)
            else:
                gids = _mut.check_upsert_ids(ids, r)
            self._next_id = max(self._next_id, int(gids.max()) + 1)
            owner = shard_of(gids, len(self._shards))
            groups = [np.nonzero(owner == s)[0]
                      for s in range(len(self._shards))]
            for s, idx in enumerate(groups):
                sh = self._shards[s]
                # concurrent folds only SHRINK a delta, so a stale read
                # here can only over-refuse, never admit past capacity
                if len(idx) and (sh._delta_rows_now() + len(idx)
                                 > sh.delta_capacity):
                    if metrics._enabled:
                        _mut._c_delta_full().inc(1, name=self._name)
                    raise DeltaFullError(
                        f"shard {s} delta at {sh._delta_rows_now()}"
                        f"/{sh.delta_capacity} rows; upsert routing "
                        f"{len(idx)} there refused — compact() (or attach "
                        "a stream.Compactor) to fold it")
            # memory-budget admission, hoisted like the capacity check: the
            # SUMMED bucket growth across home shards (and, for replica
            # groups, across every live twin) gates before any shard
            # writes (cross-shard whole-or-nothing)
            obs_mem.gate(
                res or default_resources(),
                lambda: sum(
                    self._shards[s]._growth_bytes(len(idx))
                    for s, idx in enumerate(groups) if len(idx)),
                site="upsert", detail=f"stream/sharded {self._name!r}")
            # the hoisted pass IS the admission decision: the per-shard
            # upserts get a budget-free res so their gates cannot refuse
            # mid-write — a stricter ambient default, or concurrent ledger
            # growth between the hoisted admit and shard s's write (another
            # name's publish, an off-lock fold's double-buffer), would
            # otherwise land a partial cross-shard write
            inner = res or default_resources()
            if getattr(inner, "memory_budget_bytes", None) is not None:
                inner = dataclasses.replace(inner, memory_budget_bytes=None)
            for s, idx in enumerate(groups):
                if len(idx):
                    self._shards[s].upsert(rows[idx], ids=gids[idx],
                                           res=inner)
            self._update_gauges()
        return gids

    def delete(self, ids) -> int:
        """Tombstone ids on their home shards; returns how many were live.
        Unknown or already-dead ids are a counted no-op, not an error."""
        arr = np.asarray(ids, np.int64).reshape(-1)
        if arr.size == 0:
            return 0
        with self._lock:
            owner = shard_of(arr, len(self._shards))
            killed = 0
            for s in range(len(self._shards)):
                idx = np.nonzero(owner == s)[0]
                if len(idx):
                    killed += self._shards[s].delete(arr[idx])
            self._update_gauges()
        return killed

    # -- reads --------------------------------------------------------------
    def _scatter_gather(self, states, queries, k: int, scan, res=None):
        """Fan ``queries`` to every shard state (async dispatch — jax
        overlaps the per-shard programs across their pinned devices),
        collect each shard's sealed + delta candidate sets, and merge all
        ``2S`` parts through ONE ``select_k`` dispatch. ``scan`` is the
        per-state scan half (serving: :func:`mutable._scan_state`; oracle:
        the bound ``_exact_scan``). The gather moves ONLY the parts not
        already resident on the merge device, in one ``device_put``
        (:func:`_gather_parts`), and the flush's dispatch count rides the
        obs dispatch meter + the ``stream_moved_parts`` trace note so the
        fusion win is attributable per flush."""
        from ..obs import requestlog

        k = int(k)
        parts_d, parts_i = [], []
        for s, st in enumerate(states):
            with requestlog.prefix(f"stream/shard{s}/"):
                sd, si, dd, di = scan(st, queries, k, res=res)
            for d, i in ((sd, si), (dd, di)):
                if d.shape[1] < k:  # delta buckets (and tiny oracle
                    # stores) can be narrower than k — pad on the shard's
                    # device so the merge shape below is invariant
                    d, i = _pad_part(d, i, k, self._select_min)
                parts_d.append(d)
                parts_i.append(i)
        # the gather: ONLY the (m, k) candidate tuples cross devices, and
        # only the non-resident ones move
        parts_d, parts_i, moved = _gather_parts(parts_d, parts_i,
                                                self._merge_device)
        out = _merge_parts(parts_d, parts_i, k, self._select_min)
        requestlog.annotate("stream_shards", len(states))
        requestlog.annotate("stream_moved_parts", moved)
        return out

    def search(self, queries, k: int, res=None):
        """Scatter-gather search over every shard's (sealed − tombstones)
        + delta; returns ``(distances (m, k), global ids (m, k))`` with the
        shared ``id -1 / ±inf`` sentinel in slots the live rows cannot
        fill. Identical result contract to :meth:`MutableIndex.search` —
        the 1-shard composition is bit-equal to a plain MutableIndex
        (pinned by the parity suite). A shard smaller than k contributes
        every sealed row it has (``k_sealed`` clamp) and the merge pads.
        With ``replicas > 1`` each shard's scan routes through its replica
        group's health-picked twin, failing over within this same call —
        one fenced replica degrades capacity, never the query."""
        return self._scatter_gather(self._views(), queries, k,
                                    _view_scan, res=res)

    def _views(self) -> tuple:
        """Per-shard read views: a plain shard pins its current state
        epoch; a replica group pins EVERY twin's epoch behind the live
        failover pick (:meth:`ReplicatedShard.pin_group`)."""
        return tuple(sh.pin_group() if isinstance(sh, ReplicatedShard)
                     else sh._state for sh in self._shards)

    def exact_search(self, queries, k: int, res=None):
        """EXACT fused kNN over the whole mesh's live corpus — shard-local
        exact store+delta scans composed through the same one-dispatch
        merge as :meth:`search`, so the RecallCanary's shadow oracle
        (``obs.quality.exact_oracle``) covers the sharded tier unchanged.
        Needs every shard's retained store."""
        shards = tuple(self._shards)

        def scan(sh, q, kk, res=None):
            return sh._exact_scan(q, kk, res=res)

        return self._scatter_gather(shards, queries, k, scan, res=res)

    def search_refined(self, queries, k: int, refine_ratio: int = 4,
                       res=None):
        """Scatter-gather :meth:`MutableIndex.search_refined` over the
        mesh: each shard widens its PQ scan to ``k * refine_ratio``,
        refines against its OWN tiered store (the per-shard host hop —
        mesh refine capacity is shards × host bandwidth), and the
        per-shard refined + delta parts merge through the same one
        ``select_k`` dispatch as :meth:`search`. The 1-shard composition
        is bit-equal to the plain index's ``search_refined`` (parity
        suite)."""
        shards = tuple(self._shards)
        expects(all(not isinstance(sh, ReplicatedShard) for sh in shards),
                "search_refined does not route replica groups yet — "
                "serve replicas=1 shards tiered, or use search()")

        def scan(sh, q, kk, res=None):
            return sh._refined_scan(q, kk, refine_ratio, res=res)

        return self._scatter_gather(shards, queries, k, scan, res=res)

    def refined_searcher(self, refine_ratio: int = 4):
        """Serving hook over :meth:`search_refined` (the
        ``batched_searcher`` contract) — the sharded twin of
        :meth:`MutableIndex.refined_searcher`: every shard's CURRENT
        state epoch is pinned at hook creation (the same lease-drain
        semantics as :meth:`searcher` — a staggered compaction or a
        reshard flip freezes the leased hook's view; republish picks up
        the successor)."""
        from ..neighbors._hooks import make_hook

        shards = tuple(self._shards)
        expects(all(not isinstance(sh, ReplicatedShard) for sh in shards),
                "refined_searcher does not route replica groups yet — "
                "serve replicas=1 shards tiered, or use searcher()")
        pinned = tuple((sh, sh._state) for sh in shards)
        cfg0 = shards[0]._cfg

        def scan(pin, q, kk, res=None):
            sh, st = pin
            return sh._refined_scan(q, kk, refine_ratio, res=res, st=st)

        fn = make_hook(
            lambda queries, k: self._scatter_gather(pinned, queries, k,
                                                    scan),
            f"stream/sharded/{cfg0.kind}+refine", cfg0.dim, cfg0.data_kind)
        fn.mutable = self
        return fn

    def searcher(self):
        """Serving hook pinned to every shard's CURRENT state epoch (the
        ``batched_searcher`` contract). A staggered compaction freezes only
        the folded shard's epoch inside an already-issued hook; republish
        (what the Compactor does per fold) picks up the successor — the
        same lease-drain semantics as the single-device flow, per shard.
        A reshard generalizes this to whole shards: hooks issued on the
        old topology keep serving the donor shards' frozen views until
        their leases drain."""
        return self._searcher_for(tuple(self._shards))

    def _searcher_for(self, shards):
        """The serving hook over an explicit shard list — what
        :meth:`reshard` publishes for the successor topology BEFORE the
        flip, so the registry's bucket warm compiles the new program set
        while the old topology still serves."""
        from ..neighbors._hooks import make_hook

        states = tuple(sh.pin_group() if isinstance(sh, ReplicatedShard)
                       else sh._state for sh in shards)
        cfg0 = shards[0]._cfg
        fn = make_hook(
            lambda queries, k: self._scatter_gather(
                states, queries, k, _view_scan),
            f"stream/sharded/{cfg0.kind}", cfg0.dim, cfg0.data_kind)
        # marker for the serve write path (SearchService.publish follows it
        # across compaction republishes, exactly like MutableIndex's hook)
        fn.mutable = self
        return fn

    # -- warmup -------------------------------------------------------------
    def warm(self, buckets, ks=(10,), sample=None) -> dict:
        """Compile the sharded delta-ladder program set: every shard's
        exact delta scan at every memtable bucket × (query bucket, k) —
        each ON its pinned device (placement is part of the program key) —
        plus the pad programs and the ONE cross-shard merge at its fixed
        ``(m, 2S·k)`` shape. Sealed-side programs are warmed per epoch by
        ``registry.publish`` (which runs the full hook), exactly like the
        single-device flow. Returns per-(k, bucket) compile attribution."""
        return self._warm_impl(tuple(self._shards), buckets, ks=ks,
                               sample=sample)

    def _warm_impl(self, shards, buckets, ks=(10,), sample=None) -> dict:
        """:meth:`warm` over an explicit shard list — :meth:`reshard`
        warms its successors' ladder (and the successor-count merge)
        through this BEFORE the topology flip."""
        import jax

        from .._warmup import _random_queries
        from ..obs import compile as obs_compile
        from ..neighbors import brute_force

        out: dict = {}
        key = jax.random.key(0)
        for kk in sorted(set(int(x) for x in ks)):
            out[kk] = {}
            for b in sorted(set(int(x) for x in buckets)):
                key, kq = jax.random.split(key)
                q = _random_queries(kq, b, self.dim, self.query_dtype,
                                    sample=sample)
                t0 = time.perf_counter()
                with obs_compile.attribution() as rec:
                    parts_d, parts_i = [], []
                    for sh in shards:
                        # a replica group warms EVERY twin's ladder on its
                        # own pinned device (placement is part of the
                        # program key): failover must never cold-compile —
                        # a twin that was never picked has to be hot the
                        # moment its sibling is fenced. Any twin's parts
                        # feed the merge (the gather re-places them).
                        units = (sh.replicas
                                 if isinstance(sh, ReplicatedShard)
                                 else (sh,))
                        for u in units:
                            cfg = u._cfg
                            dt = _mut._np_dtype(cfg.query_dtype)
                            sd = _mut._dev_put(
                                cfg, np.zeros((b, kk), np.float32))
                            si = _mut._dev_put(
                                cfg, np.full((b, kk), -1, np.int32))
                            dd = di = None
                            for db in u._buckets:
                                dummy = _mut._dev_put(
                                    cfg, np.zeros((db, cfg.dim), dt))
                                keep = _mut._dev_put(
                                    cfg, np.zeros((db,), bool))
                                dd, di = brute_force.knn(
                                    dummy, q, min(kk, db), cfg.metric,
                                    cfg.metric_arg, sample_filter=keep)
                                di = _mut._map_ids(di, _mut._dev_put(
                                    cfg, np.zeros((db,), np.int32)))
                                if dd.shape[1] < kk:  # same pad rule as
                                    # _scatter_gather — per (width, device)
                                    dd, di = _pad_part(dd, di, kk,
                                                       self._select_min)
                                jax.block_until_ready((dd, di))
                        parts_d += [sd, dd]
                        parts_i += [si, di]
                    parts_d, parts_i, _ = _gather_parts(
                        parts_d, parts_i, self._merge_device)
                    jax.block_until_ready(_merge_parts(
                        parts_d, parts_i, kk, self._select_min))
                out[kk][b] = {"wall_s": round(time.perf_counter() - t0, 3),
                              **rec.summary()}
        return out

    # -- compaction ---------------------------------------------------------
    def _pick_shard(self, mode: str, trigger: str | None = None) -> int:
        """The most-due shard for one staggered fold: rebuilds (and
        tombstone trips) chase the highest tombstone ratio, an AGE trip
        chases the stalest non-empty delta — picking the fullest there
        would starve a quiet shard forever while its age watermark stays
        tripped — and everything else chases the fullest delta; ties break
        low."""
        per = [sh.stats() for sh in self._shards]
        if mode == "rebuild" or trigger == "tombstone_ratio":
            ratios = [p["tombstone_ratio"] for p in per]
            if max(ratios) > 0:
                return int(np.argmax(ratios))
        if trigger == "age":
            ages = [(p["delta_oldest_at"], s) for s, p in enumerate(per)
                    if p["delta_oldest_at"] is not None]
            if ages:
                return min(ages)[1]
        return int(np.argmax([p["delta_rows"] for p in per]))

    def compact(self, mode: str = "auto", shard: int | None = None,
                res=None, trigger: str | None = None,
                ooc_chunk_rows: int | None = None) -> dict:
        """Fold ONE shard (the most-due, or an explicit ``shard=``) through
        its ordinary fold+swap — the staggered step: the other shards keep
        serving their epochs untouched, and a Compactor loop folds shard
        after shard while its watermark stays tripped, republishing between
        folds (the Compactor forwards its tripped ``trigger`` so the pick
        chases the right shard). ``ooc_chunk_rows`` forwards to the shard's
        :meth:`MutableIndex.compact` — a rebuild fold then streams the
        shard's live rows through the out-of-core build path instead of
        one device-resident array. Returns the shard's compaction report
        plus ``shard`` and the aggregate ``epoch``."""
        with self._compact_lock:
            if shard is None:
                shard = self._pick_shard(mode, trigger)
            shard = int(shard)
            expects(0 <= shard < len(self._shards),
                    "shard %d out of range (%d shards)", shard,
                    len(self._shards))
            report = self._shards[shard].compact(
                mode=mode, res=res, ooc_chunk_rows=ooc_chunk_rows)
            report["shard"] = shard
            report["shard_epoch"] = report["epoch"]
            agg = self.stats()
            report["epoch"] = agg["epoch"]  # aggregate fold count
            self._update_gauges(agg)
            return report

    # -- elastic resharding --------------------------------------------------
    def reshard(self, n_shards: int, *, publisher=None,
                name: str | None = None, ks=(10,), warm_buckets=None,
                warm_data=None, res=None,
                cause: dict | None = None) -> dict:
        """Online power-of-two split/merge to ``n_shards`` — the topology
        change as a sequence of LOCAL folds, never a stop-the-world.

        Because :func:`shard_of` routes by ``h % S``, doubling sends every
        id homed on shard ``s`` to exactly ``s`` or ``s + S``: each
        doubling (halving) step folds one donor shard (donor pair) at a
        time into its successor(s) — donors keep serving reads AND
        accepting writes throughout — then warms the new topology's whole
        program set, applies the writes that landed mid-migration
        (carry-over, exactly like compaction's mid-fold writes) and flips
        the id→shard map atomically under the write lock. A larger jump
        (e.g. 2 → 8) runs as successive doublings, each individually
        committed.

        ``publisher`` (+ ``name``/``ks``/``warm_data``) threads the flip
        through the registry's pre-flip ``publish(warm_hook=)`` seam: the
        registry warms the successor searcher at every bucket, the commit
        runs as the LAST pre-flip hook, and only then does the registry
        pointer move — serving traffic never sees a cold program or a
        half-migrated mesh, and in-flight flushes finish on the topology
        they leased (publish with the same ``ks`` the name already
        serves). Without a publisher, ``warm_buckets`` drives the
        library-mode warm (successor delta ladders + sealed scans + the
        new merge) before the flip.

        With ``wal_dir`` durability armed, each successor gets an atomic
        baseline snapshot + fresh WAL BEFORE the flip, carry-over writes
        land in the successor logs, and the topology manifest's atomic
        rename is the durable commit point: a crash at any fault point
        (``reshard/split``/``reshard/flip``/``reshard/manifest``)
        recovers via :meth:`load` to the OLD topology with zero
        acknowledged-write loss — no write resurrected either, since
        uncommitted successor files are ignored and removed.

        Returns ``{from, to, steps, rows_moved, epoch, wall_s}``. Raises
        (mesh untouched, donors still serving) on a non-power-of-two
        ratio, a successor that would own zero rows, or a shard without
        its retained row store.

        ``cause`` (a small dict — e.g. the controller's trigger/decision
        journal seqs) rides the ``reshard_started`` / ``reshard_committed``
        / ``reshard_aborted`` evidence verbatim, so an automated topology
        change stays causally chained to the sensor event that advised
        it."""
        target = int(n_shards)
        S = len(self._shards)
        expects(target >= 1, "n_shards must be >= 1, got %d", target)
        expects(target != S, "mesh is already at %d shards", S)
        big, small = max(target, S), min(target, S)
        ratio = big // small
        expects(big % small == 0 and (ratio & (ratio - 1)) == 0,
                "reshard moves between power-of-two-related shard counts "
                "(%d -> %d is not): shard_of routes by h %% S, so only a "
                "doubling/halving keeps every id's migration local to one "
                "donor group", S, target)
        expects(self._build_fn is not None,
                "reshard needs the shard build recipe — construct with "
                "build=, or pass build= to load()")
        expects(publisher is None or hasattr(publisher, "publish"),
                "publisher must expose publish() (SearchService or "
                "IndexRegistry)")
        expects(publisher is None or name is not None,
                "a publisher needs the published name")
        kks = (ks,) if isinstance(ks, int) else tuple(int(x) for x in ks)
        t0 = time.perf_counter()
        steps = []
        while len(self._shards) != target:
            nxt = (len(self._shards) * 2 if target > len(self._shards)
                   else len(self._shards) // 2)
            steps.append(self._reshard_step(
                nxt, publisher=publisher, name=name, ks=kks,
                warm_buckets=warm_buckets, warm_data=warm_data, res=res,
                cause=cause))
        return {"from": S, "to": target, "steps": steps,
                "rows_moved": sum(st["rows_moved"] for st in steps),
                "epoch": self._topology_epoch,
                "wall_s": round(time.perf_counter() - t0, 3)}

    def _reshard_step(self, target: int, *, publisher, name, ks,
                      warm_buckets, warm_data, res, cause=None) -> dict:
        """One doubling/halving: fold donors shard-at-a-time, warm, then
        commit (carry-over + flip + manifest). Holds the compaction lock
        for the whole step — a staggered fold and a migration must not
        interleave (both rebuild shard state); writes and reads are only
        ever blocked for the brief snapshot/commit critical sections."""
        with self._compact_lock:
            S = len(self._shards)
            action = "split" if target > S else "merge"
            if metrics._enabled:
                _c_migrations().inc(1, name=self._name, action=action,
                                    phase="started")
            obs_events.emit(
                "reshard_started",
                subject=("reshard", self._name, None,
                         self._topology_epoch),
                evidence={"action": action, "from": S, "to": target,
                          **({"cause": dict(cause)} if cause else {})})
            t0 = time.perf_counter()
            with self._lock:
                self._migration = {"action": action, "from": S,
                                   "to": target, "folded_donors": 0,
                                   "rows_moved": 0}
            try:
                # split: donor s feeds successors (s, s+S); merge: donors
                # (t, t+T) feed successor t — h % S and h % target agree
                # exactly on these groups (the power-of-two locality rule)
                donor_groups = ([((s,), (s, s + S)) for s in range(S)]
                                if action == "split"
                                else [((t, t + target), (t,))
                                      for t in range(target)])
                successors: list = [None] * target
                snaps: list = []
                rows_moved = 0
                for donors_idx, succ_idx in donor_groups:
                    faults.fire("reshard/split", name=self._name,
                                donors=donors_idx, action=action)
                    rows_parts, gid_parts = [], []
                    for di in donors_idx:
                        donor = self._shards[di]
                        prim = (donor._primary()
                                if isinstance(donor, ReplicatedShard)
                                else donor)
                        with self._lock:
                            # brief freeze: snapshot the donor's live rows
                            # (sealed survivors + live delta prefix) — the
                            # fold input; everything after this point
                            # carries over at the commit
                            st = prim._state
                            expects(st.store is not None,
                                    "reshard folds raw rows into successor "
                                    "builds — shard %d has no retained row "
                                    "store (retain_vectors=False)", di)
                            snap_n = int(st.delta_n)
                            s_live = np.nonzero(st.sealed_alive)[0]
                            d_live = np.nonzero(
                                st.delta_alive[:snap_n])[0]
                            rows = np.concatenate(
                                [_mut._store_rows(st.store)[s_live],
                                 st.delta[d_live]])
                            gids = np.concatenate(
                                [st.id_map[s_live],
                                 st.delta_ids[d_live].astype(np.int64)])
                            # tombstone watermarks at the snapshot: a
                            # delete (or replacing upsert) of a snapshot-
                            # live id must flip one of these, so the
                            # commit can SKIP its dead-id scan whenever
                            # they are unchanged — the common case
                            dead0 = (int(st.sealed_dead_n),
                                     snap_n - len(d_live))
                        rows_parts.append(rows)
                        gid_parts.append(gids)
                        # the DONOR rides to the commit (not the twin the
                        # fold read): a replicated donor's primary can go
                        # stale mid-migration, and the commit must read
                        # carry-over state from a twin that received
                        # every acknowledged write
                        snaps.append((donor, snap_n, gids, dead0))
                    rows = (np.concatenate(rows_parts)
                            if len(rows_parts) > 1 else rows_parts[0])
                    gids = (np.concatenate(gid_parts)
                            if len(gid_parts) > 1 else gid_parts[0])
                    owner = shard_of(gids, target)
                    for t in succ_idx:
                        mask = owner == t
                        expects(int(mask.sum()) > 0,
                                "successor shard %d of %d would own no "
                                "live rows — the corpus is too small for "
                                "this split", t, target)
                        # the heavy build runs OFF every lock: donors keep
                        # serving and accepting writes
                        successors[t] = self._make_shard(
                            rows[mask], gids[mask], t, target)
                    rows_moved += int(len(gids))
                    with self._lock:
                        self._migration["folded_donors"] += len(donors_idx)
                        self._migration["rows_moved"] = rows_moved
                succ = tuple(successors)
                # warm BEFORE any flip: successor delta ladders + pads +
                # the one (bucket, 2·target·k) merge, each on its device
                if warm_buckets:
                    self._warm_impl(succ, warm_buckets, ks=ks,
                                    sample=warm_data)
                step: dict = {"action": action, "from": S, "to": target,
                              "rows_moved": rows_moved}

                if publisher is not None:
                    # the registry's pre-flip seam: its bucket warm runs
                    # the NEW topology's full hook (sealed scans on their
                    # pinned devices + the successor-count merge), then
                    # the commit runs as the last pre-flip hook, and only
                    # then does the registry pointer flip — in-flight
                    # flushes drain on the topology they leased
                    def commit_hook(_searcher, _ks, _step=step):
                        out = self._commit_reshard(succ, snaps, target,
                                                   action, cause=cause)
                        _step.update(out)
                        return out

                    step["publish"] = publisher.publish(
                        name, self._searcher_for(succ), k=ks,
                        warm_data=warm_data, res=res,
                        warm_hook=commit_hook, cause=cause)
                else:
                    if warm_buckets:
                        self._rehearse(succ, warm_buckets, ks, warm_data)
                    step.update(self._commit_reshard(succ, snaps, target,
                                                     action, cause=cause))
                if metrics._enabled:
                    _c_migrations().inc(1, name=self._name, action=action,
                                        phase="completed")
                    _c_rows_moved().inc(rows_moved, name=self._name)
                    _h_reshard().observe(time.perf_counter() - t0,
                                         name=self._name, action=action)
                obs_events.emit(
                    "reshard_committed",
                    subject=("reshard", self._name, None,
                             step.get("epoch")),
                    evidence={"action": action, "rows_moved": rows_moved,
                              "carried_over": step.get("carried_over"),
                              **({"cause": dict(cause)} if cause else {})})
                step["wall_s"] = round(time.perf_counter() - t0, 3)
                return step
            finally:
                with self._lock:
                    self._migration = None

    def _commit_reshard(self, successors, snaps, target: int,
                        action: str, cause: dict | None = None) -> dict:
        """The atomic flip. Pre-lock: each successor gets its baseline
        atomic snapshot + a fresh WAL (durability armed). Under the mesh
        write lock: carry over every write that landed on a donor after
        its fold snapshot (deletes first, then the delta tail — the
        alive-bit re-read discipline of a compaction swap), swap the
        shard list, and commit the manifest (its ``os.replace`` is the
        durable commit point — a crash before it recovers to the old
        topology, whose donors logged every mid-migration write; no write
        is admitted between the swap and the manifest because the lock is
        held). Post-lock: donor ledger entries retire (the audit proves
        the migration's double-buffer frees once leases drain) and the
        old epoch's files are removed."""
        new_epoch = self._topology_epoch + 1
        if self._wal_dir is not None:
            from .wal import WriteAheadLog

            for t, sh in enumerate(successors):
                snap, wal_path = self._shard_files(t, epoch=new_epoch)
                # stale files of an earlier ABORTED migration at this
                # epoch (the manifest never committed them) must not be
                # mistaken for live state
                if os.path.exists(wal_path):
                    os.remove(wal_path)
                if isinstance(sh, ReplicatedShard):
                    sh.save(snap)
                else:
                    _mut.save(sh, snap)
                sh._wal = WriteAheadLog(wal_path, name=sh.name)
                sh._snapshot_path = snap
        carried = 0
        with self._lock:
            for donor, snap_n, snap_gids, dead0 in snaps:
                # re-pick the carry-over twin NOW: the fold's primary may
                # have gone stale mid-migration — a stale twin stops
                # receiving (still-acknowledged) group writes, so reading
                # its tail would silently drop them; any currently
                # non-stale twin received every group write (lockstep),
                # at the same delta offsets and tombstone counts, so the
                # fold's snap_n and watermarks transfer
                prim = (donor._primary()
                        if isinstance(donor, ReplicatedShard) else donor)
                st = prim._state
                dead_now = (int(st.sealed_dead_n),
                            snap_n
                            - int(np.count_nonzero(st.delta_alive[:snap_n])))
                if dead_now == dead0:
                    # no snapshot-live id died mid-migration (the common
                    # case): skip the O(live-rows) membership scan — this
                    # runs under the mesh write lock, stalling every write
                    dead = np.empty(0, np.int64)
                elif len(prim._loc):
                    live_now = np.fromiter(prim._loc.keys(), np.int64,
                                           count=len(prim._loc))
                    dead = np.sort(snap_gids[
                        np.isin(snap_gids, live_now, invert=True)])
                else:
                    dead = np.sort(snap_gids)
                tail = (np.nonzero(st.delta_alive[snap_n:st.delta_n])[0]
                        + snap_n)
                tail_ids = st.delta_ids[tail].astype(np.int64)
                tail_rows = st.delta[tail].copy()
                if dead.size:
                    owner = shard_of(dead, target)
                    for t in np.unique(owner):
                        successors[int(t)].delete(dead[owner == t])
                    carried += int(dead.size)
                if tail_ids.size:
                    owner = shard_of(tail_ids, target)
                    for t in np.unique(owner):
                        m2 = owner == t
                        # an id upserted mid-migration tombstones its
                        # snapshot copy in the successor here (and lands
                        # in the successor WAL — durable before the flip)
                        successors[int(t)].upsert(tail_rows[m2],
                                                  ids=tail_ids[m2])
                    carried += int(tail_ids.size)
            old_shards = self._shards
            self._shards = list(successors)
            self._topology_epoch = new_epoch
            try:
                faults.fire("reshard/flip", name=self._name,
                            epoch=new_epoch)
                if self._wal_dir is not None:
                    faults.fire("reshard/manifest", name=self._name,
                                epoch=new_epoch)
                    self._write_manifest(self._wal_dir)
            except BaseException:
                # a manifest that failed to LAND (ENOSPC, EIO — a raise,
                # not a crash) must not leave the mesh flipped in memory
                # while the durable manifest still names the old topology:
                # later acknowledged writes would land only in successor
                # WALs recovery never reads. Roll the swap back — donors
                # are untouched (carry-over only read them) and keep
                # logging, so the abort loses nothing and reshard() keeps
                # its mesh-untouched-on-raise contract.
                self._shards = old_shards
                self._topology_epoch = new_epoch - 1
                if self._wal_dir is not None:
                    for sh in successors:
                        if sh._wal is not None:
                            sh._wal.close()
                            sh._wal = None
                obs_events.emit(
                    "reshard_aborted", severity="error",
                    subject=("reshard", self._name, None, new_epoch - 1),
                    evidence={"action": action, "rolled_back_to":
                              new_epoch - 1,
                              **({"cause": dict(cause)} if cause else {})})
                raise
            obs_events.emit(
                "reshard_flip",
                subject=("reshard", self._name, None, new_epoch),
                evidence={"action": action, "shards": target,
                          "carried_over": carried})
            self._update_gauges()
        # off the write lock: donor retirement and the old epoch's files —
        # the manifest is durable, nothing references them anymore
        for sh in old_shards:
            self._retire_shard(sh)
        if self._wal_dir is not None:
            for j in range(len(old_shards)):
                for path in self._shard_files(j, epoch=new_epoch - 1):
                    try:
                        os.remove(path)
                    except OSError:
                        pass
        return {"epoch": new_epoch, "carried_over": carried}

    def _retire_shard(self, sh) -> None:
        """Donor-shard retirement: obs.mem entries retire — a retired
        entry still accounted after draining leases release the old
        topology is exactly the leak the audit reports — and WAL handles
        close (their files are gone; the successor logs own durability
        now)."""
        reps = (sh.replicas if isinstance(sh, ReplicatedShard) else (sh,))
        for rep in reps:
            obs_mem.retire(rep._state.mem)
            obs_mem.retire(rep._sealed_mem)
            if rep._wal is not None:
                rep._wal.close()
                rep._wal = None
        if isinstance(sh, ReplicatedShard) and sh._wal is not None:
            sh._wal.close()
            sh._wal = None

    def _rehearse(self, shards, buckets, ks, sample) -> None:
        """Library-mode pre-flip warm of the successors' SEALED programs:
        run the real new-topology scatter-gather at every (bucket, k),
        once per replica ordinal so every twin's per-device executables
        compile before failover can pick them. (The publisher path gets
        this from the registry's bucket warm instead.)"""
        import jax

        from .._warmup import _random_queries

        R = max((sh.n_replicas if isinstance(sh, ReplicatedShard) else 1)
                for sh in shards)
        key = jax.random.key(7)
        for r in range(R):
            states = tuple(
                (sh.replicas[min(r, sh.n_replicas - 1)]._state
                 if isinstance(sh, ReplicatedShard) else sh._state)
                for sh in shards)
            for kk in ks:
                for b in sorted(set(int(x) for x in buckets)):
                    key, kq = jax.random.split(key)
                    q = _random_queries(kq, b, self.dim, self.query_dtype,
                                        sample=sample)
                    jax.block_until_ready(self._scatter_gather(
                        states, q, int(kk), _view_scan))

    # -- mesh durability -----------------------------------------------------
    def save(self, dir: str | None = None) -> None:
        """Atomic mesh snapshot: every shard's full mutable state
        (:func:`raft_tpu.stream.save` — per-shard atomic with
        parent-directory fsync, WAL-truncating when durability is armed)
        plus the topology MANIFEST written LAST through
        ``core.serialize.atomic_write``. A crash anywhere mid-save leaves
        a loadable set: each shard pair (snapshot + WAL) is independently
        consistent — the snapshot stamps the ``wal_seq`` it covers and
        truncates only after its own rename is durable — and the manifest
        only ever references complete pairs. ``dir`` defaults to (and,
        when durability is armed, must be) the construction-time
        ``wal_dir``."""
        if dir is None:
            dir = self._wal_dir
        expects(dir is not None,
                "save() needs a directory (pass dir= or construct with "
                "wal_dir=)")
        dir = os.fspath(dir)
        if self._wal_dir is not None:
            expects(os.path.abspath(dir) == os.path.abspath(self._wal_dir),
                    "a durable mesh snapshots into its wal_dir (%r) — the "
                    "per-shard WALs truncate against exactly these files; "
                    "got %r", self._wal_dir, dir)
        os.makedirs(dir, exist_ok=True)
        # serialize with topology changes (and staggered folds): a reshard
        # committing mid-save would close donor WALs under our per-shard
        # saves and flip _shards/_topology_epoch between the snapshot loop
        # and the manifest — the lock makes a save see one topology whole
        with self._compact_lock:
            for s, sh in enumerate(self._shards):
                snap, _ = self._shard_files(s, dir=dir)
                if isinstance(sh, ReplicatedShard):
                    sh.save(snap)
                else:
                    _mut.save(sh, snap)
            self._write_manifest(dir)

    def _write_manifest(self, dir: str) -> None:
        from ..core.serialize import (atomic_write, serialize_header,
                                      serialize_scalar)

        e = self._topology_epoch
        with atomic_write(os.path.join(dir, _MANIFEST)) as f:
            serialize_header(f, "mesh")
            serialize_scalar(f, self._name)
            serialize_scalar(f, len(self._shards))
            serialize_scalar(f, int(e))
            serialize_scalar(f, int(self._replicas_n))
            serialize_scalar(f, int(self._next_id))
            for s, sh in enumerate(self._shards):
                sn, wn = self._shard_names(s, e)
                serialize_scalar(f, sn)
                serialize_scalar(f, wn if self._wal_dir is not None else "")
                serialize_scalar(f, int(sh._wal_seq))

    @classmethod
    def load(cls, dir, *, build: Callable | None = None,
             search_params=None, index_params=None,
             builder: Callable | None = None,
             devices: Sequence | None = None, comms=None,
             fencing: FencingPolicy | None = None,
             name: str | None = None, tier=None,
             clock: Callable[[], float] = time.monotonic
             ) -> "ShardedMutableIndex":
        """Recover a mesh from :meth:`save`'s manifest + per-shard
        snapshots (+ per-shard WAL replay when durability was armed).
        The manifest decides the topology: a crash mid-reshard — before
        the manifest's atomic rename — recovers to the OLD topology, each
        shard's log replayed past its snapshot's stamp through the
        ordinary write path, so no acknowledged write is lost and no
        unacknowledged write resurrected. Runtime configuration
        (``build`` — needed only to reshard again —
        ``search_params``/``index_params``/``builder``/``devices``/
        ``comms``/``fencing``) is supplied fresh, like every loader.

        A replicated mesh recovers DEGRADED-TO-ONE: the group snapshot is
        the primary twin's state (twins are in-memory redundancy; the log
        is the on-disk copy), so every acknowledged write comes back on a
        ``replicas=1`` surface — re-replicate by rebuilding the mesh
        around the recovered corpus. ``mesh.last_recovery`` aggregates
        the per-shard replay reports (``replayed``, ``topology_epoch``,
        ``degraded_from_replicas``)."""
        from ..core.serialize import check_header, deserialize_scalar

        dir = os.fspath(dir)
        if comms is not None:
            expects(devices is None, "pass devices= or comms=, not both")
            devices = list(comms.mesh.devices.flat)
        if devices is not None:
            devices = list(devices)
        with open(os.path.join(dir, _MANIFEST), "rb") as f:
            check_header(f, "mesh")
            saved_name = deserialize_scalar(f)
            n_shards = int(deserialize_scalar(f))
            epoch = int(deserialize_scalar(f))
            saved_replicas = int(deserialize_scalar(f))
            next_id = int(deserialize_scalar(f))
            entries = [(deserialize_scalar(f), deserialize_scalar(f),
                        int(deserialize_scalar(f)))
                       for _ in range(n_shards)]
        obj = cls.__new__(cls)
        obj._name = saved_name if name is None else name
        obj._clock = clock
        obj._lock = threading.RLock()
        obj._compact_lock = threading.Lock()
        obj._build_fn = build
        obj._search_params = search_params
        obj._index_params = index_params
        obj._builder = builder
        obj._retain_vectors = None
        obj._devices = devices
        obj._replicas_n = 1  # degraded-to-one restore (see docstring)
        obj._fencing = fencing
        obj._topology_epoch = epoch
        obj._migration = None
        has_wal = any(wname for _, wname, _ in entries)
        obj._wal_dir = dir if has_wal else None
        shards = []
        for j, (sname, wname, _seq) in enumerate(entries):
            shards.append(_mut.load(
                os.path.join(dir, sname),
                wal=os.path.join(dir, wname) if wname else None,
                search_params=search_params, index_params=index_params,
                builder=builder, shard=j, tier=tier,
                device=(devices[j % len(devices)] if devices else None),
                clock=clock))
        obj._shards = shards
        # per-shard stream sections carry the tier layout (raft_tpu/12) —
        # the mesh inherits whatever the shards restored
        obj._storage = shards[0]._storage
        obj._tier = tier
        obj._delta_capacity = shards[0].delta_capacity
        obj._next_id = max([next_id] + [sh._next_id for sh in shards])
        obj._finish_init()
        per = [getattr(sh, "last_recovery", None) for sh in shards]
        obj.last_recovery = {
            "n_shards": n_shards, "topology_epoch": epoch,
            "replayed": sum(p["replayed"] for p in per if p),
            "torn": any(p["torn"] for p in per if p),
            "degraded_from_replicas": saved_replicas,
            "per_shard": per,
        }
        return obj

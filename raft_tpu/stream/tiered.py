"""TieredStore: beyond-HBM storage for full-precision refine rows.

Every byte the stack served before this module had to fit in device
memory, capping corpus size at shards × HBM — even though the refine
epilogue is the only consumer of full-precision rows. This is the
DiskANN/FreshDiskANN storage split (Subramanya et al. 2019, Singh et al.
2021; ROADMAP item 1) applied to the TPU serving stack: **PQ codes and
coarse structures stay resident in HBM** (they are the per-query scan
operands), while **raw rows live in host RAM** — optionally an mmap'd
on-disk file for the cold majority — and cross to the device only as
per-batch candidate gathers for the exact-refine epilogue.

Three moving parts:

- **The row store** (:class:`TieredStore`). One (n, d) row array resident
  on exactly one cold tier (``host`` RAM, or ``disk`` via ``np.memmap``
  when :attr:`TierPolicy.disk_path` is set), plus an optional **device
  mirror** — the promoted state, byte-identical to the pre-tiering
  all-HBM store. Residency is *decided, not hardcoded*:
  :func:`decide_placement` prices the mirror against
  ``Resources.memory_budget_bytes`` through the obs.mem ledger (no
  budget = stay cold; tiering exists to spend less HBM, not more), and
  residency moves at runtime — **budget-pressure spill** (the ledger's
  gate consults :func:`raft_tpu.obs.mem.register_pressure_handler`\\ ed
  stores before refusing an admission, so a mirror is dropped to make
  room for an upsert/publish instead of shedding the write) and
  **hit-rate-driven promote** (``promote_min_hits`` host fetches with
  budget headroom lift the mirror back). Every move is a counted event
  (``raft_tpu_tier_spill_total`` / ``raft_tpu_tier_promote_total``),
  visible at ``/debug/mem`` under the ``tiers`` section.

- **The double-buffered fetch** (:meth:`TieredStore.fetch`) — the refine
  hop. Candidate slots gather on the host (``np.take`` over RAM or mmap
  pages) into a per-shape **ring of device slots** (the
  :mod:`raft_tpu.serve.staging` shape discipline): under jax's async
  dispatch, batch N+1's H2D overlaps batch N's distance compute, and
  ring REPLACEMENT keeps steady-state accounted bytes CONSTANT — the
  ledger entry for the store proves it (slot bytes are accounted once
  per shape, never per fetch; displaced uploads free by reference drop
  once their batch completes — staging's donation program is
  deliberately NOT used here, because searches are lock-free and a
  concurrent caller may still hold a returned slot, see
  ``_slot_upload``). The same ring backs :meth:`oracle_chunk_dev`, the
  chunked exact scan that lets ``exact_search``/the recall canary score
  the full corpus with **zero net device row bytes** (the pre-tiering
  oracle uploaded a whole second copy of the store).

- **Placement observability.** Per-tier bytes publish as
  ``raft_tpu_tier_bytes{tier=,name=}``; fetches, transfer bytes and the
  device-hit ratio ride ``raft_tpu_tier_fetch_total`` /
  ``raft_tpu_tier_h2d_bytes_total`` / ``raft_tpu_tier_hit_ratio``; the
  ``tiers`` section of ``/debug/mem`` lists every live store's
  residency, per-tier bytes and recent spill/promote events. The host
  side gates against the new optional ``Resources.host_budget_bytes``
  exactly like device bytes gate against ``memory_budget_bytes``.

:class:`raft_tpu.stream.MutableIndex` composes this behind its
``storage="tiered"`` policy (IVF-PQ sealed side): the retained raw-row
store becomes a TieredStore, ``search_refined`` restructures the refine
epilogue as the double-buffered gather, compaction folds migrate tier
residency through the ordinary fold-and-swap, and ``save()``/``load()``
persist the tier layout (raft_tpu/12) so a recovered index restores its
placement without re-deciding. Sizing rules and when-to-tier guidance:
docs/streaming.md "Tiered storage".
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import threading
import time
import weakref
from typing import Callable

import numpy as np

from ..core.errors import expects
from ..core.resources import default_resources
from ..obs import dispatch as obs_dispatch
from ..obs import events as obs_events
from ..obs import mem as obs_mem
from ..obs import metrics
from ..testing import faults

__all__ = ["TierPolicy", "TieredStore", "TIERS", "decide_placement",
           "tier_totals", "debug_tiers", "spillable_bytes"]

# residency tiers, hottest first — the vocabulary shared by the metrics,
# /debug/mem, obs.mem.plan(storage="tiered") and the serialized layout
TIERS = ("device", "host", "disk")


@dataclasses.dataclass(frozen=True)
class TierPolicy:
    """Runtime configuration of a :class:`TieredStore` (supplied fresh at
    ``load`` like ``search_params`` — only the decided LAYOUT is
    serialized, see ``MutableIndex.save``).

    ``disk_path``: path PREFIX for the cold mmap file (``<prefix>.e<N>``
    per store epoch, so a compaction successor never clobbers the live
    epoch's pages while draining leases still read them); ``None`` keeps
    rows in host RAM. ``oracle_chunk``: device shape (power of two) of
    the chunked exact scan — the one program size every oracle pass
    reuses. ``fetch_slots``: depth of the per-shape device slot ring the
    double-buffered gathers rotate through (2 = classic double
    buffering). ``promote_min_hits``: cold fetches before a store
    promotes its mirror — fires only under an ARMED
    ``memory_budget_bytes`` with headroom; with no budget there is no
    safe ceiling, so the store stays cold (``auto_promote=False`` pins
    residency to explicit :meth:`TieredStore.promote`/``spill`` calls).
    """

    disk_path: str | None = None
    oracle_chunk: int = 8192
    fetch_slots: int = 2
    promote_min_hits: int = 3
    auto_promote: bool = True

    def __post_init__(self):
        expects(self.oracle_chunk >= 8
                and (self.oracle_chunk & (self.oracle_chunk - 1)) == 0,
                "oracle_chunk must be a power of two >= 8, got %d",
                self.oracle_chunk)
        expects(self.fetch_slots >= 2,
                "fetch_slots must be >= 2 (double buffering), got %d",
                self.fetch_slots)


# -- metrics (catalogue: docs/observability.md) ------------------------------

@functools.lru_cache(maxsize=None)
def _g_tier_bytes():
    return metrics.gauge(
        "raft_tpu_tier_bytes",
        "live bytes per storage tier (device mirror + gather slots / host "
        "RAM rows / disk mmap rows) per tiered store", unit="bytes")


@functools.lru_cache(maxsize=None)
def _c_fetches():
    return metrics.counter(
        "raft_tpu_tier_fetch_total",
        "refine/oracle gathers served by a tiered store, by source tier")


@functools.lru_cache(maxsize=None)
def _c_h2d():
    return metrics.counter(
        "raft_tpu_tier_h2d_bytes_total",
        "host->device bytes transferred by cold-tier gathers (the refine "
        "hop's transfer cost; 0 while the mirror is resident)",
        unit="bytes")


@functools.lru_cache(maxsize=None)
def _c_spills():
    return metrics.counter(
        "raft_tpu_tier_spill_total",
        "device mirrors dropped, by reason (pressure = the obs.mem budget "
        "gate reclaimed HBM for an admission; explicit = spill() called)")


@functools.lru_cache(maxsize=None)
def _c_promotes():
    return metrics.counter(
        "raft_tpu_tier_promote_total",
        "device-mirror promotions (construction placement, hit-rate "
        "auto-promote, explicit promote(), load() layout restore)")


@functools.lru_cache(maxsize=None)
def _g_hit_ratio():
    return metrics.gauge(
        "raft_tpu_tier_hit_ratio",
        "fraction of fetched rows served device-resident (mirror hits / "
        "all fetched rows) since the store was created")


# -- jitted pieces -----------------------------------------------------------

@functools.cache
def _tier_jits():
    import jax
    import jax.numpy as jnp

    from ..core import tracing

    @jax.jit
    def gather(rows, slots):
        # device-mirror gather: negative (sentinel) slots read row 0; the
        # refine epilogue masks them by candidate id, so the value never
        # surfaces. The first half of the ``refine`` device stage
        with tracing.scope("refine"):
            return jnp.take(rows, jnp.clip(slots, 0), axis=0)

    @jax.jit
    def shift(ids, base):
        # chunk-local ids -> store-slot ids; -1 sentinels pass through.
        # base rides as a TRACED scalar so every chunk of one shape shares
        # one program
        return jnp.where(ids >= 0, ids + base, ids)

    return gather, shift


def mirror_gather(rows_dev, slots):
    """Device-side candidate gather (the promoted / all-HBM refine path):
    ``rows_dev[(clip(slots, 0))]`` with sentinel slots left to the refine
    mask. One jitted program per (slots-shape, store-shape)."""
    obs_dispatch.note(1)
    return _tier_jits()[0](rows_dev, slots)


def shift_slots(ids, base: int):
    """Shift chunk-local candidate ids into store-slot space (``-1``
    passes through); ``base`` is traced, so all chunks share a program."""
    obs_dispatch.note(1)
    return _tier_jits()[1](ids, np.int32(base))


# -- placement ---------------------------------------------------------------

def decide_placement(n_bytes: int, res=None) -> str:
    """Initial mirror placement of ``n_bytes`` of raw rows: ``"device"``
    only when a device budget is armed AND the ledger-accounted device
    bytes plus the mirror still fit it — an unbudgeted tiered store stays
    cold (the point of tiering is to spend less HBM, and the hit-rate
    promote path lifts genuinely hot stores later). Pure decision — no
    allocation, no metrics."""
    res = res or default_resources()
    budget = getattr(res, "memory_budget_bytes", None)
    if budget is None or not metrics._enabled:
        return "host"
    used = obs_mem.totals()["device_bytes"]
    return "device" if used + int(n_bytes) <= int(budget) else "host"


# -- live-store registry (/debug/mem "tiers", pressure spills) ---------------

_stores: "weakref.WeakSet[TieredStore]" = weakref.WeakSet()
_registered = False


def _ensure_registered() -> None:
    """Install the module's obs.mem hooks once, lazily at first store
    construction (imports of the stream package must not mutate the
    ledger's hook tables)."""
    global _registered
    if _registered:
        return
    _registered = True
    obs_mem.register_pressure_handler(_relieve_pressure)
    obs_mem.register_debug_section("tiers", debug_tiers)


def _relieve_pressure(need_bytes: int) -> int:
    """Budget-pressure spill: drop device mirrors (largest first) until
    ``need_bytes`` of HBM are reclaimed or no mirror remains. Called by
    :func:`raft_tpu.obs.mem.gate` BEFORE it refuses an admission — a
    resident mirror is a cache, and shedding a cache beats shedding a
    write. Returns the bytes actually freed."""
    freed = 0
    stores = sorted((s for s in list(_stores) if s.mirror_resident),
                    key=lambda s: -s.row_bytes)
    for s in stores:
        if freed >= need_bytes:
            break
        freed += s.spill(reason="pressure")
    return freed


def spillable_bytes() -> int:
    """HBM bytes a budget-pressure spill could reclaim right now: the sum
    of every live store's RESIDENT device mirror (exactly what
    :func:`_relieve_pressure` drops, in the same accounting). The control
    plane's reshard admission adds this to the budget headroom — a
    migration's double-buffer may displace caches, never live state. 0
    when no tiered store is live."""
    return sum(s.row_bytes for s in list(_stores) if s.mirror_resident)


def tier_totals() -> dict:
    """Per-tier byte totals over every live store (empty dict when no
    tiered store is live)."""
    out: dict[str, int] = {}
    for s in list(_stores):
        for tier, b in s.tier_bytes().items():
            if b:
                out[tier] = out.get(tier, 0) + b
    return out


# per-tier high-water marks since the last reset — what the bench's
# per-row ``mem.tiers`` field reads: a row's TieredStore is usually a
# frame local freed before the row-guard attaches attribution, so the
# LIVE totals would read {} there; the watermark survives the scope
# (same reset-per-row discipline as obs.mem.reset_peak)
_tier_peak: dict = {}


def _note_tier_peak() -> None:
    for tier, b in tier_totals().items():
        if b > _tier_peak.get(tier, 0):
            _tier_peak[tier] = b


def reset_tier_peak() -> None:
    """Re-base the per-tier watermarks (the bench calls this at each
    row-scope start, mirroring ``obs.mem.reset_peak``)."""
    _tier_peak.clear()
    _note_tier_peak()


def tier_peak() -> dict:
    """Per-tier high-water bytes since the last :func:`reset_tier_peak`
    (non-empty iff a tiered store lived in the window)."""
    return dict(_tier_peak)


def debug_tiers() -> dict:
    """The ``tiers`` section of ``/debug/mem``: every live store's
    residency, per-tier bytes, fetch/hit counters and recent
    spill/promote events (bounded — a debug scrape stays cheap)."""
    stores = [s.stats() for s in list(_stores)]
    stores.sort(key=lambda r: (r["name"], r["shard"] or 0))
    return {"stores": stores, "totals": tier_totals()}


# -- the store ---------------------------------------------------------------

class TieredStore:
    """Tiered raw-row store (see module docstring).

    ``rows`` (n, d) land on the cold tier chosen by ``policy`` (host RAM,
    or a ``<disk_path>.e<epoch>`` mmap when ``disk_path`` is set) and the
    device mirror is placed by :func:`decide_placement` against ``res``
    (or restored explicitly via ``residency=`` — the ``load()`` path,
    which must NOT re-decide). ``device`` pins uploads (the sharded
    tier's committed-placement contract); ``name``/``shard``/``epoch``
    key the ledger entry and the metric series."""

    def __init__(self, rows, *, name: str = "default",
                 shard: int | None = None, epoch: int = 0,
                 policy: TierPolicy | None = None, device=None, res=None,
                 residency: str | None = None,
                 clock: Callable[[], float] = time.monotonic):
        # mmap detection must see the RAW argument: np.asarray strips the
        # memmap subclass (same memory, but the disk-backed provenance —
        # what prices the rows at zero host bytes — would be lost)
        raw = rows
        rows = np.asarray(rows)
        expects(rows.ndim == 2 and rows.shape[0] > 0,
                "TieredStore rows must be (n>0, d)")
        self._policy = policy or TierPolicy()
        self._name = name
        self._shard = None if shard is None else int(shard)
        self._epoch = int(epoch)
        self._device = device
        self._clock = clock
        self._lock = threading.Lock()
        # serializes the slot-ring turn bookkeeping (see _slot_upload);
        # distinct from _lock so stats() never blocks behind a dispatch
        self._ring_lock = threading.Lock()
        self._mirror = None
        self._promoting = False  # promote-transition reservation flag
        self._cold_fetches = 0  # host/disk gathers since last promote
        self._rows_fetched = 0
        self._rows_hit = 0  # rows served from the resident mirror
        self._h2d_bytes = 0
        self._fetch_wall_s = 0.0  # host gather + upload dispatch walls
        self._spills = 0
        self._promotes = 0
        self._events: collections.deque = collections.deque(maxlen=16)
        # per-shape device slot rings (the double buffer): key -> [arrays]
        self._slots: dict[tuple, list] = {}
        self._turn: dict[tuple, int] = {}
        self._slot_bytes = 0

        res = res or default_resources()
        self._mmap_adopted = False
        if self._policy.disk_path is None and isinstance(raw, np.memmap):
            # ADOPT the caller's mmap as the cold tier in place (the
            # out-of-core build path: a ChunkedReader's backing memmap
            # becomes the refine-row store without ever materializing a
            # RAM copy). Pages are disk-backed, so the rows price zero
            # host bytes — same rule as a disk_path store.
            self._disk_file = None
            self._mmap_adopted = True
            self._rows = raw
            host_gate = 0
        elif self._policy.disk_path is not None:
            # the cold majority on disk: rows stream once into an mmap
            # whose pages the OS caches — the name+epoch suffix keeps a
            # compaction successor (or a shard/replica twin sharing the
            # policy's path prefix) from clobbering pages a draining
            # lease still reads
            self._disk_file = (f"{self._policy.disk_path}"
                               f".{name.replace('/', '_')}.e{self._epoch}")
            # unlink any existing file FIRST: open_memmap("w+") truncates
            # in place, so a same-(path, name, epoch) collision — two
            # loads of one snapshot, or a stale file from a crashed
            # process — would destroy pages a LIVE older store still
            # maps. Unlink keeps the old inode alive for its mapping and
            # gives this store a fresh one.
            _unlink_quiet(self._disk_file)
            mm = np.lib.format.open_memmap(
                self._disk_file, mode="w+", dtype=rows.dtype,
                shape=rows.shape)
            mm[:] = rows
            mm.flush()
            self._rows = mm
            # the epoch file dies with the store: a compaction successor
            # writes its own `.e<N+1>` file, and without this a
            # periodically-compacting disk-tiered index would grow disk
            # by store_bytes per fold forever (POSIX unlink-while-mapped
            # is safe — draining leases keep reading their pages). The
            # finalizer is inode-checked: if a LATER store reused this
            # path (same name/epoch — it unlinked our entry and created
            # a fresh inode), our death must not delete ITS live file
            stat = os.stat(self._disk_file)
            weakref.finalize(self, _unlink_if_same_inode, self._disk_file,
                             (stat.st_dev, stat.st_ino))
            host_gate = 0
        else:
            self._disk_file = None
            self._rows = np.ascontiguousarray(rows)
            host_gate = self._rows.nbytes
        # host-budget admission (whole-or-nothing, BEFORE the ledger entry
        # lands): a RAM-resident store prices its rows against the new
        # Resources.host_budget_bytes; an mmap'd store prices nothing (its
        # pages are disk-backed). HOST-only: constructing a store adds
        # zero device bytes, and the device budget's cumulative check
        # must not fail e.g. a compaction successor while the
        # double-buffered predecessor epoch is still accounted
        obs_mem.gate_host(res, host_gate, site="tier",
                          detail=f"tiered store {name!r}")
        self._mem = obs_mem.account(
            "tier", name=name, shard=self._shard, epoch=self._epoch,
            host=([] if self._on_disk else [self._rows]),
            owner=self)
        _ensure_registered()
        _stores.add(self)
        if residency is None:
            residency = decide_placement(self._rows.nbytes, res)
        expects(residency in ("device", "host", "disk"),
                "residency must be one of %s, got %r", TIERS, residency)
        if residency == "device":
            self.promote(res=res, reason="placement")
        self._publish_gauges()

    # -- introspection -------------------------------------------------------
    @property
    def shape(self) -> tuple:
        return self._rows.shape

    @property
    def dtype(self):
        return self._rows.dtype

    @property
    def row_bytes(self) -> int:
        """Bytes of one full row-set copy (what a mirror costs in HBM)."""
        return int(self._rows.nbytes)

    @property
    def policy(self) -> TierPolicy:
        return self._policy

    @property
    def mirror_resident(self) -> bool:
        return self._mirror is not None

    @property
    def _on_disk(self) -> bool:
        """Cold rows are disk-backed (own epoch file OR an adopted
        mmap) — they price zero host bytes either way."""
        return self._disk_file is not None or self._mmap_adopted

    @property
    def mirror(self):
        """The promoted device copy (None while cold)."""
        return self._mirror

    @property
    def residency(self) -> str:
        """The COLD-COPY tier plus promotion state: ``device`` while the
        mirror is resident, else ``disk``/``host`` per the backing array —
        the one scalar ``save()`` persists as the decided layout."""
        if self._mirror is not None:
            return "device"
        return "disk" if self._on_disk else "host"

    def host_view(self) -> np.ndarray:
        """The cold row array (ndarray or memmap) — compaction folds,
        drift sampling and serialization read rows through this (never a
        device hop)."""
        return self._rows

    def tier_bytes(self) -> dict:
        """Live bytes per tier. Device = mirror + gather slots (the
        constant double-buffer rings); exactly one of host/disk carries
        the row bytes."""
        dev = self._slot_bytes + (self.row_bytes if self._mirror is not None
                                  else 0)
        return {
            "device": int(dev),
            "host": 0 if self._on_disk else self.row_bytes,
            "disk": self.row_bytes if self._on_disk else 0,
        }

    def stats(self) -> dict:
        tb = self.tier_bytes()
        return {
            "name": self._name, "shard": self._shard, "epoch": self._epoch,
            "rows": int(self._rows.shape[0]),
            "dim": int(self._rows.shape[1]),
            "dtype": str(self._rows.dtype),
            "residency": self.residency,
            "tier_bytes": tb,
            "rows_fetched": self._rows_fetched,
            "hit_ratio": (self._rows_hit / self._rows_fetched
                          if self._rows_fetched else 0.0),
            "h2d_bytes": self._h2d_bytes,
            "fetch_wall_s": round(self._fetch_wall_s, 6),
            "spills": self._spills, "promotes": self._promotes,
            "events": list(self._events),
        }

    # -- accounting ----------------------------------------------------------
    def _reaccount(self) -> None:
        dev = [] if self._mirror is None else [self._mirror]
        with self._ring_lock:
            for ring in self._slots.values():
                dev.extend(ring)
        obs_mem.reaccount(
            self._mem, device=dev,
            host=([] if self._on_disk else [self._rows]))

    def _publish_gauges(self) -> None:
        """Publish the per-tier byte gauges + the global peak watermark.
        Called ONLY when tier bytes can actually change (construction,
        promote/spill, ring growth) — never per fetch: the watermark
        rescans every live store, which would be O(shards) per batch on
        a tiered mesh's hot path."""
        _note_tier_peak()
        if not metrics._enabled:
            return
        for tier, b in self.tier_bytes().items():
            _g_tier_bytes().set(b, tier=tier, name=self._name)
        self._publish_hit_ratio()

    def _publish_hit_ratio(self) -> None:
        if metrics._enabled and self._rows_fetched:
            _g_hit_ratio().set(self._rows_hit / self._rows_fetched,
                               name=self._name)

    # -- residency moves -----------------------------------------------------
    def promote(self, res=None, *, force: bool = False,
                reason: str = "explicit") -> bool:
        """Lift the device mirror (idempotent). Unless ``force``, the
        mirror is priced against ``res.memory_budget_bytes`` headroom
        first — a store that does not fit stays cold and returns False
        (never raises: a failed promote is a skipped optimization, not an
        error). Counted + event-logged either way it lands.

        The residency transition is RESERVED under the lock before the
        upload: two search threads crossing ``promote_min_hits``
        together would otherwise both pass the cold check and both
        upload the full row set — transiently 2x the store in HBM on
        exactly the budget-squeezed hosts tiering targets."""
        with self._lock:
            if self._mirror is not None:
                return True
            if self._promoting:
                return False  # a concurrent promote owns the transition
            self._promoting = True
        try:
            if not force and not self._headroom(res):
                return False
            import jax

            rows = np.ascontiguousarray(self._rows)
            mirror = (jax.device_put(rows, self._device)
                      if self._device is not None
                      else jax.device_put(rows))
            with self._lock:
                self._mirror = mirror
            self._promotes += 1
            self._events.append({"event": "promote", "reason": reason,
                                 "at": round(self._clock(), 3)})
            obs_events.emit(
                "tier_promote", subject=("tier", self._name, None, None),
                evidence={"reason": reason, "bytes": self.row_bytes},
                counter=_c_promotes, counter_labels={"name": self._name})
        finally:
            with self._lock:
                self._promoting = False
        self._reaccount()
        self._publish_gauges()
        return True

    def _headroom(self, res) -> bool:
        res = res or default_resources()
        budget = getattr(res, "memory_budget_bytes", None)
        if budget is None:
            # no armed budget: an auto/hit-rate promote may lift the
            # mirror (there is nothing to protect), construction placement
            # already chose cold via decide_placement
            return True
        if not metrics._enabled:
            return False
        used = obs_mem.totals()["device_bytes"]
        return used + self.row_bytes <= int(budget)

    def spill(self, reason: str = "explicit") -> int:
        """Drop the device mirror (idempotent; returns the bytes freed).
        The cold copy is authoritative, so a spill loses nothing — the
        next fetch pays the host hop again (in-flight queries keep their
        mirror snapshot). ``reason="pressure"`` is the obs.mem gate's
        reclaim path."""
        with self._lock:
            if self._mirror is None:
                return 0
            self._mirror = None
        freed = self.row_bytes
        self._cold_fetches = 0
        self._spills += 1
        self._events.append({"event": "spill", "reason": reason,
                             "at": round(self._clock(), 3)})
        obs_events.emit(
            "tier_spill",
            # a pressure spill is the budget gate reclaiming HBM —
            # operator-visible; an explicit/idle spill is routine
            severity="warning" if reason == "pressure" else "info",
            subject=("tier", self._name, None, None),
            evidence={"reason": reason, "freed_bytes": freed},
            counter=_c_spills,
            counter_labels={"name": self._name, "reason": reason})
        self._reaccount()
        self._publish_gauges()
        return freed

    def retire(self) -> None:
        """Mark this store's ledger entry expected-to-free (a compaction
        swap retiring the pre-fold epoch's store — the retirement audit
        then proves draining leases actually release it)."""
        obs_mem.retire(self._mem)

    # -- the double-buffered device hop --------------------------------------
    def _slot_upload(self, key: tuple, host_arr: np.ndarray):
        """Upload ``host_arr`` through the shape-keyed slot ring: the
        ring REPLACES its ``turn`` entry per upload, so the ledger's
        accounted slot bytes per shape are constant in steady state; a
        ring only allocates (and reaccounts) once per NEW shape. The
        displaced upload frees by reference drop once the batch that
        consumed it completes — the same flat-bytes contract
        ``serve/staging`` documents for its unpinned mode.

        Deliberately NOT the staging buffers' ``donate_argnums``
        program: donation invalidates the stale buffer at dispatch, and
        searches here are lock-free by design — a concurrent same-shape
        fetch may still HOLD a previously returned slot it has not yet
        dispatched, so donating it would fail that query ("array has
        been deleted"). Staging's donation is safe only under its
        single-flush-worker discipline, which this path cannot assume.
        The ring lock keeps the turn bookkeeping and ring growth
        consistent; the host gather stays concurrent."""
        import jax

        dev = (jax.device_put(host_arr, self._device)
               if self._device is not None else jax.device_put(host_arr))
        grew = 0
        with self._ring_lock:
            ring = self._slots.get(key)
            if ring is None:
                ring = self._slots[key] = []
                self._turn[key] = 0
            if len(ring) < self._policy.fetch_slots:
                ring.append(dev)
                grew = int(host_arr.nbytes)
            else:
                turn = self._turn[key]
                ring[turn] = dev
                self._turn[key] = (turn + 1) % len(ring)
        if grew:
            with self._lock:
                self._slot_bytes += grew
            self._reaccount()
            self._publish_gauges()
        return dev

    def fetch(self, slots, res=None):
        """Gather candidate rows by store SLOT for the refine epilogue:
        ``slots`` (m, k0) int (device or host; ``-1`` = padding — reads
        row 0, masked downstream by candidate id) → device rows
        (m, k0, d). Mirror-resident stores gather on device (a tier
        *hit*, zero transfer); cold stores gather on the host and upload
        through the replacement slot ring — under async dispatch batch N+1's
        H2D overlaps batch N's compute, which is the whole refine-hop
        cost model. Hit-rate promote rides here: ``promote_min_hits``
        cold fetches with budget headroom lift the mirror."""
        faults.fire("tier/fetch", name=self._name, residency=self.residency)
        # mirror SNAPSHOT: a pressure spill can null self._mirror from a
        # writer thread between a check and a use — the local reference
        # keeps this query on the (still-live) promoted copy; "a spill
        # loses nothing" includes queries in flight
        mirror = self._mirror
        if mirror is not None:
            out = mirror_gather(mirror, slots)
            n_rows = int(np.prod(out.shape[:-1]))
            self._rows_fetched += n_rows
            self._rows_hit += n_rows
            if metrics._enabled:
                _c_fetches().inc(1, name=self._name, src="device")
            self._publish_hit_ratio()
            return out
        t0 = time.perf_counter()
        ids = np.asarray(slots)
        gathered = np.take(self._rows, np.clip(ids, 0, None), axis=0)
        dev = self._slot_upload(("fetch",) + gathered.shape, gathered)
        self._fetch_wall_s += time.perf_counter() - t0
        self._rows_fetched += int(ids.size)
        self._cold_fetches += 1
        self._h2d_bytes += int(gathered.nbytes)
        src = "disk" if self._on_disk else "host"
        if metrics._enabled:
            _c_fetches().inc(1, name=self._name, src=src)
            _c_h2d().inc(int(gathered.nbytes), name=self._name)
        obs_dispatch.note(1)
        if (self._policy.auto_promote
                and self._cold_fetches >= self._policy.promote_min_hits):
            self._cold_fetches = 0
            # hit-rate promote fires ONLY under an ARMED budget with
            # headroom: without a budget there is no safe ceiling to
            # promote against, and uploading a beyond-HBM store because
            # it was queried 3 times is exactly the OOM tiering exists
            # to avoid (explicit promote()/load-layout restore remain
            # available without a budget)
            res_eff = res or default_resources()
            if getattr(res_eff, "memory_budget_bytes", None) is not None:
                self.promote(res=res_eff, reason="hit-rate")
        self._publish_hit_ratio()
        return dev

    # -- the chunked oracle scan ---------------------------------------------
    @property
    def oracle_chunk(self) -> int:
        """Device shape of one oracle chunk (every pass reuses it, so the
        exact scan is one program regardless of store size)."""
        return min(self._policy.oracle_chunk,
                   _pow2_at_least(self._rows.shape[0]))

    def n_oracle_chunks(self) -> int:
        c = self.oracle_chunk
        return -(-self._rows.shape[0] // c)

    def oracle_chunk_dev(self, ci: int):
        """``(rows_dev (chunk, d), base, valid)`` — chunk ``ci`` of the
        cold rows uploaded through the slot ring (zero NET device bytes
        across a scan; the last chunk zero-pads and reports ``valid`` <
        chunk so the caller can mask). Mirror-resident stores never call
        this — they scan the mirror directly."""
        c = self.oracle_chunk
        base = ci * c
        n = self._rows.shape[0]
        expects(0 <= base < n, "oracle chunk %d out of range", ci)
        t0 = time.perf_counter()
        valid = min(c, n - base)
        block = self._rows[base:base + valid]
        if valid < c:
            pad = np.zeros((c, self._rows.shape[1]), self._rows.dtype)
            pad[:valid] = block
            block = pad
        else:
            block = np.ascontiguousarray(block)
        dev = self._slot_upload(("oracle", c), block)
        self._fetch_wall_s += time.perf_counter() - t0
        self._h2d_bytes += int(block.nbytes)
        src = "disk" if self._on_disk else "host"
        if metrics._enabled:
            _c_fetches().inc(1, name=self._name, src=src)
            _c_h2d().inc(int(block.nbytes), name=self._name)
        return dev, base, valid

    # NOTE on warmup: there is deliberately no store-level warm helper —
    # the one rehearsal path is ``MutableIndex.warm_refined``, which runs
    # the REAL search_refined / chunked-scan programs (filling these same
    # rings as a side effect), so the warmed set can never drift from
    # what the serving path actually dispatches.


def _pow2_at_least(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


def _unlink_quiet(path: str) -> None:
    try:
        os.unlink(path)
    except OSError:
        pass


def _unlink_if_same_inode(path: str, devino: tuple) -> None:
    """Unlink ``path`` only if it still names the inode the owning store
    created — a later store may have reused the path with a fresh inode
    (same name/epoch collision), and the older store's death must not
    delete the live file."""
    try:
        stat = os.stat(path)
        if (stat.st_dev, stat.st_ino) == devino:
            os.unlink(path)
    except OSError:
        pass

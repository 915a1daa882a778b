"""IVF-Flat: inverted-file index over raw vectors.

Re-design of the reference's IVF-Flat (cpp/include/raft/neighbors/ivf_flat-inl.cuh;
build detail/ivf_flat_build.cuh — balanced-kmeans coarse quantizer, interleaved
list groups :86,135-153; search detail/ivf_flat_search-inl.cuh:130 — coarse GEMM
+ select_k, fused interleaved scan). The TPU re-think:

- **List layout**: the reference interleaves vectors in groups of 32 for
  coalesced warp reads; the TPU analogue is a dense padded (n_lists, capacity,
  d) array — capacity is the max list size rounded to the f32 sublane tile (8),
  balanced k-means keeps the padding overhead small, and every scan is a
  contiguous block DMA.
- **Search**: coarse scoring is one MXU GEMM + select_k (same two-stage shape
  as the reference); the list scan gathers each query's probed lists and
  scores them with an einsum that contracts d on the MXU, tiled over
  (query-tile, probe-chunk) under lax.map so the gathered block stays inside
  the workspace budget. Stored-vector norms are precomputed at build, so L2
  scores are ‖v‖² - 2·q·v — no recomputation per query.
- **Static shapes**: probes, capacity, k are all static; padding slots carry
  +inf scores and id -1, and can never win select_k.
"""

from __future__ import annotations

from ..config import auto_convert_output

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..cluster import kmeans_balanced
from ..cluster.kmeans_balanced import KMeansBalancedParams
from ..core import chunked, tracing
from ..core.errors import expects
from ..core.resources import Resources, default_resources
from ..core.serialize import (check_header, deserialize_mdspan, deserialize_scalar,
                              deserialize_tuned, serialize_header,
                              serialize_mdspan, serialize_scalar,
                              serialize_tuned)
from ..distance.pairwise import _choose_tile
from ..distance.types import DistanceType, resolve_metric
from ..matrix.select_k import _select_k
from ..obs.instrument import dtype_of, instrument, nrows
from ..obs import mem as obs_mem
from ._list_utils import (assign_to_lists, bound_capacity, list_positions,
                          plan_search_tiles, round_up)

__all__ = ["IndexParams", "SearchParams", "IvfFlatIndex", "build", "extend", "search", "save", "load"]


@dataclasses.dataclass(frozen=True)
class IndexParams:
    """Reference: ivf_flat::index_params (neighbors/ivf_flat_types.hpp)."""

    n_lists: int = 1024
    metric: str | DistanceType = "sqeuclidean"
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    # coarse-trainer EM cost policy (KMeansBalancedParams.train_mode /
    # batch_rows): "auto" runs mini-batch EM when the trainset exceeds
    # 2 x kmeans_batch_rows — at 1M scale this collapses the ~22
    # full-trainset assignment passes (the Round-6-measured dominant build
    # cost) to the two closing passes. "full" pins the pre-r07 behavior.
    kmeans_train_mode: str = "auto"
    kmeans_batch_rows: int = 65536
    add_data_on_build: bool = True
    seed: int = 0
    # storage dtype of list vectors (reference: the float/half/int8_t/uint8_t
    # ivf_flat instantiations, cpp/src/neighbors/ivf_flat_build_*.cu):
    #   "auto"     — float32 for float data, int8 for int8/uint8 data.
    #   "bfloat16" — halves the scan's HBM gather traffic (the 1M-scale
    #                bottleneck) at negligible recall cost; norms stay f32,
    #                scoring accumulates in f32 on the MXU.
    #   "int8"     — RAW 8-bit data stored as-is (uint8 shifted by -128 into
    #                the s8 domain; L2 is shift-invariant): 1-byte gathers
    #                (half of bf16) and s8 x s8 -> s32 MXU scoring with
    #                EXACT integer partial scores. Requires int8/uint8 input
    #                (quantized storage for float data is IVF-PQ's job).
    #   "float32"  — float storage for any input.
    list_dtype: str = "auto"
    # capacity bound for sub-list splitting, as a multiple of the mean list
    # size (see _list_utils.bound_capacity). 1.3 measured +24% search QPS at
    # identical 0.9999 recall vs 2.0 at 1M x 128 (the scan is bound by
    # padded-gather bytes; sibling sub-lists tie in coarse score and are
    # probed together, so tighter capacity costs no coverage here)
    split_factor: float = 1.3


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Reference: ivf_flat::search_params (neighbors/ivf_flat_types.hpp)."""

    n_probes: int = 20


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IvfFlatIndex:
    """Reference: ivf_flat::index (neighbors/ivf_flat_types.hpp:224)."""

    centers: jax.Array  # (n_lists, d) f32
    list_data: jax.Array  # (n_lists, capacity, d)
    list_ids: jax.Array  # (n_lists, capacity) int32, -1 = padding
    list_norms: jax.Array  # (n_lists, capacity) f32, +inf on padding
    list_sizes: jax.Array  # (n_lists,) int32
    metric: DistanceType
    # build-time capacity policy; extend() inherits it so the no-split /
    # split behavior chosen at build survives incremental additions
    split_factor: float = 1.3
    # what the list vectors ARE: "float32"/"bfloat16" (float storage),
    # "int8" (signed bytes as given), "uint8" (bytes stored shifted by
    # -128 into the s8 domain — queries shift the same way at search)
    data_kind: str = "float32"
    # pinned operating point (raft_tpu.tune decision dict; None = untuned):
    # consulted by batched_searcher when no explicit params are given,
    # persisted by save/load (raft_tpu/9). NOT part of the pytree (same
    # contract as cagra's seed_pool_hint): tree round trips drop it back
    # to None — defaults, never an error.
    tuned: dict | None = None

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def capacity(self) -> int:
        return self.list_data.shape[1]

    @property
    def size(self) -> int:
        """Total stored vectors. Computed on host so it stays concrete even
        when an enclosing jit trace is active (e.g. a user wrapping search()
        in jax.jit captures the index as a closure constant — staging the sum
        would make int() fail on a tracer). Unavailable when the index itself
        is a traced jit argument."""
        import numpy as np

        return int(np.asarray(jax.device_get(self.list_sizes)).sum())

    def tree_flatten(self):
        return (
            (self.centers, self.list_data, self.list_ids, self.list_norms, self.list_sizes),
            (self.metric, self.split_factor, self.data_kind),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        metric, split_factor, kind = (aux if len(aux) == 3
                                      else (*aux, "float32"))
        return cls(*children, metric=metric, split_factor=split_factor,
                   data_kind=kind)


def _count_fill_pass(kb: KMeansBalancedParams, n: int) -> None:
    """Count the build's list-fill assignment pass (one full-dataset
    nearest-center pass outside the trainer's fit) under the same
    raft_tpu_build_* series the trainer emits, so driver="single" and
    driver="distributed" report the identical em/final/fill decomposition
    (docs/observability.md). Shared by the ivf_flat and ivf_pq builds."""
    from ..obs import build as build_metrics
    from ..obs import metrics as _metrics

    if not _metrics._enabled:
        return
    mode = kmeans_balanced.resolve_train_mode(
        kb.train_mode, min(kb.max_train_points or n, n), kb.batch_rows)
    build_metrics.assignment_passes().inc(1, phase="fill", mode=mode,
                                          driver="single")


@functools.partial(jax.jit, static_argnames=("n_lists", "capacity"))
def _fill_lists(x, ids, labels, n_lists: int, capacity: int):
    """Scatter vectors into padded lists (ref: ivf_flat_build.cuh:160
    process-and-fill; one vectorized scatter instead of per-vector atomics)."""
    n, d = x.shape
    pos, counts = list_positions(labels, n_lists)
    data = jnp.zeros((n_lists, capacity, d), x.dtype)
    idbuf = jnp.full((n_lists, capacity), -1, jnp.int32)
    norms = jnp.full((n_lists, capacity), jnp.inf, jnp.float32)
    data = data.at[labels, pos].set(x)
    idbuf = idbuf.at[labels, pos].set(ids.astype(jnp.int32))
    xf = x.astype(jnp.float32)
    norms = norms.at[labels, pos].set(jnp.sum(xf * xf, axis=1))
    return data, idbuf, norms, counts.astype(jnp.int32)


def _resolve_storage(list_dtype: str, x, mt: DistanceType):
    """Resolve the list_dtype policy for a dataset: returns (data_kind,
    storage-domain x, f32 working view). Shared by the single-chip build and
    the distributed build (parallel/ivf.py) so both ingest int8/uint8
    identically."""
    expects(list_dtype in ("auto", "float32", "bfloat16", "int8"),
            "list_dtype must be 'auto', 'float32', 'bfloat16' or 'int8', "
            "got %r", list_dtype)
    int_in = x.dtype in (jnp.dtype(jnp.int8), jnp.dtype(jnp.uint8))
    ld = list_dtype
    if ld == "auto":
        ld = "int8" if int_in else "float32"
    if ld == "int8":
        expects(int_in, "list_dtype='int8' stores raw 8-bit data; got a %s "
                "dataset (quantized storage for float data is IVF-PQ's "
                "job)", x.dtype)
        # uint8 under IP is NOT shift-invariant and the per-vector sum
        # correction is not stored; int8 IP needs no shift and is exact
        expects(mt != DistanceType.InnerProduct or x.dtype == jnp.int8,
                "uint8 + inner_product is unsupported in int8 storage "
                "(the -128 shift changes inner products); use "
                "list_dtype='float32'")
        kind = str(x.dtype)
        from .brute_force import _as_signed

        x = _as_signed(x)  # all further work in the shifted s8 domain
        return kind, x, x.astype(jnp.float32)
    x = x.astype(jnp.float32) if int_in else x
    return ld, x, x.astype(jnp.float32)


def _stream_probe(dtype, d: int):
    """A zero-row device array in the reader's CANONICALIZED dtype: lets
    :func:`_resolve_storage` run its full validation/resolution without the
    corpus ever materializing. Canonicalization through the device matches
    what ``jnp.asarray`` does to the in-core twin (f64 host rows land f32),
    so the resolved storage dtype is identical in both modes."""
    return jnp.asarray(np.zeros((0, d), dtype))


def _stream_f32_view(kind: str):
    """Device-side conversion raw chunk -> the f32 working view the coarse
    trainer sees — the streamed twin of :func:`_resolve_storage`'s third
    return. Elementwise (byte shift, upcast), so it COMMUTES with the
    trainset row gather: ``convert(take(corpus, idx)) ==
    take(convert(corpus), idx)`` bitwise — half the bit-equality
    contract (core/chunked module docstring)."""
    if kind in ("int8", "uint8"):
        from .brute_force import _as_signed

        return lambda v: _as_signed(v).astype(jnp.float32)
    return lambda v: v.astype(jnp.float32)


@instrument("ivf_flat.build",
            items=lambda a, kw: nrows(a[1] if len(a) > 1 else kw["dataset"]),
            labels=lambda a, kw: {
                "dtype": dtype_of(a[1] if len(a) > 1 else kw["dataset"]),
                "n_lists": (a[0] if a else kw["params"]).n_lists,
            })
def build(params: IndexParams, dataset, res: Resources | None = None) -> IvfFlatIndex:
    """Build the index (reference: ivf_flat::build, ivf_flat-inl.cuh;
    coarse centers via balanced k-means on a training subsample, then fill)."""
    res = res or default_resources()
    stream = chunked.is_reader(dataset)
    x = None if stream else jnp.asarray(dataset)
    src = dataset if stream else x
    expects(src.ndim == 2, "dataset must be (n, d)")
    n, d = (int(s) for s in src.shape)
    expects(params.n_lists <= n, "n_lists > n_samples")
    mt = resolve_metric(params.metric)
    expects(
        mt
        in (
            DistanceType.L2Expanded,
            DistanceType.L2SqrtExpanded,
            DistanceType.L2Unexpanded,
            DistanceType.L2SqrtUnexpanded,
            DistanceType.InnerProduct,
        ),
        "ivf_flat supports L2 / inner_product metrics, got %s",
        mt.name,
    )

    if stream:
        # dtype-only storage resolution (same validation, on an empty
        # probe — the corpus never materializes here), then the STREAMED
        # admission: price the chunked build peak against BOTH budgets
        # before the coarse trainer spends anything
        kind, probe_x, _ = _resolve_storage(
            params.list_dtype, _stream_probe(dataset.dtype, d), mt)
        plan_kw = dict(
            dtype=kind if kind in ("int8", "uint8", "bfloat16") else "float32",
            streamed=True, chunk_rows=dataset.chunk_rows)
        obs_mem.gate(
            res,
            lambda: obs_mem.plan("ivf_flat", params, n, d,
                                 **plan_kw)["build_peak_bytes"],
            site="build_stream", detail=f"ivf_flat {n}x{d} ooc",
            host_bytes=lambda: obs_mem.plan("ivf_flat", params, n, d,
                                            **plan_kw)["host_peak_bytes"])
        xf = chunked.converted(dataset, _stream_f32_view(kind))
    else:
        kind, x, xf = _resolve_storage(params.list_dtype, x, mt)
        # memory-budget admission (no-op unless res.memory_budget_bytes is
        # set): refuse BEFORE the coarse trainer spends anything
        obs_mem.gate(res, lambda: obs_mem.plan(
            "ivf_flat", params, n, d,
            dtype=kind if kind in ("int8", "uint8", "bfloat16") else "float32"
        )["index_bytes"], site="build", detail=f"ivf_flat {n}x{d}")
    max_train = max(int(n * params.kmeans_trainset_fraction), params.n_lists)
    train_metric = "inner_product" if mt == DistanceType.InnerProduct else "sqeuclidean"
    kb = KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=train_metric, seed=params.seed,
        max_train_points=min(max_train, n),
        train_mode=params.kmeans_train_mode,
        batch_rows=params.kmeans_batch_rows,
    )
    with tracing.range("ivf_flat.build.coarse_kmeans"):
        centers = kmeans_balanced.fit(kb, xf, params.n_lists, res=res)
    if params.add_data_on_build:
        _count_fill_pass(kb, n)

    storage = {"bfloat16": jnp.bfloat16, "int8": jnp.int8,
               "uint8": jnp.int8}.get(kind, probe_x.dtype if stream
                                      else x.dtype)

    if not params.add_data_on_build:
        cap = 8
        empty = IvfFlatIndex(
            centers=centers,
            list_data=jnp.zeros((params.n_lists, cap, d), storage),
            list_ids=jnp.full((params.n_lists, cap), -1, jnp.int32),
            list_norms=jnp.full((params.n_lists, cap), jnp.inf, jnp.float32),
            list_sizes=jnp.zeros((params.n_lists,), jnp.int32),
            metric=mt,
            split_factor=params.split_factor,
            data_kind=kind,
        )
        obs_mem.account_index(empty)
        return empty

    seed = IvfFlatIndex(
        centers=centers,
        list_data=jnp.zeros((params.n_lists, 0, d), storage),
        list_ids=jnp.zeros((params.n_lists, 0), jnp.int32),
        list_norms=jnp.zeros((params.n_lists, 0), jnp.float32),
        list_sizes=jnp.zeros((params.n_lists,), jnp.int32),
        metric=mt,
        split_factor=params.split_factor,
        data_kind=kind,
    )
    if stream:
        return _extend_stream_signed(seed, dataset, None, res=res)
    return _extend_signed(seed, x, jnp.arange(n, dtype=jnp.int32), res=res)


# host batches past this size stream through the chunked path instead of
# one whole-batch ``jnp.asarray`` — the extend() full-materialization fix
# (a 1M x 128 f32 batch is 512 MiB of device scratch the chunked path
# replaces with two 32 MiB staged chunks)
_STREAM_EXTEND_BYTES = 256 << 20


@instrument("ivf_flat.extend",
            items=lambda a, kw: nrows(a[1] if len(a) > 1 else kw["new_vectors"]))
def extend(index: IvfFlatIndex, new_vectors, new_ids=None, res: Resources | None = None,
           split_factor: float | None = None) -> IvfFlatIndex:
    """Append vectors (reference: ivf_flat::extend, ivf_flat-inl.cuh:160,287).

    Capacity is data-dependent, so extend re-packs lists host-orchestrated:
    existing + new vectors are re-scattered into a freshly sized padded array
    (the reference reallocates lists too — ivf_list.hpp resize).

    A :class:`~raft_tpu.core.chunked.ChunkedReader` batch (or any host
    ndarray past ``_STREAM_EXTEND_BYTES``) takes the out-of-core path:
    per-chunk assign + scatter, never materializing the batch on device."""
    if (not chunked.is_reader(new_vectors)
            and isinstance(new_vectors, np.ndarray)
            and new_vectors.ndim == 2
            and new_vectors.nbytes > _STREAM_EXTEND_BYTES):
        new_vectors = chunked.ChunkedReader(new_vectors)
    if chunked.is_reader(new_vectors):
        return _extend_stream_signed(index, new_vectors, new_ids, res=res,
                                     split_factor=split_factor)
    x = jnp.asarray(new_vectors)
    if index.data_kind in ("int8", "uint8"):
        # 8-bit indexes take vectors in the index's ORIGINAL dtype; a plain
        # astype would wrap uint8 values mod 256 instead of shifting them
        expects(str(x.dtype) == index.data_kind,
                "this index stores %s vectors; got %s", index.data_kind,
                x.dtype)
        from .brute_force import _as_signed

        x = _as_signed(x)
    return _extend_signed(index, x, new_ids, res=res,
                          split_factor=split_factor)


def _extend_signed(index: IvfFlatIndex, new_vectors, new_ids=None,
                   res: Resources | None = None,
                   split_factor: float | None = None) -> IvfFlatIndex:
    """extend() after domain conversion: vectors already live in the index's
    storage domain (s8-shifted for uint8 kinds)."""
    res = res or default_resources()
    # storage dtype travels with the index (build's list_dtype choice)
    x = jnp.asarray(new_vectors).astype(index.list_data.dtype)
    expects(x.ndim == 2 and x.shape[1] == index.dim, "vector dim mismatch")
    n_new = x.shape[0]
    if new_ids is None:
        new_ids = index.size + jnp.arange(n_new, dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)

    tile = _choose_tile(n_new, index.n_lists, 1, res.workspace_bytes)
    xa = x.astype(jnp.float32) if x.dtype == jnp.int8 else x
    with tracing.range("ivf_flat.extend.assign"):
        labels = assign_to_lists(xa, index.centers, index.metric, tile)

    # merge with existing list contents (flatten old lists back to rows)
    if index.capacity > 0 and index.size > 0:
        old_mask = index.list_ids.reshape(-1) >= 0
        old_x = index.list_data.reshape(-1, index.dim)[old_mask]
        old_ids = index.list_ids.reshape(-1)[old_mask]
        old_labels = jnp.repeat(jnp.arange(index.n_lists), index.capacity)[old_mask]
        x = jnp.concatenate([old_x, x])
        new_ids = jnp.concatenate([old_ids, new_ids])
        labels = jnp.concatenate([old_labels.astype(jnp.int32), labels])

    import numpy as np

    # shared capacity policy: hot lists split into sub-lists. SEVERELY
    # oversized lists (>= 8x the cap — a mega-cluster the coarse trainer
    # could not divide) split SPATIALLY into principal-axis slabs and get
    # their OWN member-mean centers below; mild splits keep the order
    # split + duplicated centers (bound_capacity decides — see its
    # docstring for the measured rationale). IVF-Flat can re-center freely
    # because centers only drive probing/assignment, not scoring (the scan
    # reads raw vectors); differentiated centers let a query probe only
    # its nearby slabs instead of ~n_probes arbitrary siblings.
    sf = index.split_factor if split_factor is None else split_factor
    labels, rep, n_lists, capacity, spatial = bound_capacity(
        labels, index.n_lists, sf, x=x.astype(jnp.float32))
    centers = index.centers
    with tracing.range("ivf_flat.extend.fill_lists"):
        data, idbuf, norms, sizes = _fill_lists(x, new_ids, labels, n_lists, capacity)
    if rep is not None:
        centers = jnp.asarray(np.repeat(np.asarray(centers), rep, axis=0))
        if spatial is not None and spatial.any():
            # recenter EXACTLY the slab-ordered lists' children on their
            # member means (bound_capacity's per-list gate — keeping the
            # two sides aligned so order-split siblings are never
            # recentered, the regime measured worse)
            mask = idbuf >= 0
            sums = jnp.sum(jnp.where(mask[..., None],
                                     data.astype(jnp.float32), 0.0), axis=1)
            means = sums / jnp.maximum(sizes, 1)[:, None].astype(jnp.float32)
            child = jnp.asarray(np.repeat(spatial, rep))
            centers = jnp.where(child[:, None], means, centers)
    out = IvfFlatIndex(centers, data, idbuf, norms, sizes, index.metric, sf,
                       index.data_kind)
    # ledger hook (docs/observability.md): the new padded lists are the
    # long-lived allocation; the superseded index's entry auto-releases
    # when the caller drops it
    obs_mem.account_index(out)
    return out


@functools.partial(jax.jit, static_argnames=("n_lists",),
                   donate_argnums=(0, 1, 2, 3))
def _fill_chunk(data, idbuf, norms, offsets, x, ids, labels, n_lists: int):
    """One streamed scatter pass: place a chunk's rows at their running
    within-list offsets (``offsets`` carries each list's fill level across
    chunks — chunk-local rank + prior count equals the full-array rank
    ``list_positions`` would assign, since both orderings are stable by
    input position). Pad rows arrive labelled ``n_lists`` (one past the
    last list): their position math lands in the sentinel slot of the
    extended count/offset vectors and the scatter drops them out of
    bounds — no host-side filtering, so the chunk loop never syncs.
    Donation reuses the accumulator buffers in place, which is what keeps
    the build's device peak FLAT in chunk count."""
    pos_local, counts = list_positions(labels, n_lists + 1)
    offs = jnp.concatenate([offsets, jnp.zeros((1,), jnp.int32)])
    pos = pos_local + jnp.take(offs, labels)
    data = data.at[labels, pos].set(x, mode="drop")
    idbuf = idbuf.at[labels, pos].set(ids.astype(jnp.int32), mode="drop")
    xf = x.astype(jnp.float32)
    norms = norms.at[labels, pos].set(jnp.sum(xf * xf, axis=1), mode="drop")
    return data, idbuf, norms, offsets + counts[:n_lists]


def _extend_stream_signed(index: IvfFlatIndex, reader, new_ids=None,
                          res: Resources | None = None,
                          split_factor: float | None = None) -> IvfFlatIndex:
    """The streamed twin of :func:`_extend_signed`: two passes over the
    reader's chunks (assign, then scatter) instead of one whole-corpus
    device array. Bit-equal to the in-core path because every per-row
    quantity — ingest conversion, nearest-center label, within-list rank,
    norm — comes from the SAME helpers and none couples rows across a
    batch (tests/test_ooc_build.py asserts the full-index equality). The
    one intentional divergence: ``bound_capacity``'s spatial mega-cluster
    split needs the whole corpus on device, so severely oversized lists
    fall back to the order split here. Device peak is index accumulators
    + two staged chunks + the label/id vectors — CONSTANT in corpus rows
    beyond the index itself (the ``ooc_build`` bench row's claim)."""
    from ..obs import build as build_metrics
    from ..obs import metrics as _metrics

    res = res or default_resources()
    n_new, d = (int(s) for s in reader.shape)
    expects(d == index.dim, "vector dim mismatch")
    storage_dt = index.list_data.dtype
    if index.data_kind in ("int8", "uint8"):
        expects(str(reader.dtype) == index.data_kind,
                "this index stores %s vectors; got %s", index.data_kind,
                reader.dtype)
        from .brute_force import _as_signed

        def ingest(v):
            return _as_signed(v).astype(storage_dt)
    else:
        def ingest(v):
            return v.astype(storage_dt)

    if new_ids is None:
        new_ids = index.size + jnp.arange(n_new, dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)
        expects(int(new_ids.shape[0]) == n_new, "ids/vectors length mismatch")

    cr = int(reader.chunk_rows)
    emit = _metrics.enabled()
    stager = chunked.ChunkStager(cr, d, reader.dtype, kind="ivf_flat")
    try:
        # ---- pass A: per-chunk nearest-center assignment. Labels stay
        # DEVICE-resident parts until one concatenate at the end — the
        # loop itself never syncs the host (satellite guard:
        # test_ooc_build asserts a repeat build compiles nothing).
        tile = _choose_tile(cr, index.n_lists, 1, res.workspace_bytes)
        parts = []
        with tracing.range("ivf_flat.extend.assign_stream"):
            for start, block in reader.chunks():
                xs = ingest(stager.stage(block))
                xa = xs.astype(jnp.float32) if xs.dtype == jnp.int8 else xs
                parts.append(assign_to_lists(xa, index.centers,
                                             index.metric, tile))
                if emit:
                    build_metrics.ooc_chunks().inc(1, kind="ivf_flat",
                                                   stage="assign")
        labels = jnp.concatenate(parts)[:n_new]  # drop pad-row garbage
        del parts

        # merge with existing list contents (flatten old lists back to
        # rows — same ordering as _extend_signed: OLD FIRST, so stable
        # ranks, and therefore the final layout, agree with the in-core
        # twin)
        n_old = 0
        old_x = old_ids = None
        if index.capacity > 0 and index.size > 0:
            old_mask = index.list_ids.reshape(-1) >= 0
            old_x = index.list_data.reshape(-1, d)[old_mask]
            old_ids = index.list_ids.reshape(-1)[old_mask]
            old_labels = jnp.repeat(jnp.arange(index.n_lists),
                                    index.capacity)[old_mask]
            n_old = int(old_x.shape[0])
            labels = jnp.concatenate([old_labels.astype(jnp.int32), labels])

        # capacity policy over the FULL label vector (one host sync for
        # the max size — per build, not per chunk). x=None: the spatial
        # split would need the whole corpus device-resident, so severe
        # lists order-split instead (see docstring).
        sf = index.split_factor if split_factor is None else split_factor
        labels, rep, n_lists2, capacity, _ = bound_capacity(
            labels, index.n_lists, sf, x=None)
        centers = index.centers
        if rep is not None:
            centers = jnp.asarray(np.repeat(np.asarray(centers), rep,
                                            axis=0))

        # ---- pass B: chunked scatter into the sealed layout -----------
        data = jnp.zeros((n_lists2, capacity, d), storage_dt)
        idbuf = jnp.full((n_lists2, capacity), -1, jnp.int32)
        norms = jnp.full((n_lists2, capacity), jnp.inf, jnp.float32)
        offsets = jnp.zeros((n_lists2,), jnp.int32)
        # transient ledger entry: the accumulators + label/id vectors ARE
        # the streamed build's device working set (plan()'s streamed-mode
        # estimate prices exactly this); released before the sealed index
        # is accounted so /debug/mem never double-counts the layout
        ooc_tok = obs_mem.account(
            "build/ooc", name="ivf_flat",
            device_bytes=int(data.nbytes + idbuf.nbytes + norms.nbytes
                             + offsets.nbytes + labels.nbytes
                             + new_ids.nbytes),
            owner=stager)
        with tracing.range("ivf_flat.extend.fill_stream"):
            if n_old > 0:
                data, idbuf, norms, offsets = _fill_chunk(
                    data, idbuf, norms, offsets, old_x, old_ids,
                    labels[:n_old], n_lists=n_lists2)
                labels = labels[n_old:]
            # pad the tail so every chunk's slice is full-size (ONE
            # executable): sentinel label n_lists2 -> scatter dropped
            pad = -(-n_new // cr) * cr - n_new
            lab_p = (jnp.concatenate(
                [labels, jnp.full((pad,), n_lists2, jnp.int32)])
                if pad else labels)
            ids_p = (jnp.concatenate(
                [new_ids, jnp.full((pad,), -1, jnp.int32)])
                if pad else new_ids)
            for start, block in reader.chunks():
                xs = ingest(stager.stage(block))
                st = jnp.int32(start)  # operand, not executable key
                lab_c = lax.dynamic_slice_in_dim(lab_p, st, cr)
                ids_c = lax.dynamic_slice_in_dim(ids_p, st, cr)
                data, idbuf, norms, offsets = _fill_chunk(
                    data, idbuf, norms, offsets, xs, ids_c, lab_c,
                    n_lists=n_lists2)
                if emit:
                    build_metrics.ooc_chunks().inc(1, kind="ivf_flat",
                                                   stage="fill")
        sizes = offsets
        obs_mem.release(ooc_tok)
    finally:
        stager.release()
    out = IvfFlatIndex(centers, data, idbuf, norms, sizes, index.metric, sf,
                       index.data_kind)
    obs_mem.account_index(out)
    return out


@functools.partial(
    jax.jit, static_argnames=("n_probes", "k", "query_tile", "probe_chunk", "metric")
)
def _ivf_search(index: IvfFlatIndex, queries, n_probes: int, k: int,
                query_tile: int, probe_chunk: int, metric: DistanceType,
                keep_mask=None):
    m, d = queries.shape
    qf = queries.astype(jnp.float32)
    inner = metric == DistanceType.InnerProduct

    # ---- stage 1: coarse scoring (ref: ivf_flat_search-inl.cuh:130) ----
    with tracing.scope("ivf_flat.search.coarse"):
        cscore = qf @ index.centers.T  # (m, L) MXU
        if not inner:
            cn = jnp.sum(index.centers * index.centers, axis=1)
            cscore = cn[None, :] - 2.0 * cscore
        _, probes = _select_k(cscore, None, n_probes, not inner)  # (m, p)

    # pad queries to tile multiple
    num = -(-m // query_tile)
    pad = num * query_tile - m
    qp = jnp.pad(qf, ((0, pad), (0, 0))) if pad else qf
    pp = jnp.pad(probes, ((0, pad), (0, 0))) if pad else probes
    qt = qp.reshape(num, query_tile, d)
    pt = pp.reshape(num, query_tile, n_probes)

    n_chunks = n_probes // probe_chunk
    cap = index.capacity

    def per_tile(args):
        q, pr = args  # (T, d), (T, p)

        def per_chunk(c, _):
            pc = lax.dynamic_slice_in_dim(pr, c * probe_chunk, probe_chunk, axis=1)  # (T, pc)
            vecs = index.list_data[pc]  # (T, pc, cap, d) gather
            ids = index.list_ids[pc]  # (T, pc, cap)
            # NOTE: bf16 storage deliberately upcasts to f32 + HIGHEST here.
            # Measured at 1M x 128 (p=8): a native bf16 DEFAULT-precision
            # einsum is no faster (13.0k vs 15.4k QPS — the scan is bound by
            # the padded-list gather, not the matvec) and rounding the query
            # to bf16 costs recall (0.9697 vs 0.9756).
            # int8 lists ride the same upcast: the gather (the measured
            # bottleneck) moves 1 byte/dim — half of bf16 — and the f32
            # convert fuses into the dot's operand pipeline. Scoring is
            # EXACT for 8-bit values (every intermediate is an integer
            # below 2^24). A native s8 x s8 -> s32 einsum was tried and
            # REJECTED: on TPU the batched 4-d einsum decays to an inexact
            # bf16 lowering (measured 2.8% distance error, r05); only the
            # Pallas fused-kNN kernel's 2-d dot takes the true s8 MXU path.
            dots = jnp.einsum(
                "td,tpcd->tpc", q, vecs.astype(jnp.float32),
                precision=lax.Precision.HIGHEST,
            )
            if inner:
                scores = jnp.where(ids >= 0, dots, -jnp.inf)
            else:
                # +inf padding norms stay +inf; the id test also drops slots
                # whose id a caller set to -1 (stream's list-layout
                # tombstones), whose norms are finite
                norms = index.list_norms[pc]
                scores = jnp.where(ids >= 0, norms - 2.0 * dots, jnp.inf)
            if keep_mask is not None:
                from .sample_filter import apply_id_filter

                scores = apply_id_filter(scores, ids, keep_mask, not inner)
            flat_s = scores.reshape(query_tile, probe_chunk * cap)
            flat_i = ids.reshape(query_tile, probe_chunk * cap)
            return c + 1, _select_k(flat_s, flat_i, k, not inner)

        _, (cv, ci) = lax.scan(per_chunk, 0, None, length=n_chunks)
        # (chunks, T, k) → per-query merge
        cv = jnp.moveaxis(cv, 0, 1).reshape(query_tile, n_chunks * k)
        ci = jnp.moveaxis(ci, 0, 1).reshape(query_tile, n_chunks * k)
        return _select_k(cv, ci, k, not inner)

    with tracing.scope("ivf_flat.search.scan"):
        dists, idx = lax.map(per_tile, (qt, pt))
    dists = dists.reshape(num * query_tile, k)[:m]
    idx = idx.reshape(num * query_tile, k)[:m]
    if not inner:
        # convert ‖v‖²-2qv partial scores to true squared L2 by adding ‖q‖²
        qn = jnp.sum(qf * qf, axis=1, keepdims=True)
        dists = jnp.where(jnp.isfinite(dists), jnp.maximum(dists + qn, 0.0), dists)
        if metric in (DistanceType.L2SqrtExpanded, DistanceType.L2SqrtUnexpanded):
            dists = jnp.where(jnp.isfinite(dists), jnp.sqrt(dists), dists)
    if keep_mask is not None:
        # filtered-out candidates carry ±inf scores — report id -1, matching
        # the documented empty-slot sentinel
        idx = jnp.where(jnp.isinf(dists), -1, idx)
    return dists, idx


@instrument(
    "ivf_flat.search",
    items=lambda a, kw: nrows(a[2] if len(a) > 2 else kw["queries"]),
    labels=lambda a, kw: {"k": a[3] if len(a) > 3 else kw["k"],
                          "n_probes": (a[0] if a else kw["params"]).n_probes},
)
@auto_convert_output
def search(params: SearchParams, index: IvfFlatIndex, queries, k: int,
           sample_filter=None, res: Resources | None = None):
    """Search the index (reference: ivf_flat::search, ivf_flat-inl.cuh;
    pylibraft neighbors/ivf_flat search; filtered overload
    neighbors/ivf_flat.cuh search_with_filtering). Returns
    (distances (m,k), ids (m,k)); id -1 marks slots beyond the probed
    candidate count."""
    from .brute_force import _coerce_queries
    from .sample_filter import resolve_filter

    res = res or default_resources()
    queries = jnp.asarray(queries)
    expects(queries.ndim == 2 and queries.shape[1] == index.dim, "query dim mismatch")
    queries = _coerce_queries(index.data_kind, queries)
    expects(index.capacity > 0, "index is empty")
    if not isinstance(index.list_sizes, jax.core.Tracer):
        expects(index.size > 0, "index is empty")
    n_probes = min(params.n_probes, index.n_lists)
    m = queries.shape[0]
    expects(
        k <= n_probes * index.capacity,
        "k=%d exceeds the probed candidate pool (n_probes=%d x capacity=%d)",
        k, n_probes, index.capacity,
    )

    # gathered vectors (f32 staged) + norms + scores per slot; x2 for XLA
    # temporaries — the f32 staging bound holds for all storage dtypes
    query_tile, probe_chunk = plan_search_tiles(
        m, n_probes, int(k), index.capacity,
        bytes_per_probe_row=2 * index.capacity * (index.dim * 4 + 8),
        budget_bytes=res.workspace_bytes,
    )

    keep_mask = resolve_filter(sample_filter)
    if keep_mask is not None:
        from .sample_filter import validate_filter_covers

        validate_filter_covers(index, keep_mask)
    return _ivf_search(index, queries, n_probes, int(k), query_tile, probe_chunk,
                       index.metric, keep_mask)


def write_index(f, index: IvfFlatIndex) -> None:
    """Serialize to an open binary stream (the composable half of
    :func:`save` — :mod:`raft_tpu.stream` embeds sealed indexes this way)."""
    serialize_header(f, "ivf_flat")
    serialize_scalar(f, int(index.metric))
    serialize_scalar(f, float(index.split_factor))
    serialize_scalar(f, index.data_kind)
    serialize_mdspan(f, index.centers)
    serialize_mdspan(f, index.list_data)
    serialize_mdspan(f, index.list_ids)
    serialize_mdspan(f, index.list_norms)
    serialize_mdspan(f, index.list_sizes)
    serialize_tuned(f, index.tuned)


def read_index(f) -> IvfFlatIndex:
    """Deserialize from an open binary stream (pairs with
    :func:`write_index`)."""
    ver = check_header(f, "ivf_flat")
    metric = DistanceType(deserialize_scalar(f))
    split_factor = float(deserialize_scalar(f))
    # raft_tpu/5 added data_kind (int8/uint8 storage); older files —
    # including /4, whose global bump was for cagra and wrote ivf_flat
    # in the /3 layout — hold only float kinds, recoverable from the
    # stored dtype
    kind = (deserialize_scalar(f)
            if ver not in ("raft_tpu/2", "raft_tpu/3", "raft_tpu/4")
            else None)
    centers = jnp.asarray(deserialize_mdspan(f))
    data = jnp.asarray(deserialize_mdspan(f))
    ids = jnp.asarray(deserialize_mdspan(f))
    norms = jnp.asarray(deserialize_mdspan(f))
    sizes = jnp.asarray(deserialize_mdspan(f))
    if kind is None:
        kind = "bfloat16" if data.dtype == jnp.bfloat16 else "float32"
    # raft_tpu/9 appended the optional tuned record (pinned operating
    # point); older files are untuned
    tuned = deserialize_tuned(f, ver)
    return IvfFlatIndex(centers, data, ids, norms, sizes, metric, split_factor,
                        kind, tuned=tuned)


def save(index: IvfFlatIndex, path: str) -> None:
    """Serialize (reference: ivf_flat_serialize.cuh; pylibraft save).
    Atomic: temp file + rename, a crashed save keeps the previous file."""
    from ..core.serialize import atomic_write

    with atomic_write(path) as f:
        write_index(f, index)


def load(path: str, res: Resources | None = None) -> IvfFlatIndex:
    """Deserialize (reference: ivf_flat_serialize.cuh deserialize)."""
    with open(path, "rb") as f:
        return read_index(f)


def batched_searcher(index: IvfFlatIndex, params: SearchParams | None = None):
    """Stable serving hook (raft_tpu.serve; contract in :mod:`._hooks`) —
    the surface the serve registry warms and hot-swaps through. With no
    explicit ``params``, an attached tune decision (``index.tuned``, e.g.
    restored by a raft_tpu/9 load) supplies the pinned operating point —
    docs/tuning.md."""
    from ._hooks import make_hook

    if params is None and index.tuned is not None:
        from ..tune.apply import make_searcher as tuned_searcher

        return tuned_searcher(index, True, degrade_without_rows=True)
    sp = params or SearchParams()
    return make_hook(lambda queries, k: search(sp, index, queries, k),
                     "ivf_flat", index.dim, index.data_kind)

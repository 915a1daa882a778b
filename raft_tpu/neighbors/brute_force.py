"""Brute-force (exact) k-nearest neighbors.

Re-design of the reference's tiled brute-force kNN
(cpp/include/raft/neighbors/brute_force.cuh; detail/knn_brute_force.cuh:
memory-aware tile sizing chooseTileSize :78, per-tile select + merge :232-273,
knn_merge_parts detail/knn_merge_parts.cuh). TPU shape: queries are processed
in row tiles under lax.map — each tile is one MXU distance GEMM fused with
top-k — so the (n_queries, n_dataset) matrix never materializes. Per-shard
results merge with one select_k over concatenated candidates, the same merge
the reference runs after stream-pool multi-probe (knn_brute_force.cuh:490).
"""

from __future__ import annotations

from ..config import auto_convert_output

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core import tracing
from ..core.errors import expects
from ..core.held import HeldByArray, concrete
from ..core.resources import Resources, default_resources
from ..distance.pairwise import _PRECISIONS, _choose_tile, _pairwise, _pad_to_tiles
from ..distance.types import DistanceType, resolve_metric
from ..matrix.select_k import select_k
from ..obs import mem as obs_mem
from ..obs import metrics as _metrics
from ..obs.instrument import dtype_of, instrument, nrows

__all__ = ["knn", "knn_merge_parts", "search", "BruteForce"]

# metrics the fused Pallas kernel handles natively (ops/fused_knn.py);
# everything else stays on the XLA GEMM + top_k path
_FUSED_L2 = {
    DistanceType.L2Expanded: False,
    DistanceType.L2SqrtExpanded: True,
    DistanceType.L2Unexpanded: False,
    DistanceType.L2SqrtUnexpanded: True,
}


def _as_signed(x):
    """uint8 -> int8 by the -128 shift (L2-invariant; ip callers correct via
    the row operand's bias); int8 passes through. The reference instantiates int8_t and
    uint8_t kernels separately (cpp/src/neighbors/*_int8_t_*.cu /
    *_uint8_t_*.cu); on TPU the MXU's integer path is s8 x s8, so uint8
    rides the same kernel shifted."""
    if x.dtype == jnp.uint8:
        return (x.astype(jnp.int16) - 128).astype(jnp.int8)
    return x


def _coerce_queries(data_kind: str, queries):
    """Move queries into a byte index's storage domain — the search-side
    half of the _as_signed contract, shared by every index type
    (ivf_flat/ivf_pq/cagra, single-chip and distributed): integer queries
    must match the index's original dtype and shift with it; float queries
    against a shifted-uint8 index shift by -128 (L2-invariant)."""
    if data_kind not in ("int8", "uint8"):
        return queries
    if queries.dtype in (jnp.dtype(jnp.int8), jnp.dtype(jnp.uint8)):
        expects(str(queries.dtype) == data_kind,
                "this index stores %s vectors; got %s queries",
                data_kind, queries.dtype)
        return _as_signed(queries).astype(jnp.float32)
    if data_kind == "uint8":
        return queries.astype(jnp.float32) - 128.0
    return queries


def _bf_knn_s8(dataset, queries, k, metric, keep_mask, rows=None):
    """int8 MXU dispatch (~2x bf16 peak, 1-byte operand DMAs). Distances are
    EXACT integers for d <= ~340 (see ops/fused_knn mode='s8'). ``rows``
    is :func:`_rows_of` of the dataset (default: computed per call)."""
    from ..ops.fused_knn import fused_backend_ok, fused_knn

    rows = rows or _rows_of(dataset, None)
    _, interpret = fused_backend_ok()
    shifted = dataset.dtype == jnp.uint8
    ds = _as_signed(dataset)
    qs = _as_signed(queries)
    if metric in _FUSED_L2:
        return fused_knn(ds, qs, k, metric="l2", mode="s8",
                         keep_mask=keep_mask, sqrt=_FUSED_L2[metric],
                         row_operand=rows("l2"), interpret=interpret)
    # inner product: q·v = q'·v' + 128·Σv' + 128·Σq' + 128²·d for shifted
    # operands — the Σv' term rides the kernel's row operand, the
    # per-query constant is added outside
    if not shifted:
        return fused_knn(ds, qs, k, metric="ip", mode="s8",
                         keep_mask=keep_mask, interpret=interpret)
    d = dataset.shape[1]
    sim, idx = fused_knn(ds, qs, k, metric="ip", mode="s8",
                         keep_mask=keep_mask, row_operand=rows("ip_shift"),
                         interpret=interpret)
    qconst = (128.0 * jnp.sum(qs.astype(jnp.float32), axis=1, keepdims=True)
              + 16384.0 * d)
    return jnp.where(jnp.isinf(sim), sim, sim + qconst), idx


def _fused_eligible(metric, k, n, d, mode, compute):
    from ..ops.fused_knn import fused_backend_ok, shapes_eligible

    backend_ok, _ = fused_backend_ok()
    return (
        backend_ok
        and mode == "exact"
        and compute in ("float32", "float32x3", "bfloat16")
        and shapes_eligible(n, d, k)
        and (metric in _FUSED_L2
             or metric in (DistanceType.InnerProduct, DistanceType.CosineExpanded))
    )


def _bf_knn_fused(dataset, queries, k, metric, compute, keep_mask,
                  rows=None):
    """Route to the fused Pallas kernel (scores never leave VMEM); ``rows``
    as for :func:`_bf_knn_s8`."""
    from ..ops.fused_knn import fused_backend_ok, fused_knn

    rows = rows or _rows_of(dataset, None)
    mode = {"float32": "f32", "float32x3": "f32x3", "bfloat16": "bf16"}[compute]
    _, interpret = fused_backend_ok()
    if metric in _FUSED_L2:
        return fused_knn(dataset, queries, k, metric="l2", mode=mode,
                         keep_mask=keep_mask, sqrt=_FUSED_L2[metric],
                         row_operand=rows("l2"), interpret=interpret)
    if metric == DistanceType.InnerProduct:
        return fused_knn(dataset, queries, k, metric="ip", mode=mode,
                         keep_mask=keep_mask, interpret=interpret)
    # CosineExpanded: 1 - cos = 1 - ip over normalized rows (distance/pairwise
    # _cosine uses the same normalization)
    qn = jnp.linalg.norm(queries.astype(jnp.float32), axis=1, keepdims=True)
    yn = jnp.linalg.norm(dataset.astype(jnp.float32), axis=1, keepdims=True)
    sim, idx = fused_knn(dataset / jnp.maximum(yn, 1e-30),
                         queries / jnp.maximum(qn, 1e-30), k,
                         metric="ip", mode=mode, keep_mask=keep_mask,
                         interpret=interpret)
    dist = jnp.where(jnp.isinf(sim), jnp.inf, 1.0 - sim)
    return dist, idx


# -- the row operand and its holder ------------------------------------------

@functools.partial(jax.jit, static_argnames=("kind",))
def _row_operand(dataset, kind: str):
    """What the fused kernel subtracts from each row's score, over the rows
    as the kernel reads them (uint8 shifted into int8): ``kind="l2"`` the
    squared norms, ``"ip_shift"`` the shifted uint8 inner product's row
    bias. One program of its own (device stage ``brute_force.norms``)."""
    with tracing.scope("brute_force.norms"):
        x = _as_signed(dataset).astype(jnp.float32)
        if kind == "l2":
            return jnp.sum(x ** 2, axis=1)
        return -128.0 * jnp.sum(x, axis=1)


def _operand_kind(metric, dtype) -> str | None:
    """The row operand a fused search of ``metric`` over a dataset of
    ``dtype`` reads, or None when it reads none."""
    mt = resolve_metric(metric)
    if mt in _FUSED_L2:
        return "l2"
    if mt == DistanceType.InnerProduct and dtype == jnp.uint8:
        return "ip_shift"
    return None


@functools.lru_cache(maxsize=None)
def _norms_total():
    return _metrics.counter(
        "raft_tpu_brute_force_norms_total",
        "brute-force row norms by whether a search found them held "
        "(labels: result=hit|miss)")


class _NormCache(HeldByArray):
    """Row operands per dataset array, by kind
    (:class:`~raft_tpu.core.held.HeldByArray`: a dataset that is replaced
    is a new array and a miss, and the operands of an array are freed with
    it)."""

    def get(self, dataset, kind: str, *, hit: bool = True):
        """The operand of ``kind`` held for ``dataset``, computed on a miss;
        ``hit`` counts a found operand (a search does, a warm-up does not)."""
        if not concrete(dataset):
            return _row_operand(dataset, kind=kind)  # traced or host data
        with self._lock:
            entries = self._entries(dataset)
            out = entries.get(kind)
            if out is None:
                if _metrics._enabled:
                    _norms_total().inc(result="miss")
                out = entries[kind] = _row_operand(dataset, kind=kind)
            elif hit and _metrics._enabled:
                _norms_total().inc(result="hit")
            return out


_NORMS = _NormCache()


def _rows_of(dataset, index):
    """``kind -> row operand`` for a fused search of ``dataset``: the
    operand ``index`` holds when ``dataset`` is its dataset array, else
    computed for this call."""
    if index is not None and dataset is index.dataset:
        return functools.partial(_NORMS.get, dataset)
    return functools.partial(_row_operand, dataset)


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "metric_arg", "tile", "inner_tile", "approx", "compute"),
)
def _bf_knn(dataset, queries, k: int, metric: DistanceType, metric_arg: float,
            tile: int, inner_tile: int, keep_mask=None, approx: bool = False,
            compute: str = "float32"):
    m = queries.shape[0]
    n = dataset.shape[0]
    # kNN ordering is identical under expanded vs unexpanded L2, so route the
    # L2 family through the norms+GEMM path (the reference's knn makes the
    # same substitution — knn_brute_force.cuh uses expanded L2 fast paths).
    metric = {
        DistanceType.L2Unexpanded: DistanceType.L2Expanded,
        DistanceType.L2SqrtUnexpanded: DistanceType.L2SqrtExpanded,
    }.get(metric, metric)
    qt, num = _pad_to_tiles(queries, tile)
    select_min = metric != DistanceType.InnerProduct

    def body(qb):
        d = _pairwise(qb, dataset, metric, metric_arg, inner_tile, compute)  # (tile, n)
        if keep_mask is not None:
            # fused predicate filter (ref: neighbors/sample_filter_types.hpp)
            d = jnp.where(keep_mask[None, :], d, jnp.inf if select_min else -jnp.inf)
        if approx:
            # TPU-native PartialReduce selection (lax.approx_*_k): ~2x faster
            # than the exact sort-based TopK at >0.99 expected recall — the
            # TPU counterpart of the reference's recall/QPS trade knobs
            if select_min:
                top_v, top_i = lax.approx_min_k(d, k, recall_target=0.99)
            else:
                top_v, top_i = lax.approx_max_k(d, k, recall_target=0.99)
            return top_v, top_i.astype(jnp.int32)
        v = -d if select_min else d
        top_v, top_i = lax.top_k(v, k)
        return (-top_v if select_min else top_v), top_i.astype(jnp.int32)

    dists, idx = lax.map(body, qt)
    dists = dists.reshape(num * tile, k)[:m]
    idx = idx.reshape(num * tile, k)[:m]
    if keep_mask is not None:
        # when fewer than k rows pass the filter, top_k fills slots with
        # ±inf scores carrying excluded ids — report those as -1
        idx = jnp.where(jnp.isinf(dists), -1, idx)
    return dists, idx


@instrument(
    "brute_force.knn",
    items=lambda a, kw: nrows(a[1] if len(a) > 1 else kw["queries"]),
    labels=lambda a, kw: {
        "dtype": dtype_of(a[0] if a else kw["dataset"]),
        "k": a[2] if len(a) > 2 else kw["k"],
    },
)
@auto_convert_output
def knn(dataset, queries, k: int, metric="sqeuclidean", metric_arg: float = 2.0,
        sample_filter=None, mode: str = "exact", compute: str = "float32",
        res: Resources | None = None):
    """Exact kNN of ``queries`` in ``dataset`` (reference:
    brute_force::knn, neighbors/brute_force.cuh; pylibraft
    neighbors/brute_force.pyx knn). ``sample_filter`` is an optional
    :class:`~raft_tpu.neighbors.sample_filter.BitsetFilter` / boolean keep-mask
    over dataset rows. ``mode``: "exact" (sort-based TopK) or "approx"
    (TPU PartialReduce, ≥0.99 expected recall, ~2x faster). ``compute``:
    "float32" (bit-accurate distances), "float32x3" (compensated bf16x3
    contraction, f32-class accuracy at roughly half the MXU cost; falls back to
    "float32" when the fused kernel is not engaged) or "bfloat16"
    (single-pass MXU contraction — same neighbor ordering in all but
    razor-thin margins, several times the GEMM throughput).

    int8/uint8 datasets are first-class (reference: the int8_t/uint8_t
    brute-force instantiations): integer dataset+query pairs dispatch to the
    s8 x s8 -> s32 MXU kernel (~2x bf16 peak, 1-byte gathers) with EXACT
    integer distances; uint8 rides the same kernel shifted by -128 (L2 is
    shift-invariant, inner products are bias-corrected). ``compute="int8"``
    asserts intent; integer inputs use this path by default.

    On TPU, L2/inner-product/cosine searches with k ≤ 64, n ≥ 4096 and
    64 ≤ d ≤ 4096 dispatch to the fused Pallas kernel (ops/fused_knn.py;
    smaller d would mostly multiply 128-lane padding) — same neighbor sets;
    within-1-ULP distance ties may order differently. The kernel's row
    norms are computed per call here; an index holds them
    (:class:`BruteForce`, :func:`search`).
    Returns (distances (m, k), indices (m, k))."""
    return _knn(dataset, queries, k, metric, metric_arg, sample_filter, mode,
                compute, res)


def _knn(dataset, queries, k, metric, metric_arg, sample_filter, mode,
         compute, res, index=None):
    """:func:`knn`, reading the row norms ``index`` holds when
    ``dataset`` is its dataset array."""
    from .sample_filter import resolve_filter

    res = res or default_resources()
    dataset = jnp.asarray(dataset)
    queries = jnp.asarray(queries)
    expects(dataset.ndim == 2 and queries.ndim == 2, "inputs must be 2-D")
    expects(dataset.shape[1] == queries.shape[1], "feature dims must match")
    n = dataset.shape[0]
    expects(0 < k <= n, "k=%d must be in (0, n=%d]", k, n)
    expects(mode in ("exact", "approx"), "mode must be 'exact' or 'approx', got %r", mode)
    expects(compute in _PRECISIONS or compute in ("float32x3", "int8"),
            "compute must be one of %s, got %r",
            sorted(_PRECISIONS) + ["float32x3", "int8"], compute)
    mt = resolve_metric(metric)
    keep_mask = resolve_filter(sample_filter)
    if keep_mask is not None:
        expects(keep_mask.shape == (n,), "sample filter must cover all %d dataset rows", n)
    int_dtypes = (jnp.dtype(jnp.int8), jnp.dtype(jnp.uint8))
    expects(compute != "int8"
            or (dataset.dtype in int_dtypes and queries.dtype in int_dtypes),
            "compute='int8' requires int8/uint8 dataset AND queries, got "
            "%s/%s — the s8 MXU path has no meaning for float inputs",
            dataset.dtype, queries.dtype)
    if dataset.dtype in int_dtypes or queries.dtype in int_dtypes:
        # int8/uint8 ingestion (reference: brute_force int8_t/uint8_t
        # instantiations). Integer pairs route to the s8 MXU kernel —
        # distances are exact integers at these dtypes — and anything the
        # kernel can't take (mixed-precision pairs, cosine, tiny shapes,
        # no TPU) falls back to the f32 pipeline, which is also exact for
        # 8-bit integer values.
        if dataset.dtype in int_dtypes and queries.dtype in int_dtypes:
            expects(dataset.dtype == queries.dtype,
                    "int8/uint8 dataset and queries must share a dtype "
                    "(mixing signed and shifted domains is a data error), "
                    "got %s/%s", dataset.dtype, queries.dtype)
            from ..ops.fused_knn import fused_backend_ok, shapes_eligible

            if (mode == "exact" and compute in ("float32", "int8")
                    and (mt in _FUSED_L2 or mt == DistanceType.InnerProduct)
                    and fused_backend_ok()[0]
                    and shapes_eligible(n, dataset.shape[1], int(k))):
                return _bf_knn_s8(dataset, queries, int(k), mt, keep_mask,
                                  _rows_of(dataset, index))
        dataset = dataset.astype(jnp.float32)
        queries = queries.astype(jnp.float32)
    if compute == "int8":
        compute = "float32"  # explicit int8 on a non-integer/fallback path
    if _fused_eligible(mt, int(k), n, dataset.shape[1], mode, compute):
        return _bf_knn_fused(dataset, queries, int(k), mt, compute, keep_mask,
                             _rows_of(dataset, index))
    if compute == "float32x3":
        compute = "float32"  # XLA fallback has no compensated mode
    # outer tile bounds the (tile, n) score block; inner tile bounds the
    # elementwise-metric broadcast within _pairwise. This is the
    # Resources.workspace_bytes contract in action (the fused Pallas path
    # above sizes from VMEM instead); the implied transient workspace is
    # recorded so capacity planning can see it (obs.mem, pinned <= the
    # budget by test).
    tile = _choose_tile(queries.shape[0], n, 1, res.workspace_bytes)
    inner_tile = _choose_tile(tile, n, dataset.shape[1], res.workspace_bytes)
    obs_mem.note_workspace(
        "brute_force.knn",
        max(tile * n * 3 * 4,
            inner_tile * n * (dataset.shape[1] + 2) * 4))
    return _bf_knn(dataset, queries, int(k), mt, float(metric_arg), tile, inner_tile,
                   keep_mask, approx=mode == "approx", compute=compute)


@instrument(
    "brute_force.search",
    items=lambda a, kw: nrows(a[2] if len(a) > 2 else kw["queries"]),
    labels=lambda a, kw: {"k": a[3] if len(a) > 3 else kw["k"]},
)
@auto_convert_output
def search(params, index: "BruteForce", queries, k: int, sample_filter=None,
           res: Resources | None = None):
    """Exact kNN over a built index (reference: brute_force::search over a
    brute_force::index), reading the row norms the index holds
    (:meth:`BruteForce.hold_norms`): on the fused path the search is one
    program. ``params`` must be None (brute force has no search knobs; the
    argument keeps the other index kinds' signature)."""
    expects(index.dataset is not None, "index is not built")
    expects(params is None, "brute_force has no search params")
    return _knn(index.dataset, queries, k, index.metric, index.metric_arg,
                sample_filter, "exact", "float32", res, index=index)


def knn_merge_parts(part_dists, part_ids, k: int | None = None, select_min: bool = True):
    """Merge per-shard kNN candidate lists (reference:
    detail/knn_merge_parts.cuh — warp heap merge; here one select_k over the
    concatenated candidates).

    ``part_dists``/``part_ids``: (n_parts, n_queries, k_part) stacked results
    whose ids are already global. Returns merged (dists, ids) of width
    ``k or k_part``.
    """
    part_dists = jnp.asarray(part_dists)
    part_ids = jnp.asarray(part_ids)
    expects(part_dists.ndim == 3, "expected (n_parts, n_queries, k)")
    n_parts, nq, kp = part_dists.shape
    k = kp if k is None else k
    flat_d = jnp.moveaxis(part_dists, 0, 1).reshape(nq, n_parts * kp)
    flat_i = jnp.moveaxis(part_ids, 0, 1).reshape(nq, n_parts * kp)
    return select_k(flat_d, k, select_min=select_min, indices=flat_i)


class BruteForce:
    """Index-style wrapper (reference: brute_force::index,
    neighbors/brute_force_types.hpp — stores the dataset and its
    precomputed norms). The norms (an L2 metric's ``|y|^2``, or a uint8
    inner product's row bias; nothing for other metrics) are computed once
    per dataset array, in a program of their own, at build and load, and
    held with that array (:class:`_NormCache`): a dataset replaced in place
    is a new array, whose norms the next search computes."""

    def __init__(self, metric="sqeuclidean", metric_arg: float = 2.0):
        self.metric = metric
        self.metric_arg = metric_arg
        self.dataset = None
        # pinned operating point (raft_tpu.tune decision dict; None =
        # untuned) — brute force has no search knobs, but the record still
        # rides save/load (raft_tpu/9) so provenance survives uniformly
        self.tuned = None

    def build(self, dataset, res: Resources | None = None):
        # gate BEFORE the device upload ("a refused build spends
        # nothing"): size from the host-side view — for brute force the
        # dataset IS the index. Stored dtype caps at 4 bytes/elt (jax
        # downcasts f64 to f32 at asarray; byte dtypes store natively).
        import numpy as np

        from ..core import chunked

        res = res or default_resources()
        arr = (dataset if hasattr(dataset, "shape")
               and hasattr(dataset, "dtype") else np.asarray(dataset))
        if chunked.is_reader(dataset):
            # out-of-core ingest: the dataset still lands device-whole
            # (it IS the scan operand) but arrives through the staged
            # chunk pipeline — no second full-size host copy. Priced
            # against BOTH budgets before any chunk stages.
            n, d = (int(s) for s in arr.shape)
            pl = obs_mem.plan("brute_force", None, n, d,
                              dtype=str(arr.dtype), streamed=True,
                              chunk_rows=dataset.chunk_rows)
            obs_mem.gate(res, pl["build_peak_bytes"],
                         site="build_stream",
                         host_bytes=pl["host_peak_bytes"],
                         detail=f"brute_force {n}x{d} streamed")
            self.dataset = chunked.device_materialize(dataset,
                                                      kind="brute_force")
        else:
            # the dataset, plus 4 bytes a row for its held norms
            n, d = arr.shape
            need = n * (d * min(arr.dtype.itemsize, 4) + 4)
            obs_mem.gate(res, need, site="build",
                         detail=f"brute_force {arr.shape[0]}x{arr.shape[1]}")
            self.dataset = jnp.asarray(dataset)
        self.hold_norms()
        obs_mem.account_index(self)  # ledger hook (docs/observability.md)
        return self

    def hold_norms(self):
        """The row norms held for the current dataset, computed if it has
        none (None when the metric reads none)."""
        kind = _operand_kind(self.metric, self.dataset.dtype)
        return None if kind is None else _NORMS.get(self.dataset, kind,
                                                    hit=False)

    def search(self, queries, k: int, sample_filter=None,
               res: Resources | None = None):
        return search(None, self, queries, k, sample_filter=sample_filter,
                      res=res)


def write_index(f, index: BruteForce) -> None:
    """Serialize to an open binary stream (new in raft_tpu/8 — the
    brute-force index is the stream wrapper's simplest sealed kind, so it
    needs the same composable serialization as the ANN indexes; reference:
    brute_force::index stores dataset + metric, brute_force_types.hpp)."""
    from ..core.serialize import (serialize_header, serialize_mdspan,
                                  serialize_scalar, serialize_tuned)

    expects(index.dataset is not None, "index is not built")
    serialize_header(f, "brute_force")
    serialize_scalar(f, int(resolve_metric(index.metric)))
    serialize_scalar(f, float(index.metric_arg))
    serialize_mdspan(f, index.dataset)
    serialize_tuned(f, index.tuned)


def read_index(f) -> BruteForce:
    """Deserialize from an open binary stream (pairs with
    :func:`write_index`)."""
    import jax.numpy as jnp

    from ..core.serialize import (check_header, deserialize_mdspan,
                                  deserialize_scalar, deserialize_tuned)

    ver = check_header(f, "brute_force")
    metric = DistanceType(deserialize_scalar(f))
    metric_arg = float(deserialize_scalar(f))
    idx = BruteForce(metric=metric, metric_arg=metric_arg)
    idx.dataset = jnp.asarray(deserialize_mdspan(f))
    idx.tuned = deserialize_tuned(f, ver)
    idx.hold_norms()
    return idx


def save(index: BruteForce, path: str) -> None:
    """Serialize atomically (temp file + rename — a crashed save leaves
    the previous file readable; :func:`core.serialize.atomic_write`)."""
    from ..core.serialize import atomic_write

    with atomic_write(path) as f:
        write_index(f, index)


def load(path: str, res: Resources | None = None) -> BruteForce:
    with open(path, "rb") as f:
        return read_index(f)


def batched_searcher(index: BruteForce, params=None):
    """Stable serving hook (raft_tpu.serve; contract in
    :mod:`._hooks`): ``fn(queries, k) -> (distances, ids)`` with
    ``.kind``/``.dim``/``.query_dtype`` attributes. Brute force has no
    search params; ``params`` must be None."""
    from ._hooks import make_hook

    expects(index.dataset is not None, "index is not built")
    expects(params is None, "brute_force has no search params")
    return make_hook(index.search, "brute_force",
                     index.dataset.shape[1], str(index.dataset.dtype))

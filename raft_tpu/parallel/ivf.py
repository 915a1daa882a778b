"""Multi-chip IVF: distributed BUILD (no chip ever holds the full dataset)
and distributed SEARCH (shard the inverted lists, probe locally, merge
candidates over ICI).

The reference leaves multi-GPU ANN serving to users composing raft::comms
with per-shard indexes and knn_merge_parts (SURVEY.md §5; the cuML/cuGraph
pattern over docs/source/using_comms.rst). Here both halves are first-class
drivers:

- **build/build_pq/extend** (VERDICT r4 #3): dataset rows sharded over the
  mesh axis; coarse centers via the psum-EM balanced k-means (the cuML MNMG
  k-means pattern, docs/source/using_comms.rst:1-40); every per-row step
  (assignment, residual encode, norms) runs shard-local; the padded list
  arrays are then materialized ALREADY SHARDED BY LISTS with one
  S-step psum loop whose working set is one list-block (L/S lists) — at no
  point does any chip hold the full dataset or the full index.
- **search/search_pq**: the padded list arrays (and their coarse centers)
  are sharded along ``n_lists``; each chip ranks its own local centers and
  scans its local top-``n_probes`` lists, then one all_gather + select_k
  merge produces global results. Per-shard probing means each chip's scan
  work is identical (batch-synchronous, no load imbalance) and the effective
  probe count is ``size x n_probes`` local lists. A build()-produced index
  feeds search() without any resharding gather: the arrays already carry the
  list sharding the search expects.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..comms.comms import Comms, replicated, shard_along
from ..core import tracing
from ..core.errors import expects
from ..distance.types import DistanceType
from ..matrix.select_k import _select_k
from ._progcache import ProgramCache
from ..neighbors.ivf_flat import IvfFlatIndex, SearchParams, _ivf_search
from ..obs.instrument import instrument, nrows

__all__ = ["build", "build_pq", "extend", "search", "search_pq"]


def _pad_lists_to_multiple(index: IvfFlatIndex, size: int) -> IvfFlatIndex:
    """Pad the index with empty lists so n_lists divides the mesh axis —
    needed because sub-list splitting (_list_utils.split_oversized) makes
    n_lists data-dependent. Padding centers sit at +1e30 so L2 coarse scores
    rank them last; even if probed, their slots are all id -1 / +inf and
    cannot win the merge. Inner-product has no constant worst-rank center (the
    sign of q·c depends on q), so there the list count must already divide."""
    L = index.n_lists
    pad = (-L) % size
    if pad == 0:
        return index
    expects(
        index.metric != DistanceType.InnerProduct,
        "inner-product distributed search needs n_lists (%d) divisible by the "
        "mesh axis (%d) — rebuild with a different n_lists",
        L, size,
    )
    d = index.dim
    cap = index.capacity
    return IvfFlatIndex(
        centers=jnp.concatenate(
            [index.centers, jnp.full((pad, d), 1e30, index.centers.dtype)]
        ),
        list_data=jnp.concatenate(
            [index.list_data, jnp.zeros((pad, cap, d), index.list_data.dtype)]
        ),
        list_ids=jnp.concatenate(
            [index.list_ids, jnp.full((pad, cap), -1, jnp.int32)]
        ),
        list_norms=jnp.concatenate(
            [index.list_norms, jnp.full((pad, cap), jnp.inf, jnp.float32)]
        ),
        list_sizes=jnp.concatenate(
            [index.list_sizes, jnp.zeros((pad,), jnp.int32)]
        ),
        metric=index.metric,
        split_factor=index.split_factor,
        data_kind=index.data_kind,
    )


_PROGRAMS = ProgramCache(maxsize=256)


def _flat_search_fn(comms: Comms, n_probes: int, k: int, metric,
                    split_factor: float, data_kind: str):
    """Memoized jitted program per static config (see parallel/knn._knn_fn:
    a fresh jax.jit wrapper per call was measured as 38-45% overhead);
    releasable per communicator (parallel.release_programs)."""
    key = (comms, "flat", n_probes, k, metric, split_factor, data_kind)
    return _PROGRAMS.get_or_build(key, lambda: _build_flat_search_fn(
        comms, n_probes, k, metric, split_factor, data_kind))


def _build_flat_search_fn(comms: Comms, n_probes: int, k: int, metric,
                          split_factor: float, data_kind: str):
    size = comms.size()
    inner = metric == DistanceType.InnerProduct

    def step(centers, data, ids, norms, sizes, q):
        with tracing.scope("parallel.ivf.local_search"):
            shard = IvfFlatIndex(centers, data, ids, norms, sizes, metric,
                                 split_factor, data_kind)
            d_loc, i_loc = _ivf_search(
                shard, q, n_probes, k,
                query_tile=min(256, q.shape[0]), probe_chunk=n_probes,
                metric=metric,
            )
        with tracing.scope("parallel.ivf.merge"):
            d_all = comms.allgather(d_loc)  # (S, m, k) over ICI
            i_all = comms.allgather(i_loc)
            m = q.shape[0]
            d_flat = jnp.moveaxis(d_all, 0, 1).reshape(m, size * k)
            i_flat = jnp.moveaxis(i_all, 0, 1).reshape(m, size * k)
            return _select_k(d_flat, i_flat, k, not inner)

    axis = comms.axis
    return jax.jit(comms.shard_map(
        step,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=(P(), P()),
    ))


@instrument("parallel.ivf.search",
            items=lambda a, kw: nrows(a[3] if len(a) > 3 else kw["queries"]),
            labels=lambda a, kw: {"k": a[4] if len(a) > 4 else kw["k"],
                                  "size": (a[0] if a else kw["comms"]).size()})
def search(comms: Comms, params: SearchParams, index: IvfFlatIndex, queries, k: int):
    """Distributed IVF-Flat search (multi-chip analogue of ivf_flat.search).

    The index's lists are sharded along ``comms.axis``; every shard probes its
    own ``n_probes`` best local lists and the candidates merge with one
    all_gather + select_k. With L lists over S chips each chip scans
    n_probes of its L/S lists, so total probed work is S x n_probes lists —
    recall can only exceed the single-chip setting at equal ``n_probes``.

    Returns replicated (distances (m, k), global ids (m, k)).
    """
    from ..neighbors.brute_force import _coerce_queries

    queries = _coerce_queries(index.data_kind, jnp.asarray(queries))
    size = comms.size()
    index = _pad_lists_to_multiple(index, size)
    L = index.n_lists
    lists_per_shard = L // size
    n_probes = min(params.n_probes, lists_per_shard)
    expects(0 < k <= n_probes * index.capacity, "k exceeds per-shard candidate pool")

    mesh, axis = comms.mesh, comms.axis
    args = (
        shard_along(mesh, axis, index.centers),
        shard_along(mesh, axis, index.list_data),
        shard_along(mesh, axis, index.list_ids),
        shard_along(mesh, axis, index.list_norms),
        shard_along(mesh, axis, index.list_sizes),
        replicated(mesh, queries),
    )
    fn = _flat_search_fn(comms, int(n_probes), int(k), index.metric,
                         float(index.split_factor), index.data_kind)
    return fn(*args)


def _pad_pq_lists(index, size: int):
    """Pad an IvfPqIndex with empty lists so n_lists divides the mesh axis
    (same trick as _pad_lists_to_multiple: far-away centers rank last in the
    L2 coarse scoring; padded lists are size-0 so their slots can never win)."""
    from ..neighbors.ivf_pq import IvfPqIndex

    L = index.n_lists
    pad = (-L) % size
    if pad == 0:
        return index
    expects(
        index.metric != DistanceType.InnerProduct,
        "inner-product distributed search needs n_lists (%d) divisible by the "
        "mesh axis (%d) — rebuild with a different n_lists",
        L, size,
    )
    cap = index.capacity
    pq_dim = index.list_codes.shape[-1]
    far = 1e15
    codebooks = index.codebooks
    if index.codebook_kind == "per_cluster":
        codebooks = jnp.concatenate(
            [codebooks, jnp.zeros((pad,) + codebooks.shape[1:], codebooks.dtype)])
    return IvfPqIndex(
        centers=jnp.concatenate(
            [index.centers, jnp.full((pad, index.dim), far, index.centers.dtype)]),
        centers_rot=jnp.concatenate(
            [index.centers_rot,
             jnp.full((pad, index.centers_rot.shape[1]), far, index.centers_rot.dtype)]),
        rotation=index.rotation,
        codebooks=codebooks,
        list_codes=jnp.concatenate(
            [index.list_codes, jnp.zeros((pad, cap, pq_dim), index.list_codes.dtype)]),
        list_ids=jnp.concatenate(
            [index.list_ids, jnp.full((pad, cap), -1, jnp.int32)]),
        list_sizes=jnp.concatenate(
            [index.list_sizes, jnp.zeros((pad,), jnp.int32)]),
        list_consts=jnp.concatenate(
            [index.list_consts,
             jnp.zeros((pad, index.list_consts.shape[1]), jnp.float32)]),
        metric=index.metric,
        codebook_kind=index.codebook_kind,
        pq_bits=index.pq_bits,
        split_factor=index.split_factor,
        pq_split=index.pq_split,
        data_kind=index.data_kind,
    )


@instrument("parallel.ivf.search_pq",
            items=lambda a, kw: nrows(a[3] if len(a) > 3 else kw["queries"]),
            labels=lambda a, kw: {"k": a[4] if len(a) > 4 else kw["k"],
                                  "size": (a[0] if a else kw["comms"]).size()})
def search_pq(comms: Comms, params, index, queries, k: int,
              res=None):
    """Distributed IVF-PQ search: lists sharded over the mesh axis, local LUT
    scans, one all_gather + select_k merge (the same composition as IVF-Flat
    ``search`` above; reference pattern: per-shard indexes + knn_merge_parts,
    docs/source/using_comms.rst + detail/knn_merge_parts.cuh).

    ``params`` is :class:`raft_tpu.neighbors.ivf_pq.SearchParams`. Distances
    are PQ-approximate, like the single-chip search; run
    :func:`raft_tpu.neighbors.refine` against the (locally stored) dataset
    shard to sharpen candidates — the PQ index itself carries no raw vectors.

    Returns replicated (distances (m, k), global ids (m, k)).
    """
    from ..core.resources import default_resources
    from ..neighbors._list_utils import (plan_search_tiles,
                                         pq_scan_bytes_per_probe_row)
    from ..neighbors.ivf_pq import IvfPqIndex, _pq_search

    from ..neighbors.brute_force import _coerce_queries

    res = res or default_resources()
    queries = _coerce_queries(index.data_kind, jnp.asarray(queries))
    size = comms.size()
    index = _pad_pq_lists(index, size)
    L = index.n_lists
    lists_per_shard = L // size
    n_probes = min(params.n_probes, lists_per_shard)
    expects(0 < k <= n_probes * index.capacity, "k exceeds per-shard candidate pool")
    # same workspace model as the single-chip ivf_pq.search, with shard-local
    # n_probes/capacity
    n_codes = index.codebooks.shape[-2]
    query_tile, probe_chunk = plan_search_tiles(
        queries.shape[0], n_probes, int(k), index.capacity,
        bytes_per_probe_row=pq_scan_bytes_per_probe_row(
            index.capacity, index.pq_dim, n_codes),
        budget_bytes=res.workspace_bytes,
        max_query_tile=128,
    )
    per_cluster = index.codebook_kind == "per_cluster"
    expects(params.lut_dtype in ("float32", "bfloat16", "int8"),
            "lut_dtype must be 'float32', 'bfloat16' or 'int8', got %r",
            params.lut_dtype)
    # the same clear error as the single-chip search for a pq_split index
    # missing its cross terms
    from ..neighbors.ivf_pq import _check_split_consts

    _check_split_consts(index)
    expects(not index.scale_normed,
            "distributed PQ search does not shard list_scales yet; a "
            "residual_scale_norm index is single-chip only")

    mesh, axis = comms.mesh, comms.axis
    cb_arg = (shard_along(mesh, axis, index.codebooks) if per_cluster
              else replicated(mesh, index.codebooks))
    args = (
        shard_along(mesh, axis, index.centers),
        shard_along(mesh, axis, index.centers_rot),
        replicated(mesh, index.rotation),
        cb_arg,
        shard_along(mesh, axis, index.list_codes),
        shard_along(mesh, axis, index.list_ids),
        shard_along(mesh, axis, index.list_sizes),
        shard_along(mesh, axis, index.list_consts),
        replicated(mesh, queries),
    )
    fn = _pq_search_fn(comms, int(n_probes), int(k), int(query_tile),
                       int(probe_chunk), index.metric, index.codebook_kind,
                       int(index.pq_bits), float(index.split_factor),
                       bool(index.pq_split), params.lut_dtype)
    return fn(*args)


def _pq_search_fn(comms: Comms, n_probes: int, k: int, query_tile: int,
                  probe_chunk: int, metric, codebook_kind: str, pq_bits: int,
                  split_factor: float, pq_split: bool, lut_dtype: str):
    """Memoized jitted PQ-search program (see _flat_search_fn); the
    rotation travels as a replicated argument, not a closure constant, so
    two indexes of the same config share one compiled program."""
    key = (comms, "pq", n_probes, k, query_tile, probe_chunk, metric,
           codebook_kind, pq_bits, split_factor, pq_split, lut_dtype)
    return _PROGRAMS.get_or_build(key, lambda: _build_pq_search_fn(
        comms, n_probes, k, query_tile, probe_chunk, metric, codebook_kind,
        pq_bits, split_factor, pq_split, lut_dtype))


def _build_pq_search_fn(comms: Comms, n_probes: int, k: int, query_tile: int,
                        probe_chunk: int, metric, codebook_kind: str,
                        pq_bits: int, split_factor: float, pq_split: bool,
                        lut_dtype: str):
    from ..neighbors.ivf_pq import IvfPqIndex, _pq_search

    size = comms.size()
    inner = metric == DistanceType.InnerProduct
    per_cluster = codebook_kind == "per_cluster"

    def step(centers, centers_rot, rotation, codebooks, codes, ids, sizes,
             consts, q):
        with tracing.scope("parallel.ivf.local_search_pq"):
            shard = IvfPqIndex(
                centers, centers_rot, rotation, codebooks, codes, ids, sizes,
                list_consts=consts,
                metric=metric, codebook_kind=codebook_kind,
                pq_bits=pq_bits, split_factor=split_factor,
                pq_split=pq_split)
            d_loc, i_loc = _pq_search(
                shard, q, n_probes, k,
                query_tile=query_tile, probe_chunk=probe_chunk,
                metric=metric, codebook_kind=codebook_kind,
                lut_dtype=lut_dtype)
        with tracing.scope("parallel.ivf.merge"):
            d_all = comms.allgather(d_loc)
            i_all = comms.allgather(i_loc)
            m = q.shape[0]
            d_flat = jnp.moveaxis(d_all, 0, 1).reshape(m, size * k)
            i_flat = jnp.moveaxis(i_all, 0, 1).reshape(m, size * k)
            return _select_k(d_flat, i_flat, k, not inner)

    axis = comms.axis
    cb_spec = P(axis) if per_cluster else P()
    return jax.jit(comms.shard_map(
        step,
        in_specs=(P(axis), P(axis), P(), cb_spec, P(axis), P(axis), P(axis),
                  P(axis), P()),
        out_specs=(P(), P()),
    ))


# ---------------------------------------------------------------------------
# distributed build / extend (VERDICT r4 #3)
# ---------------------------------------------------------------------------
#
# Reference pattern: the MNMG builds the docs tell users to compose from
# raft::comms collectives (/root/reference/docs/source/using_comms.rst:1-40;
# the cuML MNMG k-means psum-EM over kmeans_balanced.cuh). The TPU shape:
#
#   phase 1 (one shard_map program): balanced psum-EM — per-shard fused-1-NN
#     assignment, psum center sums/counts, balancing re-seeds drawn from a
#     pooled (allgathered) subsample so every shard computes IDENTICAL
#     replicated centers; returns centers + sharded labels + global counts.
#   phase 2 (host): static capacity from the global counts (no sub-list
#     splitting in the distributed build — balanced k-means bounds skew, and
#     a data-dependent list count would break the static sharding layout).
#   phase 3 (one shard_map program): materialize the padded list arrays
#     ALREADY SHARDED BY LISTS. Cross-shard write positions come from an
#     exclusive prefix over the allgathered per-shard list counts; the
#     arrays are filled one list-block (L/S lists) at a time — scatter local
#     rows into the block, psum, owner keeps — so the peak per-chip working
#     set is one block (~dataset/S), never the dataset or the index.
#
# The produced index's arrays carry exactly the list sharding search()
# expects, so build -> search composes with no resharding gather.


def _pooled_balanced_centers(comms: Comms, x_shard, keys, L: int,
                             n_iters: int, small_ratio: float, n_global: int,
                             sub: int, inner: bool, tile: int,
                             batch_shard: int = 0):
    """Distributed balanced EM (inside shard_map). Returns replicated
    (centers, labels_shard, global_counts). Deterministic: all replicated
    math consumes identical inputs (allgathered pool, psum'd stats).

    ``batch_shard > 0`` selects mini-batch EM (the distributed twin of
    kmeans_balanced._balanced_em_minibatch): every iteration assigns one
    rotating ``batch_shard``-row mini-batch per shard (a fixed per-shard
    shuffle), the psum'd batch sums/counts drive the streaming 1/c center
    update, and the balancing re-seed runs on the psum'd batch counts
    against the batch-scaled threshold — so the EM loop's full-dataset
    passes (the Round-6-measured ~22-pass, +187%-warm overhead) collapse to
    the two closing passes (sharpening + list-fill labels) below."""
    from ..cluster.kmeans_balanced import _assign_labels, _reseed_small

    xf = x_shard.astype(jnp.float32)
    shard_rows = x_shard.shape[0]
    ksub = jax.random.fold_in(keys[0], comms.rank())
    idx = jax.random.choice(ksub, shard_rows, (sub,), replace=False)
    pool = comms.allgather(jnp.take(xf, idx, axis=0), tiled=True)  # (S*sub, d)
    init_idx = jax.random.choice(keys[1], pool.shape[0], (L,), replace=False)
    centers0 = jnp.take(pool, init_idx, axis=0)
    ptile = min(tile, pool.shape[0])
    S = comms.size()

    if batch_shard:
        kperm = jax.random.fold_in(keys[0], comms.rank() + S)
        perm = jax.random.permutation(kperm, shard_rows).astype(jnp.int32)
        offs = jnp.arange(batch_shard, dtype=jnp.int32)
        batch_global = batch_shard * S

        def body(i, carry):
            centers, ccounts, key = carry
            bidx = perm[(i * batch_shard + offs) % shard_rows]
            xb = jnp.take(xf, bidx, axis=0)
            labels = _assign_labels(xb, centers, min(tile, batch_shard), inner)
            onehot = jax.nn.one_hot(labels, L, dtype=jnp.float32, axis=0)
            sums = comms.allreduce(onehot @ xb)
            counts = comms.allreduce(jnp.sum(onehot, axis=1))
            ccounts = ccounts + counts
            # streaming 1/c mean update (exact running mean of the ccounts
            # points each center has absorbed); zero-count rows are a no-op
            centers = centers + (
                sums - counts[:, None] * centers) / jnp.maximum(
                    ccounts, 1.0)[:, None]
            # balancing on the psum'd batch counts; replacements from the
            # replicated pooled subsample (crowdedness-weighted Gumbel
            # top-k, identical on every shard)
            key, kc = jax.random.split(key)
            pool_w = counts[_assign_labels(pool, centers, ptile, inner)]
            centers, small = _reseed_small(
                centers, counts, pool_w, pool, kc, L, batch_global / L,
                small_ratio)
            # re-seeded centers forget their history: next batch replaces
            # them with its mean at Lloyd speed instead of a 1/c crawl
            ccounts = jnp.where(small, 0.0, ccounts)
            return centers, ccounts, key

        centers, _, _ = lax.fori_loop(
            0, n_iters, body,
            (centers0, jnp.zeros((L,), jnp.float32), keys[2]))
    else:
        def body(i, carry):
            centers, key = carry
            labels = _assign_labels(x_shard, centers, tile, inner)
            onehot = jax.nn.one_hot(labels, L, dtype=jnp.float32, axis=0)
            sums = comms.allreduce(onehot @ xf)
            counts = comms.allreduce(jnp.sum(onehot, axis=1))
            centers = jnp.where(counts[:, None] > 0,
                                sums / jnp.maximum(counts, 1.0)[:, None],
                                centers)
            # balancing (single-chip _balanced_em's pool trick, already sized
            # for this): re-seed small clusters from the replicated pooled
            # subsample, weighted by crowdedness, Gumbel top-k for
            # distinctness
            key, kc = jax.random.split(key)
            pool_w = counts[_assign_labels(pool, centers, ptile, inner)]
            centers, _ = _reseed_small(
                centers, counts, pool_w, pool, kc, L, n_global / L,
                small_ratio)
            return centers, key

        centers, _ = lax.fori_loop(0, n_iters, body, (centers0, keys[2]))
    # final sharpening pass without balancing so centers are true means
    labels = _assign_labels(x_shard, centers, tile, inner)
    onehot = jax.nn.one_hot(labels, L, dtype=jnp.float32, axis=0)
    sums = comms.allreduce(onehot @ xf)
    counts = comms.allreduce(jnp.sum(onehot, axis=1))
    centers = jnp.where(counts[:, None] > 0,
                        sums / jnp.maximum(counts, 1.0)[:, None], centers)
    labels = _assign_labels(x_shard, centers, tile, inner)
    gcounts = comms.allreduce(jnp.bincount(labels, length=L)).astype(jnp.int32)
    return centers, labels.astype(jnp.int32), gcounts


def _global_positions(comms: Comms, labels, L: int, base=None):
    """Write position of each local row inside its global list: exclusive
    prefix of the allgathered per-shard list counts + within-shard rank
    (+ optional replicated per-list base, used by extend)."""
    from ..neighbors._list_utils import list_positions

    lc = jnp.bincount(labels, length=L)
    all_counts = comms.allgather(lc)  # (S, L) replicated
    offs = jnp.cumsum(all_counts, axis=0) - all_counts
    my_off = offs[comms.rank()]  # (L,)
    pos, _ = list_positions(labels, L)
    gpos = my_off[labels].astype(jnp.int32) + pos
    if base is not None:
        gpos = gpos + base[labels].astype(jnp.int32)
    return gpos


def _fill_blocks(comms: Comms, payloads, labels, gpos, L: int, cap: int):
    """Materialize list-sharded padded arrays one list-block at a time.

    ``payloads``: list of (values (n_shard, ...), f32/i32 scatter dtype).
    Returns one (L/S, cap, ...) block per payload (out_spec P(axis) makes it
    the caller's (L, cap, ...) list-sharded global array). Peak per-chip
    working set: ONE block per payload — the no-full-dataset invariant."""
    S = comms.size()
    Lb = L // S
    rank = comms.rank()

    def block(b, accs):
        lo = b * Lb
        in_blk = (labels >= lo) & (labels < lo + Lb)
        # OOB sentinel (Lb / cap) + mode="drop": rows outside the block are
        # dropped by the scatter, and never wrap (negative indices would)
        lloc = jnp.where(in_blk, labels - lo, Lb)
        p = jnp.where(in_blk, gpos, cap)
        out = []
        for (vals, dt), acc in zip(payloads, accs):
            blk = jnp.zeros((Lb, cap) + vals.shape[1:], dt)
            blk = blk.at[lloc, p].set(vals.astype(dt), mode="drop")
            blk = comms.allreduce(blk)
            out.append(jnp.where(rank == b, blk, acc))
        return tuple(out)

    zeros = tuple(jnp.zeros((Lb, cap) + v.shape[1:], dt) for v, dt in payloads)
    return lax.fori_loop(0, S, block, zeros)


def _build_capacity(gcounts, extra=0) -> int:
    import numpy as np

    from ..neighbors._list_utils import round_up

    return round_up(max(int(np.asarray(gcounts).max()) + extra, 8), 8)


def _resolve_batch_shard(params, n: int, S: int, shard_rows: int) -> int:
    """Per-shard mini-batch rows for the coarse psum-EM (0 = full EM).
    The mode/threshold rule is the single-chip trainer's
    (kmeans_balanced.resolve_train_mode) applied to the GLOBAL row count —
    the distributed build trains on the full sharded dataset, there is no
    trainset-fraction subsample here."""
    from ..cluster.kmeans_balanced import resolve_train_mode

    mode = resolve_train_mode(
        getattr(params, "kmeans_train_mode", "auto"), n,
        getattr(params, "kmeans_batch_rows", 65536))
    if mode != "minibatch":
        return 0
    batch_rows = getattr(params, "kmeans_batch_rows", 65536)
    return min(shard_rows, max(batch_rows // S, 1))


def _timed_coarse_em(fn, xs, keys, n_iters: int, batch_shard: int, S: int,
                     n: int):
    """Run the jitted coarse-EM phase with the shared build metrics
    (assignment-pass counter, sampled-rows gauge, phase wall — the same
    raft_tpu_build_* series the single-chip trainer emits, labeled
    driver="distributed")."""
    import time

    from ..obs import build as build_metrics
    from ..obs import metrics

    if not metrics._enabled:
        return fn(xs, keys)
    mode = "minibatch" if batch_shard else "full"
    t0 = time.perf_counter()
    out = fn(xs, keys)
    jax.block_until_ready(out)
    build_metrics.build_phase().observe(time.perf_counter() - t0,
                             phase="parallel.ivf/coarse_em")
    build_metrics.assignment_passes().inc(n_iters, phase="em", mode=mode,
                                          driver="distributed")
    # the two closing full passes ride inside the same program, counted
    # under the SAME phase labels the single-chip driver uses (final =
    # sharpening, fill = list-fill assignment) so the series compare 1:1
    build_metrics.assignment_passes().inc(1, phase="final", mode=mode,
                                          driver="distributed")
    build_metrics.assignment_passes().inc(1, phase="fill", mode=mode,
                                          driver="distributed")
    build_metrics.sampled_rows().set(batch_shard * S if batch_shard else n, mode=mode,
                          driver="distributed")
    return out


@instrument("parallel.ivf.build",
            items=lambda a, kw: nrows(a[2] if len(a) > 2 else kw["dataset"]),
            labels=lambda a, kw: {"size": (a[0] if a else kw["comms"]).size()})
def build(comms: Comms, params, dataset, res=None) -> IvfFlatIndex:
    """Distributed IVF-Flat build: dataset rows sharded over ``comms.axis``,
    index lists sharded the way :func:`search` consumes them. ``params`` is
    :class:`raft_tpu.neighbors.ivf_flat.IndexParams` (list_dtype honored,
    incl. int8/uint8 ingestion; ``split_factor`` is ignored — the
    distributed build does not split hot lists, see module docstring)."""
    from ..distance.pairwise import _choose_tile
    from ..neighbors.ivf_flat import _resolve_storage
    from ..distance.types import resolve_metric

    x = jnp.asarray(dataset)
    expects(x.ndim == 2, "dataset must be (n, d)")
    n, d = x.shape
    S = comms.size()
    expects(n % S == 0, "dataset rows (%d) must divide the mesh axis (%d); "
            "pad first", n, S)
    L = params.n_lists
    expects(L % S == 0, "n_lists (%d) must divide the mesh axis (%d)", L, S)
    expects(L <= n, "n_lists > n_samples")
    mt = resolve_metric(params.metric)
    kind, x, _ = _resolve_storage(params.list_dtype, x, mt)
    storage = x.dtype
    inner = mt == DistanceType.InnerProduct
    mesh, axis = comms.mesh, comms.axis
    shard_rows = n // S
    sub = min(max(8 * L // S, 64), shard_rows)
    tile = _choose_tile(shard_rows, L, 1, 1 << 28)
    batch_shard = _resolve_batch_shard(params, n, S, shard_rows)

    def phase1(x_shard, keys):
        return _pooled_balanced_centers(
            comms, x_shard, keys, L, params.kmeans_n_iters, 0.25, n, sub,
            inner, tile, batch_shard=batch_shard)

    keys = replicated(mesh, jax.random.split(jax.random.key(params.seed), 3))
    xs = shard_along(mesh, axis, x)
    with tracing.range("parallel.ivf.build.coarse_kmeans"):
        centers, labels, gcounts = _timed_coarse_em(
            jax.jit(comms.shard_map(
                phase1, in_specs=(P(axis), P()),
                out_specs=(P(), P(axis), P()))),
            xs, keys, params.kmeans_n_iters, batch_shard, S, n)
    cap = _build_capacity(gcounts)

    def phase3(x_shard, lab, ids):
        xf = x_shard.astype(jnp.float32)
        gpos = _global_positions(comms, lab, L)
        data, idb, nrm = _fill_blocks(
            comms,
            [(xf, jnp.float32), (ids + 1, jnp.int32),
             (jnp.sum(xf * xf, axis=1), jnp.float32)],
            lab, gpos, L, cap)
        idb = idb - 1  # 0 (additive identity) back to the -1 empty sentinel
        nrm = jnp.where(idb < 0, jnp.inf, nrm)
        return data.astype(storage), idb, nrm

    ids = shard_along(mesh, axis, jnp.arange(n, dtype=jnp.int32))
    with tracing.range("parallel.ivf.build.fill_lists"):
        data, idb, nrm = jax.jit(comms.shard_map(
            phase3, in_specs=(P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(axis))))(xs, labels, ids)
    return IvfFlatIndex(
        centers=centers, list_data=data, list_ids=idb, list_norms=nrm,
        list_sizes=gcounts, metric=mt, split_factor=params.split_factor,
        data_kind=kind)


@instrument("parallel.ivf.extend",
            items=lambda a, kw: nrows(a[2] if len(a) > 2 else kw["new_vectors"]))
def extend(comms: Comms, index: IvfFlatIndex, new_vectors, new_ids=None) -> IvfFlatIndex:
    """Distributed IVF-Flat extend: new rows sharded over the mesh axis are
    assigned and appended shard-locally; old list contents never leave their
    owning chip (they are re-padded in place to the grown capacity)."""
    from ..distance.pairwise import _choose_tile
    from ..neighbors._list_utils import assign_to_lists
    from ..neighbors.brute_force import _as_signed

    x = jnp.asarray(new_vectors)
    S = comms.size()
    expects(x.ndim == 2 and x.shape[1] == index.dim, "vector dim mismatch")
    expects(x.shape[0] % S == 0, "new rows (%d) must divide the mesh axis "
            "(%d); pad first", x.shape[0], S)
    L = index.n_lists
    expects(L % S == 0, "index n_lists (%d) must divide the mesh axis (%d) "
            "— was it built by parallel.ivf.build?", L, S)
    if index.data_kind in ("int8", "uint8"):
        expects(str(x.dtype) == index.data_kind,
                "this index stores %s vectors; got %s", index.data_kind, x.dtype)
        x = _as_signed(x)
    else:
        x = x.astype(index.list_data.dtype)
    n_new = x.shape[0]
    if new_ids is None:
        new_ids = index.size + jnp.arange(n_new, dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)
    mesh, axis = comms.mesh, comms.axis
    tile = _choose_tile(n_new // S, L, 1, 1 << 28)

    def assign(x_shard, centers):
        xa = (x_shard.astype(jnp.float32)
              if x_shard.dtype == jnp.int8 else x_shard)
        lab = assign_to_lists(xa, centers, index.metric, tile)
        return lab, comms.allreduce(jnp.bincount(lab, length=L)).astype(jnp.int32)

    xs = shard_along(mesh, axis, x)
    labels, new_counts = jax.jit(comms.shard_map(
        assign, in_specs=(P(axis), P()), out_specs=(P(axis), P())))(
        xs, replicated(mesh, index.centers))
    import numpy as np

    new_sizes = np.asarray(index.list_sizes) + np.asarray(new_counts)
    cap = _build_capacity(new_sizes, extra=0)
    old_cap = index.capacity
    storage = index.list_data.dtype

    def phase3(x_shard, lab, ids, old_data, old_ids, old_norms, sizes):
        xf = x_shard.astype(jnp.float32)
        gpos = _global_positions(comms, lab, L, base=sizes)
        data, idb, nrm = _fill_blocks(
            comms,
            [(xf, jnp.float32), (ids + 1, jnp.int32),
             (jnp.sum(xf * xf, axis=1), jnp.float32)],
            lab, gpos, L, cap)
        idb = idb - 1
        # graft old list contents back in: slots below the old sizes belong
        # to the resident data, slots at/above them to the new psum'd rows
        grow = ((0, 0), (0, cap - old_cap), (0, 0))
        od = jnp.pad(old_data.astype(jnp.float32), grow)
        oi = jnp.pad(old_ids, grow[:2], constant_values=-1)
        on = jnp.pad(old_norms, grow[:2], constant_values=jnp.inf)
        keep_old = oi >= 0
        data = jnp.where(keep_old[..., None], od, data)
        idb = jnp.where(keep_old, oi, idb)
        nrm = jnp.where(idb < 0, jnp.inf, jnp.where(keep_old, on, nrm))
        return data.astype(storage), idb, nrm

    ids = shard_along(mesh, axis, new_ids)
    data, idb, nrm = jax.jit(comms.shard_map(
        phase3,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P(axis), P(axis))))(
        xs, labels, ids,
        shard_along(mesh, axis, index.list_data),
        shard_along(mesh, axis, index.list_ids),
        shard_along(mesh, axis, index.list_norms),
        replicated(mesh, index.list_sizes))
    return IvfFlatIndex(
        centers=index.centers, list_data=data, list_ids=idb, list_norms=nrm,
        list_sizes=jnp.asarray(new_sizes, jnp.int32), metric=index.metric,
        split_factor=index.split_factor, data_kind=index.data_kind)


@instrument("parallel.ivf.build_pq",
            items=lambda a, kw: nrows(a[2] if len(a) > 2 else kw["dataset"]),
            labels=lambda a, kw: {"size": (a[0] if a else kw["comms"]).size()})
def build_pq(comms: Comms, params, dataset, res=None):
    """Distributed IVF-PQ build (``params`` =
    :class:`raft_tpu.neighbors.ivf_pq.IndexParams`): same three phases as
    :func:`build`, plus replicated codebook training on a pooled residual
    subsample between them, and a shard-local encode feeding the list fill.
    Restrictions vs the single-chip build: per-subspace codebooks only
    ("auto" resolves to per_subspace without the per-cluster trial) and no
    sub-list splitting."""
    from ..distance.pairwise import _choose_tile
    from ..distance.types import resolve_metric
    from ..neighbors import ivf_pq as pq_mod
    from ..random.rng import as_key

    x = jnp.asarray(dataset)
    expects(x.ndim == 2, "dataset must be (n, d)")
    n, d = x.shape
    S = comms.size()
    expects(n % S == 0, "dataset rows (%d) must divide the mesh axis (%d); "
            "pad first", n, S)
    L = params.n_lists
    expects(L % S == 0, "n_lists (%d) must divide the mesh axis (%d)", L, S)
    mt = resolve_metric(params.metric)
    # int8/uint8 ingestion, identical to the single-chip build (shift into
    # the s8 domain, work in the exact f32 image)
    data_kind, x = pq_mod._resolve_pq_ingest(x, mt)
    expects(params.codebook_kind in ("auto", "per_subspace"),
            "the distributed build trains per-subspace codebooks "
            "(codebook_kind=%r is single-chip only)", params.codebook_kind)
    expects(not getattr(params, "residual_scale_norm", False),
            "residual_scale_norm is single-chip only (the distributed "
            "build's pooled codebook training does not yet normalize "
            "per-list scales)")
    pq_dim = params.pq_dim or pq_mod._default_pq_dim(d, params.pq_bits)
    pq_len = -(-d // pq_dim)
    d_rot = pq_dim * pq_len
    n_codes = 1 << params.pq_bits
    split_pref = (params.pq8_split if params.pq8_split is not None
                  else mt != DistanceType.InnerProduct)
    split = params.pq_bits == 8 and split_pref
    inner = mt == DistanceType.InnerProduct
    mesh, axis = comms.mesh, comms.axis
    shard_rows = n // S
    sub = min(max(8 * L // S, 64), shard_rows)
    tile = _choose_tile(shard_rows, L, 1, 1 << 28)

    # phase 1: coarse centers (identical machinery to the flat build)
    batch_shard = _resolve_batch_shard(params, n, S, shard_rows)

    def phase1(x_shard, keys):
        return _pooled_balanced_centers(
            comms, x_shard, keys, L, params.kmeans_n_iters, 0.25, n, sub,
            inner, tile, batch_shard=batch_shard)

    keys = replicated(mesh, jax.random.split(jax.random.key(params.seed), 3))
    xs = shard_along(mesh, axis, x)
    with tracing.range("parallel.ivf.build_pq.coarse_kmeans"):
        centers, labels, gcounts = _timed_coarse_em(
            jax.jit(comms.shard_map(
                phase1, in_specs=(P(axis), P()),
                out_specs=(P(), P(axis), P()))),
            xs, keys, params.kmeans_n_iters, batch_shard, S, n)
    cap = _build_capacity(gcounts)

    # phase 2: rotation (host, deterministic from the seed — replicated
    # constant) + replicated codebook training on a pooled residual sample
    key = as_key(params.seed)
    key, kr = jax.random.split(key)
    rotation = pq_mod._make_rotation(kr, d_rot, d, params.force_random_rotation)
    key, kc = jax.random.split(key)

    def phase2(x_shard, lab, c, kk):
        ksub = jax.random.fold_in(kk[0], comms.rank())
        idx = jax.random.choice(ksub, x_shard.shape[0], (sub,), replace=False)
        xt = jnp.take(x_shard.astype(jnp.float32), idx, axis=0)
        lt = jnp.take(lab, idx, axis=0)
        resid = (xt - jnp.take(c, lt, axis=0)) @ rotation.T
        pool = comms.allgather(resid, tiled=True)  # (S*sub, d_rot) replicated
        sub_pools = jnp.moveaxis(
            pool.reshape(pool.shape[0], pq_dim, pq_len), 1, 0)
        if split:
            return pq_mod._train_split_codebooks(
                sub_pools, kk[1], params.kmeans_n_iters)
        return pq_mod._train_codebooks_batched(
            sub_pools, kk[1], n_codes, params.kmeans_n_iters)

    cb_keys = replicated(mesh, jnp.stack([keys[0], kc]))
    with tracing.range("parallel.ivf.build_pq.train_codebooks"):
        codebooks = jax.jit(comms.shard_map(
            phase2, in_specs=(P(axis), P(axis), P(), P()),
            out_specs=P()))(xs, labels, centers, cb_keys)

    # phase 3: shard-local encode + block fill
    enc_cb_host = (pq_mod._composed_codebooks(codebooks) if split
                   else codebooks)
    consts_l2 = split and not inner

    def phase3(x_shard, lab, ids, c, enc_cb, cb):
        resid = ((x_shard.astype(jnp.float32) - jnp.take(c, lab, axis=0))
                 @ rotation.T).reshape(x_shard.shape[0], pq_dim, pq_len)
        codes = pq_mod._encode(resid, enc_cb, lab, per_cluster=False,
                               tile=min(x_shard.shape[0], 8192))
        gpos = _global_positions(comms, lab, L)
        payloads = [(codes, jnp.int32), (ids + 1, jnp.int32)]
        if consts_l2:
            payloads.append(
                (pq_mod._pq_cross_consts(codes, cb, lab, False), jnp.float32))
        out = _fill_blocks(comms, payloads, lab, gpos, L, cap)
        cbuf = (out[2] if consts_l2
                else jnp.zeros((L // comms.size(), 0), jnp.float32))
        return out[0].astype(jnp.uint8), out[1] - 1, cbuf

    ids = shard_along(mesh, axis, jnp.arange(n, dtype=jnp.int32))
    with tracing.range("parallel.ivf.build_pq.encode_fill"):
        codes_arr, idb, cbuf = jax.jit(comms.shard_map(
            phase3, in_specs=(P(axis), P(axis), P(axis), P(), P(), P()),
            out_specs=(P(axis), P(axis), P(axis))))(
            xs, labels, ids, centers, replicated(mesh, enc_cb_host),
            replicated(mesh, codebooks))
    return pq_mod.IvfPqIndex(
        centers=centers, centers_rot=centers @ rotation.T, rotation=rotation,
        codebooks=codebooks, list_codes=codes_arr, list_ids=idb,
        list_sizes=gcounts, list_consts=cbuf, metric=mt,
        codebook_kind="per_subspace", pq_bits=params.pq_bits,
        split_factor=params.split_factor, pq_split=split,
        data_kind=data_kind)

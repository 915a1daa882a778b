"""Multi-chip CAGRA: one graph index per dataset shard, beam searches run
shard-local, candidates merge over ICI.

A CAGRA graph cannot be row-sharded naively — pruned edges cross arbitrary
rows, so a beam on one chip would constantly dereference vectors living on
another. The multi-GPU pattern the reference ecosystem uses instead (per-GPU
indexes over dataset partitions, query fan-out, heap merge — the raft::comms +
knn_merge_parts composition of docs/source/using_comms.rst and
detail/knn_merge_parts.cuh) maps cleanly to SPMD: each shard owns an
independent CAGRA graph over its rows (builds are embarrassingly parallel —
on a real pod every host builds its own shard), searches are replicated
queries against every shard's graph inside one shard_map, and a single
all_gather + select_k produces the global top-k. Recall of the merged result
is at least the per-shard recall: every shard contributes its own true local
top-k candidates.
"""

from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..comms.comms import Comms, replicated, shard_along
from ..core import tracing
from ..core.errors import expects
from ._progcache import ProgramCache
from ..distance.types import DistanceType
from ..matrix.select_k import _select_k
from ..obs.instrument import instrument, nrows
from ..random.rng import as_key
from ..neighbors.cagra import (CagraIndex, IndexParams, SearchParams, _cagra_search,
                               _seed_constants, estimate_seed_pool,
                               resolve_hop_impl, resolve_max_iterations,
                               resolve_seed_pool, seed_plan)
from ..neighbors.cagra import build as build_single

__all__ = ["ShardedCagraIndex", "build", "build_merged", "merged_builder",
           "search"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedCagraIndex:
    """Stacked per-shard CAGRA indexes: shard s owns dataset rows
    [s*rows_per_shard, (s+1)*rows_per_shard) of the original ordering."""

    dataset: jax.Array   # (S, n/S, d) — f32, or int8 for byte datasets
    graph: jax.Array     # (S, n/S, graph_degree) int32, shard-local ids
    metric: DistanceType = DistanceType.L2Expanded
    # "float32" | "int8" | "uint8" — same contract as CagraIndex.data_kind
    data_kind: str = "float32"

    @property
    def n_shards(self) -> int:
        return self.dataset.shape[0]

    @property
    def rows_per_shard(self) -> int:
        return self.dataset.shape[1]

    @property
    def dim(self) -> int:
        return self.dataset.shape[2]

    def tree_flatten(self):
        return (self.dataset, self.graph), (self.metric, self.data_kind)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kind = aux[1] if len(aux) > 1 else "float32"
        return cls(*children, metric=aux[0], data_kind=kind)


@instrument("parallel.cagra.build",
            items=lambda a, kw: nrows(a[2] if len(a) > 2 else kw["dataset"]),
            labels=lambda a, kw: {"size": (a[0] if a else kw["comms"]).size()})
def build(comms: Comms, params: IndexParams, dataset) -> ShardedCagraIndex:
    """Build one CAGRA graph per shard (host loop; on a multi-host pod each
    host builds only its own shard — the graphs are fully independent)."""
    dataset = jnp.asarray(dataset)
    n = dataset.shape[0]
    size = comms.size()
    expects(n % size == 0, "dataset rows (%d) must divide the mesh axis (%d); pad first",
            n, size)
    rows = n // size
    expects(params.graph_degree < rows, "graph_degree must be < rows per shard (%d)", rows)
    with tracing.range("parallel.cagra.build.shards"):
        shards = [build_single(params, dataset[s * rows:(s + 1) * rows])
                  for s in range(size)]
    return ShardedCagraIndex(
        dataset=jnp.stack([s.dataset for s in shards]),
        graph=jnp.stack([s.graph for s in shards]),
        metric=shards[0].metric,
        data_kind=shards[0].data_kind,
    )


def _shard_bounds(n: int, size: int) -> list[tuple[int, int]]:
    """Contiguous near-equal shard row ranges; the first ``n % size`` shards
    carry one extra row. Unlike the shard_map drivers there is NO
    divisibility requirement — the merged build is a host loop, so uneven
    live-row counts (the compaction-rebuild case) need no padding."""
    base, extra = divmod(n, size)
    bounds, lo = [], 0
    for s in range(size):
        hi = lo + base + (1 if s < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


@instrument("parallel.cagra.build_merged",
            items=lambda a, kw: nrows(a[2] if len(a) > 2 else kw["dataset"]),
            labels=lambda a, kw: {"size": (a[0] if a else kw["comms"]).size()})
def build_merged(comms: Comms, params: IndexParams, dataset,
                 res=None) -> CagraIndex:
    """Sharded CAGRA build merged into ONE plain :class:`CagraIndex`.

    Each of the mesh's S shards builds an independent graph over its
    contiguous row range, then the per-shard graphs concatenate — edge ids
    offset to global — into a single index over the full dataset that every
    single-chip consumer (``cagra.search``, serve hooks,
    ``stream.MutableIndex``, save/load) takes unchanged. NOTE the loop runs
    the S builds SERIALLY in this process (like :func:`build`): the
    measured win below is the smaller-shard superlinearity alone. The
    shard builds are independent, so a multi-host deployment CAN run one
    per host and concatenate (each shard is ``build_single`` on a
    contiguous slice), but this driver does not orchestrate that.

    Why this is a *build-speed* lever: the build's dominant cost is the
    IVF-PQ self-search, whose per-row cost grows with the shard's row
    count, so S shard-local builds cost well under the global build's
    self-search even run serially on one chip (r07 CPU artifact: warm 180 s
    -> 103 s at 32k/8 — the whole measured win; no cross-host parallelism
    is involved).

    Recall contract: the merged graph has no cross-shard edges, so ONE
    beam over it splits across S disconnected subgraphs — widen itopk by
    ~S/2-S/4 to hold the single-graph operating point (measured at 32k/8:
    0.9371 @ itopk32 -> 0.995 @ 64 -> 0.9999 @ 128 vs single 1.0 @ 32), or
    search through the per-shard composition (:func:`search` on
    :func:`build`'s ShardedCagraIndex), which runs S full-width beams and
    measured NO recall cost (r06, 64k/8). Sizing details in
    docs/using_comms.md; keep shards above ~4k rows (below that the graph
    regime itself stops paying — same bound as :func:`build`).
    """
    x = jnp.asarray(dataset)
    n = x.shape[0]
    size = comms.size()
    bounds = _shard_bounds(n, size)
    min_rows = min(hi - lo for lo, hi in bounds)
    expects(params.graph_degree < min_rows,
            "graph_degree (%d) must be < rows per shard (%d)",
            params.graph_degree, min_rows)
    with tracing.range("parallel.cagra.build_merged.shards"):
        shards = [build_single(params, x[lo:hi], res=res)
                  for lo, hi in bounds]
    graph = jnp.concatenate(
        [s.graph + jnp.int32(lo) for s, (lo, _) in zip(shards, bounds)])
    merged = jnp.concatenate([s.dataset for s in shards])
    # seed-pool hint re-estimated over the MERGED graph: local-mode counts
    # add across shards, so per-shard hints undercount by up to S x
    hint = estimate_seed_pool(merged, graph, seed=params.seed)
    return CagraIndex(dataset=merged, graph=graph, metric=shards[0].metric,
                      data_kind=shards[0].data_kind, seed_pool_hint=hint)


def merged_builder(comms: Comms, params: IndexParams):
    """A ``builder=`` callable for :class:`raft_tpu.stream.MutableIndex`:
    rebuild compactions construct the successor sealed index with
    :func:`build_merged`, cutting the compaction wall that bounds the
    sustainable write churn rate (docs/streaming.md) by the sharded
    build's measured factor (~-43% at 32k/8 even serially — see
    :func:`build_merged` for what is and is not parallel)."""
    def build_fn(dataset, res=None):
        return build_merged(comms, params, dataset, res=res)

    return build_fn


@instrument("parallel.cagra.search",
            items=lambda a, kw: nrows(a[3] if len(a) > 3 else kw["queries"]),
            labels=lambda a, kw: {"k": a[4] if len(a) > 4 else kw["k"],
                                  "size": (a[0] if a else kw["comms"]).size()})
def search(comms: Comms, params: SearchParams, index: ShardedCagraIndex,
           queries, k: int):
    """Distributed CAGRA search: per-shard beam search + ICI merge.

    Returns replicated (distances (m, k), global ids (m, k)); ids refer to
    the original (pre-sharding) dataset row ordering.
    """
    from ..neighbors.brute_force import _coerce_queries

    queries = jnp.asarray(queries)
    expects(queries.ndim == 2 and queries.shape[1] == index.dim, "query dim mismatch")
    expects(k <= params.itopk_size, "k must be <= itopk_size")
    queries = _coerce_queries(index.data_kind, queries)
    size = comms.size()
    expects(index.n_shards == size, "index has %d shards but mesh axis is %d",
            index.n_shards, size)
    rows = index.rows_per_shard
    itopk = params.itopk_size
    max_iter = resolve_max_iterations(params)
    sqrt_out = index.metric in (DistanceType.L2SqrtExpanded,
                                DistanceType.L2SqrtUnexpanded)
    # shared resolution with the single-chip driver: -1 (auto) must not
    # leak into seed_plan (a negative pool silently means random entries),
    # and hop_impl picks the fused Pallas hop when eligible. Per-shard
    # indexes carry no seed_pool_hint; auto falls to the default.
    seed_pool = resolve_seed_pool(params)  # seed_plan clamps to shard rows
    hop_impl = resolve_hop_impl(
        params, index.graph.shape[-1], index.dim,
        itemsize=index.dataset.dtype.itemsize)

    mesh, axis = comms.mesh, comms.axis
    args = (
        shard_along(mesh, axis, index.dataset),
        shard_along(mesh, axis, index.graph),
        replicated(mesh, queries),
    )
    fn = _cagra_search_fn(comms, int(k), int(itopk), int(max_iter),
                          int(params.search_width), bool(sqrt_out),
                          int(seed_pool), hop_impl, index.metric,
                          int(rows))
    return fn(*args, replicated(mesh, as_key(params.seed)))


_PROGRAMS = ProgramCache(maxsize=256)


def _cagra_search_fn(comms: Comms, k: int, itopk: int, max_iter: int,
                     width: int, sqrt_out: bool, seed_pool: int,
                     hop_impl: str, metric, rows: int):
    """Memoized jitted program per static config (see parallel/knn._knn_fn
    — a fresh jax.jit wrapper per call forces a retrace per search);
    releasable per communicator (parallel.release_programs)."""
    key = (comms, k, itopk, max_iter, width, sqrt_out, seed_pool, hop_impl,
           metric, rows)
    return _PROGRAMS.get_or_build(key, lambda: _build_cagra_search_fn(
        comms, k, itopk, max_iter, width, sqrt_out, seed_pool, hop_impl,
        metric, rows))


def _build_cagra_search_fn(comms: Comms, k: int, itopk: int, max_iter: int,
                           width: int, sqrt_out: bool, seed_pool: int,
                           hop_impl: str, metric, rows: int):
    size = comms.size()
    inner = metric == DistanceType.InnerProduct

    def step(data, graph, q, key):
        with tracing.scope("parallel.cagra.local_search"):
            shard = CagraIndex(dataset=data[0], graph=graph[0], metric=metric)
            pool, n_init = seed_plan(rows, itopk, width, shard.graph_degree,
                                     seed_pool)
            seeds = _seed_constants(shard.dataset, key, pool, n_init)
            d_loc, i_loc = _cagra_search(shard, q, seeds, k, itopk,
                                         max_iter, width, sqrt_out, hop_impl)
        with tracing.scope("parallel.cagra.merge"):
            i_glob = jnp.where(i_loc >= 0,
                               i_loc + comms.rank().astype(jnp.int32) * rows, i_loc)
            d_all = comms.allgather(d_loc)
            i_all = comms.allgather(i_glob)
            m = q.shape[0]
            d_flat = jnp.moveaxis(d_all, 0, 1).reshape(m, size * k)
            i_flat = jnp.moveaxis(i_all, 0, 1).reshape(m, size * k)
            return _select_k(d_flat, i_flat, k, not inner)

    axis = comms.axis
    return jax.jit(comms.shard_map(
        step,
        in_specs=(P(axis), P(axis), P(), P()),
        out_specs=(P(), P()),
    ))

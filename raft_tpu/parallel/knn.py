"""Multi-chip exact kNN: shard the dataset, search locally, merge globally.

The reference leaves multi-GPU kNN to users composing raft::comms + per-shard
search + knn_merge_parts (SURVEY.md §5 "long-context" entry;
docs/source/using_comms.rst). Here it is a first-class driver: the dataset is
row-sharded over a mesh axis, every chip runs the local brute-force search on
its shard — the fused Pallas distance+top-k kernel (ops/fused_knn.py) when
the per-shard shapes qualify on TPU, the XLA GEMM+top_k pipeline otherwise —
and one all_gather + select_k merge produces the global result (the
reference's knn_merge_parts pattern, detail/knn_merge_parts.cuh): candidates
ride ICI, never the full distance matrix.

Non-divisible datasets self-pad: the tail shard is filled with masked rows
(the same trick the reference uses for padded inverted lists), so callers
never see the shard-divisibility invariant.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..comms.comms import Comms, replicated, shard_along
from ..core import tracing
from ..core.errors import expects
from ..distance.types import DistanceType, resolve_metric
from ..matrix.select_k import _select_k
from ..neighbors.brute_force import _bf_knn, _bf_knn_fused, _fused_eligible
from ..obs.instrument import instrument, nrows
from ._progcache import ProgramCache

__all__ = ["knn"]

# memoized jitted programs per (comms, static config) — releasable per
# communicator at mesh teardown (parallel.release_programs), since the
# cached closures pin the Comms/Mesh/devices they were staged for
_PROGRAMS = ProgramCache(maxsize=256)


def _knn_fn(comms: Comms, k: int, mt: DistanceType, metric_arg: float,
            tile: int, inner_tile: int, compute: str, use_fused: bool,
            shard_rows: int, has_keep: bool):
    """Memoized jitted program per static config. The drivers used to build
    a fresh closure + jax.jit wrapper on every call, which forced a full
    retrace per search — measured as a 38-45% driver overhead on a 1-device
    mesh (r05 parallel-driver overhead); with the program
    cached the overhead is the collectives' true cost."""
    key = (comms, k, mt, metric_arg, tile, inner_tile, compute, use_fused,
           shard_rows, has_keep)
    return _PROGRAMS.get_or_build(key, lambda: _build_knn_fn(
        comms, k, mt, metric_arg, tile, inner_tile, compute, use_fused,
        shard_rows, has_keep))


def _build_knn_fn(comms: Comms, k: int, mt: DistanceType, metric_arg: float,
                  tile: int, inner_tile: int, compute: str, use_fused: bool,
                  shard_rows: int, has_keep: bool):
    size = comms.size()
    select_min = mt != DistanceType.InnerProduct

    def local_search(x_shard, q, keep_shard):
        with tracing.scope("parallel.knn.local_search"):
            if use_fused:
                return _bf_knn_fused(x_shard, q, k, mt, compute, keep_shard)
            comp = "float32" if compute == "float32x3" else compute
            return _bf_knn(x_shard, q, k, mt, metric_arg,
                           min(tile, q.shape[0]), inner_tile, keep_shard,
                           compute=comp)

    def merge(d_loc, i_loc, m):
        with tracing.scope("parallel.knn.merge"):
            i_glob = jnp.where(i_loc >= 0,
                               i_loc + comms.rank().astype(jnp.int32) * shard_rows,
                               -1)
            d_all = comms.allgather(d_loc)
            i_all = comms.allgather(i_glob)
            d_flat = jnp.moveaxis(d_all, 0, 1).reshape(m, size * k)
            i_flat = jnp.moveaxis(i_all, 0, 1).reshape(m, size * k)
            return _select_k(d_flat, i_flat, k, select_min)

    if has_keep:
        def step(x_shard, keep_shard, q):
            d_loc, i_loc = local_search(x_shard, q, keep_shard)
            return merge(d_loc, i_loc, q.shape[0])

        return jax.jit(comms.shard_map(
            step, in_specs=(P(comms.axis), P(comms.axis), P()),
            out_specs=(P(), P())))

    def step(x_shard, q):
        d_loc, i_loc = local_search(x_shard, q, None)
        return merge(d_loc, i_loc, q.shape[0])

    return jax.jit(comms.shard_map(
        step, in_specs=(P(comms.axis), P()), out_specs=(P(), P())))


@instrument("parallel.knn",
            items=lambda a, kw: nrows(a[2] if len(a) > 2 else kw["queries"]),
            labels=lambda a, kw: {"k": a[3] if len(a) > 3 else kw["k"],
                                  "size": (a[0] if a else kw["comms"]).size()})
def knn(comms: Comms, dataset, queries, k: int, metric="sqeuclidean", metric_arg: float = 2.0,
        tile: int = 2048, inner_tile: int = 512, compute: str = "float32"):
    """Distributed exact kNN (multi-chip analogue of brute_force.knn).

    ``dataset`` is sharded along ``comms.axis`` (row-wise; a non-divisible
    row count is padded with masked rows internally); ``queries`` are
    replicated. ``compute`` selects the local kernel's contraction mode
    ("float32" | "float32x3" | "bfloat16", as brute_force.knn). Returns
    replicated (distances (m, k), global indices). ``k`` must fit one shard's
    rows (the per-shard candidate width of the merge).
    """
    dataset = jnp.asarray(dataset)
    queries = jnp.asarray(queries)
    n, d = dataset.shape
    size = comms.size()
    n_pad = -(-n // size) * size
    shard_rows = n_pad // size
    expects(0 < k <= shard_rows,
            "k=%d must be <= per-shard rows (%d rows over %d shards)",
            k, shard_rows, size)
    mt = resolve_metric(metric)
    keep = None
    if n_pad != n:
        dataset = jnp.pad(dataset, ((0, n_pad - n), (0, 0)))
        keep = jnp.arange(n_pad) < n
    use_fused = _fused_eligible(mt, int(k), shard_rows, d, "exact", compute)
    x_sharded = shard_along(comms.mesh, comms.axis, dataset)
    q_repl = replicated(comms.mesh, queries)
    fn = _knn_fn(comms, int(k), mt, float(metric_arg), int(tile),
                 int(inner_tile), compute, bool(use_fused), int(shard_rows),
                 keep is not None)
    if keep is None:
        return fn(x_sharded, q_repl)
    keep_sh = shard_along(comms.mesh, comms.axis, keep)
    return fn(x_sharded, keep_sh, q_repl)

"""CAGRA: graph-based ANN index.

Re-design of the reference's CAGRA (cpp/include/raft/neighbors/cagra.cuh;
build detail/cagra/cagra_build.cuh — kNN graph via IVF-PQ :42,86 + refine
:167-184, then detour-count pruning graph_core.cuh:128 kern_prune + reverse
-edge merge; search detail/cagra/search_plan.cuh + single/multi-CTA persistent
kernels, bitonic itopk + visited hashmap). SURVEY.md flags the search as "the
one algorithm whose control flow is fundamentally device-side dynamic"; the
TPU re-think makes it batch-synchronous:

- **Build**: identical pipeline shape — IVF-PQ over the dataset, batched
  search (queries = dataset), exact refine, then *vectorized* detour pruning:
  the reference counts 2-hop detours per edge with a per-node CUDA kernel;
  here the detour count of edge (u→v) = number of w ∈ N(u) ranked closer
  than v with v ∈ N(w) — computed for all edges at once with one batched
  membership test over the neighbor lists (einsum of one-hot comparisons),
  then reverse-edge merge.
- **Search**: best-first beam search over the whole query batch in lockstep
  under lax.while_loop: each hop expands the best unvisited beam entry per
  query, gathers its fixed-degree adjacency row (one row DMA per query),
  scores all expansions with an MXU batched dot, and merges into the beam
  with one sort — the bitonic itopk + hashmap of the persistent kernel
  becomes sort-based dedup on (id, score) pairs, fully static shapes.
"""

from __future__ import annotations

from ..config import auto_convert_output

import dataclasses
import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import tracing
from ..core.errors import expects
from ..core.held import HeldByArray, concrete
from ..core.logger import logger
from ..core.resources import Resources, default_resources
from ..core.serialize import (check_header, deserialize_mdspan, deserialize_scalar,
                              deserialize_tuned, serialize_header,
                              serialize_mdspan, serialize_scalar,
                              serialize_tuned)
from ..distance.types import DistanceType, resolve_metric
from ..obs import build as _build_metrics
from ..obs import mem as obs_mem
from ..obs import metrics as _metrics
from ..obs.instrument import dtype_of, instrument, nrows
from ..random.rng import as_key
from . import ivf_pq as ivf_pq_mod
from .refine import refine

__all__ = ["IndexParams", "SearchParams", "CagraIndex", "build", "search",
           "build_knn_graph", "optimize", "save", "load"]


@dataclasses.dataclass(frozen=True)
class IndexParams:
    """Reference: cagra::index_params (cagra_types.hpp:48-64)."""

    intermediate_graph_degree: int = 64  # ref :55
    graph_degree: int = 32  # ref :57
    metric: str | DistanceType = "sqeuclidean"
    # 0 → auto: pq_bits=4 when the dataset is high-dimensional (pq_dim >= 32,
    # e.g. d >= 64) — the TPU-fast LUT scan (one-hot contraction axis 16
    # codes, ~10x the pq8 QPS), with graph quality restored by the exact
    # refine pass — and pq_bits=8 for low-dim data where 16 codes per
    # subspace quantize too coarsely and the pq8 axis is cheap anyway (the
    # reference always uses 8; its smem LUT is bits-insensitive).
    build_pq_bits: int = 0
    build_n_lists: int = 0  # 0 → sqrt(n) heuristic
    # probes for the self-search that builds the knn graph. The r04 profile
    # (bench/cagra_build_profile.py) put 98% of the 445 s 1M build in this
    # search, scaling linearly in probes — and a full-build A/B measured
    # p=8 vs p=32 recall IDENTICAL to 4 decimals (0.9714 @ itopk32 /
    # 0.9964 @ itopk64) at 122.6 s vs 445 s on clustered data: a dataset
    # point's top-64 neighbors live in its home + adjacent lists. On
    # small/uniform data the same drop costs real graph quality (0.80 →
    # 0.63 edge recall at 4k x 24 uniform), so 0 (default) = MEASURED auto:
    # chunk 0 is built at p=32 and p=8 and the cheap setting is kept for
    # the remaining chunks only when its refined edge lists overlap the
    # wide ones >= 95% (escalating to 16, then 32). Explicit values are
    # honored as-is. (r04 CAGRA build.)
    build_n_probes: int = 0
    # gpu_top_k multiplier (ref cagra_build.cuh:99 defaults 2.0 against pq8);
    # 3.0 compensates pq4's coarser candidate ordering — the wider exact
    # refine pool costs far less than pq8's 10x-slower LUT scan
    refine_rate: float = 3.0
    # NOTE a bf16 hop-scoring dataset copy was tried and measured WORSE on
    # both axes at 1M x 128 (QPS 28.5k -> 26.1k, recall 0.971 -> 0.699 at
    # itopk=32): the per-hop vector fetches are latency-bound, not
    # bandwidth-bound, and bf16 score noise misorders the beam on tight
    # clusters. Removed.
    # query rows per device dispatch during the self-search/refine phases —
    # keeps any single device program under watchdog/VMEM pressure limits.
    # Honored down to 1 (lower = more, smaller dispatches; useful when VMEM
    # limits bite at high d); values below ~1024 cost dispatch overhead
    build_chunk: int = 16384
    # top-k implementation for the build self-search's candidate selects
    # (k = gpu_top_k + 1, 193 at defaults — the call site the wide-k Pallas
    # selector was commissioned for, VERDICT r4 #5 / r5 #3). Threads into
    # ivf_pq.SearchParams.select_impl inside _build_chunk_step:
    #   "auto"   — the measured select_k dispatch rule (k <= 256 reachable
    #              since r06's half-width merge lifted the chaining cap;
    #              build-chunk per-chunk widths of ~10-40k cols sit below
    #              the 65536-col wide-k threshold, so auto stays on
    #              lax.top_k until the driver A/B justifies lowering it).
    #   "pallas" — force the streaming selector (the A/B arm
    #              bench/cagra_build_select_ab.py measures; two wide
    #              instances per program — per-chunk + final merge — is
    #              exactly the composition the r06 workaround unlocked).
    #   "xla"    — force lax.top_k.
    build_select_impl: str = "auto"
    # coarse-trainer EM policy for the build's internal IVF-PQ index
    # (ivf_pq.IndexParams.kmeans_train_mode/kmeans_batch_rows — same
    # contract): "auto" runs mini-batch EM above 2 x batch_rows trainset
    # rows, so the 1M self-search index build sheds its ~20 full-trainset
    # assignment passes. Build speed is a serving feature here: the stream
    # Compactor's CAGRA rebuild path means this wall bounds sustainable
    # write churn (docs/streaming.md).
    build_kmeans_train_mode: str = "auto"
    build_kmeans_batch_rows: int = 65536
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Reference: cagra::search_params (cagra_types.hpp:66-120)."""

    itopk_size: int = 64  # beam width (ref :66)
    max_iterations: int = 0  # 0 → auto (ref :71)
    search_width: int = 1  # beam entries expanded per hop (ref :93)
    # entry-point candidate pool: the beam is seeded with the best
    # `n_init` of `seed_pool` uniformly-sampled dataset points, scored by one
    # (m, seed_pool) MXU GEMM. The reference seeds from `num_pickup` purely
    # random points (search_plan.cuh random_samplings); a scored pool costs
    # one cheap matmul and keeps recall on clustered data where random
    # entries land in the wrong basin and the graph has no cross-cluster
    # edges. Pool size sets the entry-coverage recall ceiling at scale:
    # measured at 1M x 128 / 2000 clusters (itopk=32), pool 4096 → 0.846
    # recall, 16384 → 0.973 at identical QPS — the GEMM is not the hop
    # loop's bottleneck. THE POOL MUST SCALE WITH THE DATA'S LOCAL MODES: on
    # multi-scale (near-duplicate-clump) data with ~32k clumps, 16384 →
    # 0.880 but 65536 → 0.979 (-13% QPS) and 131072 → 0.995 (-24%) at
    # itopk=32 (r04 SIFT-class 1M harness sweep) —
    # the beam cannot hop into a clump no seed landed near.
    #   -1 (default) → AUTO: use the index's measured seed_pool_hint (the
    #     build estimates the local-mode count from the knn graph's
    #     neighbor-distance jump profile — the search-side twin of the r04
    #     build_n_probes autotune; reference analogue: adjust_search_params,
    #     detail/cagra/search_plan.cuh:119), falling back to 16384 when the
    #     build saw no clump structure.
    #   0 → plain random entries (reference behavior).
    #   >0 → explicit pool size, honored as-is.
    seed_pool: int = -1
    # hop-loop implementation (r05, VERDICT r4 #1; full study in
    # r05 fused hop study; r06 arena iteration in
    # "Round-6 arena residual attack"):
    #   "auto" → "fused_arena" on TPU when eligible (itopk +
    #     search_width*degree <= 128), else the XLA loop.
    #   "fused_arena" — ONE Pallas launch per hop (scoring + dedup + merge +
    #     pick, beam state VMEM-resident; gathers stay in XLA per the r04
    #     head-to-head) with a threshold-gated arena merge: candidates
    #     insert over the arena's worst only while they beat it, so late
    #     hops pay ~0 merge passes. The insertion loop carries its gate in
    #     a register and its candidate scores as loop values (r06); the r05
    #     arena (SMEM gate, scratch-stashed pool, 1.27x the XLA loop at 1M
    #     itopk=32) and a sorted-extraction merge (measured NEUTRAL vs XLA)
    #     were A/B controls no cell ran, and were deleted.
    #   "xla" — the op-at-a-time hop loop (reference shape): the path for
    #     shapes the kernel cannot take, and the fused kernel's reference.
    hop_impl: str = "auto"
    # RNG seed (int / RngState / raw key) for the seed-pool draw (ref
    # search_params :118 rand_xor_mask). Determinism contract: the same
    # (seed, index, queries, params) always searches the same sampled pool,
    # so results are bitwise reproducible; vary the seed to decorrelate the
    # entry-coverage ceiling across calls (VERDICT r3 weak #3 — a fixed
    # key tied every search to one 16384-point draw). The pool, its rows
    # and the corpus norms depend only on (index, seed, pool size), so
    # search() draws them once per index and seed and reuses them in every
    # later search (:func:`_seed_constants`); an RngState advances per
    # call and so draws afresh each time.
    seed: int = 0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CagraIndex:
    """Reference: cagra::index (cagra_types.hpp:123-220) — dataset + fixed
    -degree neighbor graph."""

    dataset: jax.Array  # (n, d) — f32, or int8 for byte datasets (the
    # reference's dtype-generic cagra::index<T>: int8/uint8 datasets store
    # native bytes, quartering the hop loop's vector-gather traffic; uint8
    # is held shifted by -128 in the s8 domain — L2 is shift-invariant and
    # queries shift the same way at search)
    graph: jax.Array  # (n, graph_degree) int32
    metric: DistanceType = DistanceType.L2Expanded
    # "float32" | "int8" | "uint8": what the stored dataset IS (uint8 kinds
    # hold shifted s8 bytes); governs extend/search query coercion
    data_kind: str = "float32"
    # measured at build time from the knn graph's neighbor-distance jump
    # profile: the seed-pool size that covers the data's local modes
    # (0 = no clump structure detected; SearchParams.seed_pool=-1 consumes
    # this). The reference stores no search hints on the index — its
    # adjust_search_params (search_plan.cuh:119) rescales at search time
    # from itopk alone, which cannot see data clumpiness.
    # NOT part of the pytree (neither child nor aux): search() resolves it
    # on the host BEFORE the jit boundary, and putting it in aux would make
    # indexes differing only in hint recompile _cagra_search (minutes at
    # 1M). Pytree round trips (device_put, tree_map) drop it back to 0 —
    # the default pool, never an error; save/load preserves it.
    seed_pool_hint: int = 0
    # pinned operating point (raft_tpu.tune decision dict; None = untuned):
    # consulted by batched_searcher when no explicit params are given,
    # persisted by save/load (raft_tpu/9). Same non-pytree contract as
    # seed_pool_hint: tree round trips drop it back to None.
    tuned: dict | None = None

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]

    @property
    def graph_degree(self) -> int:
        return self.graph.shape[1]

    def tree_flatten(self):
        return (self.dataset, self.graph), (self.metric, self.data_kind)

    @classmethod
    def tree_unflatten(cls, aux, children):
        metric, kind = aux if isinstance(aux, tuple) else (aux, "float32")
        return cls(*children, metric=metric, data_kind=kind)


def knn_build_plan(params: IndexParams, n: int, d: int):
    """Derived internal-build parameters (k, gpu_top_k, n_lists, pq_bits) —
    one definition shared by build_knn_graph and bench/cagra_build_profile
    so the profiler always measures the real pipeline."""
    k = params.intermediate_graph_degree
    gpu_top_k = min(int(k * params.refine_rate), n - 1)
    n_lists = params.build_n_lists or max(int(n ** 0.5), 8)
    n_lists = min(n_lists, n // 4 if n >= 32 else n)
    # threshold evaluated against the reference-equivalent ~d/2 heuristic
    # (pq_bits=8 arg) so the bits-aware default change in _default_pq_dim
    # does not shift this auto decision (pq4 from d >= 64, as documented)
    pq_bits = params.build_pq_bits or (
        4 if ivf_pq_mod._default_pq_dim(d, 8) >= 32 else 8)
    return k, gpu_top_k, n_lists, pq_bits


def build_knn_graph(params: IndexParams, dataset, res: Resources | None = None):
    """Stage 1 (reference: build_knn_graph, cagra_build.cuh:42): IVF-PQ over
    the dataset, search with queries = dataset, exact refine."""
    res = res or default_resources()
    x = jnp.asarray(dataset)
    n, d = x.shape
    k, gpu_top_k, n_lists, pq_bits = knn_build_plan(params, n, d)
    pq = ivf_pq_mod.build(
        ivf_pq_mod.IndexParams(
            n_lists=n_lists,
            metric=params.metric,
            pq_bits=pq_bits,
            kmeans_train_mode=params.build_kmeans_train_mode,
            kmeans_batch_rows=params.build_kmeans_batch_rows,
            seed=params.seed,
        ),
        x,
        res=res,
    )
    # query the dataset against itself in host-side chunks (one giant
    # dispatch trips device watchdogs at 100k+ rows; the reference batches
    # here too — cagra_build.cuh:86 loops over max_batch_size query blocks),
    # k+1 then drop self. The whole per-chunk pipeline (PQ search + exact
    # refine + self-edge drop) is ONE jitted program: when dispatch is slow
    # the per-dispatch cost dominates the build (identical code measured
    # 228 s to 20+ min), so 62 chunks must cost 62 dispatches, not ~400.
    chunk = max(int(params.build_chunk), 1)
    mt = resolve_metric(params.metric)

    # per-chunk walls force a host-device sync per chunk (they would break
    # the async dispatch pipeline on EVERY build — metrics are on by
    # default), so they are strictly opt-in: set RAFT_TPU_BUILD_CHUNK_WALLS=1
    # when profiling. The always-on phase wall is the single total
    # "cagra/knn_graph" observation in build() — one sync per build.
    import os

    chunk_walls = (_metrics._enabled
                   and os.environ.get("RAFT_TPU_BUILD_CHUNK_WALLS", "") == "1")

    def chunk_step(s, probes):
        xb = x[s:s + chunk]
        rows = jnp.arange(s, min(s + chunk, n), dtype=jnp.int32)
        if not chunk_walls:
            return _build_chunk_step(x, pq, xb, rows, probes, int(gpu_top_k),
                                     int(k), mt, int(res.workspace_bytes),
                                     params.build_select_impl)
        t0 = time.perf_counter()
        out = _build_chunk_step(x, pq, xb, rows, probes, int(gpu_top_k),
                                int(k), mt, int(res.workspace_bytes),
                                params.build_select_impl)
        jax.block_until_ready(out)
        _build_metrics.build_phase().observe(time.perf_counter() - t0,
                                             phase="cagra/knn_chunk")
        return out

    probes = int(params.build_n_probes)
    parts = []
    if probes == 0:
        # measured auto (r04 CAGRA build): the
        # self-search is 98% of the build and linear in probes, but how few
        # probes preserve the graph depends on the data (clustered 1M: p=8
        # == p=32 to 4 decimals of search recall; uniform 4k: p=8 costs
        # 0.17 edge recall). So pay p=32 once on chunk 0 — whose edges are
        # kept, nothing is wasted — and adopt the cheapest of p=8/16 whose
        # refined edge lists overlap it >= 95% for the remaining chunks.
        import numpy as np

        probes = 32
        wide = chunk_step(0, 32)
        parts.append(wide)
        if n > chunk:  # autotune only pays when more chunks follow
            # the decision sample is up to 2048 rows drawn UNIFORMLY across
            # [0, n) — row order often correlates with structure (data
            # appended cluster-by-cluster), so a head slice would judge p=8
            # on one unrepresentative region (r04 advisor finding). The
            # extra wide search of the sample is cheap relative to the
            # build. Clamped by build_chunk: the user's chunk bound exists
            # to keep any single dispatch under VMEM/watchdog limits, and
            # trial dispatches must honor it too.
            t_rows = min(2048, chunk, n)
            rng = np.random.default_rng(params.seed)
            sample = np.sort(rng.choice(n, size=t_rows, replace=False))
            rt = jnp.asarray(sample, dtype=jnp.int32)
            xt = x[rt]
            wide_h = np.asarray(_build_chunk_step(
                x, pq, xt, rt, 32, int(gpu_top_k), int(k), mt,
                int(res.workspace_bytes), params.build_select_impl))
            for p_try in (8, 16):
                trial = np.asarray(_build_chunk_step(
                    x, pq, xt, rt, p_try, int(gpu_top_k), int(k), mt,
                    int(res.workspace_bytes), params.build_select_impl))
                overlap = np.mean([
                    len(set(a) & set(b)) / len(a)
                    for a, b in zip(trial.tolist(), wide_h.tolist())])
                if overlap >= 0.95:
                    probes = p_try
                    logger.info(
                        "cagra build_n_probes auto: p=%d edge lists overlap "
                        "p=32 at %.3f — using %d probes for the remaining "
                        "chunks", p_try, overlap, p_try)
                    break
            else:
                logger.info("cagra build_n_probes auto: keeping 32 probes "
                            "(cheaper settings overlapped < 0.95)")
    for s in range(chunk if parts else 0, n, chunk):
        parts.append(chunk_step(s, probes))
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]


@functools.partial(
    jax.jit,
    static_argnames=("n_probes", "gpu_top_k", "k", "metric", "workspace_bytes",
                     "select_impl"))
def _build_chunk_step(x, pq, xb, rows, n_probes: int, gpu_top_k: int, k: int,
                      metric, workspace_bytes: int, select_impl: str = "auto"):
    """One knn-graph build chunk — PQ search + exact refine + self-edge drop —
    as a single program: when dispatch is slow the per-dispatch cost
    dominates the build (identical code measured 228 s to 20+ min), so N
    chunks must cost N dispatches, not ~6N. Module-level and
    argument-passing (x/pq are jit arguments, not closure constants) so the
    compilation caches across build() calls. ``workspace_bytes`` (static) threads the caller's
    Resources budget into the dominant build phase, so a constrained
    workspace bounds the PQ scan block here too."""
    from . import ivf_pq as ivf_pq_mod
    from .refine import refine
    from ..core.resources import Resources

    chunk_res = Resources(workspace_bytes=workspace_bytes)
    # select_impl threads the wide-k selector into the k = gpu_top_k + 1
    # candidate selects below (the r05-commissioned call site; see
    # IndexParams.build_select_impl)
    sp = ivf_pq_mod.SearchParams(n_probes=n_probes, select_impl=select_impl)
    _, cand = ivf_pq_mod.search(sp, pq, xb, gpu_top_k + 1, res=chunk_res)
    _, refined = refine(x, xb, cand, k + 1, metric=metric, res=chunk_res)
    # drop self-edges (ref: build_knn_graph removes the query itself)
    self_col = refined == rows[:, None]
    # shift left past self matches: mask self then take first k valid
    big = jnp.where(
        self_col, jnp.iinfo(jnp.int32).max,
        jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    )
    order = jnp.argsort(big, axis=1)[:, :k]
    return jnp.take_along_axis(refined, order, axis=1)


@functools.partial(jax.jit, static_argnames=("out_degree", "tile"))
def _prune_graph(graph, out_degree: int, tile: int):
    """Stage 2 (reference: optimize/kern_prune, graph_core.cuh:128).

    Edge (u→v_j) is detourable if some higher-ranked neighbor w of u also has
    v in *its* list — i.e. a 2-hop path u→w→v with both hops ranked better.
    The reference counts these per edge with a per-node kernel; here a
    vectorized membership test — N(N(u)) vs N(u) — evaluated per node tile
    under lax.map so the (tile, k, k, k) comparison block stays bounded.
    Keep the out_degree lowest-detour-count edges (rank-stable).
    """
    n, k = graph.shape
    num = -(-n // tile)
    pad = num * tile - n
    gp = jnp.pad(graph, ((0, pad), (0, 0))) if pad else graph
    gt = gp.reshape(num, tile, k)
    rank_lt = jnp.tril(jnp.ones((k, k), jnp.bool_), -1).T  # i < j mask, (k_i, k_j)

    def per_tile(g):
        nbr_of_nbr = graph[g]  # (t, k, k): N(w) for each w = g[u, i]
        v = g[:, None, :, None]  # (t, 1, k, 1) target ids
        w_lists = nbr_of_nbr[:, :, None, :]  # (t, k, 1, k)
        hit = jnp.any(v == w_lists, axis=-1)  # (t, k, k): hit[u, i, j] = v_j ∈ N(w_i)
        detours = jnp.sum(jnp.where(rank_lt[None], hit, False), axis=1)  # (t, k)
        score = detours.astype(jnp.int32) * k + jnp.arange(k, dtype=jnp.int32)[None, :]
        keep = jnp.argsort(score, axis=1)[:, :out_degree]
        return jnp.take_along_axis(g, jnp.sort(keep, axis=1), axis=1)

    out = lax.map(per_tile, gt)
    return out.reshape(num * tile, out_degree)[:n]


@functools.partial(jax.jit, static_argnames=("out_degree",))
def _reverse_merge(graph, out_degree: int):
    """Reverse-edge merge (reference: graph_core.cuh optimize tail): half the
    final degree comes from pruned forward edges, half from the highest
    -priority reverse edges."""
    n, k = graph.shape
    fwd_keep = out_degree - out_degree // 2
    rev_keep = out_degree // 2

    # reverse edge priority: rank of u in v's list (lower = stronger).
    # Scatter-free formulation: a (dst, rank) scatter over n·k updates
    # serializes on TPU (measured 520 s at 100k x 64 — XLA lowers
    # non-trivial scatters to a sequential loop). Instead sort edges once by
    # the combined key dst·k + rank (unique, so one stable sort orders by
    # (dst, rank)), then for each destination v GATHER its best incoming
    # sources from the contiguous run [searchsorted(v·k), +rev_keep) — sort +
    # binary search + gather are all TPU-native.
    expects(n * k < 2 ** 31, "reverse merge packs dst*degree+rank into int32; "
            "n*degree=%d overflows — shard the graph first", n * k)
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    key = graph.reshape(-1).astype(jnp.int32) * k + jnp.tile(
        jnp.arange(k, dtype=jnp.int32), n
    )
    s_key, s_src = lax.sort((key, src), num_keys=1)
    starts = jnp.searchsorted(s_key, jnp.arange(n, dtype=jnp.int32) * k)  # (n,)
    # ends[v] == starts[v+1] (all keys < n*k) — no second binary-search sweep
    ends = jnp.concatenate([starts[1:], jnp.array([n * k], starts.dtype)])
    offs = jnp.arange(rev_keep, dtype=jnp.int32)[None, :]
    take = starts[:, None] + offs  # (n, rev_keep)
    valid = take < ends[:, None]
    rev = jnp.where(valid, jnp.take(s_src, jnp.minimum(take, n * k - 1)), -1)

    merged = jnp.concatenate([graph[:, :fwd_keep], rev], axis=1)
    # fill -1 slots (nodes with few reverse edges) from remaining fwd edges
    fill = graph[:, fwd_keep:fwd_keep + rev_keep]
    if fill.shape[1] < rev_keep:
        fill = jnp.pad(fill, ((0, 0), (0, rev_keep - fill.shape[1])), constant_values=-1)
    tail = merged[:, fwd_keep:]
    tail = jnp.where(tail >= 0, tail, fill)
    tail = jnp.where(tail >= 0, tail, graph[:, :rev_keep])  # last resort: dup fwd
    return jnp.concatenate([merged[:, :fwd_keep], tail], axis=1)


def optimize(knn_graph, out_degree: int, res: Resources | None = None):
    """Prune + reverse merge (reference: cagra::optimize → graph_core.cuh)."""
    res = res or default_resources()
    g = jnp.asarray(knn_graph)
    expects(out_degree <= g.shape[1], "out_degree must be <= input degree")
    k = g.shape[1]
    tile = max(min(g.shape[0], res.workspace_bytes // max(k * k * k, 1)), 8)
    pruned = _prune_graph(g, out_degree, min(tile, 4096))
    return _reverse_merge(pruned, out_degree)


@jax.jit
def _neighbor_dist_profile(x, knn_graph, sample_ids):
    """Sorted squared L2 from sampled rows to their knn-graph neighbors —
    the raw material for the seed-pool autotune (one small gather + dot)."""
    xs = x[sample_ids].astype(jnp.float32)  # (t, d)
    vecs = x[knn_graph[sample_ids]].astype(jnp.float32)  # (t, kk, d)
    d2 = jnp.sum((vecs - xs[:, None, :]) ** 2, axis=-1)
    return jnp.sort(d2, axis=1)


# calibrated neighbor-distance jump threshold for clump detection (see
# estimate_seed_pool's docstring for the r05 measurement); interpolated
# into the decision AND the logs so the diagnostics always report the rule
# actually applied (ADVICE r5 low)
_SEED_JUMP_RATIO = 2.0


def estimate_seed_pool(dataset, knn_graph, seed: int = 0) -> int:
    """Measured seed-pool policy (the search-side twin of the r04
    build_n_probes autotune; reference analogue: adjust_search_params,
    detail/cagra/search_plan.cuh:119 — which rescales from itopk alone and
    cannot see data structure).

    Mechanism: the search seeds the beam from a uniformly-sampled pool, and
    the pruned graph rarely crosses between near-duplicate clumps — so
    recall at scale is capped by how many local modes the pool covers
    (r04 seed_pool sweep: 16384 → 0.880 on ~32k-clump data,
    65536 → 0.979). The clump scale is read off the knn graph the build just
    produced: on multi-scale data each node's sorted neighbor distances jump
    at the clump boundary; the median jump position is the clump size s,
    n/s the mode count M, and pool = ~2M samples seed ≥85% of modes
    (1 - e^-2), which the beam's cross-clump hops finish off.

    The ≥2.0 squared-distance ratio threshold is MEASURED (r05, true-64NN
    profiles over 2048 sampled rows): the SIFT-class 1M set shows a median
    max-ratio of 2.68 at a tight position (~30 = its ~31-point clumps, so
    >50% of rows clear 2.0), while the isotropic clustered set's median is
    1.046 with ZERO rows reaching 2.0 — within-cluster distances ramp
    smoothly (~1.05x steps) and high-dim concentration keeps every
    consecutive ratio near 1. An earlier ≥4.0 threshold missed the real
    clump boundary (~2.7x: the nearest SIBLING clump sits much closer than
    the mean offset) and shipped the 0.880-recall default on exactly the
    data the autotune exists for. Isotropic data keeps the default pool (a
    bigger pool there is a pure QPS loss — r02: -18% QPS for +0.0001
    recall).
    """
    import numpy as np

    x = jnp.asarray(dataset)
    g = jnp.asarray(knn_graph)
    n = x.shape[0]
    if n < 4096 or g.shape[1] < 8:
        return 0  # below any scale where pool coverage binds
    t = min(2048, n)
    rng = np.random.default_rng(seed)
    sample = jnp.asarray(
        np.sort(rng.choice(n, size=t, replace=False)), dtype=jnp.int32)
    d2 = np.asarray(_neighbor_dist_profile(x, g, sample))
    # floor: exact duplicates give d2=0; ratios need a scale-relative floor
    floor = max(float(np.median(d2[:, -1])), 1e-30) * 1e-6
    d2 = np.maximum(d2, floor)
    ratios = d2[:, 1:] / d2[:, :-1]
    jump = ratios.max(axis=1)
    pos = ratios.argmax(axis=1) + 1  # in-clump neighbor count before the jump
    clumpy = jump >= _SEED_JUMP_RATIO  # measured calibration: see docstring
    frac = float(np.mean(clumpy))
    if frac < 0.5:
        logger.info("cagra seed_pool auto: no clump structure (%.0f%% of "
                    "sampled rows show a >=%.0fx neighbor-distance jump; "
                    "median max-ratio %.2f) — default pool", frac * 100,
                    _SEED_JUMP_RATIO, float(np.median(jump)))
        return 0
    s = float(np.median(pos[clumpy])) + 1.0  # + self
    modes = n / s
    pool = 1 << int(np.ceil(np.log2(max(2.0 * modes, 1.0))))
    pool = int(min(max(pool, 0), 131072))
    if pool <= 16384:
        logger.info("cagra seed_pool auto: clump size ~%.0f → ~%.0f modes — "
                    "default pool covers them", s, modes)
        return 0
    logger.info("cagra seed_pool auto: %.0f%% of rows jump >=%.0fx at median "
                "position %.0f → ~%.0f local modes → seed_pool_hint=%d",
                frac * 100, _SEED_JUMP_RATIO, s, modes, pool)
    return pool


@instrument("cagra.build",
            items=lambda a, kw: nrows(a[1] if len(a) > 1 else kw["dataset"]),
            labels=lambda a, kw: {
                "dtype": dtype_of(a[1] if len(a) > 1 else kw["dataset"])})
def build(params: IndexParams, dataset, res: Resources | None = None) -> CagraIndex:
    """Full CAGRA build (reference: cagra::build, cagra.cuh; the int8_t /
    uint8_t instantiations map to byte datasets here: the index stores the
    dataset in its native 8-bit dtype — uint8 shifted by -128 into the s8
    domain, L2-invariant — and the whole build pipeline (IVF-PQ self-search,
    exact refine, pruning) runs on the exact f32 image of those bytes)."""
    from ..core import chunked

    res = res or default_resources()
    stream = chunked.is_reader(dataset)
    if stream:
        # out-of-core ingest: price the streamed upload against BOTH
        # budgets, then land the corpus device-whole through the staged
        # chunk pipeline (the graph build itself runs in-core — CAGRA's
        # scan operand is the dataset)
        n, d = (int(s) for s in dataset.shape)
        kind = (str(dataset.dtype)
                if np.dtype(dataset.dtype) in (np.dtype(np.int8),
                                               np.dtype(np.uint8))
                else "float32")
        pl = obs_mem.plan("cagra", params, n, d, dtype=kind,
                          streamed=True, chunk_rows=dataset.chunk_rows)
        obs_mem.gate(res, pl["build_peak_bytes"], site="build_stream",
                     host_bytes=pl["host_peak_bytes"],
                     detail=f"cagra {n}x{d} streamed")
        x = chunked.device_materialize(dataset, kind="cagra")
    else:
        x = jnp.asarray(dataset)
    expects(x.ndim == 2, "dataset must be (n, d)")
    expects(params.graph_degree <= params.intermediate_graph_degree,
            "graph_degree must be <= intermediate_graph_degree")
    mt = resolve_metric(params.metric)
    expects(
        mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
               DistanceType.L2Unexpanded, DistanceType.L2SqrtUnexpanded),
        "cagra supports L2 metrics (reference parity), got %s", mt.name,
    )
    kind = "float32"
    if x.dtype in (jnp.dtype(jnp.int8), jnp.dtype(jnp.uint8)):
        from .brute_force import _as_signed

        kind = str(x.dtype)
        x = _as_signed(x)  # stored (and scored) in the shifted s8 domain
    # memory-budget admission (no-op unless res.memory_budget_bytes is
    # set): refuse BEFORE the knn-graph self-search spends anything; the
    # streamed gate above already priced the chunked upload
    if not stream:
        obs_mem.gate(res, lambda: obs_mem.plan(
            "cagra", params, x.shape[0], x.shape[1],
            dtype=kind)["index_bytes"],
            site="build", detail=f"cagra {x.shape[0]}x{x.shape[1]}")
    t0 = time.perf_counter()
    with tracing.range("cagra.build.knn_graph"):
        knn_graph = build_knn_graph(params, x, res=res)
    if _metrics._enabled:
        jax.block_until_ready(knn_graph)
        _build_metrics.build_phase().observe(time.perf_counter() - t0,
                                             phase="cagra/knn_graph")
    hint = estimate_seed_pool(x, knn_graph, seed=params.seed)
    t0 = time.perf_counter()
    with tracing.range("cagra.build.optimize"):
        graph = optimize(knn_graph, params.graph_degree, res=res)
    if _metrics._enabled:
        jax.block_until_ready(graph)
        _build_metrics.build_phase().observe(time.perf_counter() - t0,
                                 phase="cagra/optimize")
    out = CagraIndex(dataset=x, graph=graph, metric=mt, data_kind=kind,
                     seed_pool_hint=hint)
    obs_mem.account_index(out)  # ledger hook (docs/observability.md)
    return out


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


class SeedConstants(NamedTuple):
    """What the seed stage reads that depends only on the index and the
    search seed, never on the queries."""

    dn2: jax.Array  # (n,) f32 squared row norms of the dataset
    ids: jax.Array  # (S,) i32 scored pool ids, or (n_init,) plain entries
    pool_vecs: jax.Array | None = None  # (S, d) f32 pool rows; None: plain
    pool_dn2: jax.Array | None = None  # (S,) f32 pool row norms


def seed_plan(n: int, itopk: int, search_width: int, graph_degree: int,
              seed_pool: int) -> tuple[int, int]:
    """``(pool, n_init)`` of a search over ``n`` rows: ``n_init`` beam
    entries, chosen from a scored pool of ``pool`` rows, or drawn plainly
    at random when ``pool`` is 0 (a pool no larger than ``n_init`` scores
    nothing). Small datasets score every point."""
    n_init = min(max(itopk, search_width * graph_degree), n)
    pool = min(int(seed_pool), n)
    return (pool if pool > n_init else 0), n_init


def _seed_constants(dataset, key, pool: int, n_init: int) -> SeedConstants:
    """The seed stage's query-free inputs (ref: search_plan
    random_samplings): the corpus norms, and either a pool of ``pool`` rows
    drawn without replacement, with their rows and norms, or ``n_init``
    plain random entries. search() computes them once per index and seed
    (:class:`_SeedCache`); the distributed step computes them in its
    program."""
    with tracing.scope("cagra.seed_constants"):
        n = dataset.shape[0]
        dn2 = jnp.sum(dataset.astype(jnp.float32) ** 2, axis=1)
        if not pool:
            ids = jax.random.choice(key, n, (n_init,), replace=False)
            return SeedConstants(dn2, ids.astype(jnp.int32))
        ids = jax.random.choice(key, n, (pool,),
                                replace=False).astype(jnp.int32)
        return SeedConstants(dn2, ids, dataset[ids].astype(jnp.float32),
                             dn2[ids])


_seed_constants_jit = jax.jit(_seed_constants,
                              static_argnames=("pool", "n_init"))


@functools.lru_cache(maxsize=None)
def _seed_constants_total():
    return _metrics.counter(
        "raft_tpu_cagra_seed_constants_total",
        "CAGRA searches by whether their seed constants were cached "
        "(labels: result=hit|miss)")


class _SeedCache(HeldByArray):
    """Seed constants per index dataset, keyed by ``(seed, pool, n_init)``
    (:class:`~raft_tpu.core.held.HeldByArray`: the dataset is held weakly,
    so an index dropped in a compaction swap frees its constants with it,
    and a new dataset never finds another's norms). Each dataset keeps its
    ``PER_DATASET`` most recently used entries."""

    PER_DATASET = 4

    def get(self, dataset, seed, pool: int, n_init: int) -> SeedConstants:
        # an int seed names its key; any other seed is read back as its
        # key's bytes (an RngState advances once per search, as it did
        # when every search drew its own pool)
        key = None if isinstance(seed, int) else as_key(seed)
        if not (concrete(dataset) and (key is None or concrete(key))):
            return self._compute(dataset, seed if key is None else key,
                                 pool, n_init)  # traced or host data
        tag = (seed if key is None else
               np.asarray(jax.random.key_data(key)).tobytes())
        entry = (tag, pool, n_init)
        with self._lock:
            entries = self._entries(dataset)
            out = entries.get(entry)
            if out is not None:
                entries.move_to_end(entry)
                if _metrics._enabled:
                    _seed_constants_total().inc(result="hit")
                return out
            out = entries[entry] = self._compute(
                dataset, seed if key is None else key, pool, n_init)
            if len(entries) > self.PER_DATASET:
                entries.popitem(last=False)
            return out

    @staticmethod
    def _compute(dataset, seed, pool: int, n_init: int) -> SeedConstants:
        if _metrics._enabled:
            _seed_constants_total().inc(result="miss")
        return _seed_constants_jit(dataset, as_key(seed), pool=pool,
                                   n_init=n_init)


_SEEDS = _SeedCache()


@functools.partial(
    jax.jit,
    static_argnames=("k", "itopk", "max_iter", "search_width", "sqrt_out",
                     "hop_impl"),
)
def _cagra_search(index: CagraIndex, queries, seeds: SeedConstants, k: int,
                  itopk: int, max_iter: int, search_width: int,
                  sqrt_out: bool, hop_impl: str = "xla", keep_mask=None):
    """The beam search from the ``seeds`` of :func:`_seed_constants`,
    computed for this index, ``itopk`` and ``search_width``."""
    n, d = index.dataset.shape
    m = queries.shape[0]
    deg = index.graph_degree
    qf = queries.astype(jnp.float32)
    data = index.dataset
    width = search_width
    exp_per_hop = width * deg
    dn2 = seeds.dn2  # (n,) vector norms

    def dist_to(q, ids):
        """Squared L2 from query rows to dataset rows ids: (m, e)."""
        vecs = data[ids]  # (m, e, d)
        dots = jnp.einsum("md,med->me", q, vecs.astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)
        return dn2[ids] - 2.0 * dots  # + ‖q‖² added at the end

    def dedup_sort(ids, dists, visited):
        """Distance-sorted beam with duplicate ids killed (keep closest) —
        the TPU form of the reference's visited hashmap + bitonic itopk.
        Two multi-operand lax.sorts (payloads carried in-sort, no argsort +
        gather rounds): (id, dist)-lexsort groups duplicates with the
        closest copy first, then a dist-sort restores beam order."""
        sid, sd, sv = lax.sort((ids, dists, visited), dimension=1, num_keys=2)
        dup = jnp.concatenate(
            [jnp.zeros((ids.shape[0], 1), jnp.bool_), sid[:, 1:] == sid[:, :-1]], axis=1
        )
        sd = jnp.where(dup | (sid < 0), jnp.inf, sd)
        sd2, sid2, sv2 = lax.sort((sd, sid, sv), dimension=1, num_keys=1)
        return sid2, sd2, sv2

    # three device stages: the seeds (pool GEMM, re-score), the hop loop
    # (gathers and the hop kernel or XLA body), and the closing select with
    # the output epilogue
    with tracing.scope("cagra.search.seed"):
        # ---- init beam: entry points (ref: search_plan random_samplings) ----
        _, n_init = seed_plan(n, itopk, width, deg, 0)
        if seeds.pool_vecs is not None:
            # score a sampled pool with one MXU GEMM, seed per-query best entries
            pool_ids = seeds.ids
            pool_d = seeds.pool_dn2[None, :] - 2.0 * jnp.einsum(
                "md,sd->ms", qf, seeds.pool_vecs,
                precision=lax.Precision.DEFAULT
            )  # (m, S)
            if keep_mask is not None:
                # mask the entry pool too: the n_init seeds must be the best
                # SURVIVING pool candidates, or a heavy filter could leave a
                # query with an all-filtered beam while kept rows exist
                pool_d = jnp.where(keep_mask[pool_ids][None, :], pool_d, jnp.inf)
            _, best = lax.top_k(-pool_d, n_init)
            init_ids = pool_ids[best]  # (m, n_init), per-query seeds
            # re-score selected seeds exactly: the bf16 pool scores only pick
            # entries; beam/output distances must match the expanded nodes'
            # HIGHEST-precision scale or near-tie dedup keeps the wrong copy
            init_d = dist_to(qf, init_ids)
        else:
            init_ids = jnp.broadcast_to(seeds.ids[None, :], (m, n_init))
            init_d = dist_to(qf, init_ids)
        if keep_mask is not None:
            # mask epilogue on the entry candidates (same contract as the
            # ivf_pq/ivf_flat scan epilogues): filtered seeds carry +inf scores
            # and can never win a beam slot. Like those scans — and unlike
            # FreshDiskANN's traverse-through-deletes — filtered nodes are not
            # expanded either (each hop's candidates are masked below), so heavy
            # filtering should widen itopk to keep beam coverage.
            init_d = jnp.where(jnp.take(keep_mask, init_ids, axis=0),
                               init_d, jnp.inf)

        pad = itopk + exp_per_hop - n_init
        beam_ids = jnp.pad(init_ids, ((0, 0), (0, max(pad, 0))), constant_values=-1)[:, : itopk + exp_per_hop]
        beam_d = jnp.pad(init_d, ((0, 0), (0, max(pad, 0))), constant_values=jnp.inf)[:, : itopk + exp_per_hop]
        beam_visited = jnp.zeros(beam_ids.shape, jnp.bool_)

        beam_ids, beam_d, beam_visited = dedup_sort(beam_ids, beam_d, beam_visited)

    if hop_impl == "fused_arena":
        # one Pallas launch per hop: scoring+dedup+merge+pick with beam state
        # VMEM-resident (VERDICT r4 #1; ops/cagra_hop.py docstring has the
        # profile-driven rationale). Beam distances carry the FULL ||v-q||^2
        # inside this loop (the kernel scores directly), so +qn moves to init.
        from ..ops.cagra_hop import cagra_hop, hop_backend_ok

        _, interpret = hop_backend_ok()
        cw = width * deg

        def fcond(state):
            _, _, _, _, nocand, it = state
            # a query is done when its FIRST pick found nothing unvisited
            # (picks are best-first, so later picks can only also fail)
            return jnp.logical_and(it < max_iter,
                                   jnp.logical_not(jnp.all(nocand[:, 0] > 0)))

        def fbody(state):
            bd, bi, bv, pick, nocand, it = state
            safe = jnp.minimum(pick, n - 1)              # (m, width)
            nbrs = index.graph[safe].reshape(m, cw)      # (m, width*deg)
            # native-dtype gather: byte datasets move 1 byte/dim into the
            # kernel (a quarter of f32 DMA bytes); the f32 upcast happens
            # INSIDE the kernel at the tile level (exact for 8-bit values)
            vecs = data[jnp.maximum(nbrs, 0)]
            valid = jnp.repeat(1 - nocand, deg, axis=1)  # per-candidate
            if keep_mask is not None:
                # filtered candidates ride the kernel's existing validity
                # lane — masked before the in-VMEM merge/select, zero extra
                # kernel passes
                valid = valid * jnp.take(
                    keep_mask, jnp.maximum(nbrs, 0), axis=0).astype(jnp.int32)
            bd, bi, bv, pick, nocand = cagra_hop(
                qf, bd, bi, bv, nbrs, vecs, valid, itopk, width,
                interpret=interpret)
            return bd, bi, bv, pick, nocand, it + 1

        with tracing.scope("cagra.search.hops"):
            qn = jnp.sum(qf * qf, axis=1, keepdims=True)
            P = 128
            bd = jnp.full((m, P), jnp.inf, jnp.float32
                          ).at[:, :itopk].set(
                              jnp.maximum(beam_d[:, :itopk] + qn, 0.0))
            bi = jnp.full((m, P), -1, jnp.int32).at[:, :itopk].set(
                beam_ids[:, :itopk])
            bv = jnp.ones((m, P), jnp.int32).at[:, :itopk].set(
                beam_visited[:, :itopk].astype(jnp.int32))
            # prime: candidates masked (valid=0) — the merge inserts nothing,
            # and the kernel emits the first hop's picks
            zero_nbrs = jnp.full((m, cw), -1, jnp.int32)
            zero_vecs = jnp.zeros((m, cw, d), data.dtype)
            bd, bi, bv, pick, nocand = cagra_hop(
                qf, bd, bi, bv, zero_nbrs, zero_vecs,
                jnp.zeros((m, cw), jnp.int32), itopk, width,
                interpret=interpret)

            bd, bi, bv, _, _, _ = lax.while_loop(
                fcond, fbody, (bd, bi, bv, pick, nocand, 0))
        with tracing.scope("cagra.search.select"):
            # the arena beam is unsorted — one final sort (the XLA path pays
            # a sort per hop; the arena pays it once here)
            from ..matrix.select_k import _select_k

            bd, bi = _select_k(bd, bi, itopk, True)
            out_d = jnp.maximum(bd[:, :k], 0.0)
            if sqrt_out:
                out_d = jnp.sqrt(out_d)
            # slots the (possibly filtered) beam never filled report the shared
            # empty-slot sentinel: id -1 with the +inf score already in place
            return out_d, jnp.where(jnp.isinf(out_d), -1, bi[:, :k])

    def cond(state):
        _, _, visited, it, done = state
        return jnp.logical_and(it < max_iter, jnp.logical_not(done))

    def body(state):
        ids, dists, visited, it, _ = state
        # pick the best `width` unvisited entries within the itopk window
        # (the all-converged early-exit check rides along: an r04 interleaved
        # A/B measured it free at m=10k — 28.5-31.0k QPS with vs 28.4-29.6k
        # without — so it stays unconditional)
        cand_d = jnp.where(visited[:, :itopk], jnp.inf, dists[:, :itopk])
        pick = jnp.argsort(cand_d, axis=1, stable=True)[:, :width]  # (m, w)
        pick_ids = jnp.take_along_axis(ids, pick, axis=1)  # (m, w)
        no_cand = jnp.all(jnp.isinf(jnp.take_along_axis(cand_d, pick, axis=1)), axis=1)
        visited = visited.at[jnp.arange(m)[:, None], pick].set(True)

        # expand: gather adjacency rows (ref: single-CTA graph row fetch)
        safe_pick = jnp.maximum(pick_ids, 0)
        nbrs = index.graph[safe_pick].reshape(m, exp_per_hop)  # (m, w*deg)
        nbrs = jnp.where(pick_ids.repeat(deg, axis=1) >= 0, nbrs, -1)
        ok = nbrs >= 0
        if keep_mask is not None:
            # candidate mask epilogue: filtered expansions score +inf before
            # the beam merge select (the ivf scan epilogue contract)
            ok = ok & jnp.take(keep_mask, jnp.maximum(nbrs, 0), axis=0)
        nd = jnp.where(ok, dist_to(qf, jnp.maximum(nbrs, 0)), jnp.inf)

        # merge expansions into the beam tail, re-sort, dedup
        ids = ids.at[:, itopk:].set(nbrs)
        dists = dists.at[:, itopk:].set(nd)
        visited = visited.at[:, itopk:].set(False)
        ids, dists, visited = dedup_sort(ids, dists, visited)

        done = jnp.all(no_cand)
        return ids, dists, visited, it + 1, done

    with tracing.scope("cagra.search.hops"):
        beam_ids, beam_d, beam_visited, _, _ = lax.while_loop(
            cond, body, (beam_ids, beam_d, beam_visited, 0, False)
        )

    with tracing.scope("cagra.search.select"):
        out_d = beam_d[:, :k] + jnp.sum(qf * qf, axis=1, keepdims=True)
        out_d = jnp.maximum(out_d, 0.0)
        if sqrt_out:
            out_d = jnp.sqrt(out_d)
        # slots the (possibly filtered) beam never filled report the shared
        # empty-slot sentinel: id -1 with the +inf score already in place
        return out_d, jnp.where(jnp.isinf(out_d), -1, beam_ids[:, :k])


def resolve_max_iterations(params: SearchParams) -> int:
    """Default hop budget (reference: adjust_search_params, cagra_search.cuh)."""
    return params.max_iterations or (
        params.itopk_size // max(params.search_width, 1) + 10)


def resolve_seed_pool(params: SearchParams, hint: int = 0) -> int:
    """seed_pool=-1 (auto) → the index's measured hint, else the r02 default.
    Shared by the single-chip and distributed drivers so -1 never leaks into
    seed_plan (where a negative pool would silently mean random entries)."""
    pool = int(params.seed_pool)
    if pool < 0:
        pool = int(hint) or 16384
    return pool


def resolve_hop_impl(params: SearchParams, graph_degree: int, dim: int,
                     itemsize: int = 4) -> str:
    """Validate + resolve ``params.hop_impl`` (shared by the single-chip and
    distributed searches — same eligibility rules, same clear errors).
    ``itemsize`` is the dataset element size: byte datasets stage a quarter
    of the candidate-block VMEM, widening fused eligibility at high d."""
    from ..ops.cagra_hop import hop_backend_ok, hop_shapes_eligible

    expects(params.hop_impl in ("auto", "xla", "fused_arena"),
            "hop_impl must be 'auto', 'xla' or 'fused_arena', got %r",
            params.hop_impl)
    eligible = (hop_backend_ok()[0] and hop_shapes_eligible(
        params.itopk_size, graph_degree, params.search_width, dim,
        itemsize=itemsize))
    if params.hop_impl == "auto":
        # the fused arena is the measured winner (r05 study):
        # 41-42k vs 32-33k XLA QPS at 1M itopk=32, identical 0.9714 recall
        # (1.27x in-process)
        return "fused_arena" if eligible else "xla"
    if params.hop_impl == "fused_arena":
        expects(eligible, "hop_impl='fused_arena' needs itopk + "
                "search_width*graph_degree <= 128, the staged candidate "
                "block (128*search_width*graph_degree*d_pad*itemsize bytes, "
                "double-buffered) within the kernel VMEM budget, and a TPU "
                "backend (or RAFT_TPU_CAGRA_HOP_INTERPRET=1 for tests); "
                "got itopk=%d width=%d degree=%d d=%d itemsize=%d",
                params.itopk_size, params.search_width, graph_degree, dim,
                itemsize)
    return params.hop_impl


@instrument(
    "cagra.search",
    items=lambda a, kw: nrows(a[2] if len(a) > 2 else kw["queries"]),
    labels=lambda a, kw: {"k": a[3] if len(a) > 3 else kw["k"],
                          "itopk": (a[0] if a else kw["params"]).itopk_size},
)
@auto_convert_output
def search(params: SearchParams, index: CagraIndex, queries, k: int,
           sample_filter=None, res: Resources | None = None):
    """Batch-synchronous beam search (reference: cagra::search,
    cagra_search.cuh:70; SINGLE_CTA persistent kernel re-shaped for SPMD).

    ``sample_filter`` is an optional
    :class:`~raft_tpu.neighbors.sample_filter.BitsetFilter` / boolean
    keep-mask over dataset rows — the same ``resolve_filter`` /
    ``validate_filter_covers`` contract as ivf_pq/ivf_flat: filtered
    candidates take +inf scores in the mask epilogue BEFORE the beam select,
    and slots the filtered beam cannot fill report id -1 with +inf distance.
    Filtered nodes are also not expanded (unlike FreshDiskANN's
    traverse-through-deletes), so at heavy filter ratios widen
    ``itopk_size`` to preserve recall."""
    from .brute_force import _coerce_queries
    from .sample_filter import resolve_filter, validate_filter_covers

    res = res or default_resources()
    queries = jnp.asarray(queries)
    expects(queries.ndim == 2 and queries.shape[1] == index.dim, "query dim mismatch")
    expects(k <= params.itopk_size, "k must be <= itopk_size (ref cagra_types.hpp:66)")
    # byte indexes: integer queries must match the index dtype and shift
    # with it; float queries against a uint8 index shift by -128 (same
    # contract as ivf_flat/ivf_pq)
    queries = _coerce_queries(index.data_kind, queries)
    itopk = params.itopk_size
    max_iter = resolve_max_iterations(params)
    sqrt_out = index.metric in (DistanceType.L2SqrtExpanded, DistanceType.L2SqrtUnexpanded)
    pool, n_init = seed_plan(
        index.size, itopk, params.search_width, index.graph_degree,
        resolve_seed_pool(params, index.seed_pool_hint))
    impl = resolve_hop_impl(params, index.graph_degree, index.dim,
                            itemsize=index.dataset.dtype.itemsize)
    keep_mask = resolve_filter(sample_filter)
    if keep_mask is not None:
        validate_filter_covers(index, keep_mask)
    seeds = _SEEDS.get(index.dataset, params.seed, pool, n_init)
    return _cagra_search(index, queries, seeds, int(k),
                         int(itopk), int(max_iter),
                         int(params.search_width), sqrt_out, impl,
                         keep_mask)


def write_index(f, index: CagraIndex) -> None:
    """Serialize to an open binary stream (the composable half of
    :func:`save` — :mod:`raft_tpu.stream` embeds sealed indexes this way)."""
    serialize_header(f, "cagra")
    serialize_scalar(f, int(index.metric))
    serialize_scalar(f, int(index.seed_pool_hint))
    serialize_scalar(f, index.data_kind)
    serialize_mdspan(f, index.dataset)
    serialize_mdspan(f, index.graph)
    serialize_tuned(f, index.tuned)


def read_index(f) -> CagraIndex:
    """Deserialize from an open binary stream (pairs with
    :func:`write_index`)."""
    ver = check_header(f, "cagra")
    metric = DistanceType(deserialize_scalar(f))
    # raft_tpu/4 added the measured seed_pool_hint; older files search
    # with the default pool (correct, just not data-tuned)
    hint = deserialize_scalar(f) if ver not in (
        "raft_tpu/2", "raft_tpu/3") else 0
    # raft_tpu/6 added data_kind (byte datasets); older files could
    # only hold float data
    kind = deserialize_scalar(f) if ver not in (
        "raft_tpu/2", "raft_tpu/3", "raft_tpu/4", "raft_tpu/5") else "float32"
    dataset = jnp.asarray(deserialize_mdspan(f))
    graph = jnp.asarray(deserialize_mdspan(f))
    # raft_tpu/9 appended the optional tuned record (pinned operating
    # point); older files are untuned
    tuned = deserialize_tuned(f, ver)
    return CagraIndex(dataset=dataset, graph=graph, metric=metric,
                      data_kind=kind, seed_pool_hint=hint, tuned=tuned)


def save(index: CagraIndex, path: str) -> None:
    """Serialize (reference: cagra_serialize.cuh).
    Atomic: temp file + rename, a crashed save keeps the previous file."""
    from ..core.serialize import atomic_write

    with atomic_write(path) as f:
        write_index(f, index)


def load(path: str, res: Resources | None = None) -> CagraIndex:
    with open(path, "rb") as f:
        return read_index(f)


def batched_searcher(index: CagraIndex, params: SearchParams | None = None):
    """Stable serving hook (raft_tpu.serve; contract in :mod:`._hooks`) —
    the surface the serve registry warms and hot-swaps through. The serving
    ``k`` must satisfy ``k <= itopk_size`` (search()'s own precondition).
    With no explicit ``params``, an attached tune decision (``index.tuned``,
    e.g. restored by a raft_tpu/9 load) supplies the pinned operating
    point — docs/tuning.md."""
    from ._hooks import make_hook

    if params is None and index.tuned is not None:
        from ..tune.apply import make_searcher as tuned_searcher

        return tuned_searcher(index, True, degrade_without_rows=True)
    sp = params or SearchParams()
    return make_hook(lambda queries, k: search(sp, index, queries, k),
                     "cagra", index.dim, index.data_kind)

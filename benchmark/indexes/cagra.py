"""CAGRA served as a ``MutableIndex`` over the built graph, as the exact
cell serves brute force. The search settings the configuration leaves at
the library's auto values (seed pool, hops, hop loop) are printed to
standard error as they resolved."""

from __future__ import annotations

import sys
import time


def build(cfg: dict, corpus, corpus_host) -> dict:
    import jax

    from raft_tpu.neighbors import cagra
    from raft_tpu.stream import MutableIndex

    ix = cfg["index"]
    params = cagra.IndexParams(
        graph_degree=ix["graph_degree"],
        intermediate_graph_degree=ix["intermediate_graph_degree"],
        seed=ix["build_seed"])
    sp = cagra.SearchParams(itopk_size=ix["itopk_size"],
                            search_width=ix["search_width"])
    t0 = time.perf_counter()
    idx = cagra.build(params, corpus)
    jax.block_until_ready(idx.graph)
    build_s = time.perf_counter() - t0
    # no compaction in a read-only cell: keep no host copy of the rows
    mut = MutableIndex(idx, search_params=sp, retain_vectors=False,
                       name=cfg["name"])
    pool = min(cagra.resolve_seed_pool(sp, idx.seed_pool_hint), idx.size)
    hop_impl = cagra.resolve_hop_impl(sp, idx.graph_degree, idx.dim,
                                      idx.dataset.dtype.itemsize)
    print(f"cagra: seed_pool {pool}, max_iterations "
          f"{cagra.resolve_max_iterations(sp)}, hop_impl {hop_impl}, "
          f"build_s {build_s:.1f}", file=sys.stderr, flush=True)
    return {"hook": mut.searcher(), "build_s": build_s, "work": {}}


def control(cfg: dict, corpus) -> dict:
    """The reference, computed in bfloat16, in the program's place: the
    configuration serves float32 distances, and the program's graph search
    has no lower-precision scoring of its own."""
    from raft_tpu.neighbors._hooks import make_hook

    from benchmark.reference import exact_l2

    return {"hook": make_hook(exact_l2.bf16_search(corpus), "control/bf16",
                              cfg["data"]["dim"])}

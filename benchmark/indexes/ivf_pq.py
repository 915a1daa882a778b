"""IVF-PQ served as ``MutableIndex.refined_searcher`` (the operating point
PR 21 proved on the chip): build, wrap, and the numbers the roofline's work
count reads from the built index."""

from __future__ import annotations

import time

import numpy as np


def build(cfg: dict, corpus, corpus_host) -> dict:
    import jax

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.stream import MutableIndex

    ix = cfg["index"]
    params = ivf_pq.IndexParams(n_lists=ix["n_lists"], pq_bits=ix["pq_bits"],
                                pq_dim=ix["pq_dim"], seed=ix["build_seed"])
    sp = ivf_pq.SearchParams(n_probes=ix["n_probes"],
                             lut_dtype=ix["lut_dtype"])
    t0 = time.perf_counter()
    idx = ivf_pq.build(params, corpus)
    jax.block_until_ready(idx)
    build_s = time.perf_counter() - t0
    mut = MutableIndex(idx, search_params=sp, index_params=params,
                       dataset=corpus_host(), name=cfg["name"])
    work = {"centers": np.asarray(idx.centers, np.float32),
            "list_sizes": np.asarray(idx.list_sizes, np.int64)}
    return {"hook": mut.refined_searcher(ix["refine_ratio"]),
            "build_s": build_s, "work": work}


def control(cfg: dict, corpus) -> dict:
    """The reference, computed in bfloat16, in the program's place: the
    configuration serves float32 distances (exact refine), and the program
    has no lower-precision refine of its own."""
    from raft_tpu.neighbors._hooks import make_hook

    from benchmark.reference import exact_l2

    return {"hook": make_hook(exact_l2.bf16_search(corpus), "control/bf16",
                              cfg["data"]["dim"])}

"""IVF-PQ over a uint8 corpus, served as ``MutableIndex.refined_searcher``:
the byte twin of ``indexes/ivf_pq.py``.

A byte deployment at BIGANN scale builds from its ``.u8bin`` file, not
from a device copy of the corpus: the build reads the host copy through
``chunked.ChunkedReader``, so the device holds the index and two staged
chunks, never a float32 working copy of the corpus. The wrap keeps the
host uint8 rows as its refine store (``dataset=``), which the refine
epilogue holds on the device as uint8."""

from __future__ import annotations

import time

import numpy as np


def build(cfg: dict, corpus, corpus_host) -> dict:
    import jax

    from raft_tpu.core import chunked
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.stream import MutableIndex

    ix = cfg["index"]
    host = corpus_host()
    params = ivf_pq.IndexParams(
        n_lists=ix["n_lists"], pq_bits=ix["pq_bits"], pq_dim=ix["pq_dim"],
        kmeans_trainset_fraction=ix["kmeans_trainset_fraction"],
        seed=ix["build_seed"])
    sp = ivf_pq.SearchParams(n_probes=ix["n_probes"],
                             lut_dtype=ix["lut_dtype"])
    t0 = time.perf_counter()
    idx = ivf_pq.build(params, chunked.ChunkedReader(host))
    jax.block_until_ready(idx)
    build_s = time.perf_counter() - t0
    mut = MutableIndex(idx, search_params=sp, index_params=params,
                       dataset=host, name=cfg["name"])
    work = {"centers": np.asarray(idx.centers, np.float32),
            "list_sizes": np.asarray(idx.list_sizes, np.int64)}
    return {"hook": mut.refined_searcher(ix["refine_ratio"]),
            "build_s": build_s, "work": work}


def control(cfg: dict, corpus) -> dict:
    """The byte reference with its distances rounded to bfloat16, in the
    program's place: the configuration serves exact distances (the refine
    of uint8 rows is exact in float32), and the program has no
    lower-precision refine of its own."""
    from raft_tpu.neighbors._hooks import make_hook

    from benchmark.reference import exact_l2_bytes

    return {"hook": make_hook(exact_l2_bytes.bf16_rounded_search(corpus),
                              "control/bf16", cfg["data"]["dim"], "uint8")}

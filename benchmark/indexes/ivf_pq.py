"""IVF-PQ served as ``MutableIndex.refined_searcher`` (the operating point
PR 21 proved on the chip): build, wrap, and the numbers the roofline's work
count reads from the built index.

A configuration with a ``stream`` block builds over all rows but the last
``held_out`` (which the traffic's writer inserts), wraps with its
``delta_capacity``, and warms the delta's bucket ladder at the serving
batch, so no write compiles in the window."""

from __future__ import annotations

import time

import numpy as np


def build(cfg: dict, corpus, corpus_host) -> dict:
    import jax

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.stream import MutableIndex

    ix = cfg["index"]
    stream = cfg.get("stream")
    wrap = {}
    if stream is not None:
        n = cfg["data"]["rows"] - int(stream["held_out"])
        corpus = corpus[:n]
        host = corpus_host()[:n]
        wrap["delta_capacity"] = int(stream["delta_capacity"])
    else:
        host = corpus_host()
    params = ivf_pq.IndexParams(n_lists=ix["n_lists"], pq_bits=ix["pq_bits"],
                                pq_dim=ix["pq_dim"], seed=ix["build_seed"])
    sp = ivf_pq.SearchParams(n_probes=ix["n_probes"],
                             lut_dtype=ix["lut_dtype"])
    t0 = time.perf_counter()
    idx = ivf_pq.build(params, corpus)
    jax.block_until_ready(idx)
    build_s = time.perf_counter() - t0
    mut = MutableIndex(idx, search_params=sp, index_params=params,
                       dataset=host, name=cfg["name"], **wrap)
    if stream is not None:
        mut.warm([cfg["serve"]["max_batch"]], ks=(cfg["k"],))
    work = {"centers": np.asarray(idx.centers, np.float32),
            "list_sizes": np.asarray(idx.list_sizes, np.int64)}
    return {"hook": mut.refined_searcher(ix["refine_ratio"]),
            "build_s": build_s, "work": work}


def control(cfg: dict, corpus) -> dict:
    """The reference, computed in bfloat16, in the program's place: the
    configuration serves float32 distances (exact refine), and the program
    has no lower-precision refine of its own. With a ``stream`` block the
    control is the bf16 reference over the live set, which takes the
    writes."""
    from raft_tpu.neighbors._hooks import make_hook

    from benchmark.reference import exact_l2, exact_l2_live

    dim = cfg["data"]["dim"]
    stream = cfg.get("stream")
    if stream is None:
        return {"hook": make_hook(exact_l2.bf16_search(corpus),
                                  "control/bf16", dim)}
    live = exact_l2_live.Bf16Live(
        corpus, cfg["data"]["rows"] - int(stream["held_out"]))
    hook = make_hook(live.search, "control/bf16-live", dim)
    hook.mutable = live
    return {"hook": hook}

"""Exact search served as a ``MutableIndex`` over a brute-force index: the
sealed scan is ``brute_force.knn``, i.e. the fused Pallas kernel
(``ops/fused_knn.py``) on the chip."""

from __future__ import annotations

import time


def build(cfg: dict, corpus, corpus_host) -> dict:
    import jax

    from raft_tpu.neighbors import brute_force
    from raft_tpu.stream import MutableIndex

    t0 = time.perf_counter()
    bf = brute_force.BruteForce(metric=cfg["data"]["metric"]).build(corpus)
    jax.block_until_ready(bf.dataset)
    build_s = time.perf_counter() - t0
    # no compaction in a read-only cell: keep no host copy of the rows
    mut = MutableIndex(bf, retain_vectors=False, name=cfg["name"])
    return {"hook": mut.searcher(), "build_s": build_s, "work": {}}


def control(cfg: dict, corpus) -> dict:
    """The program's own lower-precision path switched on: the exact scan
    with ``compute="bfloat16"`` (the configuration states float32)."""
    from raft_tpu.neighbors import brute_force
    from raft_tpu.neighbors._hooks import make_hook

    metric = cfg["data"]["metric"]
    return {"hook": make_hook(
        lambda q, k: brute_force.knn(corpus, q, k, metric,
                                     compute="bfloat16"),
        "control/brute_force_bf16", cfg["data"]["dim"])}

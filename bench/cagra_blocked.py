"""CAGRA graph-blocked layout experiment (VERDICT r2 #6).

Hypothesis (r02): hops are latency-bound row gathers; reordering
dataset rows so graph neighbors fall in shared blocks (coarse-cluster order)
turns the per-hop (m, width*deg) row gather into a friendlier DMA pattern.

Method: build ONE 1M CAGRA index, then measure search QPS on (a) the index
as built, (b) the same index with rows permuted into cluster-sorted order and
the graph relabeled (identical graph structure -> identical recall, so any
QPS delta is pure memory-layout effect). Run on real TPU.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
from raft_tpu.config import enable_compilation_cache
from raft_tpu.neighbors import cagra
from raft_tpu.neighbors._list_utils import assign_to_lists
from raft_tpu.distance.types import DistanceType


def make_1m():
    n, d, m, ncl = 1_000_000, 128, 10_000, 2000
    kc, kl, kn, kq1, kq2, kq3 = jax.random.split(jax.random.key(42), 6)
    centers = jax.random.uniform(kc, (ncl, d), jnp.float32) * 10.0

    def draw(kk_lab, kk_noise, count):
        labels = jax.random.randint(kk_lab, (count,), 0, ncl)
        return centers[labels] + 0.5 * jax.random.normal(kk_noise, (count, d))

    dataset = draw(kl, kn, n)
    qsets = []
    for kk in (kq1, kq2, kq3):
        ka, kb = jax.random.split(kk)
        qsets.append(draw(ka, kb, m))
    return dataset, qsets


def measure(idx, qsets, sp, k=10):
    out = None
    best = float("inf")
    _ = np.asarray(cagra.search(sp, idx, qsets[0], k)[1])  # warm
    for qs in qsets[1:]:
        t0 = time.perf_counter()
        out = cagra.search(sp, idx, qs, k)
        np.asarray(out[1])
        best = min(best, time.perf_counter() - t0)
    return qsets[0].shape[0] / best, out


def recall(ids, gt):
    ids = np.asarray(ids)
    return float(np.mean([len(set(ids[r, :10]) & set(gt[r])) / 10
                          for r in range(gt.shape[0])]))


def main():
    enable_compilation_cache()
    print("dataset...", flush=True)
    dataset, qsets = make_1m()
    jax.block_until_ready([dataset] + qsets)

    from raft_tpu.neighbors.brute_force import _bf_knn_fused

    _, gt = _bf_knn_fused(dataset, qsets[-1][:1000], 10,
                          DistanceType.L2Expanded, "float32", None)
    gt = np.asarray(gt)

    print("build...", flush=True)
    t0 = time.perf_counter()
    idx = cagra.build(cagra.IndexParams(), dataset)
    jax.block_until_ready(idx.graph)
    print(f"build {time.perf_counter() - t0:.1f}s", flush=True)

    sp = cagra.SearchParams(itopk_size=32)
    qps, out = measure(idx, qsets, sp)
    print(f"baseline       qps={qps:9.1f} recall={recall(out[1][:1000], gt):.4f}",
          flush=True)

    # --- blocked layout: rows sorted by coarse cluster ---
    print("cluster + permute...", flush=True)
    kb = KMeansBalancedParams(n_iters=10, seed=0, max_train_points=200_000)
    centers = kmeans_balanced.fit(kb, dataset, 1024)
    labels = assign_to_lists(dataset, centers, DistanceType.L2Expanded, 4096)
    perm = jnp.argsort(labels, stable=True)          # new_row -> old_row
    inv = jnp.zeros_like(perm).at[perm].set(jnp.arange(perm.shape[0]))
    data_p = jnp.take(dataset, perm, axis=0)
    graph_p = jnp.take(inv.astype(jnp.int32),
                       jnp.take(idx.graph, perm, axis=0), axis=0)
    idx_p = cagra.CagraIndex(dataset=data_p, graph=graph_p, metric=idx.metric)
    jax.block_until_ready(idx_p.graph)

    qps_p, out_p = measure(idx_p, qsets, sp)
    ids_back = jnp.take(perm, jnp.maximum(out_p[1], 0))[:1000]
    print(f"cluster-sorted qps={qps_p:9.1f} recall={recall(ids_back, gt):.4f}",
          flush=True)
    print(f"delta: {qps_p / qps - 1:+.1%}", flush=True)


if __name__ == "__main__":
    main()

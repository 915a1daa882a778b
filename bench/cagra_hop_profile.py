"""In-kernel per-phase profile of the fused CAGRA hop at 1M (VERDICT r4 #1
done-bar: the negative-result evidence must localize the kernel's own cost —
scoring vs dedup vs merge vs the XLA-side gathers).

Variants (one process, interleaved):
  full       the shipping fused hop
  nodedup    beam-membership masks skipped
  nomerge    dedup+extraction skipped (beam passes through; pick still runs)
  noscore    distance computation skipped (gathers still happen)
  nogate     arena merges only: insertion loop UNGATED (full-vs-nogate =
             the threshold gate's measured worth; r06 residual carve)
  gatheronly no kernel at all — the while_loop + two gathers + trivial ops

``--merge`` profiles a specific merge impl (extract | arena | arena_smem) —
the r06 residual attack carves the ARENA loop, the r05 study carved extract.

Run on the TPU host:  python bench/cagra_hop_profile.py [--rounds 3]
                      python bench/cagra_hop_profile.py --merge arena
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--itopk", type=int, default=32)
    ap.add_argument("--merge", default="extract",
                    choices=["extract", "arena", "arena_smem"])
    args = ap.parse_args()

    from raft_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import bench as drv
    from raft_tpu.neighbors import cagra
    from raft_tpu.ops.cagra_hop import cagra_hop

    print(f"backend: {jax.default_backend()}", file=sys.stderr)
    dataset, qsets = drv._make_1m()
    jax.block_until_ready([dataset] + qsets)
    idx = cagra.build(cagra.IndexParams(), dataset)
    jax.block_until_ready(idx.graph)
    print("build done", file=sys.stderr)

    itopk = args.itopk
    deg = idx.graph_degree
    n, d = idx.dataset.shape
    max_iter = itopk + 10
    m = qsets[0].shape[0]

    @jax.jit
    def init_state(queries, key, data):
        qf = queries.astype(jnp.float32)
        dn2 = jnp.sum(data.astype(jnp.float32) ** 2, axis=1)
        pool_ids = jax.random.choice(key, n, (16384,), replace=False).astype(jnp.int32)
        pool_vecs = data[pool_ids].astype(jnp.float32)
        pool_d = dn2[pool_ids][None, :] - 2.0 * qf @ pool_vecs.T
        _, best = lax.top_k(-pool_d, itopk)
        init_ids = pool_ids[best]
        vecs0 = data[init_ids]
        init_d = jnp.sum((vecs0 - qf[:, None, :]) ** 2, axis=-1)
        order = jnp.argsort(init_d, axis=1)
        bd = jnp.full((m, 128), jnp.inf, jnp.float32
                      ).at[:, :itopk].set(jnp.take_along_axis(init_d, order, 1))
        bi = jnp.full((m, 128), -1, jnp.int32
                      ).at[:, :itopk].set(jnp.take_along_axis(init_ids, order, 1))
        bv = jnp.ones((m, 128), jnp.int32).at[:, :itopk].set(0)
        return qf, bd, bi, bv

    merge = args.merge

    @functools.partial(jax.jit, static_argnames=("profile",))
    def run(state, data, graph, profile):
        qf, bd, bi, bv = state

        if profile == "gatheronly":
            def body(state):
                bd, bi, bv, pick, nocand, it = state
                nbrs = graph[pick[:, 0]]
                vecs = data[jnp.maximum(nbrs, 0)].astype(jnp.float32)
                # trivial consumption standing in for the kernel
                s = jnp.sum(vecs, axis=(1, 2), keepdims=False)[:, None]
                pick = (pick + nbrs[:, :1] + (s > 0)) % n
                return bd, bi, bv, pick, nocand, it + 1

            st = (bd, bi, bv, jnp.zeros((m, 1), jnp.int32),
                  jnp.zeros((m, 1), jnp.int32), 0)
            bd, bi, *_ = lax.while_loop(
                lambda s: s[-1] < max_iter, body, st)
            return bd[:, :10], bi[:, :10]

        zero_nbrs = jnp.full((m, deg), -1, jnp.int32)
        zero_vecs = jnp.zeros((m, deg, d), jnp.float32)
        bd, bi, bv, pick, nocand = cagra_hop(
            qf, bd, bi, bv, zero_nbrs, zero_vecs,
            jnp.zeros((m, deg), jnp.int32), itopk, width=1, profile=profile,
            merge=merge)

        def body(state):
            bd, bi, bv, pick, nocand, it = state
            nbrs = graph[jnp.minimum(pick[:, 0], n - 1)]
            vecs = data[jnp.maximum(nbrs, 0)].astype(jnp.float32)
            valid = jnp.repeat(1 - nocand, deg, axis=1)
            bd, bi, bv, pick, nocand = cagra_hop(
                qf, bd, bi, bv, nbrs, vecs, valid, itopk, width=1,
                profile=profile, merge=merge)
            return bd, bi, bv, pick, nocand, it + 1

        bd, bi, *_ = lax.while_loop(
            lambda s: jnp.logical_and(s[-1] < max_iter,
                                      jnp.logical_not(jnp.all(s[-2] > 0))),
            body, (bd, bi, bv, pick, nocand, 0))
        return bd[:, :10], bi[:, :10]

    variants = ["full", "nodedup", "nomerge", "noscore", "gatheronly"]
    if merge in ("arena", "arena_smem"):
        # the arena folds dedup into insertion, so nodedup is meaningless;
        # nogate prices the threshold gate instead
        variants = ["full", "nogate", "nomerge", "noscore", "gatheronly"]
    key = jax.random.key(0)
    states = [init_state(qs, key, idx.dataset) for qs in qsets]
    jax.block_until_ready(states)
    print("init states ready", file=sys.stderr)
    live = []
    for v in variants:
        try:  # compile+warm; isolate compile failures per variant
            t0 = time.perf_counter()
            jax.block_until_ready(run(states[0], idx.dataset, idx.graph, v))
            print(f"{v} compiled in {time.perf_counter()-t0:.0f}s",
                  file=sys.stderr)
            live.append(v)
        except Exception as e:
            print(f"{v} FAILED to compile/run: {str(e)[:160]}",
                  file=sys.stderr)
    times = {v: [] for v in live}
    for r in range(args.rounds):
        for v in live:
            best = float("inf")
            for st in states[1:]:
                t0 = time.perf_counter()
                jax.block_until_ready(run(st, idx.dataset, idx.graph, v))
                best = min(best, time.perf_counter() - t0)
            times[v].append(m / best)
    for v in live:
        print(f"{v:11s} QPS {[f'{x/1e3:.1f}k' for x in times[v]]}")


if __name__ == "__main__":
    main()

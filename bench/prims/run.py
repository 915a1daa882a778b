#!/usr/bin/env python
"""raft_tpu primitive micro-benchmarks.

Counterpart of the reference's google-benchmark prim suite
(cpp/bench/prims/{distance,matrix,cluster,neighbors}/ — e.g.
distance/distance_exp_l2.cu, matrix/select_k.cu, cluster/kmeans.cu). Each
case reports wall ms and achieved GB/s or GFLOP/s.

Timing protocol (see docs/ann_benchmarks.md "Measurement honesty"): every
iteration gets distinct input slices, iterations are chained inside one XLA
program via lax.map, and the output is materialized to host, so the timing
cannot end before the device finished.

Usage: python bench/prims/run.py [--filter substr] [--iters N]
"""

from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

if os.environ.get("JAX_PLATFORMS"):
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])


def measure(make_fn, batches, iters: int):
    """make_fn() -> jitted fn over stacked batches; returns s/iter."""
    import jax
    import numpy as np

    f = make_fn()
    np.asarray(jax.tree_util.tree_leaves(f(batches[0]))[0])  # compile+warm
    best = float("inf")
    for b in batches[1:]:
        t0 = time.perf_counter()
        np.asarray(jax.tree_util.tree_leaves(f(b))[0])
        best = min(best, time.perf_counter() - t0)
    return best / iters


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--filter", default="")
    ap.add_argument("--iters", type=int, default=4, help="chained iterations per timing call")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    rng = np.random.default_rng(0)
    iters = args.iters
    rows = []

    def bench(name, make_fn, batches, work, unit, n_iters=None):
        if args.filter and args.filter not in name:
            return
        sec = measure(make_fn, batches, n_iters or iters)
        rate = work / sec / 1e9
        rows.append((name, sec * 1e3, rate, unit))
        print(f"{name:42s} {sec*1e3:9.2f} ms   {rate:9.1f} {unit}")

    # ---- pairwise distance (ref: distance_exp_l2.cu) ----
    m, n, d = 4096, 4096, 128
    for metric in ("sqeuclidean", "cosine", "l1"):
        from raft_tpu.distance.pairwise import _pairwise
        from raft_tpu.distance.types import resolve_metric

        mt = resolve_metric(metric)
        xs = [jnp.asarray(rng.random((iters, m, d), np.float32)) for _ in range(3)]
        y = jnp.asarray(rng.random((n, d), np.float32))

        def mk(mt=mt):
            def one(x):
                return jnp.sum(_pairwise(x, y, mt, 2.0, 1024))
            return jax.jit(lambda xb: lax.map(one, xb))

        bench(f"pairwise_distance/{metric} {m}x{n}x{d}", mk, xs,
              iters * 2.0 * m * n * d, "GFLOP/s")

    # ---- fused L2 1-NN (ref: distance/fused_l2_nn.cu) ----
    from raft_tpu.distance.fused_nn import _fused_l2_nn

    k_centers = 1024
    c = jnp.asarray(rng.random((k_centers, d), np.float32))
    xs = [jnp.asarray(rng.random((iters, m, d), np.float32)) for _ in range(3)]

    def mk_fnn():
        def one(x):
            return _fused_l2_nn(x, c, False, 2048)[1]
        return jax.jit(lambda xb: lax.map(one, xb))

    bench(f"fused_l2_nn {m}x{k_centers}x{d}", mk_fnn, xs,
          iters * 2.0 * m * k_centers * d, "GFLOP/s")

    # ---- select_k (ref: matrix/select_k.cu) ----
    from raft_tpu.matrix.select_k import _select_k

    for nn_cols, kk in ((16384, 64), (65536, 10)):
        xs = [jnp.asarray(rng.random((iters, 512, nn_cols), np.float32)) for _ in range(3)]

        def mk_sel(kk=kk):
            def one(x):
                return _select_k(x, None, kk, True)
            return jax.jit(lambda xb: lax.map(one, xb))

        bench(f"select_k n={nn_cols} k={kk} rows=512", mk_sel, xs,
              iters * 512 * nn_cols * 4, "GB/s")

    # ---- kmeans one Lloyd step (ref: cluster/kmeans.cu) ----
    from raft_tpu.cluster.kmeans import _assign, _update

    kc = 256
    xs = [jnp.asarray(rng.random((iters, 65536, 64), np.float32)) for _ in range(3)]
    c0 = jnp.asarray(rng.random((kc, 64), np.float32))

    def mk_km():
        def one(x):
            _, labels = _assign(x, c0, 8192)
            sums, counts = _update(x, labels, None, kc)
            return sums
        return jax.jit(lambda xb: lax.map(one, xb))

    bench(f"kmeans_lloyd_step 65536x64 k={kc}", mk_km, xs,
          iters * 2.0 * 65536 * kc * 64 * 2, "GFLOP/s")

    # ---- brute-force knn (ref: neighbors/knn.cuh) ----
    from raft_tpu.neighbors.brute_force import _bf_knn
    from raft_tpu.distance.types import DistanceType

    ds = jnp.asarray(rng.random((100_000, 128), np.float32))
    xs = [jnp.asarray(rng.random((iters, 2000, 128), np.float32)) for _ in range(3)]

    def mk_knn():
        def one(q):
            return _bf_knn(ds, q, 10, DistanceType.L2Expanded, 2.0, 1000, 1000)[1]
        return jax.jit(lambda xb: lax.map(one, xb))

    bench("bf_knn 100k x 128, q=2000, k=10", mk_knn, xs,
          iters * 2.0 * 2000 * 100_000 * 128, "GFLOP/s")

    # ---- sparse prims at scale (VERDICT r4 #9; ref: bench/prims/sparse/) --
    # sparse pairwise L2: 4096-query tiles vs a 100k x 10k, ~1% density CSR
    # dataset — exercises the ELL-densify-per-tile path at real width
    sp_name = "sparse_l2 4096x100000 d=10000 nnz/row=100"
    if not args.filter or args.filter in sp_name:
        from raft_tpu.sparse.types import make_csr
        from raft_tpu.sparse import distance as spdist

        n_rows, n_cols, nnz_row = 100_000, 10_000, 100
        qrows = 4096
        # ~1% density: exactly nnz_row nonzeros per row (ELL-friendly,
        # matches the reference's uniform-density sparse bench inputs)
        idxs = rng.integers(0, n_cols, (n_rows, nnz_row)).astype(np.int32)
        vals = rng.random((n_rows, nnz_row)).astype(np.float32)
        indptr = np.arange(n_rows + 1, dtype=np.int32) * nnz_row
        y_csr = make_csr(jnp.asarray(indptr), jnp.asarray(idxs.reshape(-1)),
                         jnp.asarray(vals.reshape(-1)),
                         (n_rows, n_cols))
        qi = rng.integers(0, n_cols, (qrows, nnz_row)).astype(np.int32)
        qv = [jnp.asarray(rng.random((qrows, nnz_row), np.float32))
              for _ in range(3)]
        q_indptr = jnp.asarray(
            np.arange(qrows + 1, dtype=np.int32) * nnz_row)
        qi_flat = jnp.asarray(qi.reshape(-1))

        def mk_sp():
            # NOT jitted: pairwise_distance is host-orchestrated (it sizes
            # the ELL width from data-dependent degrees) and jits its tiles
            # internally — wrapping it would trip a ConcretizationTypeError
            def one(qvals):
                x_csr = make_csr(q_indptr, qi_flat, qvals.reshape(-1),
                                 (qrows, n_cols))
                return spdist.pairwise_distance(x_csr, y_csr,
                                                metric="sqeuclidean")
            return one

        # one (qrows, n_rows) distance block per call (no iters chaining);
        # work ~ dense-equivalent GEMM
        bench(sp_name, mk_sp, qv, 2.0 * qrows * n_rows * n_cols,
              "GFLOP/s(dense-eq)", n_iters=1)

    # Boruvka MST on a 1M-edge random graph (ref: sparse/mst.cu)
    mst_name = "mst 200000v 1000000e"
    if not args.filter or args.filter in mst_name:
        from raft_tpu.solver.mst import mst
        from raft_tpu.sparse.types import make_coo

        n_v, n_e = 200_000, 1_000_000
        mst_batches = []
        for s in range(3):
            r2 = np.random.default_rng(s)
            # connected-ish: a random spanning chain + random extra edges
            chain_r = np.arange(n_v - 1, dtype=np.int32)
            chain_c = chain_r + 1
            er = r2.integers(0, n_v, n_e - (n_v - 1)).astype(np.int32)
            ec = r2.integers(0, n_v, n_e - (n_v - 1)).astype(np.int32)
            rr = np.concatenate([chain_r, er])
            cc = np.concatenate([chain_c, ec])
            ww = r2.random(n_e).astype(np.float32)
            mst_batches.append(make_coo(jnp.asarray(rr), jnp.asarray(cc),
                                        jnp.asarray(ww), (n_v, n_v)))

        def mk_mst():
            return jax.jit(lambda g: mst(g).weights)

        # rate unit is Medges/s: pass work = edges * 1e3 so bench()'s /1e9
        # yields Medges/s in-place
        bench(mst_name, mk_mst, mst_batches, n_e * 1e3, "Medges/s",
              n_iters=1)

    # Lanczos k=8 on a 100k-node graph Laplacian (ref: sparse/lanczos.cu)
    lz_name = "lanczos k=8 laplacian 100000v"
    if not args.filter or args.filter in lz_name:
        from raft_tpu.solver.lanczos import eigsh
        from raft_tpu.sparse.linalg import laplacian
        from raft_tpu.sparse.types import make_coo
        from raft_tpu.sparse.convert import coo_to_csr

        n_v, n_e = 100_000, 1_000_000
        lz_batches = []
        for s in range(3):
            r2 = np.random.default_rng(10 + s)
            rr = r2.integers(0, n_v, n_e).astype(np.int32)
            cc = r2.integers(0, n_v, n_e).astype(np.int32)
            ww = np.abs(r2.random(n_e)).astype(np.float32)
            # symmetrize by doubling (rows+cols swapped)
            coo = make_coo(jnp.asarray(np.concatenate([rr, cc])),
                           jnp.asarray(np.concatenate([cc, rr])),
                           jnp.asarray(np.concatenate([ww, ww])),
                           (n_v, n_v))
            lz_batches.append(coo_to_csr(coo))

        def mk_lz():
            def one(csr):
                lap = laplacian(csr)
                vals, _, _ = eigsh(lap, k=8, max_iter=200, seed=0)
                return vals
            return jax.jit(one)

        bench(lz_name, mk_lz, lz_batches, 2 * n_e * 200, "Gnnz-mv/s",
              n_iters=1)

    return 0


if __name__ == "__main__":
    sys.exit(main())

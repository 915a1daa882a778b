#!/usr/bin/env python
"""BASELINE config 2: k-means fit on make_blobs(1M x 128), k=1024, one chip.

Counterpart of the reference's cluster bench (cpp/bench/prims/cluster/kmeans.cu)
at the 1M x 128, k=1024 operating point. Reports fit wall time (excluding
the first-call compile, which is timed separately), per-iteration time, and
inertia parity against the generating blob centers (the inertia of labeling
every point by its true generator is the achievable floor; a correct Lloyd
run from kmeans++ lands within a few percent of it).

Since r07 the bench ALSO measures the balanced coarse trainer — the k-means
that actually runs inside every IVF build — which now defaults to mini-batch
EM at this scale (KMeansBalancedParams.train_mode="auto": rotating 65536-row
batches, one closing full pass; the Round-6-measured ~22 full-dataset
assignment passes are gone). ``--full-em`` pins the pre-r07 full-EM behavior
for the A/B; the drift test asserting the new defaults lives in
tests/test_kmeans.py::test_params_defaults_drift.

Usage: python bench/kmeans_1m.py [--n 1000000] [--k 1024] [--iters 20]
       [--full-em] [--skip-lloyd]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

if os.environ.get("JAX_PLATFORMS"):
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--k", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--full-em", action="store_true",
                    help="pin the balanced trainer to the pre-r07 full-EM "
                         "path (train_mode='full') for the A/B")
    ap.add_argument("--batch-rows", type=int, default=65536,
                    help="mini-batch rows for the balanced trainer")
    ap.add_argument("--skip-lloyd", action="store_true",
                    help="skip the plain-Lloyd BASELINE table-2 measurement")
    args = ap.parse_args()

    import jax
    import numpy as np

    from raft_tpu.cluster import kmeans, kmeans_balanced
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
    from raft_tpu.random import make_blobs

    rng = np.random.default_rng(0)
    true_centers = rng.uniform(-10.0, 10.0, (args.k, args.d)).astype(np.float32)
    x, _ = make_blobs(args.n, args.d, centers=true_centers, cluster_std=1.0, seed=0)
    jax.block_until_ready(x)

    # inertia floor: cost of the generating centers
    floor = float(kmeans.cluster_cost(x, true_centers))
    out = {}

    if not args.skip_lloyd:
        params = kmeans.KMeansParams(
            n_clusters=args.k, max_iter=args.iters, tol=0.0, init="kmeans++", seed=0
        )

        t0 = time.perf_counter()
        res = kmeans.fit(params, x)
        np.asarray(res.centroids)
        first = time.perf_counter() - t0

        t0 = time.perf_counter()
        res = kmeans.fit(params, x)
        np.asarray(res.centroids)
        fit_s = time.perf_counter() - t0

        out.update({
            "metric": f"kmeans fit {args.n}x{args.d} k={args.k} ({args.iters} iters)",
            "fit_s": round(fit_s, 2),
            "first_call_s": round(first, 2),
            "s_per_iter": round(fit_s / max(int(res.n_iter), 1), 3),
            "n_iter": int(res.n_iter),
            "inertia": float(res.inertia),
            "inertia_floor": floor,
            "inertia_ratio": round(float(res.inertia) / floor, 4) if floor else None,
        })

    # -- balanced coarse trainer (the IVF-build path; minibatch default) ----
    mode = "full" if args.full_em else "auto"
    kb = KMeansBalancedParams(n_iters=args.iters, seed=0, train_mode=mode,
                              batch_rows=args.batch_rows)
    resolved = kmeans_balanced.resolve_train_mode(mode, args.n,
                                                  args.batch_rows)

    t0 = time.perf_counter()
    centers = kmeans_balanced.fit(kb, x, args.k)
    np.asarray(centers)
    b_first = time.perf_counter() - t0

    t0 = time.perf_counter()
    centers = kmeans_balanced.fit(kb, x, args.k)
    np.asarray(centers)
    b_fit_s = time.perf_counter() - t0
    b_inertia = float(kmeans.cluster_cost(x, centers))

    out.update({
        "balanced_metric": (
            f"kmeans_balanced fit {args.n}x{args.d} k={args.k} "
            f"({args.iters} iters, {resolved} EM)"),
        "balanced_train_mode": resolved,
        "balanced_batch_rows": args.batch_rows,
        "balanced_fit_s": round(b_fit_s, 2),
        "balanced_first_call_s": round(b_first, 2),
        "balanced_inertia": b_inertia,
        "balanced_inertia_ratio": round(b_inertia / floor, 4) if floor else None,
    })

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

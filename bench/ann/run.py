#!/usr/bin/env python
"""raft_tpu ANN benchmark harness.

Re-design of the reference's standalone ANN benchmark
(cpp/bench/ann/src/common/benchmark.hpp — build mode :111, search mode :168;
JSON configs cpp/bench/ann/conf/*.json; QPS-vs-recall workflow
docs/source/cuda_ann_benchmarks.md). Same JSON schema shape: a ``dataset``
section (big-ANN .fbin/.u8bin files via the native runtime loader, or a
``synthetic`` spec so the harness runs hermetically), ``search_basic_param``
(batch_size, k, run_count), and an ``index`` list with ``build_param`` +
``search_params`` sweeps.

Usage:
  python bench/ann/run.py --conf bench/ann/conf/synthetic-64.json --build
  python bench/ann/run.py --conf bench/ann/conf/synthetic-64.json --search
  # or both passes in one go:
  python bench/ann/run.py --conf ... --build --search

Outputs one CSV row per (index, search_param): algo, params, build_s,
recall@k, qps — written to ``results/<dataset>.csv`` next to the conf file
and echoed to stdout.
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

# honor JAX_PLATFORMS even when jax was imported before this line (the
# variable alone is read at jax import)
if os.environ.get("JAX_PLATFORMS"):
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    # the shared recipe lives in raft_tpu.core.platform.force_virtual_cpu;
    # this path keeps the user's explicit platform choice instead of cpu

# persistent jit cache: repeat harness runs skip 1M-scale compiles entirely
# (JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache — see
# docs/warm_builds.md); RAFT_TPU_NO_JIT_CACHE=1 opts out for cold timings
if not os.environ.get("RAFT_TPU_NO_JIT_CACHE"):
    import raft_tpu.config

    raft_tpu.config.enable_compilation_cache()


def load_dataset(spec: dict):
    """Return (base (n,d) f32, queries (m,d) f32, metric str)."""
    import numpy as np

    metric = spec.get("distance", "euclidean")
    metric = {"euclidean": "sqeuclidean", "inner": "inner_product"}.get(metric, metric)
    if "synthetic" in spec:
        syn = spec["synthetic"]
        rng = np.random.default_rng(syn.get("seed", 0))
        n_clusters = syn.get("clusters", 0)
        if syn.get("dtype") == "uint8":
            # BigANN-class byte descriptors (reference:
            # cpp/bench/ann/conf/bigann-100M.json over .u8bin files):
            # clustered integer vectors in [0, 255], kept uint8 end-to-end
            # so the int8 storage/scoring path is what gets measured
            dim = syn["dim"]
            expects_clusters = max(n_clusters, 1)
            centers = rng.integers(30, 226, (expects_clusters, dim))
            std = syn.get("cluster_std", 12.0)

            def draw_u8(count):
                labels = rng.integers(0, expects_clusters, count)
                x = centers[labels] + rng.normal(0, std, (count, dim))
                return np.clip(np.rint(x), 0, 255).astype(np.uint8)

            return draw_u8(syn["n"]), draw_u8(syn["n_queries"]), metric
        if syn.get("family") == "heavytail":
            # second independent realistic family (VERDICT r4 #10),
            # deliberately breaking the siftclass generator's symmetries:
            # - cluster POPULATIONS are Zipf-distributed (a few huge
            #   clusters, a long tail of tiny ones) — stresses list
            #   splitting and probe allocation;
            # - per-cluster intrinsic dims VARY (4..32) and the subspaces
            #   are CORRELATED across clusters (each cluster draws its
            #   basis rows from one shared 64-direction pool, the way real
            #   descriptor manifolds share global structure);
            # - residual scales are LOGNORMAL per cluster — local density
            #   varies by orders of magnitude, unlike one fine_std.
            dim = syn["dim"]
            ncl = syn.get("clusters", 2000)
            zipf = syn.get("zipf", 1.0)
            w = (1.0 / np.arange(1, ncl + 1)) ** zipf
            w /= w.sum()
            centers = rng.random((ncl, dim)).astype(np.float32) * 10
            pool = rng.normal(size=(64, dim)).astype(np.float32)
            pool /= np.linalg.norm(pool, axis=1, keepdims=True)
            max_id = 32
            idims = rng.integers(4, max_id + 1, ncl)
            basis_rows = np.stack([rng.choice(64, max_id, replace=False)
                                   for _ in range(ncl)])
            bases = pool[basis_rows]                       # (ncl, 32, dim)
            mask = (np.arange(max_id)[None, :]
                    < idims[:, None]).astype(np.float32)   # (ncl, 32)
            scales = rng.lognormal(mean=np.log(0.25), sigma=0.8,
                                   size=ncl).astype(np.float32)

            def draw_ht(count):
                parts = []
                for s in range(0, count, 50_000):
                    c = min(50_000, count - s)
                    labels = rng.choice(ncl, c, p=w)
                    z = (rng.normal(size=(c, max_id)).astype(np.float32)
                         * mask[labels] * scales[labels][:, None])
                    parts.append((centers[labels] + np.einsum(
                        "ni,nid->nd", z, bases[labels])).astype(np.float32))
                return np.concatenate(parts) if len(parts) > 1 else parts[0]

            return draw_ht(syn["n"]), draw_ht(syn["n_queries"]), metric
        if n_clusters:
            dim = syn["dim"]
            centers = rng.random((n_clusters, dim), np.float32) * 10
            std = syn.get("cluster_std", 0.5)
            idim = syn.get("intrinsic_dim", 0)
            if idim:
                # SIFT-class: low intrinsic dimension + multi-scale local
                # density (sub-clumps within each cluster) — the same
                # dataset CLASS as bench.py:_make_lid_1m (the driver
                # regression row), not the same instance: bench.py draws
                # on-device with jax.random (a host generator would cost a
                # 512 MB upload), this harness draws host-side; parameters
                # live in the conf so the two stay tuned together
                n_clumps = syn.get("clumps", 16)
                fine_std = syn.get("fine_std", 0.15)
                bases = rng.normal(size=(n_clusters, idim, dim)).astype(np.float32)
                bases /= np.linalg.norm(bases, axis=-1, keepdims=True)
                offsets = (std * rng.normal(
                    size=(n_clusters, n_clumps, idim))).astype(np.float32)

                def draw(count):
                    # chunked: bases[labels] is a (count, idim, dim) f32
                    # temporary (~8.2 GB at 1M x 16 x 128 — the same hazard
                    # bench.py bounds with 50k-row blocks)
                    parts = []
                    for s in range(0, count, 50_000):
                        c = min(50_000, count - s)
                        labels = rng.integers(0, n_clusters, c)
                        clump = rng.integers(0, n_clumps, c)
                        z = (offsets[labels, clump]
                             + fine_std * rng.normal(size=(c, idim))
                             ).astype(np.float32)
                        parts.append((centers[labels] + np.einsum(
                            "ni,nid->nd", z, bases[labels])).astype(np.float32))
                    return np.concatenate(parts) if len(parts) > 1 else parts[0]

                return draw(syn["n"]), draw(syn["n_queries"]), metric

            # clustered data (gaussian blobs): realistic IVF/graph recall
            # behavior, unlike uniform noise
            def draw(count):
                labels = rng.integers(0, n_clusters, count)
                return (centers[labels] + rng.normal(0, std, (count, dim))).astype(np.float32)

            return draw(syn["n"]), draw(syn["n_queries"]), metric
        base = rng.random((syn["n"], syn["dim"]), np.float32)
        queries = rng.random((syn["n_queries"], syn["dim"]), np.float32)
        return base, queries, metric
    from raft_tpu.runtime import load_bin

    def native(arr):
        # int8/uint8 files stay integer (the indexes take them first-class:
        # int8 list storage + s8 MXU scoring); floats normalize to f32
        return arr if arr.dtype in (np.int8, np.uint8) else arr.astype(np.float32)

    base = native(load_bin(spec["base_file"]))
    queries = native(load_bin(spec["query_file"]))
    if "subset_size" in spec:
        base = base[: spec["subset_size"]]
    return base, queries, metric


def ground_truth(base, queries, k: int, metric: str, cache: pathlib.Path):
    import numpy as np

    if cache.exists():
        gt = np.load(cache)
        if gt.shape == (queries.shape[0], k):
            return gt
    from raft_tpu.neighbors import knn

    _, idx = knn(base, queries, k, metric=metric)
    gt = np.asarray(idx)
    cache.parent.mkdir(parents=True, exist_ok=True)
    np.save(cache, gt)
    return gt


def recall(found, gt) -> float:
    import numpy as np

    m, k = gt.shape
    hits = 0
    for i in range(m):
        hits += len(set(found[i].tolist()) & set(gt[i].tolist()))
    return hits / (m * k)


# ---------------------------------------------------------------------------
# Algorithm wrappers (the reference's per-library src/<algo> adapters)
# ---------------------------------------------------------------------------


class Algo:
    """build(dataset) -> index state; search(queries, k, params) -> ids."""

    def __init__(self, metric: str, build_param: dict):
        self.metric = metric
        self.build_param = build_param

    def build(self, dataset):
        raise NotImplementedError

    def search(self, queries, k: int, params: dict):
        raise NotImplementedError


class BruteForceAlgo(Algo):
    def build(self, dataset):
        import jax.numpy as jnp

        self.dataset = jnp.asarray(dataset)

    def search(self, queries, k, params):
        from raft_tpu.neighbors import knn

        return knn(self.dataset, queries, k, metric=self.metric)[1]


class IvfFlatAlgo(Algo):
    def build(self, dataset):
        from raft_tpu.neighbors import ivf_flat

        params = ivf_flat.IndexParams(metric=self.metric, **self.build_param)
        self.index = ivf_flat.build(params, dataset)

    def search(self, queries, k, params):
        from raft_tpu.neighbors import ivf_flat

        return ivf_flat.search(ivf_flat.SearchParams(**params), self.index, queries, k)[1]


class IvfPqAlgo(Algo):
    def build(self, dataset):
        from raft_tpu.neighbors import ivf_pq

        params = ivf_pq.IndexParams(metric=self.metric, **self.build_param)
        self.index = ivf_pq.build(params, dataset)

    def search(self, queries, k, params):
        from raft_tpu.neighbors import ivf_pq

        refine_ratio = params.pop("refine_ratio", 1)
        sp = ivf_pq.SearchParams(**params)
        if refine_ratio > 1:
            from raft_tpu.neighbors import refine

            d, i = ivf_pq.search(sp, self.index, queries, k * refine_ratio)
            return refine(self._dataset, queries, i, k, metric=self.metric)[1]
        return ivf_pq.search(sp, self.index, queries, k)[1]

    def build_and_keep(self, dataset):
        # device-resident copy: refine gathers from it every search call, and
        # re-uploading an n x d f32 dataset per call (512 MB at 1M x 128)
        # dominates the measurement
        import jax.numpy as jnp

        self._dataset = jnp.asarray(dataset)


class CagraAlgo(Algo):
    def build(self, dataset):
        from raft_tpu.neighbors import cagra

        params = cagra.IndexParams(metric=self.metric, **self.build_param)
        self.index = cagra.build(params, dataset)

    def search(self, queries, k, params):
        from raft_tpu.neighbors import cagra

        return cagra.search(cagra.SearchParams(**params), self.index, queries, k)[1]


class BallCoverAlgo(Algo):
    def build(self, dataset):
        from raft_tpu.neighbors import ball_cover

        self.index = ball_cover.build(dataset, metric=self.metric, **self.build_param)

    def search(self, queries, k, params):
        from raft_tpu.neighbors import ball_cover

        return ball_cover.knn_query(self.index, queries, k, **params)[1]


ALGOS = {
    "raft_tpu.brute_force": BruteForceAlgo,
    "raft_tpu.ivf_flat": IvfFlatAlgo,
    "raft_tpu.ivf_pq": IvfPqAlgo,
    "raft_tpu.cagra": CagraAlgo,
    "raft_tpu.ball_cover": BallCoverAlgo,
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--conf", required=True, help="JSON config path")
    ap.add_argument("--build", action="store_true")
    ap.add_argument("--search", action="store_true")
    ap.add_argument("--index-filter", default=None,
                    help="only run index entries whose name contains this substring")
    args = ap.parse_args()
    if not (args.build or args.search):
        ap.error("pass --build and/or --search")

    import jax
    import numpy as np

    conf_path = pathlib.Path(args.conf)
    conf = json.loads(conf_path.read_text())
    out_dir = conf_path.parent.parent / "results"
    out_dir.mkdir(parents=True, exist_ok=True)

    base, queries, metric = load_dataset(conf["dataset"])
    basic = conf.get("search_basic_param", {})
    k = basic.get("k", 10)
    run_count = basic.get("run_count", 3)
    batch_size = min(basic.get("batch_size", len(queries)), len(queries))
    queries = queries[:batch_size]
    # one host->device upload; per-call re-upload would bill 5 MB/call of
    # host->device traffic to every algorithm equally
    queries_dev = jax.numpy.asarray(queries)

    gt = None
    rows = []
    built = {}

    entries = conf["index"]
    if args.index_filter:
        entries = [e for e in entries if args.index_filter in e["name"]]

    for entry in entries:
        name, algo_id = entry["name"], entry["algo"]
        if algo_id not in ALGOS:
            print(f"[skip] {name}: unknown algo {algo_id}", file=sys.stderr)
            continue
        algo = ALGOS[algo_id](metric, entry.get("build_param", {}))
        build_s = float("nan")
        if args.build or args.search:  # build in-process (indexes are pytrees)
            t0 = time.perf_counter()
            algo.build(base)
            if hasattr(algo, "build_and_keep"):
                algo.build_and_keep(base)
            build_s = time.perf_counter() - t0
            built[name] = build_s
            print(f"[build] {name}: {build_s:.2f}s")
        if not args.search:
            continue
        if gt is None:
            # cache key covers the FULL dataset spec (seed/clusters/std/files
            # all change the true neighbors) plus the loaded shape, so a
            # file regenerated in place with a different size also misses
            import hashlib

            spec_hash = hashlib.md5(
                json.dumps(conf["dataset"], sort_keys=True).encode()
            ).hexdigest()[:10]
            gt = ground_truth(
                base, queries, k, metric,
                out_dir / (
                    f"gt-{spec_hash}-{metric}-n{base.shape[0]}-d{base.shape[1]}"
                    f"-q{len(queries)}-k{k}.npy"
                ),
            )
        for sp in entry.get("search_params", [{}]):
            sp_label = json.dumps(sp, sort_keys=True)
            try:
                ids = algo.search(queries_dev, k, dict(sp))  # warmup/compile
                ids_np = np.asarray(ids)
                times = []
                for _ in range(run_count):
                    # host materialization: the ids reach the host, so the
                    # timing cannot end before the device finished
                    t0 = time.perf_counter()
                    ids = algo.search(queries_dev, k, dict(sp))
                    ids_np = np.asarray(ids)
                    times.append(time.perf_counter() - t0)
                qps = len(queries) / min(times)
                rec = recall(ids_np, gt)
            except Exception as e:  # parameter combos can be invalid (k > pool)
                print(f"[error] {name} {sp_label}: {e}", file=sys.stderr)
                continue
            rows.append({
                "name": name, "algo": algo_id, "search_params": sp_label,
                "k": k, "batch_size": len(queries), "build_s": round(build_s, 3),
                f"recall@{k}": round(rec, 4), "qps": round(qps, 1),
            })
            print(f"[search] {name} {sp_label}: recall@{k}={rec:.4f} qps={qps:.1f}")

    if rows:
        # keyed by the conf file, not the dataset name: several configs share
        # a dataset (variant/split-factor sweeps) and must not clobber the
        # full-config results they are compared against
        out_csv = out_dir / f"{conf_path.stem}.csv"
        with open(out_csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
            w.writeheader()
            w.writerows(rows)
        print(f"wrote {out_csv} ({len(rows)} rows)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

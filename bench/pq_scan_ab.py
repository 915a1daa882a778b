"""A/B the IVF-PQ scan formulations at 1M (one-hot MXU contraction vs
compare+select gather vs the Pallas fused kernel when present).

Protocol matches bench.py's driver rows: LID 1M x 128 dataset, pq4x64 (and
optionally pq8x32-split), n_probes=8, k=10, 10k-query sets, best-of-2 wall
time with host materialization. Run on the TPU host:

    python bench/pq_scan_ab.py [--pq8] [--lut bfloat16]
"""

from __future__ import annotations

import argparse
import sys
import time
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pq8", action="store_true", help="also run pq8x32-split")
    ap.add_argument("--pq8-only", action="store_true")
    ap.add_argument("--lut", default="bfloat16",
                    help="comma list of lut dtypes (each crossed with impls)")
    ap.add_argument("--impls", default="onehot,select")
    ap.add_argument("--probes", type=int, default=8)
    args = ap.parse_args()

    from raft_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import numpy as np

    import bench as drv
    from raft_tpu.neighbors import ivf_pq

    print(f"backend: {jax.default_backend()}", file=sys.stderr)
    dataset, qsets = drv._make_lid_1m()
    jax.block_until_ready([dataset] + qsets)
    gt = drv._ground_truth(dataset, qsets[-1][:1000])

    configs = [("pq4x64", dict(n_lists=1024, pq_bits=4, pq_dim=64, seed=0))]
    if args.pq8 or args.pq8_only:
        configs.append(("pq8x32s", dict(n_lists=1024, pq_bits=8, pq_dim=32, seed=0)))
    if args.pq8_only:
        configs = configs[1:]

    for cname, cfg in configs:
        t0 = time.perf_counter()
        idx = ivf_pq.build(ivf_pq.IndexParams(**cfg), dataset)
        jax.block_until_ready(idx.list_codes)
        print(f"{cname} build {time.perf_counter() - t0:.1f}s", file=sys.stderr)

        impls = [(i, lt) for i in args.impls.split(",")
                 for lt in args.lut.split(",")]
        searchers = {}
        m = qsets[0].shape[0]
        for impl, lt in impls:
            sp = ivf_pq.SearchParams(n_probes=args.probes, lut_dtype=lt,
                                     scan_impl=impl)
            fn = (lambda q, sp=sp: ivf_pq.search(sp, idx, q, 10))
            np.asarray(fn(qsets[0])[1])  # compile + warm
            searchers[(impl, lt)] = fn

        # throughput drifts between minutes, so the
        # impls are timed INTERLEAVED round-robin and every round is printed;
        # compare within rounds, not across runs
        times = {i: [] for i in impls}
        for rnd in range(4):
            for key in impls:
                q = qsets[1 + rnd % 2]
                t0 = time.perf_counter()
                out = searchers[key](q)
                np.asarray(out[1])
                times[key].append(time.perf_counter() - t0)
        for impl, lt in impls:
            out = searchers[(impl, lt)](qsets[-1])
            rec = drv._recall(np.asarray(out[1])[:1000], gt)
            qps = [m / t for t in times[(impl, lt)]]
            print(f"{cname} impl={impl} lut={lt} p={args.probes} "
                  f"QPS rounds={[f'{x:.0f}' for x in qps]} best={max(qps):.0f} "
                  f"recall={rec:.4f}", flush=True)


if __name__ == "__main__":
    main()

"""Interleaved A/B of the wide-k streaming selector (64 < k <= 256) vs
lax.top_k (VERDICT r4 #5 done-bar shapes: 10k rows, >= 65k cols,
k in {128, 256}; plus the CAGRA-build-relevant k=193).

Protocol: one process, round-robin variants,
distinct inputs chained inside one jitted program per timing call, only a
checksum materialized to host. Run on the TPU host:

    python bench/topk_wide_ab.py [--rows 10000] [--cols 65536] [--rounds 4]
"""

from __future__ import annotations

import argparse
import sys
import time
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000)
    ap.add_argument("--cols", type=int, default=65_536)
    ap.add_argument("--ks", default="128,193,256")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--chain", type=int, default=4,
                    help="distinct matrices chained per timing call")
    args = ap.parse_args()

    from raft_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from raft_tpu.ops.topk import topk_pallas

    print(f"backend: {jax.default_backend()}", file=sys.stderr)
    m, n, chain = args.rows, args.cols, args.chain
    nmats = max(chain, 4)
    keys = jax.random.split(jax.random.key(0), nmats)
    mats = [jax.random.uniform(k, (m, n), jnp.float32) for k in keys]
    jax.block_until_ready(mats)
    bytes_gb = m * n * 4 * max(chain, 1) / 1e9

    for k in (int(s) for s in args.ks.split(",")):

        @functools.partial(jax.jit, static_argnames=())
        def chain_pallas(ms, k=k):
            ms = ms[:chain]
            acc = jnp.zeros((), jnp.float32)
            for x in ms:
                v, i = topk_pallas(x, k, select_min=True)
                acc = acc + v[:, k - 1].sum() + (i[:, 0] % 7).sum()
            return acc

        @functools.partial(jax.jit, static_argnames=())
        def chain_lax(ms, k=k):
            ms = ms[:chain]
            acc = jnp.zeros((), jnp.float32)
            for x in ms:
                nv, ni = lax.top_k(-x, k)
                acc = acc + (-nv)[:, k - 1].sum() + (ni[:, 0] % 7).sum()
            return acc

        if chain == 1:
            # unchained mode: one kernel per call on ROTATING distinct
            # matrices (two kh=256 pallas_calls chained in one XLA program
            # hit a TPU-internal error on an older toolchain; standalone
            # calls are fine). Distinct inputs per call keep a repeated
            # dispatch from reading as a fast one.
            def make_unchained(op, k=k):
                cnt = {"i": 0}

                def f(ms):
                    x = ms[cnt["i"] % len(ms)]
                    cnt["i"] += 1
                    if op == "pallas":
                        v, i = topk_pallas(x, k, select_min=True)
                        return v[:, k - 1].sum() + (i[:, 0] % 7).sum()
                    nv, ni = lax.top_k(-x, k)
                    return (-nv)[:, k - 1].sum() + (ni[:, 0] % 7).sum()
                return f

            chain_pallas = make_unchained("pallas")
            chain_lax = make_unchained("lax")

        variants = {"pallas": chain_pallas, "lax": chain_lax}
        # correctness spot-check before timing
        v, i = topk_pallas(mats[0][:64], k, select_min=True)
        v0, i0 = lax.top_k(-mats[0][:64], k)
        np.testing.assert_allclose(np.asarray(v), -np.asarray(v0), atol=0)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))

        for name, fn in variants.items():
            float(fn(mats))  # compile + warm
        times = {name: [] for name in variants}
        for r in range(args.rounds):
            for name, fn in variants.items():
                t0 = time.perf_counter()
                float(fn(mats))
                times[name].append(time.perf_counter() - t0)
        best = {name: min(ts) for name, ts in times.items()}
        for name, ts in times.items():
            print(f"k={k:4d} {name:7s} best {best[name]*1e3:8.2f} ms "
                  f"({bytes_gb/best[name]:6.1f} GB/s)  all "
                  f"{[f'{t*1e3:.1f}' for t in ts]}")
        print(f"k={k:4d} pallas/lax speedup: {best['lax']/best['pallas']:.3f}x")


if __name__ == "__main__":
    main()

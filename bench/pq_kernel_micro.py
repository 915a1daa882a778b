"""Micro-bench for the Pallas PQ LUT-scan kernel (ops/pq_scan.py), isolated
from the full IVF search: one chunk's worth of synthetic codes/LUTs at the
1M-scale shapes (B = query_tile * probe_chunk = 1024, cap ~ 1336, S = 64).

Protocol follows bench.py: ITERS DISTINCT inputs chained in one jitted
program via lax.map, host-materialized, best of 2 distinct stacks, so a
repeated identical dispatch cannot read as a fast one. Run on the TPU host:

    python bench/pq_kernel_micro.py
"""

from __future__ import annotations

import sys
import time
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ITERS = 8


def timeit(fn, stacks):
    """fn maps one (codes, lut) pair; chained over ITERS distinct inputs.
    Only a per-iter checksum leaves the device — a full (B, cap) f32 output
    costs a device->host transfer that swamps the kernel time."""
    f = jax.jit(lambda cs, ls: lax.map(lambda a: fn(*a), (cs, ls))
                .sum(axis=(1, 2)))
    np.asarray(f(*stacks[0]))  # compile + warm
    best = float("inf")
    for st in stacks[1:]:
        t0 = time.perf_counter()
        sums = np.asarray(f(*st))
        best = min(best, time.perf_counter() - t0)
    # one full output for the correctness check, outside the timing
    out = jax.jit(fn)(*[a[0] for a in stacks[-1]])
    return best / ITERS, np.asarray(out)


def onehot_ref(codes_u8, lut_ks):
    B, cap, S = codes_u8.shape
    K = lut_ks.shape[1]
    oh = codes_u8[..., None] == jnp.arange(K, dtype=jnp.uint8)
    ohf = oh.reshape(B, cap, S * K)
    lutf = jnp.swapaxes(lut_ks, 1, 2).reshape(B, S * K)
    return lax.dot_general(ohf.astype(jnp.bfloat16), lutf.astype(jnp.bfloat16),
                           (((2,), (1,)), ((0,), (0,))),
                           preferred_element_type=jnp.float32)


def main():
    from raft_tpu.ops.pq_scan import pq_lut_scan

    B, cap, S, K = 1024, 1336, 64, 16

    def stack(seed):
        r = np.random.default_rng(seed)
        cs = jnp.asarray(r.integers(0, K, (ITERS, B, cap, S), dtype=np.uint8))
        ls = jnp.asarray(r.random((ITERS, B, K, S), np.float32))
        return cs, ls

    stacks = [stack(s) for s in range(3)]
    jax.block_until_ready(stacks)
    i8_stacks = [(c.astype(jnp.int8), l) for c, l in stacks]
    n_scores = B * cap

    t, ref_last = timeit(onehot_ref, stacks)
    print(f"onehot bf16:  {t*1e3:8.2f} ms  {n_scores/t/1e9:6.2f} Gscore/s",
          flush=True)

    for bt, capb in ((8, None), (8, 256), (8, 128), (16, None), (32, None),
                     (64, None)):
        def f(c, l, bt=bt, capb=capb):
            return pq_lut_scan(c, l, bt=bt, capb=capb)
        try:
            t, out = timeit(f, i8_stacks)
            err = float(np.abs(out - ref_last).max())
            print(f"pallas bt={bt:3d} capb={capb}: "
                  f"{t*1e3:8.2f} ms  {n_scores/t/1e9:6.2f} Gscore/s  "
                  f"maxerr={err:.3f}", flush=True)
        except Exception as e:
            print(f"pallas bt={bt:3d} capb={capb}: ERROR "
                  f"{type(e).__name__}: {str(e)[:160]}", flush=True)


if __name__ == "__main__":
    main()

"""Minimal repro + parameter bisect for the kh=256 two-instance chaining
failure (VERDICT r5 weak #1 / next #3).

History: chaining TWO wide-k (k > 128 → kh=256) topk_pallas instances inside
ONE XLA program hit "TPU backend error (Internal)" on the r05 toolchain,
while every standalone call — and kh=128 chains 4-deep — compiled fine
(r05 wide-k selector study). The r05 kernel's one
structural feature unique to kh=256 was its 2*kh = 512-lane merge
intermediates; r06 reformulated the merge to cap every intermediate at kh
lanes (ops/topk.py wide_merge="half") and lifted the select_k dispatch to
k <= 256 on that basis. This harness is the evidence machine:

  * ``--mode repro``  — ONE jit program with two chained wide-k instances at
    the CAGRA build-chunk shapes (the commissioned call site: per-chunk
    select over probe_chunk*capacity cols, then the final merge over
    n_chunks*k cols, k = gpu_top_k+1 = 193). Runs each wide_merge form and
    prints PASS/FAIL — "concat" reproduces the r05 failure if the toolchain
    still has it; "half" must PASS or the r06 dispatch lift is wrong and
    RAFT_TPU_WIDE_SELECT_CAP=128 should be set while bisecting.
  * ``--mode bisect`` — sweeps the kernel parameters the failure could key
    on (kh via k, qt, blk, vmem_limit, one-vs-two instances, same-vs-
    different shapes) and prints a PASS/FAIL grid that localizes the
    trigger: if ONLY (concat, two-instance, kh=256) rows fail, the 512-lane
    width is root-caused as the distinguishing feature and the failure is a
    Mosaic limit worth reporting upstream (reference bar: one-kernel k<=1024,
    matrix/detail/select_radix.cuh).

CPU (interpret) runs validate numerics only; the failure is TPU-compile-time,
so run on the TPU host:

    python bench/topk_chain_repro.py --mode repro
    python bench/topk_chain_repro.py --mode bisect
"""

from __future__ import annotations

import argparse
import functools
import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def _try(label, fn):
    import numpy as np

    try:
        out = fn()
        np.asarray(out)
        print(f"PASS  {label}")
        return True
    except Exception as e:
        msg = str(e).replace("\n", " ")[:140]
        print(f"FAIL  {label}: {type(e).__name__}: {msg}")
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["repro", "bisect"], default="repro")
    ap.add_argument("--rows", type=int, default=2048,
                    help="query rows (the build chunk runs 16384; 2048 "
                    "keeps the bisect grid fast — the failure keyed on "
                    "kernel config, not m)")
    ap.add_argument("--cols", type=int, default=10432,
                    help="first-instance cols (build chunk: probe_chunk * "
                    "capacity; 8 * 1304 at the 1M defaults)")
    ap.add_argument("--k", type=int, default=193,
                    help="gpu_top_k + 1 at the CAGRA build defaults")
    args = ap.parse_args()

    from raft_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.topk import topk_pallas

    print(f"backend: {jax.default_backend()}", file=sys.stderr)
    if jax.default_backend() != "tpu":
        print("note: not a TPU backend — interpret-mode numerics only; the "
              "chaining failure is TPU-compile-time", file=sys.stderr)
    m, n, k = args.rows, args.cols, args.k
    x = jax.random.uniform(jax.random.key(0), (m, n), jnp.float32)
    n2 = 4 * k  # final-merge width (n_chunks * k at 4 probe chunks)

    def chained(wm, k1, k2, qt=256, blk=4096):
        """Two wide instances in ONE program: select k1 over (m, n), then
        re-select k2 over the (m, 4*k1) concatenation of the results —
        exactly the per-chunk + final-merge composition of _pq_search."""

        @jax.jit
        def f(x):
            v1, i1 = topk_pallas(x, k1, blk=blk, qt=qt, wide_merge=wm)
            pool = jnp.tile(v1, (1, 4))
            v2, i2 = topk_pallas(pool, k2, blk=blk, qt=qt, wide_merge=wm)
            return v2.sum() + (i2 % 7).sum() + (i1 % 5).sum()

        return f(x)

    if args.mode == "repro":
        ok = {}
        for wm in ("half", "concat"):
            ok[wm] = _try(f"two kh=256 instances, wide_merge={wm} "
                          f"(m={m}, n={n}->{n2}, k={k})",
                          functools.partial(chained, wm, k, k))
        if ok.get("half") and not ok.get("concat"):
            print("=> r05 failure reproduced on 'concat'; 'half' fixed it "
                  "(the 512-lane intermediates were the trigger)")
        elif all(ok.values()):
            print("=> both forms pass on this toolchain (failure gone or "
                  "environment-specific); the dispatch lift stands")
        elif not ok.get("half"):
            print("=> 'half' STILL FAILS: set RAFT_TPU_WIDE_SELECT_CAP=128 "
                  "and run --mode bisect")
        return

    # bisect grid: localize what the failure keys on
    cases = []
    for wm in ("half", "concat"):
        cases += [
            (f"{wm} one-instance k=193", lambda wm=wm: jax.jit(
                lambda x: topk_pallas(x, 193, wide_merge=wm)[0].sum())(x)),
            (f"{wm} two-instance k=193/193", functools.partial(
                chained, wm, 193, 193)),
            (f"{wm} two-instance k=193/129", functools.partial(
                chained, wm, 193, 129)),
            (f"{wm} two-instance mixed k=193/96 (kh 256+128)",
             functools.partial(chained, wm, 193, 96)),
            (f"{wm} two-instance k=128/128 (kh=128 control)",
             functools.partial(chained, wm, 128, 128)),
            (f"{wm} two-instance k=193/193 qt=128", functools.partial(
                chained, wm, 193, 193, qt=128)),
            (f"{wm} two-instance k=193/193 blk=2048", functools.partial(
                chained, wm, 193, 193, blk=2048)),
        ]
    results = {label: _try(label, fn) for label, fn in cases}
    fails = [l for l, ok in results.items() if not ok]
    print(f"\n{len(fails)}/{len(results)} failing: {fails or 'none'}")


if __name__ == "__main__":
    main()

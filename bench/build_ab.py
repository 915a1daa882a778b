"""Build-speed A/B driver (ISSUE 6, the Round-6 follow-up): mini-batch vs
full coarse EM, sharded vs single CAGRA builds, and the distributed-build
overhead control — one artifact, one renderer.

The Round-6 study (r06 distributed-build overhead) named
the balanced coarse trainer's ~22 full-dataset assignment passes as the
dominant IVF build cost (+187% warm at 1M distributed, 50.3-51.3 s of the 1M
single-chip build). This driver measures the r07 remedies:

- ``--ab em``       mini-batch vs full coarse EM on the IVF-PQ build: warm
                    build wall + the recall anchor at the BENCH operating
                    point (held within tolerance is the acceptance bar).
- ``--ab overhead`` the Round-6 1-device-mesh distributed-vs-single warm
                    overhead A/B, run in BOTH EM modes — the within-15%
                    acceptance bar reads off the minibatch row.
- ``--ab cagra``    sharded-merged vs single CAGRA build
                    (parallel.cagra.build_merged): build wall + recall@10 of
                    both indexes against exact ground truth.
- ``--ab all``      everything above into one artifact.

Run on hardware (the committed CPU-mesh artifact is the reduced-scale
control):

    python bench/build_ab.py --ab all --n 1000000 --cagra-n 1000000 \
        --out BUILD_AB_r07.json

Render the BASELINE follow-up table FROM the artifact (stdlib only — no
prose drift; the numbers in the doc ARE the artifact's):

    python bench/build_ab.py --table BUILD_AB_r07.json
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def _timed_builds(fn, materialize, repeats: int):
    """cold + warm walls + cold compile attribution for a build closure."""
    import jax

    from raft_tpu.obs import compile as obs_compile

    walls, compile_s = [], []
    for _ in range(repeats + 1):
        t0 = time.perf_counter()
        with obs_compile.attribution() as rec:
            idx = fn()
            jax.block_until_ready(materialize(idx))
        walls.append(time.perf_counter() - t0)
        compile_s.append(rec.compile_s)
    # first call is cold (compile-dominated); best of the rest is warm
    return {"cold_s": round(walls[0], 2),
            "cold_compile_s": round(compile_s[0], 2),
            "warm_s": round(min(walls[1:]), 2)}, idx


def _clustered(n: int, d: int, ncl: int, seed: int = 0):
    import jax
    import jax.numpy as jnp

    from raft_tpu.random import make_blobs

    x, _ = make_blobs(n, d, n_clusters=ncl, cluster_std=1.0, seed=seed)
    x = jnp.asarray(x, jnp.float32)
    jax.block_until_ready(x)
    return x


def _recall(ids, gt):
    import numpy as np

    ids, gt = np.asarray(ids), np.asarray(gt)
    k = gt.shape[1]
    return float(np.mean([len(set(ids[r].tolist()) & set(gt[r].tolist())) / k
                          for r in range(gt.shape[0])]))


def measure_em_ab(n: int, d: int, n_lists: int, pq_dim: int = 64,
                  n_probes: int = 8, k: int = 10, repeats: int = 2,
                  n_eval: int = 1000, ncl: int = 2000,
                  batch_rows: int = 65536) -> dict:
    """Mini-batch vs full coarse EM on the IVF-PQ build: warm build wall +
    the recall anchor at the BENCH operating point (pq4, bf16 LUT). The
    acceptance bar: warm build cut >= 30% at 1M with the anchor held."""
    import dataclasses

    from raft_tpu.neighbors import brute_force, ivf_pq

    x = _clustered(n, d, ncl)
    q = x[:n_eval]
    _, gt = brute_force.knn(x, q, k)
    base = ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4, pq_dim=pq_dim,
                              kmeans_batch_rows=batch_rows, seed=0)
    sp = ivf_pq.SearchParams(n_probes=n_probes, lut_dtype="bfloat16")
    out = {"name": f"em_ab_ivf_pq_{n//1000}k", "n": n, "d": d,
           "n_lists": n_lists, "n_probes": n_probes, "k": k}
    for mode in ("full", "minibatch"):
        params = dataclasses.replace(base, kmeans_train_mode=mode)
        timing, idx = _timed_builds(lambda p=params: ivf_pq.build(p, x),
                                    lambda i: i.list_codes, repeats)
        _, ids = ivf_pq.search(sp, idx, q, k)
        timing["recall"] = round(_recall(ids, gt), 4)
        out[mode] = timing
        del idx
    out["warm_cut"] = round(
        1.0 - out["minibatch"]["warm_s"] / max(out["full"]["warm_s"], 1e-9), 3)
    out["recall_gap"] = round(
        out["minibatch"]["recall"] - out["full"]["recall"], 4)
    return out


def measure_overhead(n: int, d: int, n_lists: int, repeats: int = 2,
                     batch_rows: int = 65536) -> dict:
    """The Round-6 1-device-mesh distributed-vs-single build A/B, in both EM
    modes: full reproduces the r06 +187%-class overhead (the psum-EM's full
    -dataset passes), minibatch is the r07 remedy — the within-15% bar."""
    import dataclasses

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from raft_tpu.comms.comms import Comms
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.parallel import ivf as pivf

    comms = Comms(Mesh(np.array(jax.devices()[:1]), ("data",)), "data")
    x = _clustered(n, d, max(n // 500, 16))
    base = ivf_flat.IndexParams(n_lists=n_lists, kmeans_batch_rows=batch_rows,
                                seed=0)
    out = {"name": f"dist_overhead_{n//1000}k", "n": n, "d": d,
           "n_lists": n_lists}
    for mode in ("full", "minibatch"):
        params = dataclasses.replace(base, kmeans_train_mode=mode)
        single, _ = _timed_builds(
            lambda p=params: ivf_flat.build(p, x), lambda i: i.list_data,
            repeats)
        dist, _ = _timed_builds(
            lambda p=params: pivf.build(comms, p, x), lambda i: i.list_data,
            repeats)
        out[mode] = {
            "single": single, "distributed": dist,
            "warm_overhead": round(
                dist["warm_s"] / max(single["warm_s"], 1e-9) - 1.0, 3)}
    return out


def measure_cagra_ab(n: int, d: int, shards: int, itopk: int = 32,
                     k: int = 10, n_eval: int = 1000, ncl: int | None = None,
                     repeats: int = 1, batch_rows: int = 65536) -> dict:
    """Sharded-merged vs single CAGRA build: wall + recall@10 of BOTH
    indexes against exact ground truth (the r06 64k/8-shard result said the
    merged graph holds recall; this prices the build-speed side).

    ``ncl`` defaults to the BENCH family's rows-per-cluster (~500, the 1M
    set's proportions). Shard-local graphs' recall depends on CLUSTER
    MEMBERS PER SHARD, not shard rows: the r07 CPU artifact measured a
    -0.058 recall gap at 2 members/shard (32k rows, 2000 clusters, 8
    shards) vs parity at bench proportions — pass ``ncl`` explicitly to
    probe that boundary (docs/using_comms.md records the sizing rule)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import dataclasses

    from raft_tpu.comms.comms import Comms
    from raft_tpu.neighbors import brute_force, cagra
    from raft_tpu.parallel import cagra as pcagra

    ndev = len(jax.devices())
    comms = Comms(Mesh(np.array(jax.devices()[:min(shards, ndev)]),
                       ("data",)), "data")
    if ncl is None:
        ncl = max(n // 500, 16)
    x = _clustered(n, d, ncl)
    q = x[:n_eval]
    _, gt = brute_force.knn(x, q, k)
    params = cagra.IndexParams(build_kmeans_batch_rows=batch_rows, seed=0)
    sp = cagra.SearchParams(itopk_size=itopk)
    out = {"name": f"cagra_build_ab_{n//1000}k_{ncl}cl", "n": n, "d": d,
           "ncl": ncl, "shards": comms.size(), "itopk": itopk, "k": k}
    single, idx1 = _timed_builds(lambda: cagra.build(params, x),
                                 lambda i: i.graph, repeats)
    _, ids = cagra.search(sp, idx1, q, k)
    single["recall"] = round(_recall(ids, gt), 4)
    del idx1
    merged, idx2 = _timed_builds(
        lambda: pcagra.build_merged(comms, params, x), lambda i: i.graph,
        repeats)
    _, ids = cagra.search(sp, idx2, q, k)
    merged["recall"] = round(_recall(ids, gt), 4)
    # the beam-width recovery arm: one beam over S disconnected shard
    # subgraphs needs a wider itopk — the r07 CPU artifact measured
    # 0.9371 -> 0.995 -> 0.9999 at itopk 32/64/128 vs the single graph's
    # 1.0 @ 32 (docs/using_comms.md sizing rule)
    sweep = {}
    for t in (2 * itopk, 4 * itopk):
        _, ids = cagra.search(
            dataclasses.replace(sp, itopk_size=t), idx2, q, k)
        sweep[str(t)] = round(_recall(ids, gt), 4)
    merged["itopk_sweep"] = sweep
    del idx2
    out["single"] = single
    out["merged"] = merged
    out["warm_cut"] = round(
        1.0 - merged["warm_s"] / max(single["warm_s"], 1e-9), 3)
    out["recall_gap"] = round(merged["recall"] - single["recall"], 4)
    return out


# ---------------------------------------------------------------------------
# artifact → markdown (stdlib only: runs on the doc-writing host)
# ---------------------------------------------------------------------------

def render_table(artifact: dict) -> str:
    """The BASELINE "Round-6 follow-up" table generated FROM the artifact —
    the committed prose and the committed JSON are the same bytes."""
    lines = [
        "| row | arm | warm_s | cold_s | recall | delta |",
        "|---|---|---|---|---|---|",
    ]
    for r in artifact.get("rows", []):
        name = r.get("name", "?")
        if "error" in r:
            lines.append(f"| {name} | ERROR | | | | {r['error'][:60]} |")
            continue
        if name.startswith("em_ab"):
            for arm in ("full", "minibatch"):
                a = r[arm]
                lines.append(
                    f"| {name} | {arm} | {a['warm_s']} | {a['cold_s']} | "
                    f"{a['recall']:.4f} | |")
            lines.append(
                f"| {name} | | | | | warm_cut **{r['warm_cut']}**, "
                f"recall_gap {r['recall_gap']} |")
        elif name.startswith("dist_overhead"):
            for arm in ("full", "minibatch"):
                a = r[arm]
                lines.append(
                    f"| {name} | {arm} single | {a['single']['warm_s']} | "
                    f"{a['single']['cold_s']} | | |")
                lines.append(
                    f"| {name} | {arm} distributed | "
                    f"{a['distributed']['warm_s']} | "
                    f"{a['distributed']['cold_s']} | | warm_overhead "
                    f"**{a['warm_overhead']}** |")
        elif name.startswith("cagra_build_ab"):
            for arm in ("single", "merged"):
                a = r[arm]
                lines.append(
                    f"| {name} | {arm} (S={r['shards']}) | {a['warm_s']} | "
                    f"{a['cold_s']} | {a['recall']:.4f} | |")
            sweep = (r["merged"].get("itopk_sweep")
                     or r.get("merged_itopk_sweep"))
            if sweep:
                arm = ", ".join(f"itopk {t}: {v:.4f}"
                                for t, v in sorted(sweep.items(),
                                                   key=lambda kv: int(kv[0])))
                lines.append(f"| {name} | merged, wider beam | | | {arm} | |")
            lines.append(
                f"| {name} | | | | | warm_cut **{r['warm_cut']}**, "
                f"recall_gap {r['recall_gap']} |")
    head = (f"elapsed {artifact.get('elapsed_s')}s, config "
            f"{json.dumps(artifact.get('config', {}))}. Table generated by "
            "`python bench/build_ab.py --table <artifact>` — the numbers "
            "below ARE the artifact's.")
    return head + "\n\n" + "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ab", choices=("em", "overhead", "cagra", "all"),
                    default="all")
    ap.add_argument("--n", type=int, nargs="*", default=[100_000, 1_000_000],
                    help="IVF A/B scales (em + overhead)")
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--n-lists", type=int, default=1024)
    ap.add_argument("--cagra-n", type=int, default=1_000_000)
    ap.add_argument("--cagra-ncl", type=int, nargs="*", default=None,
                    help="cluster counts for the CAGRA A/B set, one row per "
                         "value (default: one row at bench-family "
                         "proportions, n/500; the committed r07 artifact "
                         "used 65 + a deliberately thin 2000)")
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--batch-rows", type=int, default=65536,
                    help="kmeans_batch_rows for every build (shrink it to "
                         "demonstrate the cut at reduced CPU-mesh scales)")
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--table", type=str, default=None,
                    help="render the BASELINE table from an artifact and exit")
    args = ap.parse_args(argv)

    if args.table:
        with open(args.table) as f:
            print(render_table(json.load(f)))
        return 0

    from raft_tpu.obs import compile as obs_compile

    obs_compile.install()
    t0 = time.perf_counter()
    rows = []

    def guarded(fn, *a, **kw):
        try:
            rows.append(fn(*a, **kw))
        except Exception as e:  # labeled row, keep going (bench contract)
            rows.append({"name": getattr(fn, "__name__", "?"),
                         "error": f"{type(e).__name__}: {str(e)[:200]}"})

    if args.ab in ("em", "all"):
        for n in args.n:
            guarded(measure_em_ab, n, args.d, args.n_lists,
                    repeats=args.repeats, batch_rows=args.batch_rows)
    if args.ab in ("overhead", "all"):
        for n in args.n:
            guarded(measure_overhead, n, args.d, args.n_lists,
                    repeats=args.repeats, batch_rows=args.batch_rows)
    if args.ab in ("cagra", "all"):
        for ncl in (args.cagra_ncl or [None]):
            guarded(measure_cagra_ab, args.cagra_n, args.d, args.shards,
                    ncl=ncl, repeats=max(args.repeats - 1, 1),
                    batch_rows=args.batch_rows)

    artifact = {
        "rows": rows, "elapsed_s": round(time.perf_counter() - t0, 1),
        "config": {"n": args.n, "d": args.d, "n_lists": args.n_lists,
                   "cagra_n": args.cagra_n,
                   "cagra_ncl": args.cagra_ncl, "shards": args.shards,
                   "repeats": args.repeats, "batch_rows": args.batch_rows},
    }
    line = json.dumps(artifact)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

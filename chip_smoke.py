"""Serve a 1M-vector index on one TPU chip end to end, and check the answers.

The quickest proof that the system runs on the chip. One process, no child
processes, all data generated from ``--seed``. Phases:

1. device     — fail unless JAX reports a TPU;
2. corpus     — 1,000,000 x 128 f32 SIFT-class vectors + 10,000 queries
                (the shapes of ann-benchmarks' sift-128-euclidean);
3. reference  — exact top-10 of the first 1,000 queries, plain ``jnp`` at
                ``precision=HIGHEST`` re-ranked in float64 on the host;
4. ivf_pq     — IVF-PQ (1024 lists, pq4 x 64) wrapped in
                ``stream.MutableIndex``, published into ``SearchService``
                and served (8 probes, bf16 LUT, refine x4) to 4 client
                threads in requests of 1-64 rows; recall@10 >= 0.95;
5. brute_force — exact ``brute_force.knn``; recall 1.0 up to f32 distance
                ties, with the fused Pallas kernel in the compiled program;
6. cagra      — default build, ``itopk_size=32``; the hop resolves to the
                fused arena kernel; recall@10 >= 0.95.

``--four-chips`` runs only the multi-chip paths and what they are compared
with: ``parallel.knn`` over a 4-device mesh, the same corpus as a 4-shard
``ShardedMutableIndex`` (one shard per device) served through
``SearchService``, and last the single-chip ``MutableIndex`` answer from the
same build seed.

Any failure raises and exits non-zero. The last line of standard output is
``{"ok": true, "device": {...}}`` only after every phase passed on a TPU.
``--rehearse-on-cpu`` (never given by a driver) runs the same phases on the
CPU backend with the Pallas kernels interpreted, at the tiny sizes you pass,
and ends with a line that is not a result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
T0 = time.perf_counter()

RECALL_FLOOR = 0.95


def say(phase: str, **kv) -> None:
    parts = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{time.perf_counter() - T0:7.1f}s] {phase}: {parts}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


# -- the plain reference (independent of raft_tpu) ---------------------------

def exact_reference(dataset, queries, k: int, pool: int = 64, block: int = 100):
    """Exact top-k ids and float64 distances. Candidates come from the
    expanded-form distance at HIGHEST precision (f32 cancellation can swap
    near neighbours, so ``pool`` >> k), re-ranked exactly in float64."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    @jax.jit
    def candidates(x, qb):
        xn = jnp.sum(x * x, axis=1)
        dots = jnp.matmul(qb, x.T, precision=lax.Precision.HIGHEST)
        _, idx = lax.top_k(2.0 * dots - xn[None, :], pool)
        return idx, x[idx]

    ids, dists = [], []
    for s in range(0, queries.shape[0], block):
        qb = queries[s:s + block]
        idx, vecs = candidates(dataset, qb)
        idx, vecs = np.asarray(idx), np.asarray(vecs, np.float64)
        d = ((vecs - np.asarray(qb, np.float64)[:, None, :]) ** 2).sum(-1)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        ids.append(np.take_along_axis(idx, order, axis=1))
        dists.append(np.take_along_axis(d, order, axis=1))
    return np.concatenate(ids), np.concatenate(dists)


def recall(ids, ref_ids) -> float:
    import numpy as np

    ids, ref_ids = np.asarray(ids), np.asarray(ref_ids)
    k = ref_ids.shape[1]
    return float(np.mean([len(set(ids[r, :k]) & set(ref_ids[r])) / k
                          for r in range(ref_ids.shape[0])]))


def recall_up_to_ties(dataset, queries, ids, ref_dist):
    """Share of returned ids whose exact distance is within the f32
    resolution of the expanded form of the k-th reference distance — an
    exact search may order ties at that resolution either way. Returns
    (recall, tolerance used)."""
    import numpy as np

    ids = np.asarray(ids)
    q = np.asarray(queries, np.float64)
    vecs = np.asarray(dataset[np.maximum(ids, 0)], np.float64)
    d = ((vecs - q[:, None, :]) ** 2).sum(-1)
    scale = (q * q).sum(1) + (vecs * vecs).sum(-1).max(1)
    tol = 8 * np.finfo(np.float32).eps * scale
    ok = (ids >= 0) & (d <= ref_dist[:, -1:] + tol[:, None])
    distinct = np.array([len(set(r)) == len(r) for r in ids])
    return float((ok.mean(1) * distinct).mean()), float(tol.max())


# -- serving ------------------------------------------------------------------

def serve_judged(svc, name: str, queries, k: int, seed: int, threads: int = 4):
    """Answer ``queries`` through ``svc.submit`` from ``threads`` client
    threads in requests of 1 to max_batch rows. Returns (ids, failures,
    requests)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    plan, s = [], 0
    while s < queries.shape[0]:
        r = int(min(rng.integers(1, svc.max_batch + 1), queries.shape[0] - s))
        plan.append((s, r))
        s += r
    out = np.full((queries.shape[0], k), -1, np.int64)
    failures = []

    def client(t):
        for s, r in plan[t::threads]:
            try:
                _, ids = svc.submit(name, queries[s:s + r], k).result(600)
                out[s:s + r] = np.asarray(ids)
            except Exception as e:  # counted, then fails the phase
                failures.append(f"{type(e).__name__}: {str(e)[:200]}")

    ts = [threading.Thread(target=client, args=(t,)) for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return out, failures, len(plan)


def publish_and_serve(label, hook, queries, ref_ids, k, seed, row_of=None,
                      max_batch=64):
    """Publish ``hook`` (warm-before-flip), serve ``queries`` through
    ``submit`` in requests of 1 to ``max_batch`` rows and check the
    answers; ``row_of`` maps served global ids back to corpus rows."""
    import numpy as np

    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.serve import SearchService

    svc = SearchService(max_batch=max_batch, max_wait_us=2000.0)
    try:
        t0 = time.perf_counter()
        with obs_compile.attribution() as warm:
            svc.publish(label, hook, k=k)  # warm every bucket, then flip
        say(label, publish_s=round(time.perf_counter() - t0, 1),
            publish_compile_s=round(warm.compile_s, 1),
            cache_hits=warm.cache_hits, cache_misses=warm.cache_misses)
        t0 = time.perf_counter()
        with obs_compile.attribution() as hot:
            ids, failures, n_req = serve_judged(svc, label, queries, k, seed)
        if row_of is not None:
            ids = np.where(ids >= 0, row_of[np.maximum(ids, 0)], -1)
        r = recall(ids, ref_ids)
        say(label, requests=n_req, failed=len(failures),
            serve_wall_s=round(time.perf_counter() - t0, 2),
            serve_cold_compiles=hot.cache_misses, recall_at_10=r)
    finally:
        svc.shutdown()
    check(not failures, f"{label}: {len(failures)} failed requests, first: "
          f"{failures[:1]}")
    check(r >= RECALL_FLOOR, f"{label}: recall@10 {r} < {RECALL_FLOOR}")
    return ids


# -- phases -------------------------------------------------------------------

def phase_device(args):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    say("device", platform=d0.platform, kind=repr(d0.device_kind),
        count=len(devs), jax=jax.__version__)
    want = "cpu" if args.rehearse_on_cpu else "tpu"
    check(d0.platform == want, f"platform is {d0.platform!r}, need {want!r} "
          "(no accelerator: nothing to smoke; no fallback)")
    if args.four_chips:
        check(len(devs) >= 4, f"--four-chips needs 4 devices, have {len(devs)}")
    return devs


def phase_corpus(args):
    import jax
    import numpy as np

    from raft_tpu.random import make_sift_like

    t0 = time.perf_counter()
    dataset, (queries,) = make_sift_like(args.n, args.queries, seed=args.seed)
    jax.block_until_ready((dataset, queries))
    judged = np.asarray(queries[:args.judged])
    say("corpus", rows=dataset.shape[0], dim=dataset.shape[1],
        queries=queries.shape[0], judged=judged.shape[0],
        device_bytes=dataset.nbytes + queries.nbytes,
        wall_s=round(time.perf_counter() - t0, 1))
    return dataset, judged


def phase_reference(dataset, judged, k):
    t0 = time.perf_counter()
    ref_ids, ref_dist = exact_reference(dataset, judged, k)
    say("reference", rows=dataset.shape[0], queries=judged.shape[0], k=k,
        wall_s=round(time.perf_counter() - t0, 1))
    return ref_ids, ref_dist


def ivf_pq_params(n: int, shards: int = 1):
    """The operating point: 1024 lists at 1M rows (lists of ~977 rows; a
    smaller corpus keeps the list size), pq4 x 64, 8 probes, bf16 LUT.
    Each of ``shards`` shards gets its share of the lists."""
    from raft_tpu.neighbors import ivf_pq

    n_lists = max(1024 * n // 1_000_000 // shards, 16)

    return (ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4, pq_dim=64, seed=0),
            ivf_pq.SearchParams(n_probes=8, lut_dtype="bfloat16"))


def phase_ivf_pq(args, dataset, judged, ref_ids, k):
    import jax
    import numpy as np

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.stream import MutableIndex

    params, sp = ivf_pq_params(args.n)
    t0 = time.perf_counter()
    with obs_compile.attribution() as rec:
        idx = ivf_pq.build(params, dataset)
        jax.block_until_ready(idx.list_codes)
    say("ivf_pq", build_s=round(time.perf_counter() - t0, 1),
        build_compile_s=round(rec.compile_s, 1), cache_hits=rec.cache_hits,
        cache_misses=rec.cache_misses, n_lists=params.n_lists,
        pq="4x64", n_probes=sp.n_probes, lut="bfloat16", refine=4)
    m = MutableIndex(idx, search_params=sp, index_params=params,
                     dataset=np.asarray(dataset), name="sift")
    publish_and_serve("ivf_pq", m.refined_searcher(4), judged, ref_ids, k,
                      args.seed)


def phase_brute_force(args, dataset, judged, ref_dist, k):
    import jax

    from raft_tpu.neighbors import brute_force

    t0 = time.perf_counter()
    compiled = jax.jit(lambda x, q: brute_force.knn(x, q, k)).lower(
        dataset, judged).compile()
    fused = "tpu_custom_call" in compiled.as_text()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, ids = compiled(dataset, judged)
    ids = jax.device_get(ids)
    r, tol = recall_up_to_ties(dataset, judged, ids, ref_dist)
    say("brute_force", compile_s=round(compile_s, 1),
        search_s=round(time.perf_counter() - t0, 2),
        tpu_custom_call=fused if not args.rehearse_on_cpu else "not checked "
        "(rehearsal interprets the kernel)", recall_up_to_ties=r,
        tie_tolerance=tol)
    check(fused or args.rehearse_on_cpu,
          "brute_force: no tpu_custom_call in the compiled search — the "
          "fused Pallas kernel did not run")
    check(r == 1.0, f"brute_force: recall {r} != 1.0 up to ties")


def phase_cagra(args, dataset, judged, ref_ids, k):
    import jax

    from raft_tpu.neighbors import cagra

    t0 = time.perf_counter()
    idx = cagra.build(cagra.IndexParams(), dataset)
    jax.block_until_ready(idx.graph)
    build_s = time.perf_counter() - t0
    sp = cagra.SearchParams(itopk_size=32)
    hop = cagra.resolve_hop_impl(sp, idx.graph_degree, idx.dim)
    t0 = time.perf_counter()
    _, ids = cagra.search(sp, idx, judged, k)
    ids = jax.device_get(ids)
    search_s = time.perf_counter() - t0
    r = recall(ids, ref_ids)
    # the kernel in the program itself, not only the routing decision:
    # lowering the same public search shows the Mosaic call (no compile)
    fused = "tpu_custom_call" in jax.jit(
        lambda ix, q: cagra.search(sp, ix, q, k)).lower(idx, judged).as_text()
    say("cagra", rows=dataset.shape[0], build_wall_s=round(build_s, 1),
        first_search_wall_s=round(search_s, 2), hop_impl=hop,
        tpu_custom_call=fused if not args.rehearse_on_cpu else "not checked "
        "(rehearsal interprets the kernel)",
        graph_degree=idx.graph_degree, recall_at_10=r)
    check(hop == "fused_arena", f"cagra: hop_impl auto resolved to {hop!r}")
    check(fused or args.rehearse_on_cpu,
          "cagra: no tpu_custom_call in the lowered search — the fused hop "
          "kernel is not in the program")
    check(r >= RECALL_FLOOR, f"cagra: recall@10 {r} < {RECALL_FLOOR}")


def balanced_ids(n: int, shards: int):
    """Global ids for rows 0..n-1 that the routing hash spreads over
    ``shards`` in equal counts (n divisible by shards): every shard then
    builds at one shape, so the shards share their compiled programs.
    Returns (ids, row_of) with ``row_of[ids[i]] == i``."""
    import numpy as np

    from raft_tpu.stream.sharded import shard_of

    per = n // shards
    cand = np.arange(2 * n, dtype=np.int64)
    owner = shard_of(cand, shards)
    ids = np.concatenate([cand[owner == s][:per] for s in range(shards)])
    check(ids.shape == (n,), "balanced ids: candidate range too small")
    row_of = np.full(2 * n, -1, np.int64)
    row_of[ids] = np.arange(n)
    return ids, row_of


def phase_four_chips(args, devs, dataset, judged, ref_ids, ref_dist, k):
    import jax
    import numpy as np

    from raft_tpu.comms import Comms
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.parallel import knn as pknn
    from raft_tpu.stream import MutableIndex, ShardedMutableIndex
    from jax.sharding import Mesh

    devs = devs[:4]
    host = np.asarray(dataset)
    # each shard keeps the single chip's per-device scan work: 8 probes of
    # lists a quarter the size (n_lists 256 over n/4 rows)
    params, sp = ivf_pq_params(args.n)
    shard_params, _ = ivf_pq_params(args.n, shards=4)

    comms = Comms(Mesh(np.array(devs), ("data",)), "data")
    t0 = time.perf_counter()
    _, pids = pknn.knn(comms, dataset, judged, k)
    pids = jax.device_get(pids)
    r, tie_tol = recall_up_to_ties(dataset, judged, pids, ref_dist)
    say("parallel.knn", devices=comms.size(),
        wall_s=round(time.perf_counter() - t0, 1), recall_up_to_ties=r,
        tie_tolerance=tie_tol)
    check(r == 1.0, f"parallel.knn: recall {r} != 1.0 up to ties")

    check(args.n % 4 == 0, "--four-chips needs --n divisible by 4")
    gids, row_of = balanced_ids(args.n, 4)
    t0 = time.perf_counter()
    sharded = ShardedMutableIndex(
        host, n_shards=4, ids=gids,
        build=lambda rows: ivf_pq.build(shard_params, rows),
        search_params=sp, devices=devs, name="sift4")
    placed = []
    for s, sh in enumerate(sharded.shards):
        on = {d for leaf in jax.tree_util.tree_leaves(sh._state.sealed)
              for d in leaf.devices()}
        placed.append(sorted(str(d) for d in on))
        check(on == {devs[s]}, f"shard {s} arrays live on {on}, "
              f"want {devs[s]}")
    say("sharded", shards=4, build_s=round(time.perf_counter() - t0, 1),
        rows_per_shard=args.n // 4, n_lists_per_shard=shard_params.n_lists,
        placement=placed)
    # every shard compiles its own programs for its own device: a ladder
    # up to 16 rows (5 buckets, not 7) keeps the cold warm inside a call
    ids = publish_and_serve("sharded_ivf_pq", sharded.refined_searcher(4),
                            judged, ref_ids, k, args.seed, row_of=row_of,
                            max_batch=16)

    # the comparison last, so a time limit cuts it and not the paths above
    t0 = time.perf_counter()
    single = MutableIndex(ivf_pq.build(params, dataset), search_params=sp,
                          index_params=params, dataset=host, name="single")
    _, single_ids = single.search_refined(judged, k, 4)
    single_ids = np.asarray(single_ids)
    say("single-chip twin", build_and_search_s=round(time.perf_counter()
                                                     - t0, 1),
        recall_at_10=recall(single_ids, ref_ids))
    overlap = recall(ids, single_ids)
    # two answers that each reach recall 0.95 share at least 90% of ids
    tol = 2 * RECALL_FLOOR - 1
    say("sharded vs single-chip", id_overlap=overlap, tolerance=round(tol, 4))
    check(overlap >= tol, f"sharded ids agree with the single-chip answer "
          f"on {overlap} < {tol}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=1_000_000, help="corpus rows")
    ap.add_argument("--queries", type=int, default=10_000)
    ap.add_argument("--judged", type=int, default=1_000,
                    help="queries answered and checked against the reference")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device sharded path")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="CPU rehearsal at tiny sizes: interpreted kernels, "
                    "no result line")
    args = ap.parse_args(argv)
    k = 10

    # the repo next to this script, or nothing: a lone copy must fail
    check(os.path.isdir(os.path.join(HERE, "raft_tpu")),
          f"no raft_tpu package beside {__file__}")
    sys.path.insert(0, HERE)
    if args.rehearse_on_cpu:
        check(args.n <= 200_000, "--rehearse-on-cpu is for tiny sizes")
        for kernel in ("FUSED_KNN", "CAGRA_HOP", "PQ_SCAN"):
            os.environ[f"RAFT_TPU_{kernel}_INTERPRET"] = "1"

    devs = phase_device(args)

    from raft_tpu.config import enable_compilation_cache
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.runtime import native

    from jax import monitoring

    slow = []  # (seconds, program) of every backend compile

    def on_compile(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            slow.append((seconds, kw.get("fun_name", "?")))

    monitoring.register_event_duration_secs_listener(on_compile)
    cache = enable_compilation_cache()
    say("setup", compile_cache_dir=cache,
        from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        native_runtime=native.available())

    with obs_compile.attribution() as total:
        dataset, judged = phase_corpus(args)
        ref_ids, ref_dist = phase_reference(dataset, judged, k)
        if args.four_chips:
            phase_four_chips(args, devs, dataset, judged, ref_ids, ref_dist, k)
        else:
            phase_ivf_pq(args, dataset, judged, ref_ids, k)
            phase_brute_force(args, dataset, judged, ref_dist, k)
            phase_cagra(args, dataset, judged, ref_ids, k)
    say("total", wall_s=round(time.perf_counter() - T0, 1),
        compile_s=round(total.compile_s, 1), programs=total.programs,
        cache_hits=total.cache_hits, cache_misses=total.cache_misses)
    say("slowest compiles", top=[(round(s, 1), name) for s, name
                                 in sorted(slow, reverse=True)[:6]])

    d0 = devs[0]
    if args.rehearse_on_cpu:
        print(f"REHEARSAL PASSED on {d0.platform} x{len(devs)} — not a chip "
              "result", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

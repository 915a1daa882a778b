"""raft_tpu benchmark entry point (run by the driver on real TPU hardware).

Prints a full-result JSON line after every completed row (take the LAST
line), and is contractually unkillable: ANY Python-visible failure —
including jax import errors, TPU backend init raising OR hanging (watchdog),
and SIGTERM delivered while the interpreter is running Python code — still
emits a complete, parseable snapshot with the failure recorded as a row, and
the process exits 0. (SIGKILL, or a SIGTERM arriving inside a non-yielding
native call, can still drop only the rows after the last printed line.) This
mirrors the reference harness, which always writes its result files and
confines each benchmark case to its own try/catch
(cpp/bench/ann/src/common/benchmark.hpp:111-200).

The primary metric stays the exact brute-force kNN search throughput on
100k x 128, k=10, batch 10k (the round-1 protocol, so rounds are
comparable), served by the fused Pallas distance+top-k kernel
(ops/fused_knn.py). The "rows" field carries the regression suite the driver
archives per round:

  exact_fused_knn_100k           f32 (exact) flagship — the primary value
  exact_xla_control              plain XLA GEMM+top_k, SAME process/queries —
                                 the fused/control ratio is the session-
                                 independent round-over-round signal
  exact_fused_knn_100k_bf16      same kernel, single-pass bf16 MXU mode
  exact_fused_knn_100k_f32x3     compensated bf16x3 mode (f32-class accuracy)
  exact_fused_knn_100k_i8        same data quantized to int8: s8 x s8 -> s32
                                 MXU mode, 1/4 the dataset DMA bytes; carries
                                 i8_over_f32 (recall is vs the f32 row's ids)
  ivf_pq_1m_lid_pq4x64_r4        IVF-PQ on the SIFT-class low-intrinsic-dim
                                 1M set: pq4x64, p8, bf16 LUT, refine 4
  ivf_pq_1m_i8                   the same LID set quantized to int8 bytes
                                 (BigANN regime): byte build + byte refine;
                                 carries i8_over_f32 vs the f32 LID row
  serve_ivf_pq_100k              raft_tpu.serve A/B: closed-loop threaded
                                 load through SearchService (micro-batched)
                                 vs sequential batch-1 search on the same
                                 index; carries serve_over_seq, p50/p99 ms,
                                 mean batch occupancy, and the mid-load
                                 hot-swap proof (swap.failed == 0,
                                 swap.compile_s == 0). `--serve` runs ONLY
                                 this row (parameter iteration loop).
  serve_pipeline_100k            host-free flush pipeline A/B (ISSUE 12):
                                 the SAME closed-loop threaded load served
                                 synchronously (pipeline_depth=0, the
                                 BENCH_r05-era flush) vs pipelined
                                 (bounded in-flight completion + pinned
                                 double-buffered staging with donation) —
                                 per-flush QPS and p50/p99 both modes at
                                 identical recall, the queue-wait vs
                                 flush-time decomposition per mode (the
                                 win must land on the flush side), mean
                                 dispatches per flush, zero failed
                                 queries, zero cold compiles across the
                                 pipelined window, and flat staging-ledger
                                 bytes across post-load waves (donation
                                 returns the previous query buffer).
                                 `--serve-pipeline` runs ONLY this row.
  serve_churn_ivf_pq_100k        raft_tpu.stream churn row: closed-loop
                                 mixed read/write load on a
                                 MutableIndex(ivf_pq) — p50/p99 search
                                 latency + write throughput under sustained
                                 upsert+delete, >= 2 mid-load compaction
                                 swaps with zero failed queries
                                 (churn.failed == 0), mid-churn recall@10
                                 within 0.01 of a fresh-oracle build
                                 (recall_gap), and zero cold compiles on
                                 the search hot path (churn.compile_s == 0,
                                 rehearsal-warmed). `--serve-churn` runs
                                 ONLY the churn rows.
  serve_churn_cagra_100k         the same churn protocol on a CAGRA-backed
                                 MutableIndex: compactions run the REBUILD
                                 path (no extend for graphs), so the row
                                 measures build speed as serving capacity —
                                 write_rows_per_s is bounded by the rebuild
                                 wall (churn.compaction_wall_s); the r07
                                 mini-batch coarse EM + sharded builds
                                 surface here as write throughput.
  serve_shard_churn_100k         sharded serving tier (ISSUE 9):
                                 ShardedMutableIndex(ivf_flat) scatter-
                                 gathered over 1/2/4/8 device-pinned
                                 shards at proportional operating points —
                                 closed-loop QPS per shard count
                                 (qps_by_shards, scaling_1_to_4, cores),
                                 then a mixed read/write churn window at
                                 the top shard count with STAGGERED
                                 one-shard-per-cycle compactions (>= 2
                                 folds, churn.failed == 0), zero cold
                                 compiles (rehearsal-warmed; includes the
                                 mesh-wide canary's shadow reranks), and
                                 the fresh-oracle recall inside the live
                                 canary's Wilson interval. `--serve-shard`
                                 runs ONLY this row.
  canary_smoke_100k              raft_tpu.obs.quality overhead A/B
                                 (ISSUE 8): closed-loop served QPS with
                                 canary sampling at 0% vs 1% vs 5% (the
                                 background drainer shadow-reranking
                                 against the exact live-corpus kNN), the
                                 streaming recall estimate + Wilson
                                 interval bracketing the offline truth
                                 (canary.oracle_in_interval), and the
                                 compile-free hot path with monitoring ON
                                 (compile_s == 0). `--canary-smoke` runs
                                 ONLY this row. The churn rows above also
                                 carry a "canary" field: the estimate
                                 measured UNDER churn with compaction
                                 swaps, bracketed against recall_mut.
  tune_smoke_10k                 raft_tpu.tune loop proof (ISSUE 7): a
                                 tiny-budget autotune sweep on a 10k IVF-PQ
                                 index — chosen vs grid-head (hand-picked)
                                 operating point with the QPS ratio in the
                                 row; the full sweeps write TUNE_rXX.json
                                 via bench/tune_sweep.py. `--tune-smoke`
                                 runs ONLY this row.
  fault_smoke_100k               availability proof (ISSUE 11): a sharded
                                 mesh with per-shard replica groups serves
                                 a loaded window during which one replica
                                 is killed (fault-injected) and later
                                 revived — zero failed queries (same-flush
                                 failover to the surviving twin), the
                                 victim actually fenced (strikes > 0) and
                                 healed through the backoff re-probe
                                 (recovery_s), zero cold compiles across
                                 the fence/failover/probe window
                                 (rehearsal-warmed). `--fault-smoke` runs
                                 ONLY the fault rows.
  crash_recovery_100k            crash-durability proof (ISSUE 11): a 100k
                                 MutableIndex with a write-ahead log takes
                                 an un-compacted write burst, "dies" via a
                                 SimulatedCrash between WAL append and
                                 memtable insert, and recovers through
                                 stream.load(wal=) + replay + warm() —
                                 recall_recovered == 1.0 vs an uncrashed
                                 twin (gated by bench/compare.py),
                                 recovery_s + replay_rows_per_s recorded,
                                 zero cold compiles post-warm.
  reshard_churn_100k             elastic-resharding proof (ISSUE 13): a
                                 loaded 2-shard x 2-replica mesh DOUBLES
                                 its shard count online — reader threads
                                 live through fold, carry-over and the
                                 atomic flip with one replica killed
                                 mid-migration — zero failed queries,
                                 zero cold compiles (rehearsal protocol;
                                 the successors' ladders + the doubled
                                 merge warm pre-flip), recall_pre/post vs
                                 the exact mesh oracle held across the
                                 flip (compare.py-gated), plus a measured
                                 crash-mid-reshard recovery: SimulatedCrash
                                 between successor swap and manifest
                                 write, load() recovers the OLD topology
                                 id-for-id (recall_crash_recovered).
                                 `--reshard` runs ONLY this row.
  ivf_flat_1m_p8                 IVF-Flat on the isotropic clustered 1M set
  cagra_1m_itopk32               CAGRA on the same set

  Ratio fields ride IN the rows (fused_over_control on exact_xla_control,
  i8_over_f32 on the i8 rows) so BASELINE round notes can be regenerated
  from the JSON artifact alone (VERDICT item 7).

  Each guarded row scope also attaches an "obs" attribution dict
  (compile_s, cache_hits/misses, collective_bytes — from raft_tpu.obs via
  jax.monitoring) so the artifact says WHERE the seconds went, not just the
  QPS; `--no-metrics` disables the whole obs surface (rows then carry no
  "obs" field) and proves the disabled path the obs_overhead test guards.

Measurement notes:
- batches are chained inside ONE jitted program with DISTINCT query data and
  materialized to host, so a repeated identical dispatch or a blocking wait
  that returns early cannot inflate QPS;
- all data is generated on-device (jax.random) — a 512 MB host->device
  transfer would dominate the timings;
- the persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR when set,
  else <checkout>/.jax_cache) is enabled at startup, so 1M index builds are
  cold-jit only the first time a checkout runs them;
- rows degrade gracefully: each row has its own try/except, and rows beyond
  the soft time budget are skipped rather than failing the whole bench;
- a complete JSON line is (re)printed after every finished row, so if the
  driver kills the process on a slow-chip day, the LAST printed line still
  carries every row completed so far.
"""

from __future__ import annotations

import json
import sys
import time

SOFT_BUDGET_S = 480.0  # stop starting new rows beyond this
_T0 = time.perf_counter()

_STATE = {"primary": 0.0, "rows": [], "metrics": True}


def _elapsed():
    return time.perf_counter() - _T0


def _note(msg):
    print(f"[bench +{_elapsed():.0f}s] {msg}", file=sys.stderr, flush=True)


def _emit():
    """Print the full result line; called after every completed row so the
    last line on stdout is always a complete, parseable snapshot. When the
    fused kernel did not run, vs_baseline is null. vs_baseline divides by
    the round-1 flagship QPS, which older code measured on a setup that is
    gone: a claim, not a baseline. Depends on nothing but the stdlib, so it
    works even when jax itself is broken."""
    print(json.dumps({
        "metric": "exact brute-force kNN QPS (100k x 128 f32, k=10, batch 10k)",
        "value": round(_STATE["primary"], 1),
        "unit": "QPS",
        "vs_baseline": (round(_STATE["primary"] / 110805.2, 3)
                        if _STATE["primary"] > 0
                        else None),
        "rows": _STATE["rows"],
        "metrics_enabled": _STATE["metrics"],
        "elapsed_s": round(_elapsed(), 1),
    }), flush=True)


def _obs_snap():
    """Flat obs snapshot, or None when metrics are disabled/unavailable —
    never fatal (the bench must survive a broken raft_tpu import)."""
    try:
        from raft_tpu import obs

        if not obs.enabled():
            return None
        return obs.to_json()
    except Exception:
        return None


def _obs_attach(rows, start, before):
    """Attach the compile/cache/collective attribution of one guarded row
    scope to every row it appended (ISSUE 2: BENCH artifacts carry the
    attribution alongside QPS). Rows produced by the same scope share the
    scope's delta; under --no-metrics no "obs" field appears at all (the
    disabled-path proof)."""
    if before is None:
        return
    after = _obs_snap()
    if after is None:
        return
    try:
        from raft_tpu import obs

        d = obs.delta(before, after)

        def tot(prefix):
            return sum(v for k, v in d.items() if k.startswith(prefix))

        summary = {
            "compile_s": round(
                tot('raft_tpu_compile_seconds_sum{stage="compile"}'), 3),
            "cache_hits": int(tot(
                'raft_tpu_compile_cache_total{outcome="hit"}')),
            "cache_misses": int(tot(
                'raft_tpu_compile_cache_total{outcome="miss"}')),
            "collective_bytes": int(tot("raft_tpu_collective_bytes_total")),
        }
        for r in rows[start:]:
            r.setdefault("obs", summary)
    except Exception:
        pass


def _mem_snap():
    """Ledger totals at a row-scope start (peak re-based so the scope's
    peak is the ROW's peak), or None when metrics are disabled — the
    disabled bench carries no "mem" field, mirroring the "obs" field."""
    try:
        from raft_tpu.obs import mem as obs_mem
        from raft_tpu.obs import metrics as obs_metrics

        if not obs_metrics.enabled():
            return None
        obs_mem.reset_peak()
        try:
            from raft_tpu.stream import tiered as _tiered

            _tiered.reset_tier_peak()
        except Exception:
            pass
        return obs_mem.totals()
    except Exception:
        return None


def _mem_attach(rows, start, before):
    """Attach the ledger's peak device/host bytes over one guarded row
    scope to every row it appended (ISSUE 10: BENCH rows carry memory
    alongside QPS — the capacity half of the perf story). Peaks are the
    scope's own (reset at _mem_snap); deltas subtract the scope-entry
    totals, so a row that allocates and frees reports delta ~0 with a
    real peak."""
    if before is None:
        return
    try:
        from raft_tpu.obs import mem as obs_mem

        after = obs_mem.totals()
        summary = {
            "device_bytes": after["device_bytes"],
            "device_peak_bytes": after["device_peak_bytes"],
            "device_delta_bytes":
                after["device_bytes"] - before["device_bytes"],
            "host_bytes": after["host_bytes"],
            "host_peak_bytes": after["host_peak_bytes"],
            "host_delta_bytes": after["host_bytes"] - before["host_bytes"],
        }
        try:
            # per-tier attribution (ISSUE 15): rows whose scope held a
            # TieredStore carry the tier byte split — the per-scope
            # WATERMARK, not the live totals: a row's store is usually a
            # frame local already freed by the time attribution attaches.
            # Gated by bench/compare.py like recall fields (a lost tier
            # measurement must fail, not pass silently)
            from raft_tpu.stream import tiered as _tiered

            tiers = _tiered.tier_peak()
            if tiers:
                summary["tiers"] = tiers
        except Exception:
            pass
        for r in rows[start:]:
            r.setdefault("mem", summary)
    except Exception:
        pass


def _events_snap():
    """Journal counts-by-kind at a row-scope start, or None when metrics
    are disabled — the disabled bench carries no "events" field, mirroring
    the "obs"/"mem" fields. Cumulative counts, so ring eviction during the
    scope cannot under-report."""
    try:
        from raft_tpu.obs import events as obs_events
        from raft_tpu.obs import metrics as obs_metrics

        if not obs_metrics.enabled():
            return None
        return obs_events.counts_by_kind()
    except Exception:
        return None


def _events_delta(before):
    """Per-kind event counts emitted since ``before`` (ISSUE 17: the
    fault/reshard/tiered rows carry what the event plane SAW — a fence
    that fired zero ``replica_fenced`` events is a lost measurement).
    Gated by bench/compare.py on field presence like recall fields."""
    if before is None:
        return None
    try:
        from raft_tpu.obs import events as obs_events

        after = obs_events.counts_by_kind()
        delta = {k: after[k] - before.get(k, 0) for k in sorted(after)
                 if after[k] - before.get(k, 0) > 0}
        return delta
    except Exception:
        return None


def _recall(ids, gt):
    import numpy as np

    ids, gt = np.asarray(ids), np.asarray(gt)
    k = gt.shape[1]
    return float(np.mean([len(set(ids[r, :k]) & set(gt[r])) / k
                          for r in range(gt.shape[0])]))


def _measure_qps(search_fn, query_sets, m, use_jit=True):
    """Best-of-N wall time over distinct query sets, host-materialized.

    ``use_jit=False`` for index searches: they carry their own internal jit
    caches, and an enclosing jit would re-trace the whole 1M-scale pipeline
    into one giant program (minutes of extra compile for no steady-state
    gain).
    """
    import jax
    import numpy as np

    jax.block_until_ready(query_sets)
    f = jax.jit(search_fn) if use_jit else search_fn
    np.asarray(jax.tree_util.tree_leaves(f(query_sets[0]))[0])  # compile+warm
    best = float("inf")
    out = None
    for qs in query_sets[1:]:
        t0 = time.perf_counter()
        out = f(qs)
        np.asarray(jax.tree_util.tree_leaves(out)[0])
        best = min(best, time.perf_counter() - t0)
    return m / best, out


def _flagship_exact(rows, n=100_000, d=128, m=10_000, k=10, n_batches=10):
    """Exact kNN 100k x 128 — identical protocol to round 1 (the shape
    arguments exist ONLY so the CPU smoke test can exercise every row body
    at interpret-mode scale; the driver always runs the defaults).

    Sets _STATE["primary"]; every sub-measurement is individually guarded
    so one mode's failure never loses another's row. A fused-kernel failure
    is an error row under the flagship's name: no other pipeline stands in
    for it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from raft_tpu.neighbors.brute_force import _bf_knn_fused
    from raft_tpu.distance.types import DistanceType
    key = jax.random.key(0)
    kd, *kq = jax.random.split(key, 5)
    dataset = jax.random.uniform(kd, (n, d), jnp.float32)

    def one_set(kk):
        return jax.random.uniform(kk, (n_batches, m, d), jnp.float32)

    qsets = [one_set(kk) for kk in kq]

    def mode_searches(mode):
        def searches(qs):
            return lax.map(lambda q: _bf_knn_fused(
                dataset, q, k, DistanceType.L2Expanded, mode, None), qs)
        return searches

    # the plain XLA GEMM+top_k pipeline: the in-process control row
    def searches_xla(qs):
        from raft_tpu.neighbors.brute_force import _bf_knn

        return lax.map(lambda q: _bf_knn(
            dataset, q, k, DistanceType.L2Expanded, 2.0, 1000, 1000), qs)

    try:
        qps, out_f32 = _measure_qps(mode_searches("float32"), qsets,
                                    n_batches * m)
        _STATE["primary"] = qps
        rows.append({"name": "exact_fused_knn_100k", "qps": round(qps, 1),
                     "recall": 1.0, "build_s": 0.0})
        _emit()  # the primary row must survive a kill during bf16/f32x3
    except Exception as e:  # pragma: no cover - bench resilience
        # a fused-kernel failure (e.g. a Mosaic lowering change) is an
        # error row: the flagship's name never carries another pipeline
        rows.append({"name": "exact_fused_knn_100k", "error": str(e)[:200]})
        return

    # in-process control (VERDICT r4 #7): the plain XLA GEMM+top_k pipeline
    # measured in the SAME process on the SAME query sets. Absolute numbers
    # swing between sessions, so the round-over-round signal is the
    # fused/control RATIO within one process, not the absolute vs_baseline
    # quotient across sessions.
    try:
        qps_c, _ = _measure_qps(searches_xla, qsets, n_batches * m)
        rows.append({"name": "exact_xla_control", "qps": round(qps_c, 1),
                     "recall": 1.0, "build_s": 0.0,
                     "fused_over_control": round(_STATE["primary"] / qps_c, 3)})
    except Exception as e:  # pragma: no cover - bench resilience
        rows.append({"name": "exact_xla_control", "error": str(e)[:200]})
    _emit()

    # bf16 (one MXU pass instead of six; ~0.98 worst-case set recall on
    # uniform data) and f32x3 (three passes, f32-class accuracy) modes,
    # measured alongside (VERDICT r2 #2). Each row's recall is the set recall
    # of its ids against the f32 row's ids on the same query set (VERDICT r3
    # #7: the accuracy claims must live in the driver artifact, not
    # docstrings). Guarded per mode.
    import numpy as np

    ref_ids = np.asarray(out_f32[1])[0, :1000]  # first batch, 1k queries
    for mode, row_name in (("bfloat16", "exact_fused_knn_100k_bf16"),
                           ("float32x3", "exact_fused_knn_100k_f32x3")):
        try:
            qps_m, out_m = _measure_qps(mode_searches(mode), qsets,
                                        n_batches * m)
            rec = _recall(np.asarray(out_m[1])[0, :1000], ref_ids)
            rows.append({"name": row_name, "qps": round(qps_m, 1),
                         "recall": round(rec, 4), "build_s": 0.0})
        except Exception as e:  # pragma: no cover - bench resilience
            rows.append({"name": row_name, "error": str(e)[:200]})
        _emit()

    # int8 row (the byte-dataset tentpole): the SAME uniform data quantized
    # onto the 256 byte levels — one quarter of the f32 dataset DMA bytes,
    # s8 x s8 -> s32 MXU contraction (~2x bf16 peak). Recall is vs the f32
    # row's ids on identical queries, so the row's recall claim is "vs exact
    # f32 ground truth" (it folds in the quantization of the 1/255-wide
    # bins, not just kernel error); the i8_over_f32 ratio rides in the row
    # so round notes regenerate from the JSON artifact alone.
    try:
        from raft_tpu.neighbors.brute_force import _bf_knn_s8

        def to_i8(a):
            return jnp.clip(jnp.round(a * 255.0 - 128.0),
                            -128, 127).astype(jnp.int8)

        ds_i8 = to_i8(dataset)
        qsets_i8 = [to_i8(qs) for qs in qsets]

        def searches_s8(qs):
            return lax.map(lambda q: _bf_knn_s8(
                ds_i8, q, k, DistanceType.L2Expanded, None), qs)

        qps_i, out_i = _measure_qps(searches_s8, qsets_i8, n_batches * m)
        rec = _recall(np.asarray(out_i[1])[0, :1000], ref_ids)
        rows.append({"name": "exact_fused_knn_100k_i8",
                     "qps": round(qps_i, 1), "recall": round(rec, 4),
                     "build_s": 0.0,
                     "i8_over_f32": round(qps_i / _STATE["primary"], 3)})
    except Exception as e:  # pragma: no cover - bench resilience
        rows.append({"name": "exact_fused_knn_100k_i8", "error": str(e)[:200]})
    _emit()


def _make_clustered(n, d, m, ncl, n_qsets=3, seed=42):
    """Isotropic clustered synthetic set + query sets, generated on-device
    (same distribution as bench/ann/run.py load_dataset: gaussian blobs with
    full-dimensional residuals — PQ's worst case). Shared by the 1M rows and
    the serve row (which runs it at 100k)."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.key(seed), 3 + n_qsets)
    kc, kl, kn = keys[:3]
    centers = jax.random.uniform(kc, (ncl, d), jnp.float32) * 10.0

    def draw(kk_lab, kk_noise, count):
        labels = jax.random.randint(kk_lab, (count,), 0, ncl)
        return centers[labels] + 0.5 * jax.random.normal(kk_noise, (count, d))

    dataset = draw(kl, kn, n)
    qsets = []
    for kk in keys[3:]:
        ka, kb = jax.random.split(kk)
        qsets.append(draw(ka, kb, m))
    return dataset, qsets


def _make_1m():
    return _make_clustered(1_000_000, 128, 10_000, 2000)


def _make_lid_1m():
    """The SIFT-class 1M x 128 set (:func:`raft_tpu.random.make_sift_like`)
    with its three 10k query sets, at the seed every 1M row has used."""
    from raft_tpu.random import make_sift_like

    return make_sift_like(1_000_000, 10_000, n_qsets=3, seed=7)


def _lid_estimate(dataset, k=20, n_sample=1000):
    """Levina-Bickel MLE intrinsic-dimension estimate from k-NN radii of a
    dataset sample (the measured grounding VERDICT r3 #2 asked for; real
    descriptor data reports ~5-15 at comparable scales)."""
    import jax
    import numpy as np

    from raft_tpu.distance.types import DistanceType
    from raft_tpu.neighbors.brute_force import _bf_knn_fused

    ids = jax.random.choice(jax.random.key(1), dataset.shape[0],
                            (n_sample,), replace=False)
    d2, _ = _bf_knn_fused(dataset, dataset[ids], k + 1,
                          DistanceType.L2Expanded, "float32", None)
    r = np.sqrt(np.maximum(np.asarray(d2)[:, 1:], 1e-12))  # drop self
    inv = np.log(r[:, -1:] / np.maximum(r[:, :-1], 1e-12)).mean(axis=1)
    return float(np.mean(1.0 / np.maximum(inv, 1e-9)))


def _ground_truth(dataset, queries, k=10):
    import numpy as np

    from raft_tpu.neighbors.brute_force import _bf_knn_fused
    from raft_tpu.distance.types import DistanceType

    _, gt = _bf_knn_fused(dataset, queries, k,
                          DistanceType.L2Expanded, "float32", None)
    return np.asarray(gt)


def _row_ivf_pq_lid(rows, box=None):
    """IVF-PQ regression row (VERDICT r2 missing #2): the shipped default
    config (pq4x64, bits-aware auto pq_dim) + refine 4 on the SIFT-class set
    — the r02 sweep's headline operating point (0.9991 @ 26.4k QPS).
    ``box`` (optional dict) receives the generated dataset/qsets so the i8
    row can quantize the same data instead of paying a second 1M draw."""
    import jax
    import numpy as np

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors.refine import refine

    _note("LID 1M dataset")
    dataset, qsets = _make_lid_1m()
    jax.block_until_ready([dataset] + qsets)
    if box is not None:
        box["dataset"], box["qsets"] = dataset, qsets
    _note("LID estimate")
    lid = _lid_estimate(dataset)
    _note("LID ground truth 1k queries")
    gt = _ground_truth(dataset, qsets[-1][:1000])

    _note("ivf_pq build")
    t0 = time.perf_counter()
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=1024, pq_bits=4, pq_dim=64, seed=0), dataset)
    jax.block_until_ready(idx.list_codes)
    build_s = time.perf_counter() - t0
    sp = ivf_pq.SearchParams(n_probes=8, lut_dtype="bfloat16")

    def searcher(q):
        _, cand = ivf_pq.search(sp, idx, q, 40)
        return refine(dataset, q, cand, 10)

    qps, out = _measure_qps(searcher, qsets, qsets[0].shape[0], use_jit=False)
    rows.append({"name": "ivf_pq_1m_lid_pq4x64_r4",
                 "qps": round(qps, 1),
                 "recall": round(_recall(np.asarray(out[1])[:1000], gt), 4),
                 "build_s": round(build_s, 1),
                 "lid_estimate": round(lid, 1)})


def _row_ivf_pq_i8(rows, dataset, qsets, n_lists=1024, pq_dim=64):
    """IVF-PQ on int8 bytes (the byte-dataset tentpole; reference ships
    dedicated ivf_pq int8_t/uint8_t instantiations — BigANN-class byte data
    is PQ's home regime): the LID set affinely quantized onto the 256 byte
    levels. Ground truth is the exact kNN of the SAME bytes (s8 MXU path,
    exact integer distances), so the row's recall measures the index, not
    the quantization; the i8_over_f32 QPS ratio vs the f32 LID row rides in
    the row itself so round notes regenerate from the JSON artifact."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors.brute_force import knn
    from raft_tpu.neighbors.refine import refine

    lo = float(dataset.min())
    scale = 255.0 / max(float(dataset.max()) - lo, 1e-9)

    def to_i8(a):
        return jnp.clip(jnp.round((a - lo) * scale - 128.0),
                        -128, 127).astype(jnp.int8)

    ds = to_i8(dataset)
    qs = [to_i8(q) for q in qsets]
    jax.block_until_ready([ds] + qs)
    _note("i8 ground truth 1k queries")
    _, gt = knn(ds, qs[-1][:1000], 10)  # exact s8 kNN of the bytes
    gt = np.asarray(gt)

    _note("ivf_pq i8 build")
    t0 = time.perf_counter()
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4, pq_dim=pq_dim,
                           seed=0), ds)
    jax.block_until_ready(idx.list_codes)
    build_s = time.perf_counter() - t0
    sp = ivf_pq.SearchParams(n_probes=8, lut_dtype="bfloat16")

    def searcher(q):
        _, cand = ivf_pq.search(sp, idx, q, 40)
        return refine(ds, q, cand, 10)  # exact byte refine (1-byte gathers)

    qps, out = _measure_qps(searcher, qs, qs[0].shape[0], use_jit=False)
    f32_qps = next((r["qps"] for r in rows
                    if r.get("name") == "ivf_pq_1m_lid_pq4x64_r4"
                    and "qps" in r), None)
    rows.append({"name": "ivf_pq_1m_i8",
                 "qps": round(qps, 1),
                 "recall": round(_recall(np.asarray(out[1])[:1000], gt), 4),
                 "build_s": round(build_s, 1),
                 "i8_over_f32": (round(qps / f32_qps, 3)
                                 if f32_qps else None)})


def _row_serve(rows, n=100_000, d=128, n_lists=1024, pq_dim=64, k=10,
               n_probes=8, threads=8, per_thread=400, seq_queries=512,
               max_batch=64, max_wait_us=2000.0, ncl=2000):
    """Serving-layer A/B (raft_tpu.serve): closed-loop multi-threaded load
    through SearchService vs the same index searched sequentially at
    batch 1 — the protocol every caller WITHOUT a batcher runs today.

    Three claims ride in the row (the ISSUE 3 acceptance set):
    - ``serve_over_seq`` — micro-batching amortizes per-dispatch overhead
      across the bucket; the acceptance bar is >= 3x at identical recall
      (same index, same params, so recall is measured once on the service's
      own outputs against exact ground truth).
    - a **mid-load hot-swap**: a second index (pre-built outside the timed
      window) is published while the closed loop runs; ``swap.failed`` MUST
      be 0 (in-flight requests finish on the old version).
    - **zero cold compiles on the serving path**: the whole loaded window —
      including the swap's warmup and flip — runs under obs compile
      attribution; ``swap.compile_s``/``swap.cache_misses`` must be 0
      because publish() warmed every bucket BEFORE the flip and the rebuilt
      index is HLO-identical at every bucket shape.

    p50/p99 are per-request milliseconds measured by the submitting
    threads; occupancy is the obs histogram's mean over the window."""
    import threading

    import jax
    import numpy as np

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import metrics as obs_metrics
    from raft_tpu.serve import SearchService

    _note("serve: dataset")
    dataset, qsets = _make_clustered(n, d, max(threads * per_thread, 1000),
                                     ncl, n_qsets=1, seed=11)
    jax.block_until_ready([dataset] + qsets)
    _note("serve: ground truth")
    gt = _ground_truth(dataset, qsets[0][:1000], k=k)  # gt width = serving k
    # host copy: the submitters slice single rows per request, and eager
    # jax slicing would compile one tiny program per offset — the serve
    # path must stay on the warmed bucket programs only
    pool = np.asarray(qsets[0])

    _note("serve: ivf_pq build v1")
    t0 = time.perf_counter()
    params = ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4, pq_dim=pq_dim,
                                seed=0)
    idx = ivf_pq.build(params, dataset)
    jax.block_until_ready(idx.list_codes)
    build_s = time.perf_counter() - t0
    sp = ivf_pq.SearchParams(n_probes=n_probes, lut_dtype="bfloat16")

    # the served pipeline is the flagship operating point: PQ candidates at
    # 4k wide + exact refine (the ivf_pq_1m_lid_pq4x64_r4 pattern) —
    # published as a CUSTOM hook (any callable with kind/dim/query_dtype),
    # the serve surface for composed pipelines
    def hook_for(index):
        from raft_tpu.neighbors.refine import refine

        def fn(queries, k_):
            _, cand = ivf_pq.search(sp, index, queries, 4 * k_)
            return refine(dataset, queries, cand, k_)

        fn.kind, fn.dim, fn.query_dtype = "ivf_pq+refine", d, "float32"
        return fn

    serving = hook_for(idx)

    # sequential batch-1 baseline: warm the batch-1 program first, then a
    # timed loop of one-query calls — the no-batcher serving pattern
    _note("serve: sequential batch-1 baseline")

    def one(q):
        out = serving(q, k)
        jax.block_until_ready(out)
        return out

    one(pool[:1])
    t0 = time.perf_counter()
    for j in range(seq_queries):
        one(pool[j:j + 1])
    seq_qps = seq_queries / (time.perf_counter() - t0)

    # the swap target is built OUTSIDE the timed window (a production
    # rebuild happens on a builder host); only publish() lands mid-load
    _note("serve: ivf_pq build v2 (swap target)")
    idx2 = ivf_pq.build(params, dataset)
    jax.block_until_ready(idx2.list_codes)

    _note("serve: closed-loop load, %d threads" % threads)
    svc = SearchService(max_batch=max_batch, max_wait_us=max_wait_us,
                        max_queue_rows=max(4 * max_batch * threads, 256))
    svc.publish("serve", serving, k=k)
    stream = f"serve.k{k}"
    occ_before = obs_metrics.to_json()
    n_req = threads * per_thread
    lats, results, failures = [], {}, []
    lock = threading.Lock()
    swap_at = n_req // 2
    served = [0]
    swap_gate = threading.Event()

    def submitter(tid):
        my_lats, my_res = [], {}
        for j in range(per_thread):
            qi = (tid + j * threads) % pool.shape[0]
            t0 = time.perf_counter()
            try:
                _, ids = svc.search("serve", pool[qi:qi + 1], k)
            except Exception as e:  # pragma: no cover - any loss fails the row
                with lock:
                    failures.append(f"{type(e).__name__}: {str(e)[:80]}")
                    served[0] += 1  # the swap gate must open even on losses
                    if served[0] >= swap_at:
                        swap_gate.set()
                continue
            my_lats.append(time.perf_counter() - t0)
            if qi < 1000:
                my_res[qi] = np.asarray(ids)[0]
            with lock:
                served[0] += 1
                if served[0] >= swap_at:
                    swap_gate.set()
        with lock:
            lats.extend(my_lats)
            results.update(my_res)

    with obs_compile.attribution() as serving_rec:
        workers = [threading.Thread(target=submitter, args=(t,))
                   for t in range(threads)]
        t_load = time.perf_counter()
        for w in workers:
            w.start()
        # hot-swap at mid-load: warm + flip while the loop is in flight
        swap_gate.wait(timeout=600)
        swap_report = svc.publish("serve", hook_for(idx2), k=k)
        for w in workers:
            w.join(600)
        load_s = time.perf_counter() - t_load
    svc.shutdown()

    occ_delta = obs_metrics.delta(occ_before, obs_metrics.to_json())
    occ_sum = occ_delta.get(
        'raft_tpu_serve_batch_occupancy_sum{stream="%s"}' % stream, 0.0)
    occ_cnt = occ_delta.get(
        'raft_tpu_serve_batch_occupancy_count{stream="%s"}' % stream, 0)
    lats_ms = np.sort(np.array(lats if lats else [0.0])) * 1e3
    if results:
        got = np.stack([results[i] for i in sorted(results)])
        recall = round(_recall(got, gt[sorted(results)]), 4)
    else:  # pragma: no cover - every request failed; the row still emits
        recall = None
    rows.append({
        "name": "serve_ivf_pq_100k",
        "qps": round((n_req - len(failures)) / load_s, 1),
        "seq_qps": round(seq_qps, 1),
        "serve_over_seq": round(
            (n_req - len(failures)) / load_s / seq_qps, 3),
        "p50_ms": round(float(lats_ms[len(lats_ms) // 2]), 3),
        "p99_ms": round(float(lats_ms[int(len(lats_ms) * 0.99) - 1]), 3),
        "mean_batch_occupancy": round(occ_sum / max(occ_cnt, 1), 3),
        "recall": recall,
        "build_s": round(build_s, 1),
        "threads": threads, "max_batch": max_batch,
        "max_wait_us": max_wait_us,
        "swap": {
            "failed": len(failures),
            "version": swap_report["version"],
            # zero-cold-compile proof for the WHOLE loaded window (swap
            # warmup + flip + every flush): publish warmed before the flip
            # and the rebuilt index is HLO-identical per bucket
            "compile_s": round(serving_rec.compile_s, 3),
            "cache_misses": serving_rec.cache_misses,
        },
        "failures": failures[:5],
    })


def _row_serve_pipeline(rows, n=100_000, d=128, n_lists=1024, pq_dim=64,
                        k=10, n_probes=8, threads=8, per_thread=300,
                        max_batch=64, max_wait_us=2000.0, ncl=2000,
                        depth=2, waves=3):
    """Host-free flush pipeline A/B (ISSUE 12): the same closed-loop
    threaded load through SearchService served with the synchronous flush
    (``pipeline_depth=0`` — the batcher blocks on the device per flush,
    the BENCH_r05-era protocol) vs the pipelined flush (bounded in-flight
    completion stage + pinned double-buffered staging with donation).

    The acceptance set rides in the row:

    - ``pipelined_over_sync`` — per-flush QPS ratio at identical recall
      (same index, same query pool, both modes' recall in the row);
    - ``decomp`` — the PR 7 split histograms per mode: a request's p99
      decomposes into queue wait + flush share, and the pipeline's win
      must land on the FLUSH side (overlapped H2D/compute/D2H), not on
      queue accounting;
    - ``dispatches_per_flush_mean`` — the obs.dispatch fusion meter
      (pipelined mode; the sync flush materializes inline and records
      none);
    - zero failed queries both modes and ZERO cold compiles across the
      whole pipelined loaded window (publish warmed the bucket ladder,
      the committed-placement executables, and the per-bucket donated
      stage programs before the first flush);
    - ``staging`` — uploads/donation-frees counters plus per-wave
      samples across ``waves`` post-load single-bucket waves: the
      ledger's accounted staging bytes stay FLAT (the footprint is
      constant by design — one slot per bucket) while
      ``donation_frees`` ADVANCES every wave, i.e. XLA actually deleted
      the previous flush's query buffer on every donated upload (the
      frees counter, fed by ``is_deleted()``, is the observation that
      donation works; a backend that ignored ``donate_argnums`` would
      flatline it).
    """
    import threading

    import jax
    import numpy as np

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import mem as obs_mem
    from raft_tpu.obs import metrics as obs_metrics
    from raft_tpu.serve import SearchService

    _note("pipeline: dataset")
    dataset, qsets = _make_clustered(n, d, max(threads * per_thread, 1000),
                                     ncl, n_qsets=1, seed=13)
    jax.block_until_ready([dataset] + qsets)
    _note("pipeline: ground truth")
    gt = _ground_truth(dataset, qsets[0][:1000], k=k)
    # host copy: single-row slices per request must not compile per offset
    pool = np.asarray(qsets[0])

    _note("pipeline: ivf_pq build")
    params = ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4, pq_dim=pq_dim,
                                seed=0)
    idx = ivf_pq.build(params, dataset)
    jax.block_until_ready(idx.list_codes)
    sp = ivf_pq.SearchParams(n_probes=n_probes, lut_dtype="bfloat16")

    # the flagship composed pipeline (PQ candidates at 4k + exact refine),
    # published as a custom hook — the same serving surface _row_serve uses
    def hook():
        from raft_tpu.neighbors.refine import refine

        def fn(queries, k_):
            _, cand = ivf_pq.search(sp, idx, queries, 4 * k_)
            return refine(dataset, queries, cand, k_)

        fn.kind, fn.dim, fn.query_dtype = "ivf_pq+refine", d, "float32"
        return fn

    stream = f"pipe.k{k}"
    n_req = threads * per_thread

    def load(svc):
        """One closed-loop window — identical protocol both modes."""
        lats, results, failures = [], {}, []
        lock = threading.Lock()

        def submitter(tid):
            my_lats, my_res = [], {}
            for j in range(per_thread):
                qi = (tid + j * threads) % pool.shape[0]
                t0 = time.perf_counter()
                try:
                    _, ids = svc.search("pipe", pool[qi:qi + 1], k)
                except Exception as e:  # pragma: no cover - fails the row
                    with lock:
                        failures.append(f"{type(e).__name__}: {str(e)[:80]}")
                    continue
                my_lats.append(time.perf_counter() - t0)
                if qi < 1000:
                    my_res[qi] = np.asarray(ids)[0]
            with lock:
                lats.extend(my_lats)
                results.update(my_res)

        before = obs_metrics.to_json()
        workers = [threading.Thread(target=submitter, args=(t,))
                   for t in range(threads)]
        t_load = time.perf_counter()
        for w in workers:
            w.start()
        for w in workers:
            w.join(600)
        load_s = time.perf_counter() - t_load
        delta = obs_metrics.delta(before, obs_metrics.to_json())

        def hist_ms(nm):
            s = delta.get('raft_tpu_serve_%s_sum{stream="%s"}'
                          % (nm, stream), 0.0)
            c = delta.get('raft_tpu_serve_%s_count{stream="%s"}'
                          % (nm, stream), 0)
            return round(1e3 * s / max(c, 1), 3)

        lats_ms = np.sort(np.array(lats if lats else [0.0])) * 1e3
        recall = None
        if results:  # pragma: no branch - losses already fail the row
            got = np.stack([results[i] for i in sorted(results)])
            recall = round(_recall(got, gt[sorted(results)]), 4)
        disp_c = delta.get(
            'raft_tpu_serve_dispatches_per_flush_count{stream="%s"}'
            % stream, 0)
        disp_s = delta.get(
            'raft_tpu_serve_dispatches_per_flush_sum{stream="%s"}'
            % stream, 0.0)
        return {
            "qps": round((n_req - len(failures)) / load_s, 1),
            "p50_ms": round(float(lats_ms[len(lats_ms) // 2]), 3),
            "p99_ms": round(float(lats_ms[int(len(lats_ms) * 0.99) - 1]), 3),
            "recall": recall, "failed": len(failures),
            "failures": failures[:5],
            "queue_wait_ms_mean": hist_ms("queue_wait_seconds"),
            "flush_ms_mean": hist_ms("flush_seconds"),
            "dispatches_per_flush_mean":
                round(disp_s / disp_c, 2) if disp_c else None,
        }

    _note("pipeline: sync (depth=0) closed loop, %d threads" % threads)
    svc = SearchService(max_batch=max_batch, max_wait_us=max_wait_us,
                        max_queue_rows=max(4 * max_batch * threads, 256),
                        pipeline_depth=0)
    svc.publish("pipe", hook(), k=k)
    sync = load(svc)
    svc.shutdown()

    _note("pipeline: pipelined (depth=%d) closed loop" % depth)
    svc = SearchService(max_batch=max_batch, max_wait_us=max_wait_us,
                        max_queue_rows=max(4 * max_batch * threads, 256),
                        pipeline_depth=depth,
                        staging_device=jax.devices()[0])
    report = svc.publish("pipe", hook(), k=k)
    with obs_compile.attribution() as rec:
        piped = load(svc)
        # donation/no-growth proof: serial single-row waves AFTER the load
        # (bucket-1 flushes only, every slot long since resident) — the
        # accounted staging bytes stay FLAT while donation_frees ADVANCES
        # every wave (the previous buffer actually deleted per upload)
        levels = []
        for _ in range(waves):
            for j in range(2 * max_batch):
                svc.search("pipe", pool[j:j + 1], k)
            ent = [e for e in obs_mem.breakdown()
                   if e["component"] == "serve/staging"
                   and e["name"] == stream]
            stw = svc.staging_stats().get(stream, {})
            levels.append({
                "ledger_bytes": (int(ent[0]["device_bytes"]
                                     + ent[0]["host_bytes"])
                                 if ent else -1),
                "donation_frees": stw.get("donation_frees", -1),
                "uploads": stw.get("uploads", -1),
            })
    staging = dict(svc.staging_stats().get(stream, {}))
    staging["by_wave"] = levels
    svc.shutdown()

    rows.append({
        "name": "serve_pipeline_100k",
        "qps": piped["qps"],
        "p50_ms": piped["p50_ms"], "p99_ms": piped["p99_ms"],
        "recall": piped["recall"],
        "sync_qps": sync["qps"],
        "sync_p50_ms": sync["p50_ms"], "sync_p99_ms": sync["p99_ms"],
        "sync_recall": sync["recall"],
        "pipelined_over_sync": round(
            piped["qps"] / max(sync["qps"], 1e-9), 3),
        "decomp": {
            mode: {"queue_wait_ms_mean": r["queue_wait_ms_mean"],
                   "flush_ms_mean": r["flush_ms_mean"]}
            for mode, r in (("sync", sync), ("pipelined", piped))},
        "dispatches_per_flush_mean": piped["dispatches_per_flush_mean"],
        "staging": staging,
        "failed": sync["failed"] + piped["failed"],
        "failures": (sync["failures"] + piped["failures"])[:5],
        "pipeline": {
            "depth": depth,
            "staging_warmed": report.get("staging_warmed"),
            # zero-cold-compile proof for the WHOLE pipelined window
            # (load + the ledger waves): publish warmed the ladder, the
            # committed placements, and the donated stage programs
            "compile_s": round(rec.compile_s, 3),
            "cache_misses": rec.cache_misses,
        },
        "threads": threads, "max_batch": max_batch,
        "max_wait_us": max_wait_us,
    })


def _row_serve_churn(rows, n=100_000, d=128, n_lists=1024, pq_dim=64, k=10,
                     n_probes=8, threads=8, writer_steps=64,
                     upserts_per_step=96, deletes_per_step=32,
                     delta_capacity=4096, compact_fill=0.75,
                     max_batch=64, max_wait_us=2000.0, ncl=2000,
                     n_eval=512):
    """Mutable-index churn A/B (raft_tpu.stream, ISSUE 5): closed-loop
    mixed read/write load on MutableIndex(ivf_pq) at 100k — reader threads
    search through SearchService while a writer upserts + deletes and the
    compactor folds the delta into the sealed index mid-load (>= 2 swaps).

    Four claims ride in the row (the ISSUE 5 acceptance set):
    - **zero failed/dropped queries** across the whole churn window,
      compaction swaps included (``churn.failed == 0``);
    - **mid-churn recall parity**: recall@10 of the live mutable index
      (measured through the service, at warmed bucket shapes, right after
      the first compaction) within 0.01 of a fresh oracle ivf_pq build over
      exactly the live rows at that instant (``recall_gap``);
    - **write throughput** (``write_rows_per_s``) alongside p50/p99 search
      latency — the mixed-load numbers a capacity plan needs;
    - **zero cold compiles on the search hot path**: the whole loaded
      window — reads, writes, both compaction folds, the publish warms and
      flips — runs under obs compile attribution and must report
      ``compile_s == 0`` / ``cache_misses == 0``. The compaction-epoch
      programs are compiled beforehand by a REHEARSAL of the same
      (deterministic) write schedule against a throwaway wrapper of the
      same sealed index — the production analogue of provisioning warmup
      (docs/warm_builds.md): the write schedule alone determines every
      post-compaction shape, so the rehearsal compiles exactly the program
      set the live window replays, and the attribution then PROVES the
      swaps and the hot path are compile-free.

    The writer triggers ``Compactor.run_once`` synchronously at the
    delta-fill watermark (writer-driven rather than the background poll
    thread, so fold sizes are schedule-deterministic and the rehearsal's
    shapes match); the background-thread mode is covered by
    tests/test_stream.py."""
    from raft_tpu.neighbors import ivf_pq

    params = ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4, pq_dim=pq_dim,
                                seed=0)
    sp = ivf_pq.SearchParams(n_probes=n_probes, lut_dtype="bfloat16")
    _serve_churn_impl(
        rows, name="serve_churn_ivf_pq_100k", note="churn",
        build=lambda x: ivf_pq.build(params, x),
        materialize=lambda idx: idx.list_codes,
        search_params=sp,
        oracle_search=lambda idx, q, kk: ivf_pq.search(sp, idx, q, kk),
        # the live recall canary rides this row (ISSUE 8): the mutable
        # retains the raw rows so the canary's exact shadow oracle covers
        # sealed + delta with tombstones applied
        mutable_kwargs=dict(retain_vectors=False), canary_rate=0.05,
        n=n, d=d, k=k, threads=threads, writer_steps=writer_steps,
        upserts_per_step=upserts_per_step, deletes_per_step=deletes_per_step,
        delta_capacity=delta_capacity, compact_fill=compact_fill,
        max_batch=max_batch, max_wait_us=max_wait_us, ncl=ncl, n_eval=n_eval)


def _row_serve_churn_cagra(rows, n=100_000, d=128, k=10, itopk=32,
                           threads=8, writer_steps=48, upserts_per_step=96,
                           deletes_per_step=32, delta_capacity=4096,
                           compact_fill=0.75, max_batch=64,
                           max_wait_us=2000.0, ncl=2000, n_eval=512):
    """CAGRA-backed MutableIndex churn row (ISSUE 6): same protocol and
    acceptance claims as ``_row_serve_churn``, but compaction runs the
    REBUILD path — CAGRA has no extend(), so every fold reconstructs the
    sealed graph from the retained live rows (reclaiming tombstones). That
    makes the row the direct measurement of the build-speed-as-a-serving
    -feature claim: sustainable ``write_rows_per_s`` is bounded by the
    rebuild wall (``churn.compaction_wall_s``), so coarse-EM and sharded
    -build speedups surface here as measured write throughput. Rehearsal
    still proves zero cold compiles: the deterministic schedule fixes every
    epoch's sealed row count, so the rehearsal compiles the exact rebuild +
    search program set the live window replays."""
    from raft_tpu.neighbors import cagra

    params = cagra.IndexParams(seed=0)
    sp = cagra.SearchParams(itopk_size=itopk)
    _serve_churn_impl(
        rows, name="serve_churn_cagra_100k", note="churn-cagra",
        build=lambda x: cagra.build(params, x),
        materialize=lambda idx: idx.graph,
        search_params=sp,
        oracle_search=lambda idx, q, kk: cagra.search(sp, idx, q, kk),
        # rebuild compaction: row store auto-recovered from the sealed
        # dataset; index_params configure each rebuild
        mutable_kwargs=dict(index_params=params),
        n=n, d=d, k=k, threads=threads, writer_steps=writer_steps,
        upserts_per_step=upserts_per_step, deletes_per_step=deletes_per_step,
        delta_capacity=delta_capacity, compact_fill=compact_fill,
        max_batch=max_batch, max_wait_us=max_wait_us, ncl=ncl, n_eval=n_eval)


def _serve_churn_impl(rows, *, name, note, build, materialize, search_params,
                      oracle_search, mutable_kwargs, n, d, k, threads,
                      writer_steps, upserts_per_step, deletes_per_step,
                      delta_capacity, compact_fill, max_batch, max_wait_us,
                      ncl, n_eval, canary_rate=0.0):
    """The shared churn protocol (see _row_serve_churn's docstring for the
    claims): dataset + sealed build, rehearsal (compiles every compaction
    epoch's program set), the attributed live window, then the fresh-oracle
    recall snapshot. ``build``/``oracle_search`` close over the index
    module's params so the IVF-PQ and CAGRA rows differ only in the sealed
    kind and therefore in the fold mode (extend vs rebuild).

    ``canary_rate > 0`` additionally rides the live recall canary
    (ISSUE 8): the mutable retains its raw rows, a RecallCanary samples
    that fraction of served queries at the flush path and shadow-reranks
    them against the exact live-corpus kNN at every write step; the row
    then carries the streaming estimate + Wilson interval and whether the
    fresh-oracle offline recall (recall_mut) fell inside it. The canary
    runs INSIDE the attributed window, so churn.compile_s == 0 also proves
    the canary added zero cold compiles on or off the hot path — its
    per-epoch exact programs are covered by the rehearsal (which warms the
    rehearsal canary after every fold of the same deterministic
    schedule)."""
    import threading

    import jax
    import numpy as np

    from raft_tpu import stream
    from raft_tpu.neighbors.brute_force import knn
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import quality
    from raft_tpu.serve import IndexRegistry, SearchService

    total_upserts = writer_steps * upserts_per_step
    total_deletes = writer_steps * deletes_per_step
    assert total_deletes < n, "delete schedule exceeds the dataset"

    _note(f"{note}: dataset")
    dataset, qsets = _make_clustered(n + total_upserts, d, max(n_eval, 1000),
                                     ncl, n_qsets=1, seed=13)
    jax.block_until_ready([dataset] + qsets)
    x_host = np.asarray(dataset[:n])
    churn_host = np.asarray(dataset[n:])  # the upsert pool, same distribution
    pool = np.asarray(qsets[0])
    eval_q = pool[:n_eval]

    _note(f"{note}: sealed build")
    t0 = time.perf_counter()
    idx = build(dataset[:n])
    jax.block_until_ready(materialize(idx))
    build_s = time.perf_counter() - t0
    sp = search_params

    policy = stream.CompactionPolicy(delta_fill=compact_fill,
                                     tombstone_ratio=None, max_age_s=None)
    mk = dict(mutable_kwargs)
    if canary_rate > 0:
        # the canary's exact oracle needs the raw live rows (PQ codes
        # cannot reconstruct them); CAGRA/brute-force recover them from
        # the sealed dataset already
        mk.pop("retain_vectors", None)
        mk.setdefault("dataset", x_host)

    def write_schedule(mutable, comp, on_step=None, after_compact=None):
        """The deterministic churn schedule — run once as the rehearsal and
        once for real. Returns (#compactions, list of compaction reports)."""
        reports = []
        for step in range(writer_steps):
            lo = step * upserts_per_step
            mutable.upsert(churn_host[lo:lo + upserts_per_step],
                           ids=n + np.arange(lo, lo + upserts_per_step))
            dlo = step * deletes_per_step
            mutable.delete(np.arange(dlo, dlo + deletes_per_step))
            while comp.due():
                reports.append(comp.run_once())
                if after_compact is not None:
                    after_compact()
            if on_step is not None:
                on_step(step, len(reports))
        return reports

    # ---- rehearsal: compile every compaction-epoch program off-line ------
    _note(f"{note}: rehearsal (compiles the epoch program set)")
    from raft_tpu.serve import bucket_sizes

    m0 = stream.MutableIndex(idx, search_params=sp,
                             delta_capacity=delta_capacity, name="rehearsal",
                             **mk)
    reg0 = IndexRegistry(buckets=bucket_sizes(max_batch))
    reg0.publish("churn-rehearsal", m0, k=k)
    m0.warm(reg0.buckets, ks=(k,))
    canary0 = after_compact0 = None
    if canary_rate > 0:
        # the rehearsal canary never samples — it exists to compile the
        # exact-oracle program of EVERY epoch's sealed-store shape (the
        # schedule is deterministic, so the live window replays them)
        canary0 = quality.RecallCanary(
            quality.exact_oracle(m0), k=k, sample_rate=0.0,
            buckets=bucket_sizes(max_batch), name="churn-rehearsal")
        canary0.warm()
        after_compact0 = canary0.warm
    comp0 = stream.Compactor(m0, publisher=reg0, name="churn-rehearsal",
                             ks=(k,), policy=policy)
    rehearsal_reports = write_schedule(m0, comp0,
                                       after_compact=after_compact0)
    del m0, comp0, reg0, canary0

    # ---- the real, attributed window -------------------------------------
    _note(f"{note}: live window, {threads} reader threads")
    m = stream.MutableIndex(idx, search_params=sp,
                            delta_capacity=delta_capacity, name=note,
                            **mk)
    canary = None
    if canary_rate > 0:
        canary = quality.RecallCanary(
            quality.exact_oracle(m), k=k, sample_rate=canary_rate,
            reservoir=1024, buckets=bucket_sizes(max_batch), name="churn")
    svc = SearchService(max_batch=max_batch, max_wait_us=max_wait_us,
                        max_queue_rows=max(4 * max_batch * threads, 256),
                        canary=canary)
    svc.publish("churn", m, k=k)
    m.warm(svc.buckets, ks=(k,))
    if canary is not None:
        canary.warm()  # epoch-0 programs (cache-hot from the rehearsal)
    comp = stream.Compactor(m, publisher=svc, name="churn", ks=(k,),
                            policy=policy)

    done = threading.Event()
    lats, failures, served = [], [], [0]
    lock = threading.Lock()
    eval_box = {}

    def reader(tid):
        my_lats, j = [], 0
        while not done.is_set():
            qi = (tid + j * threads) % pool.shape[0]
            j += 1
            t0 = time.perf_counter()
            try:
                svc.search("churn", pool[qi:qi + 1], k)
            except Exception as e:  # pragma: no cover - any loss fails the row
                with lock:
                    failures.append(f"{type(e).__name__}: {str(e)[:80]}")
                continue
            my_lats.append(time.perf_counter() - t0)
        with lock:
            lats.extend(my_lats)
            served[0] += len(my_lats)

    def on_step(step, n_compactions):
        # the canary's shadow rerank runs every step, off the reader hot
        # path, on the writer's cadence (deterministic drains; zero cold
        # compiles — the rehearsal covered every epoch's oracle program)
        if canary is not None:
            canary.drain()
        # mid-churn recall snapshot: right after the schedule's midpoint
        # (past the first compaction), query the service at warmed bucket
        # shapes and record the exact live-set bookkeeping for the oracle
        if step == writer_steps // 2 and "ids" not in eval_box:
            got = []
            for lo in range(0, n_eval, max_batch):
                _, ids = svc.search("churn", eval_q[lo:lo + max_batch], k)
                got.append(np.asarray(ids))
            eval_box["ids"] = np.concatenate(got)
            eval_box["del_done"] = (step + 1) * deletes_per_step
            eval_box["ins_done"] = (step + 1) * upserts_per_step
            eval_box["compactions_at_eval"] = n_compactions

    with obs_compile.attribution() as rec:
        workers = [threading.Thread(target=reader, args=(t,))
                   for t in range(threads)]
        t_load = time.perf_counter()
        for w in workers:
            w.start()
        t_write = time.perf_counter()
        reports = write_schedule(m, comp, on_step)
        write_s = time.perf_counter() - t_write
        done.set()
        for w in workers:
            w.join(600)
        if canary is not None:
            canary.drain()  # flush the tail samples inside the window
        load_s = time.perf_counter() - t_load
    svc.shutdown()

    # ---- oracle: fresh build over the mid-churn live rows ----------------
    _note(f"{note}: fresh-oracle build over the mid-churn live set")
    del_done, ins_done = eval_box["del_done"], eval_box["ins_done"]
    live_mat = np.concatenate([x_host[del_done:], churn_host[:ins_done]])
    live_gids = np.concatenate([np.arange(del_done, n),
                                n + np.arange(ins_done)])
    _, gt_pos = knn(live_mat, eval_q, k)
    gt_gids = live_gids[np.asarray(gt_pos)]
    recall_mut = _recall(eval_box["ids"], gt_gids)
    oracle = build(live_mat)
    jax.block_until_ready(materialize(oracle))
    _, o_pos = oracle_search(oracle, eval_q, k)
    o_pos = np.asarray(o_pos)
    oracle_gids = np.where(o_pos >= 0, live_gids[np.clip(o_pos, 0, None)], -1)
    recall_oracle = _recall(oracle_gids, gt_gids)

    lats_ms = np.sort(np.array(lats if lats else [0.0])) * 1e3
    canary_field = None
    if canary is not None:
        est = canary.estimate()
        canary_field = {
            "rate": canary_rate,
            "recall": round(est["recall"], 4),
            "wilson_low": round(est["wilson_low"], 4),
            "wilson_high": round(est["wilson_high"], 4),
            "reranked": est["reranked"], "seen": est["seen"],
            # the acceptance check: the fresh-oracle offline measurement
            # (recall_mut below) inside the canary's live Wilson interval
            "oracle_in_interval": bool(canary.in_interval(recall_mut)),
        }
    rows.append({
        "name": name,
        "qps": round(served[0] / load_s, 1),
        "p50_ms": round(float(lats_ms[len(lats_ms) // 2]), 3),
        "p99_ms": round(float(lats_ms[int(len(lats_ms) * 0.99) - 1]), 3),
        "write_rows_per_s": round(
            (total_upserts + total_deletes) / write_s, 1),
        "recall_mut": round(recall_mut, 4),
        "recall_oracle": round(recall_oracle, 4),
        "recall_gap": round(recall_mut - recall_oracle, 4),
        "build_s": round(build_s, 1),
        "threads": threads, "max_batch": max_batch,
        "delta_capacity": delta_capacity,
        "canary": canary_field,
        "churn": {
            "failed": len(failures),
            "compactions": len(reports),
            "compaction_wall_s": [r["wall_s"] for r in reports],
            "folded_rows": [r["folded"] for r in reports],
            "upserts": total_upserts, "deletes": total_deletes,
            # zero-cold-compile proof for the WHOLE loaded window (both
            # folds, their publish warms + flips, every flush): the
            # rehearsal pre-compiled the epoch program set, so a non-zero
            # value here means something compiled ON the serving path
            "compile_s": round(rec.compile_s, 3),
            "cache_misses": rec.cache_misses,
        },
        "failures": failures[:5],
    })


def _row_serve_shard(rows, n=100_000, d=128, n_lists=1024, k=10,
                     n_probes=32, shard_counts=(1, 2, 4, 8), threads=8,
                     per_thread=150, writer_steps=48, upserts_per_step=96,
                     deletes_per_step=32, delta_capacity=2048,
                     compact_fill=0.5, max_batch=64, max_wait_us=2000.0,
                     ncl=2000, n_eval=512, canary_rate=0.05):
    """Sharded serving tier (ISSUE 9): the whole serve+stream stack
    scatter-gathered across the mesh — ShardedMutableIndex(ivf_flat) at
    1/2/4/8 shards, per-shard operating points sized PROPORTIONALLY
    (``n_lists/S`` lists, ``n_probes/S`` probes — constant scanned-corpus
    fraction, so recall holds and total per-query compute is flat while
    the critical path spreads over S devices; docs/using_comms.md
    "Serving-tier sizing").

    Claims riding in the row (the ROADMAP-1 done-bar):
    - ``qps_by_shards`` + ``scaling_1_to_4`` — closed-loop served QPS per
      shard count; scaling = (qps[4]/qps[1])/4, i.e. the fraction of
      linear. On real multi-chip hardware the per-shard searches execute
      concurrently (one device each — candidates, never rows, cross the
      interconnect); on a CPU mesh the virtual devices share host cores,
      so the ceiling is min(S, cores)/S — ``cores`` rides in the row so
      the artifact prices that in.
    - **staggered mid-load compaction**: a writer churns
      upserts+deletes while readers serve; the Compactor folds ONE shard
      per cycle (>= 2 folds, distinct-shard staggering recorded in
      ``churn.compaction_shards``) with ``churn.failed == 0`` across every
      fold's warm republish.
    - **zero cold compiles** across the whole loaded churn window — every
      flush, every fold, every publish warm, the canary's shadow reranks —
      proven by obs compile attribution after a rehearsal twin replays the
      same deterministic schedule (the churn-row protocol, sharded).
    - **mid-churn recall inside the canary's Wilson interval**: the live
      RecallCanary shadow-reranks against the exact mesh-wide oracle
      (``exact_search`` composed through the same one-dispatch merge) and
      the fresh-oracle offline measurement must land inside its interval
      (``canary.oracle_in_interval``)."""
    import os
    import threading

    import jax
    import numpy as np

    from raft_tpu import stream
    from raft_tpu.neighbors import brute_force, ivf_flat
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import quality
    from raft_tpu.serve import SearchService, bucket_sizes

    total_upserts = writer_steps * upserts_per_step
    total_deletes = writer_steps * deletes_per_step
    assert total_deletes < n, "delete schedule exceeds the dataset"

    _note("shard: dataset")
    dataset, qsets = _make_clustered(n + total_upserts, d, max(n_eval, 1000),
                                     ncl, n_qsets=1, seed=23)
    jax.block_until_ready([dataset] + qsets)
    x_host = np.asarray(dataset[:n])
    churn_host = np.asarray(dataset[n:])
    pool = np.asarray(qsets[0])
    eval_q = pool[:n_eval]
    devs = jax.devices()

    def make_sharded(S, name):
        # proportional sizing: constant scanned-corpus fraction per query
        nl = max(n_lists // S, 8)
        sp = ivf_flat.SearchParams(n_probes=max(n_probes // S, 1))
        return stream.ShardedMutableIndex(
            x_host, n_shards=S,
            build=lambda rows: ivf_flat.build(
                ivf_flat.IndexParams(n_lists=nl, seed=0), rows),
            search_params=sp, delta_capacity=delta_capacity,
            devices=[devs[s % len(devs)] for s in range(S)], name=name)

    # ---- read-only QPS ladder over shard counts --------------------------
    qps_by_shards = {}
    failures = []

    def loaded_window(svc, name):
        def worker(tid):
            for j in range(per_thread):
                qi = (tid + j * threads) % pool.shape[0]
                try:
                    svc.search(name, pool[qi:qi + 1], k)
                except Exception as e:  # pragma: no cover - fails the row
                    failures.append(f"{type(e).__name__}: {str(e)[:80]}")
        ws = [threading.Thread(target=worker, args=(t,))
              for t in range(threads)]
        t0 = time.perf_counter()
        for w in ws:
            w.start()
        for w in ws:
            w.join(600)
        return threads * per_thread / (time.perf_counter() - t0)

    t0 = time.perf_counter()
    for S in shard_counts:
        _note(f"shard: build + serve at {S} shard(s)")
        sm = make_sharded(S, f"mesh{S}")
        svc = SearchService(max_batch=max_batch, max_wait_us=max_wait_us,
                            max_queue_rows=max(4 * max_batch * threads, 256))
        svc.publish("mesh", sm, k=k)
        sm.warm(svc.buckets, ks=(k,))
        loaded_window(svc, "mesh")  # warm the closed loop itself
        qps_by_shards[str(S)] = round(loaded_window(svc, "mesh"), 1)
        svc.shutdown()
        del sm, svc
    build_s = time.perf_counter() - t0

    # ---- staggered-compaction churn at the largest shard count -----------
    S = shard_counts[-1]
    policy = stream.CompactionPolicy(delta_fill=compact_fill,
                                     tombstone_ratio=None, max_age_s=None)

    def write_schedule(sm, comp, on_step=None, after_compact=None):
        reports = []
        for step in range(writer_steps):
            lo = step * upserts_per_step
            sm.upsert(churn_host[lo:lo + upserts_per_step],
                      ids=n + np.arange(lo, lo + upserts_per_step))
            dlo = step * deletes_per_step
            sm.delete(np.arange(dlo, dlo + deletes_per_step))
            while comp.due():
                reports.append(comp.run_once())  # ONE shard per cycle
                if after_compact is not None:
                    after_compact()
            if on_step is not None:
                on_step(step, len(reports))
        return reports

    _note(f"shard: rehearsal at {S} shards (compiles the epoch program set)")
    m0 = make_sharded(S, "shard-rehearsal")
    svc0 = SearchService(max_batch=max_batch, max_wait_us=max_wait_us,
                         max_queue_rows=max(4 * max_batch * threads, 256))
    svc0.publish("shard-rehearsal", m0, k=k)
    m0.warm(svc0.buckets, ks=(k,))
    canary0 = quality.RecallCanary(
        quality.exact_oracle(m0), k=k, sample_rate=0.0,
        buckets=bucket_sizes(max_batch), name="shard-rehearsal")
    canary0.warm()
    comp0 = stream.Compactor(m0, publisher=svc0, name="shard-rehearsal",
                             ks=(k,), policy=policy)
    write_schedule(m0, comp0, after_compact=canary0.warm)
    svc0.shutdown()
    del m0, comp0, canary0, svc0

    _note(f"shard: live churn window at {S} shards, {threads} readers")
    sm = make_sharded(S, "shard")
    canary = quality.RecallCanary(
        quality.exact_oracle(sm), k=k, sample_rate=canary_rate,
        reservoir=1024, buckets=bucket_sizes(max_batch), name="shard")
    svc = SearchService(max_batch=max_batch, max_wait_us=max_wait_us,
                        max_queue_rows=max(4 * max_batch * threads, 256),
                        canary=canary)
    svc.publish("shard", sm, k=k)
    sm.warm(svc.buckets, ks=(k,))
    canary.warm()
    comp = stream.Compactor(sm, publisher=svc, name="shard", ks=(k,),
                            policy=policy)

    done = threading.Event()
    lats, served = [], [0]
    lock = threading.Lock()
    eval_box = {}

    def reader(tid):
        my_lats, j = [], 0
        while not done.is_set():
            qi = (tid + j * threads) % pool.shape[0]
            j += 1
            t0 = time.perf_counter()
            try:
                svc.search("shard", pool[qi:qi + 1], k)
            except Exception as e:  # pragma: no cover - fails the row
                with lock:
                    failures.append(f"{type(e).__name__}: {str(e)[:80]}")
                continue
            my_lats.append(time.perf_counter() - t0)
        with lock:
            lats.extend(my_lats)
            served[0] += len(my_lats)

    def on_step(step, n_compactions):
        canary.drain()  # shadow reranks on the writer cadence, off-path
        if step == writer_steps // 2 and "ids" not in eval_box:
            got = []
            for lo in range(0, n_eval, max_batch):
                _, ids = svc.search("shard", eval_q[lo:lo + max_batch], k)
                got.append(np.asarray(ids))
            eval_box["ids"] = np.concatenate(got)
            eval_box["del_done"] = (step + 1) * deletes_per_step
            eval_box["ins_done"] = (step + 1) * upserts_per_step

    with obs_compile.attribution() as rec:
        workers = [threading.Thread(target=reader, args=(t,))
                   for t in range(threads)]
        t_load = time.perf_counter()
        for w in workers:
            w.start()
        t_write = time.perf_counter()
        reports = write_schedule(sm, comp, on_step)
        write_s = time.perf_counter() - t_write
        done.set()
        for w in workers:
            w.join(600)
        canary.drain()
        load_s = time.perf_counter() - t_load
    svc.shutdown()

    # ---- fresh oracle over the mid-churn live rows -----------------------
    _note("shard: fresh-oracle build over the mid-churn live set")
    del_done, ins_done = eval_box["del_done"], eval_box["ins_done"]
    live_mat = np.concatenate([x_host[del_done:], churn_host[:ins_done]])
    live_gids = np.concatenate([np.arange(del_done, n),
                                n + np.arange(ins_done)])
    _, gt_pos = brute_force.knn(live_mat, eval_q, k)
    gt_gids = live_gids[np.asarray(gt_pos)]
    recall_mut = _recall(eval_box["ids"], gt_gids)
    oracle = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=n_lists, seed=0), live_mat)
    jax.block_until_ready(oracle.list_data)
    _, o_pos = ivf_flat.search(ivf_flat.SearchParams(n_probes=n_probes),
                               oracle, eval_q, k)
    o_pos = np.asarray(o_pos)
    oracle_gids = np.where(o_pos >= 0, live_gids[np.clip(o_pos, 0, None)], -1)
    recall_oracle = _recall(oracle_gids, gt_gids)

    lats_ms = np.sort(np.array(lats if lats else [0.0])) * 1e3
    est = canary.estimate()
    q1 = qps_by_shards.get(str(shard_counts[0]), 0)
    q4 = qps_by_shards.get("4")
    rows.append({
        "name": "serve_shard_churn_100k",
        "qps": round(served[0] / load_s, 1),
        "qps_by_shards": qps_by_shards,
        "scaling_1_to_4": (round(q4 / q1 / 4.0, 3)
                           if q4 and q1 else None),
        "cores": os.cpu_count(),
        "shards": S,
        "p50_ms": round(float(lats_ms[len(lats_ms) // 2]), 3),
        "p99_ms": round(float(lats_ms[int(len(lats_ms) * 0.99) - 1]), 3),
        "write_rows_per_s": round(
            (total_upserts + total_deletes) / write_s, 1),
        "recall_mut": round(recall_mut, 4),
        "recall_oracle": round(recall_oracle, 4),
        "recall_gap": round(recall_mut - recall_oracle, 4),
        "build_s": round(build_s, 1),
        "threads": threads, "max_batch": max_batch,
        "delta_capacity": delta_capacity,
        "canary": {
            "rate": canary_rate,
            "recall": round(est["recall"], 4),
            "wilson_low": round(est["wilson_low"], 4),
            "wilson_high": round(est["wilson_high"], 4),
            "reranked": est["reranked"], "seen": est["seen"],
            "oracle_in_interval": bool(canary.in_interval(recall_mut)),
        },
        "churn": {
            "failed": len(failures),
            "compactions": len(reports),
            # one shard per fold — the staggering record (a global
            # stop-the-world would show as one shard repeated back-to-back
            # with every delta full; distinct shards = staggered)
            "compaction_shards": [r["shard"] for r in reports],
            "compaction_wall_s": [r["wall_s"] for r in reports],
            "folded_rows": [r["folded"] for r in reports],
            "upserts": total_upserts, "deletes": total_deletes,
            "compile_s": round(rec.compile_s, 3),
            "cache_misses": rec.cache_misses,
        },
        "failures": failures[:5],
    })


def _row_canary_smoke(rows, n=100_000, d=128, n_lists=1024, pq_dim=64, k=10,
                      n_probes=8, threads=8, per_thread=150,
                      rates=(0.0, 0.01, 0.05), max_batch=64,
                      max_wait_us=2000.0, ncl=2000, n_eval=512):
    """Canary overhead A/B (ISSUE 8): the same closed-loop served load at
    canary sampling 0% vs 1% vs 5%, with the background drainer running its
    exact shadow reranks concurrently — the row measures what live quality
    monitoring actually costs the serving path. Three claims ride in it:

    - ``qps_by_rate`` / ``slowdown_at_5pct``: sampling is a host-side
      reservoir tap and the rerank is off the hot path, so the cost should
      be device contention only (a few percent at 5%);
    - the **hot path stays compile-free with the canary on**: the whole
      loaded window (all three rates, drains included) runs under obs
      compile attribution and must report ``compile_s == 0`` — the canary
      was warmed at every rerank bucket beforehand;
    - the canary's streaming estimate brackets the offline truth:
      ``recall_offline`` (held-out queries through the service vs the
      exact oracle) must sit inside the Wilson interval
      (``canary.oracle_in_interval``)."""
    import threading

    import jax
    import numpy as np

    from raft_tpu import stream
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import quality
    from raft_tpu.serve import SearchService, bucket_sizes

    _note("canary: dataset")
    dataset, qsets = _make_clustered(n, d, max(threads * per_thread, 1000),
                                     ncl, n_qsets=1, seed=17)
    jax.block_until_ready([dataset] + qsets)
    x_host = np.asarray(dataset)
    pool = np.asarray(qsets[0])
    eval_q = pool[:n_eval]

    _note("canary: ivf_pq build")
    t0 = time.perf_counter()
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4, pq_dim=pq_dim,
                           seed=0), dataset)
    jax.block_until_ready(idx.list_codes)
    build_s = time.perf_counter() - t0
    sp = ivf_pq.SearchParams(n_probes=n_probes, lut_dtype="bfloat16")
    m = stream.MutableIndex(idx, search_params=sp, dataset=x_host,
                            name="canary")
    canary = quality.RecallCanary(
        quality.exact_oracle(m), k=k, sample_rate=0.0, reservoir=1024,
        buckets=bucket_sizes(max_batch), name="canary")
    svc = SearchService(max_batch=max_batch, max_wait_us=max_wait_us,
                        max_queue_rows=max(4 * max_batch * threads, 256),
                        canary=canary)
    svc.publish("canary", m, k=k)
    m.warm(svc.buckets, ks=(k,))
    _note("canary: oracle warm")
    canary.warm()

    # offline truth at warmed bucket shapes: the served pipeline's recall
    # vs the exact live-corpus oracle on held-out queries
    got = []
    for lo in range(0, n_eval, max_batch):
        _, ids = svc.search("canary", eval_q[lo:lo + max_batch], k)
        got.append(np.asarray(ids))
    _, oracle_ids = m.exact_search(eval_q, k)
    recall_offline = _recall(np.concatenate(got), np.asarray(oracle_ids))

    failures = []

    def loaded_window():
        def worker(tid):
            for j in range(per_thread):
                qi = (tid + j * threads) % pool.shape[0]
                try:
                    svc.search("canary", pool[qi:qi + 1], k)
                except Exception as e:  # pragma: no cover - fails the row
                    failures.append(f"{type(e).__name__}: {str(e)[:80]}")
        ws = [threading.Thread(target=worker, args=(t,))
              for t in range(threads)]
        t0 = time.perf_counter()
        for w in ws:
            w.start()
        for w in ws:
            w.join(600)
        return threads * per_thread / (time.perf_counter() - t0)

    qps_by_rate = {}
    with obs_compile.attribution() as rec:
        for rate in rates:
            _note(f"canary: loaded window at rate {rate:g}")
            canary.set_rate(rate)
            if rate > 0:
                canary.start(poll_interval_s=0.005)
            qps_by_rate[f"{rate:g}"] = round(loaded_window(), 1)
            if rate > 0:
                canary.stop()  # drains the tail INSIDE the attribution
    svc.shutdown()  # free the worker threads + index before later rows
    est = canary.estimate()
    base = qps_by_rate[f"{rates[0]:g}"]
    worst = qps_by_rate[f"{rates[-1]:g}"]
    rows.append({
        "name": "canary_smoke_100k",
        "qps": base,
        "qps_by_rate": qps_by_rate,
        "slowdown_at_5pct": round(base / max(worst, 1e-9), 3),
        "recall_offline": round(recall_offline, 4),
        "canary": {
            "recall": round(est["recall"], 4),
            "wilson_low": round(est["wilson_low"], 4),
            "wilson_high": round(est["wilson_high"], 4),
            "reranked": est["reranked"], "seen": est["seen"],
            "oracle_in_interval": bool(canary.in_interval(recall_offline)),
        },
        "build_s": round(build_s, 1),
        "threads": threads, "max_batch": max_batch,
        # zero-cold-compile proof with the canary ON: sampling, draining
        # and reranking across the whole loaded window compiled nothing
        "compile_s": round(rec.compile_s, 3),
        "cache_misses": rec.cache_misses,
        "failed": len(failures),
        "failures": failures[:5],
    })


def _row_tune_smoke(rows, n=10_000, d=64, ncl=200, n_lists=64, k=10, m=512,
                    repeats=2):
    """Tiny-budget autotune sweep riding the default bench (ISSUE 7): a
    10k IVF-PQ index swept over the 3-point smoke grid through
    raft_tpu.tune — proving the measure→choose→record loop end-to-end on
    whatever hardware the bench runs, without wall-clock blowup. The row
    carries the chosen operating point, the grid-head (hand-picked)
    baseline, and their QPS ratio; by the engine's choice rule the chosen
    point matches or beats the head at equal-or-better recall. Heavy
    sweeps live in bench/tune_sweep.py (the TUNE_rXX.json driver)."""
    import jax

    from raft_tpu import tune
    from raft_tpu.neighbors import ivf_pq

    _note("tune: dataset")
    dataset, qsets = _make_clustered(n, d, m, ncl, n_qsets=1, seed=19)
    jax.block_until_ready([dataset] + qsets)
    _note("tune: ivf_pq build")
    t0 = time.perf_counter()
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4,
                           pq_dim=max(min(32, d // 2), 1), seed=0), dataset)
    jax.block_until_ready(idx.list_codes)
    build_s = time.perf_counter() - t0
    _note("tune: sweep")
    dec = tune.sweep(idx, qsets[0], k=k, dataset=dataset,
                     grid=tune.smoke_grid("ivf_pq"),
                     recall_target="default", repeats=repeats)
    ev = dec.evidence
    rows.append({
        "name": "tune_smoke_10k",
        "qps": ev["chosen_qps"], "recall": ev["chosen_recall"],
        "build_s": round(build_s, 1),
        "decision": dec.key, "chosen": dict(dec.params),
        "default": dict(ev["default_params"]),
        "default_qps": ev["default_qps"],
        "default_recall": ev["default_recall"],
        "recall_target": ev["recall_target"],
        "n_trials": len(ev["trials"]),
        "chosen_qps_over_default": ev["chosen_qps_over_default"],
    })


def _row_mem_smoke(rows, n=100_000, d=64, n_lists=512, k=10, cycles=3):
    """Capacity-observability proof riding the default bench (ISSUE 10):
    ``cycles`` publish→retire cycles of same-config IVF-PQ indexes through
    one registry, measured by the obs.mem ledger. Asserted per cycle:

    - accounted device bytes return to the (baseline + one live index)
      level after every retire + gc — the registry free path does not
      leak (the PR 9 leak class, now a bench-gated invariant);
    - the per-cycle ledger PEAK stays flat from cycle 2 onward (each swap
      double-buffers old+new while warming; flat steady-state peaks = no
      monotonic growth across swaps);
    - cycles after the first compile NOTHING (same static config = same
      program set; compile attribution must read 0);
    - the retirement audit is clean after the final gc;
    - ``obs.mem.plan()`` brackets the measured index bytes within ±20%
      (the estimator's accuracy contract at 100k, on bench hardware).
    """
    import gc

    import jax
    import numpy as np

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import mem as obs_mem
    from raft_tpu.serve import IndexRegistry

    assert cycles >= 2, "mem smoke needs >= 2 cycles (steady-state " \
                        "assertions compare against the post-warmup peak)"
    _note("mem smoke: dataset")
    rng = np.random.default_rng(7)
    dataset = rng.random((n, d), np.float32)
    params = ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4,
                                pq_dim=max(min(32, d // 2), 1), seed=0)
    reg = IndexRegistry(buckets=(1, 8, 64))
    gc.collect()
    baseline = obs_mem.totals()["device_bytes"]
    peaks, levels, compile_steady = [], [], 0.0
    measured = None
    t0 = time.perf_counter()
    for c in range(cycles):
        obs_mem.reset_peak()
        with obs_compile.attribution() as rec:
            idx = ivf_pq.build(params, dataset)
            jax.block_until_ready(idx.list_codes)
            measured = int(idx.list_codes.nbytes + idx.list_ids.nbytes
                           + idx.list_sizes.nbytes + idx.centers.nbytes
                           + idx.centers_rot.nbytes + idx.rotation.nbytes
                           + idx.codebooks.nbytes + idx.list_consts.nbytes
                           + idx.list_scales.nbytes)
            reg.publish("mem_smoke", idx, k=k)
            del idx  # the registry version now holds the only reference
        if c > 0:
            compile_steady += rec.compile_s
        gc.collect()
        peaks.append(obs_mem.totals()["device_peak_bytes"])
        levels.append(obs_mem.totals()["device_bytes"])
        # one live index remains published; everything a retired cycle
        # allocated must be gone
        assert levels[-1] <= baseline + measured + 1024, (
            f"cycle {c}: accounted {levels[-1]} B > baseline {baseline} + "
            f"live index {measured} — the retire path leaked")
    # cycle 1 starts from an empty registry; every later cycle builds the
    # successor WHILE the predecessor is still published, so the steady
    # state is a double-buffer peak — flat from cycle 2 onward is the
    # no-monotonic-growth invariant
    assert max(peaks[1:]) <= peaks[1] * 1.05 + 1024, (
        f"per-cycle peak grew past the steady-state double-buffer: {peaks}")
    assert compile_steady == 0.0, (
        f"steady-state cycles compiled {compile_steady}s — same-config "
        "publish must reuse every program")
    audit = obs_mem.audit(collect=True)
    assert audit["clean"], f"retirement audit: {audit['retired_unfreed']}"
    est = obs_mem.plan("ivf_pq", params, n, d)["index_bytes"]
    assert abs(est - measured) <= 0.20 * measured, (
        f"plan {est} vs measured {measured} outside 20%")
    out = reg.active("mem_smoke")  # metadata read keeps the API honest
    rows.append({
        "name": "mem_smoke_100k",
        "cycles": cycles, "wall_s": round(time.perf_counter() - t0, 1),
        "baseline_bytes": baseline, "index_bytes": measured,
        "plan_bytes": est, "plan_ratio": round(est / measured, 3),
        "peak_bytes_by_cycle": peaks, "level_bytes_by_cycle": levels,
        "steady_compile_s": round(compile_steady, 3),
        "audit_clean": audit["clean"], "published_version": out.version,
        "mem_note": "levels = baseline + one live index per cycle; "
                    "peaks flat across publish→retire swaps",
    })


def _row_fault_smoke(rows, n=100_000, d=64, n_lists=512, k=10,
                     n_probes=16, shards=2, replicas=2, steps=160,
                     qbatch=64, fence_at=40, heal_at=110,
                     write_every=10, write_rows=16, delta_capacity=2048):
    """Availability proof riding the default bench (ISSUE 11): a sharded
    mesh with per-shard replica groups serves a loaded window during which
    one replica is killed outright (fault-injected search failures) and
    later revived. Asserted:

    - **zero failed queries**: every batch in the window answers — the
      scatter retries the surviving twin in the same call (one dead
      replica = degraded capacity, never a failed query);
    - the dead replica is actually FENCED (breaker strikes observed) and,
      after the fault clears, HEALS through the backoff re-probe —
      ``recovery_s`` records fault-cleared → all replicas serving again;
    - **zero cold compiles** across the measured window, fence, failover
      retries, probes and writes included — rehearsal protocol: the same
      schedule replays unmeasured first, then obs compile attribution
      must read 0 over the measured pass;
    - writes keep applying to the fenced replica (fenced-for-READS is not
      stale: it missed nothing) so the heal needs no rebuild.
    """
    import jax
    import numpy as np

    from raft_tpu import stream
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.testing import faults

    assert fence_at < heal_at < steps
    ev_before = _events_snap()
    _note("fault smoke: dataset")
    rng = np.random.default_rng(11)
    x = rng.random((n, d), np.float32)
    pool = rng.random((1024, d), np.float32)
    churn = rng.random((steps * write_rows, d), np.float32)
    nl = max(n_lists // shards, 8)
    sp = ivf_flat.SearchParams(n_probes=max(n_probes // shards, 1))

    def run_window(sm):
        """The deterministic schedule: searches + light writes; at
        fence_at the replica shard 0 currently PREFERS (lowest scan-wall
        EWMA, breaker closed — the one `_pick` returns next) is killed,
        revived at heal_at. Killing the preferred twin, not a fixed
        ordinal, is what makes the strike deterministic: the next scatter
        is guaranteed to pick it, strike it, and fail over."""
        failed, t_heal, recovery_s = 0, None, None
        t0 = time.perf_counter()
        try:
            for i in range(steps):
                if i == fence_at:
                    grp = sm._shards[0]
                    with grp._lock:
                        j = min((jj for jj, h in enumerate(grp._health)
                                 if h.fenced_until is None and not h.stale),
                                key=lambda jj: grp._health[jj].ewma or 0.0)
                    victim = grp._replicas[j].name
                    faults.inject(
                        "replica/search", exc=faults.FaultError("killed"),
                        match=lambda c, v=victim: c["replica"] == v)
                if i == heal_at:
                    faults.clear("replica/search")
                    t_heal = time.perf_counter()
                q = pool[(i * qbatch) % 960:(i * qbatch) % 960 + qbatch]
                try:
                    dq, iq = sm.search(q, k)
                    assert np.asarray(iq).shape == (qbatch, k)
                except Exception:
                    failed += 1
                if write_every and i % write_every == 0:
                    sm.upsert(churn[i * write_rows:(i + 1) * write_rows])
                if (t_heal is not None and recovery_s is None
                        and sm.health()["healthy_min"] == replicas):
                    recovery_s = time.perf_counter() - t_heal
        finally:
            faults.clear("replica/search")
        # drain the fence if the loop ended before the probe window
        while recovery_s is None:
            sm.search(pool[:qbatch], k)
            if sm.health()["healthy_min"] == replicas:
                recovery_s = time.perf_counter() - t_heal
        # settle: "healthy" above can mean the fence merely EXPIRED —
        # one more search routes the pending probe (probes win _pick)
        # so the breaker actually closes and the heal reaches the
        # event journal (replica_probe ok + replica_unfenced)
        sm.search(pool[:qbatch], k)
        return {"failed": failed, "recovery_s": recovery_s,
                "wall_s": time.perf_counter() - t0}

    def make_mesh(name):
        sm = stream.ShardedMutableIndex(
            x, n_shards=shards, replicas=replicas,
            build=lambda r: ivf_flat.build(
                ivf_flat.IndexParams(n_lists=nl, seed=0), r),
            search_params=sp, delta_capacity=delta_capacity,
            fencing=stream.FencingPolicy(max_consecutive=2,
                                         backoff_s=0.05,
                                         backoff_max_s=0.5),
            name=name)
        sm.warm((qbatch,), ks=(k,))
        jax.block_until_ready(sm.search(pool[:qbatch], k))  # sealed side
        return sm

    _note("fault smoke: rehearsal")
    rehearsal = make_mesh("fault_rehearsal")
    run_window(rehearsal)
    del rehearsal

    _note("fault smoke: measured window")
    mesh = make_mesh("fault")
    with obs_compile.attribution() as rec:
        out = run_window(mesh)
    strikes = sum(h.strikes for h in mesh._shards[0]._health)
    assert out["failed"] == 0, (
        f"{out['failed']} queries failed during the fence window — the "
        "failover contract is zero failed queries")
    assert strikes > 0, "the victim replica was never struck — the fault " \
                        "window did not exercise failover"
    assert rec.compile_s == 0.0, (
        f"loaded window compiled {rec.compile_s}s after rehearsal — "
        "failover/probe paths minted a new program")
    row = {
        "name": "fault_smoke_100k", "n": n, "shards": shards,
        "replicas": replicas, "queries": steps,
        "failed_queries": out["failed"], "strikes": strikes,
        "recovery_s": round(out["recovery_s"], 3),
        "qps": round(steps * qbatch / out["wall_s"], 1),
        "compile_s_loaded": rec.compile_s,
        "wall_s": round(out["wall_s"], 1),
        "fault_note": "one replica killed mid-load and revived; zero "
                      "failed queries, zero cold compiles; recovery_s = "
                      "fault cleared -> every replica serving",
    }
    events = _events_delta(ev_before)   # gated by compare.py on presence
    if events is not None:
        row["events"] = events
    rows.append(row)


def _row_crash_recovery(rows, n=100_000, d=64, n_lists=512, k=10,
                        n_probes=16, write_steps=40, write_rows=64,
                        delete_rows=8, delta_capacity=4096, n_eval=256):
    """Crash-durability proof riding the default bench (ISSUE 11): a
    100k MutableIndex with a write-ahead log takes ``write_steps``
    un-compacted upsert+delete batches, then the process "dies" — a
    :class:`~raft_tpu.testing.faults.SimulatedCrash` injected between the
    WAL append and the memtable insert of the final write, after which
    the in-memory object is abandoned. Recovery is the real cold-start
    path: ``stream.load(snapshot, wal=)`` (atomic snapshot + WAL replay)
    + ``warm()``. Asserted and recorded:

    - **every logged write is recovered** — an uncrashed twin replays the
      identical write script in-process and the recovered index matches
      it id-for-id over ``n_eval`` queries (``recall_recovered`` = match
      fraction, gated at 1.0 by bench/compare.py like every recall
      field);
    - ``recovery_s`` (load + replay wall), ``replay_rows_per_s`` and the
      WAL's size/record count ride the artifact — the measured price of
      crash durability at 100k;
    - **zero cold compiles** on the post-warm serving window (compile
      attribution over a query loop after ``warm()``).
    """
    import os
    import shutil
    import tempfile

    import jax
    import numpy as np

    from raft_tpu import stream
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.testing import faults

    _note("crash recovery: dataset + sealed build")
    rng = np.random.default_rng(13)
    x = rng.random((n, d), np.float32)
    churn = rng.random((write_steps * write_rows, d), np.float32)
    eval_q = rng.random((n_eval, d), np.float32)
    sealed = ivf_flat.build(ivf_flat.IndexParams(n_lists=n_lists, seed=0), x)
    jax.block_until_ready(sealed.list_data)
    sp = ivf_flat.SearchParams(n_probes=n_probes)

    tmp = tempfile.mkdtemp(prefix="raft_crash_")
    try:
        snap = os.path.join(tmp, "snap.bin")
        wpath = os.path.join(tmp, "wal.log")

        def write_script(m):
            """The acknowledged writes (deterministic — the twin replays it)."""
            for s in range(write_steps - 1):
                m.upsert(churn[s * write_rows:(s + 1) * write_rows])
                m.delete(list(range(s * delete_rows, (s + 1) * delete_rows)))
            return churn[(write_steps - 1) * write_rows:]

        _note("crash recovery: write burst + injected crash")
        m = stream.MutableIndex(sealed, search_params=sp,
                                delta_capacity=delta_capacity, wal=wpath)
        stream.save(m, snap)  # the pre-burst snapshot (atomic)
        last_batch = write_script(m)
        wal_bytes = m._wal.size_bytes
        with faults.scope():
            faults.inject("stream/post-wal", faults.SimulatedCrash("kill -9"))
            try:
                m.upsert(last_batch)
                raise AssertionError("crash fault never fired")
            except faults.SimulatedCrash:
                pass
        replayable = write_steps * write_rows  # every LOGGED upsert row
        del m  # the process is gone; snap + wal.log are all that survive

        _note("crash recovery: load + WAL replay")
        t0 = time.perf_counter()
        rec = stream.load(snap, wal=wpath, search_params=sp)
        recovery_s = time.perf_counter() - t0
        assert rec.last_recovery["replayed"] == 2 * (write_steps - 1) + 1, (
            f"replay applied {rec.last_recovery['replayed']} records, "
            f"expected every logged write")
        t0 = time.perf_counter()
        rec.warm((n_eval,), ks=(k,))
        warm_s = time.perf_counter() - t0
        jax.block_until_ready(rec.search(eval_q, k))  # sealed-side rehearsal
        with obs_compile.attribution() as att:
            for _ in range(3):
                dr, ir = rec.search(eval_q, k)
            jax.block_until_ready((dr, ir))
        assert att.compile_s == 0.0, (
            f"post-warm serving compiled {att.compile_s}s — the recovered "
            "cold-start path must be compile-free after warm()")

        _note("crash recovery: uncrashed twin parity")
        twin = stream.MutableIndex(sealed, search_params=sp,
                                   delta_capacity=delta_capacity)
        last = write_script(twin)
        twin.upsert(last)  # the crashed write WAS logged, so replay applies it
        dt, it = twin.search(eval_q, k)
        ids_match = float(np.mean(np.asarray(ir) == np.asarray(it)))
        assert rec.size == twin.size, (rec.size, twin.size)
        assert ids_match == 1.0, (
            f"recovered index diverges from the uncrashed twin "
            f"(id match {ids_match:.4f}) — an acknowledged write was lost")
        rows.append({
            "name": "crash_recovery_100k", "n": n,
            "wal_records": rec.last_recovery["replayed"],
            "wal_bytes": wal_bytes,
            "recovered_rows": replayable,
            "recall_recovered": ids_match,  # gated by bench/compare.py
            "recovery_s": round(recovery_s, 3),
            "warm_s": round(warm_s, 3),
            "replay_rows_per_s": round(replayable / recovery_s, 1),
            "compile_s_post_warm": att.compile_s,
            "crash_note": "SimulatedCrash between WAL append and memtable "
                          "insert of the final write; recovery = atomic "
                          "snapshot + replay of every logged record",
        })
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _row_reshard_churn(rows, n=100_000, d=64, n_lists=512, k=10,
                       n_probes=16, shards=2, replicas=2, steps=40,
                       qbatch=64, reshard_at=20, write_every=4,
                       write_rows=16, delta_capacity=4096, n_eval=256,
                       readers=2):
    """Elastic-resharding proof riding the default bench (ISSUE 13): a
    loaded ``shards``×``replicas`` mesh DOUBLES its shard count online —
    reader threads hammer the scatter-gather for the whole window, one
    replica of shard 0 is killed the moment the migration starts (the
    currently-preferred twin, so the next pick strikes deterministically),
    and writes land mid-migration through the reshard/split fault seam (so
    successor shapes stay schedule-deterministic for the rehearsal
    protocol). Asserted:

    - **zero failed queries** across fold, kill, carry-over and flip —
      failover covers the dead twin, leases drain on the old topology;
    - **zero cold compiles** over the measured window (rehearsal protocol:
      the identical schedule replays warm; the successors' ladders and the
      doubled-merge shape were compiled pre-flip);
    - **recall anchor held**: recall@k vs the exact mesh oracle measured
      before and after the flip (``recall_pre``/``recall_post``, both
      gated by bench/compare.py like every recall field);
    - **measured crash-mid-reshard recovery**: a third durable mesh takes
      the same write burst, a SimulatedCrash fires at ``reshard/flip``
      (between the successor swap and the manifest write), and
      ``ShardedMutableIndex.load`` recovers the OLD topology —
      ``crash_recovery_s`` recorded, ``recall_crash_recovered`` == 1.0
      id-for-id vs an uncrashed twin (gated).
    """
    import os
    import shutil
    import tempfile
    import threading

    import jax
    import numpy as np

    from raft_tpu import stream
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.testing import faults

    assert reshard_at < steps and replicas >= 2
    ev_before = _events_snap()
    _note("reshard churn: dataset")
    rng = np.random.default_rng(17)
    x = rng.random((n, d), np.float32)
    pool = rng.random((1024, d), np.float32)
    churn = rng.random(((steps + 2) * write_rows, d), np.float32)
    eval_q = rng.random((n_eval, d), np.float32)
    nl = max(n_lists // shards, 8)
    sp = ivf_flat.SearchParams(n_probes=max(n_probes // shards, 1))

    def build(r):
        return ivf_flat.build(ivf_flat.IndexParams(n_lists=nl, seed=0), r)

    def recall_vs_oracle(sm):
        _, ia = sm.search(eval_q, k)
        _, ie = sm.exact_search(eval_q, k)
        ia, ie = np.asarray(ia), np.asarray(ie)
        return float(np.mean([len(set(a.tolist()) & set(b.tolist())) / k
                              for a, b in zip(ia, ie)]))

    def make_mesh(name, dir_=None):
        sm = stream.ShardedMutableIndex(
            x, n_shards=shards, replicas=replicas, build=build,
            search_params=sp, delta_capacity=delta_capacity,
            wal_dir=dir_,
            fencing=stream.FencingPolicy(max_consecutive=2, backoff_s=0.05,
                                         backoff_max_s=0.5),
            name=name)
        sm.warm((qbatch, n_eval), ks=(k,))
        jax.block_until_ready(sm.search(pool[:qbatch], k))  # sealed side
        jax.block_until_ready(sm.search(eval_q, k))
        jax.block_until_ready(sm.exact_search(eval_q, k))  # oracle shapes
        return sm

    def run_window(sm):
        """The deterministic schedule: the main thread writes and
        reshards while reader threads search continuously (fixed qbatch —
        readers cannot perturb program shapes). The replica kill and the
        mid-migration write ride the reshard/split fault seam, so they
        land at the same schedule point in rehearsal and measured runs."""
        failed = [0]
        served = [0]
        stop = threading.Event()
        lock = threading.Lock()

        def reader(tid):
            j = 0
            while not stop.is_set():
                q = pool[((tid * 61 + j) * qbatch) % 960:
                         ((tid * 61 + j) * qbatch) % 960 + qbatch]
                try:
                    _, iq = sm.search(q, k)
                    assert np.asarray(iq).shape == (qbatch, k)
                    with lock:
                        served[0] += 1
                except Exception:
                    with lock:
                        failed[0] += 1
                j += 1

        def on_fold(ctx):
            if ctx.get("donors") == (0,):
                # kill the preferred twin of shard 0 the moment its fold
                # starts (lowest EWMA, breaker closed — what _pick returns
                # next, making the strike deterministic)
                grp = sm.shards[0]
                with grp._lock:
                    j = min((jj for jj, h in enumerate(grp._health)
                             if h.fenced_until is None and not h.stale),
                            key=lambda jj: grp._health[jj].ewma or 0.0)
                sm._victim = grp._replicas[j].name
                faults.inject(
                    "replica/search", exc=faults.FaultError("killed"),
                    match=lambda c, v=sm._victim: c["replica"] == v)
            else:
                # a write only the carry-over (and, durably, the
                # successor WALs) can deliver
                sm.upsert(churn[steps * write_rows:
                                (steps + 1) * write_rows])

        out = {}
        t0 = time.perf_counter()
        threads = [threading.Thread(target=reader, args=(t,), daemon=True)
                   for t in range(readers)]
        for t in threads:
            t.start()
        try:
            for i in range(steps):
                if i == reshard_at:
                    out["recall_pre"] = recall_vs_oracle(sm)
                    donors = list(sm.shards)  # strike state dies with them
                    faults.inject("reshard/split", callback=on_fold)
                    rep = sm.reshard(2 * shards,
                                     warm_buckets=(qbatch, n_eval))
                    faults.clear("reshard/split")
                    faults.clear("replica/search")
                    out["reshard_s"] = rep["wall_s"]
                    out["rows_moved"] = rep["rows_moved"]
                    out["carried_over"] = rep["steps"][0]["carried_over"]
                    out["strikes"] = sum(
                        h.strikes for grp in donors
                        for h in getattr(grp, "_health", []))
                if i % write_every == 0:
                    sm.upsert(churn[i * write_rows:(i + 1) * write_rows])
            out["recall_post"] = recall_vs_oracle(sm)
        finally:
            faults.clear("reshard/split")
            faults.clear("replica/search")
            stop.set()
            for t in threads:
                t.join(60)
                assert not t.is_alive(), "reader wedged"
        out["failed"] = failed[0]
        out["served"] = served[0]
        out["wall_s"] = time.perf_counter() - t0
        return out

    _note("reshard churn: rehearsal")
    rehearsal = make_mesh("reshard_rehearsal")
    run_window(rehearsal)
    del rehearsal

    _note("reshard churn: measured window")
    mesh = make_mesh("reshard")
    with obs_compile.attribution() as rec:
        out = run_window(mesh)
    assert out["failed"] == 0, (
        f"{out['failed']} queries failed across the reshard window — the "
        "topology flip must never fail a query")
    assert mesh.n_shards == 2 * shards
    assert out["strikes"] > 0, (
        "the killed replica was never struck — the migration window did "
        "not exercise failover")
    assert rec.compile_s == 0.0, (
        f"reshard window compiled {rec.compile_s}s after rehearsal — the "
        "flip minted a program the pre-flip warm missed")
    assert out["recall_post"] >= out["recall_pre"] - 0.02, out

    _note("reshard churn: crash-mid-reshard recovery")
    tmp = tempfile.mkdtemp(prefix="raft_reshard_")
    try:
        dur = make_mesh("reshard_crash", dir_=os.path.join(tmp, "mesh"))
        twin = make_mesh("reshard_twin")
        for sm2 in (dur, twin):
            for s in range(6):
                sm2.upsert(churn[s * write_rows:(s + 1) * write_rows],
                           ids=np.arange(n + s * write_rows,
                                         n + (s + 1) * write_rows))
                sm2.delete(list(range(s * 8, s * 8 + 8)))
        with faults.scope():
            faults.inject("reshard/flip", faults.SimulatedCrash("kill -9"))
            try:
                dur.reshard(2 * shards)
                raise AssertionError("crash fault never fired")
            except faults.SimulatedCrash:
                pass
        del dur  # the process is gone; the wal_dir is all that survives
        t0 = time.perf_counter()
        rec2 = stream.ShardedMutableIndex.load(os.path.join(tmp, "mesh"),
                                               search_params=sp)
        crash_recovery_s = time.perf_counter() - t0
        assert rec2.n_shards == shards, (
            "crash before the manifest write must recover the OLD topology")
        _, ir = rec2.search(eval_q, k)
        _, it = twin.search(eval_q, k)
        ids_match = float(np.mean(np.asarray(ir) == np.asarray(it)))
        assert ids_match == 1.0, (
            f"recovered mesh diverges from the uncrashed twin "
            f"(id match {ids_match:.4f}) — an acknowledged write was lost")
        replayed = rec2.last_recovery["replayed"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    row = {
        "name": "reshard_churn_100k", "n": n,
        "shards_from": shards, "shards_to": 2 * shards,
        "replicas": replicas,
        "queries": out["served"] * qbatch,
        "failed_queries": out["failed"],
        "strikes": out["strikes"],
        "rows_moved": out["rows_moved"],
        "carried_over": out["carried_over"],
        "reshard_s": round(out["reshard_s"], 3),
        "recall_pre": round(out["recall_pre"], 4),   # gated by compare.py
        "recall_post": round(out["recall_post"], 4),  # gated by compare.py
        "qps": round(out["served"] * qbatch / out["wall_s"], 1),
        "compile_s_loaded": rec.compile_s,
        "crash_recovery_s": round(crash_recovery_s, 3),
        "recall_crash_recovered": ids_match,          # gated by compare.py
        "wal_records_replayed": replayed,
        "wall_s": round(out["wall_s"], 1),
        "reshard_note": "shard count doubled under live read/write load "
                        "with one replica killed mid-migration; zero "
                        "failed queries, zero cold compiles across the "
                        "flip; crash_recovery_s = load of a mesh killed "
                        "between successor swap and manifest write",
    }
    events = _events_delta(ev_before)   # gated by compare.py on presence
    if events is not None:
        row["events"] = events
    rows.append(row)


def _row_controller_drift(rows, n=100_000, d=64, ncl=256, n_lists=256,
                          k=10, m=512, n_eval=256, qbatch=64, repeats=1):
    """Self-driving retune proof (ISSUE 18): a heavytail corpus serves
    under a deliberately-collapsed operating point (``n_probes=1``), the
    drift detector's ``retune_advised`` sensor event reaches the
    controller through the journal tap, and the controller runs its
    bounded sweep and republishes ``tuned=`` through the registry's
    warm-before-flip seam. Asserted:

    - **recall recovers**: ``recall_recovered`` (post-retune, gated by
      bench/compare.py) beats the collapsed pre-retune point (recorded as
      ``pre_retune_at_k`` — deliberately NOT a ``recall*`` field: it is
      low by construction and must not be gated upward);
    - **zero failed queries** across the flip — the old version serves
      until the tuned successor is warm;
    - **zero cold compiles** over the measured window (rehearsal
      protocol: the identical sense→decide→actuate schedule replays
      against a fresh registry/controller with every program warm);
    - **the causal seq chain** sensor → ``control/decision`` →
      ``control/action_completed`` → ``serve_published`` holds in the
      journal, with the decision/trigger seqs cross-referenced — the
      whole actuation replays from the journal alone.
    """
    import numpy as np

    from raft_tpu import tune
    from raft_tpu.control import ControlPolicy, Controller
    from raft_tpu.neighbors import brute_force, ivf_flat
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import events as obs_events
    from raft_tpu.obs import quality
    from raft_tpu.serve import IndexRegistry
    from raft_tpu.tune import reference

    ev_before = _events_snap()
    _note("controller drift: dataset")
    x, q = reference._clustered(n, d, m, ncl, seed=29, heavytail=True)
    xq = np.asarray(q)
    eval_q = xq[:n_eval]
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=n_lists, seed=0), x)
    _, gt_i = brute_force.BruteForce().build(x).search(eval_q, k)
    gt = np.asarray(gt_i)
    family = tune.family_of(idx, x)
    # the collapsed pin: right family (the guard is not what this row
    # exercises), starved operating point
    pin = tune.Decision(kind="ivf_flat", dtype="float32", family=family,
                        params={"n_probes": 1})
    grid = [{"n_probes": max(n_lists // 8, 2)},
            {"n_probes": max(n_lists // 4, 4)},
            {"n_probes": max(n_lists // 2, 8)}]

    def run_window():
        reg = IndexRegistry(buckets=(qbatch,))
        reg.publish("drift", idx, tuned=pin, k=(k,), warm_data=x[:1024])
        ctl = Controller(publisher=reg,
                         policy=ControlPolicy(retune_cooldown_s=0.0))
        ctl.watch("drift", idx, xq[:128], dataset=x, k=k, ks=(k,),
                  grid=grid, repeats=repeats, warm_data=x[:1024],
                  decision=pin)
        ctl.arm()
        det = quality.DriftDetector(tune.shape_family(n, d, "bal"),
                                    name="drift", min_rows=256)
        out = {"failed": 0, "served": 0}

        def measure():
            v = reg.active("drift")
            hits = 0
            for b in range(0, n_eval, qbatch):
                try:
                    _, ii = v.searcher(eval_q[b:b + qbatch], k)
                except Exception:
                    out["failed"] += 1
                    continue
                out["served"] += 1
                for r_, g_ in zip(np.asarray(ii), gt[b:b + qbatch]):
                    hits += len(set(r_.tolist()) & set(g_.tolist()))
            return hits / (n_eval * k)

        t0 = time.perf_counter()
        try:
            out["pre"] = measure()
            det.offer_rows(np.asarray(x[:2048]))
            det.check()          # heavytail vs the "bal" pin -> advised
            out["handled"] = ctl.step()
            out["post"] = measure()
            out["version"] = reg.active("drift").version
        finally:
            ctl.disarm()
        out["wall_s"] = time.perf_counter() - t0
        return out

    _note("controller drift: rehearsal")
    run_window()
    # the sweep picks its arm by measured QPS, which timing noise can flip
    # between two windows: warm every arm the measured window may publish
    for params in grid:
        IndexRegistry(buckets=(qbatch,)).publish(
            "drift", idx, k=(k,), warm_data=x[:1024],
            tuned=tune.Decision(kind="ivf_flat", dtype="float32",
                                family=family, params=params))

    _note("controller drift: measured window")
    with obs_compile.attribution() as rec:
        out = run_window()
    assert out["failed"] == 0, (
        f"{out['failed']} query batches failed across the retune flip")
    assert out["handled"] == 1, out
    assert out["version"] == 2, out
    assert out["post"] > out["pre"], (
        f"retune did not recover recall: {out['pre']} -> {out['post']}")
    assert rec.compile_s == 0.0, (
        f"measured window compiled {rec.compile_s}s after rehearsal — the "
        "controller's republish minted a cold program on the hot path")
    # the causal seq chain, straight off the journal (newest = measured run)
    sensor = obs_events.query(kind="retune_advised", name="drift")[-1]
    dec = obs_events.query(kind="control/decision", name="drift")[-1]
    done = obs_events.query(kind="control/action_completed",
                            name="drift")[-1]
    pub = obs_events.query(kind="serve_published", name="drift")[-1]
    assert sensor["seq"] < dec["seq"] < done["seq"], (sensor, dec, done)
    assert dec["evidence"]["trigger_seq"] == sensor["seq"], dec
    assert done["evidence"]["decision_seq"] == dec["seq"], done
    assert pub["evidence"]["cause"]["decision_seq"] == dec["seq"], pub

    row = {
        "name": "controller_drift_100k", "n": n, "d": d,
        "queries": out["served"] * qbatch,
        "failed_queries": out["failed"],
        "pre_retune_at_k": round(out["pre"], 4),      # collapsed on purpose
        "recall_recovered": round(out["post"], 4),    # gated by compare.py
        "retuned_version": out["version"],
        "trigger_seq": sensor["seq"],
        "decision_seq": dec["seq"],
        "compile_s_loaded": rec.compile_s,
        "wall_s": round(out["wall_s"], 1),
        "controller_note": "drift sensor -> journal tap -> bounded sweep "
                           "-> tuned republish through warm-before-flip; "
                           "recall recovered with zero failed queries and "
                           "zero cold compiles; decision seq chain "
                           "asserted from the journal alone",
    }
    events = _events_delta(ev_before)   # gated by compare.py on presence
    if events is not None:
        row["events"] = events
    rows.append(row)


def _row_controller_ramp(rows, n=100_000, d=64, n_lists=256, k=10,
                         shards=2, n_probes=16, qbatch=64, n_eval=256,
                         ramp_steps=8, ramp_rows=512,
                         delta_capacity=8192):
    """Self-driving reshard proof (ISSUE 18): an upsert ramp pushes a
    mesh past the compactor's ``reshard_rows_per_shard`` watermark, the
    standing ``reshard_advised`` event reaches the controller through the
    journal tap, and the controller doubles the topology online under its
    headroom/burn admission (library mode: ``warm_buckets`` pre-warms the
    successors, so the flip mints no program). Asserted: zero failed
    queries across the ramp AND the flip, zero cold compiles over the
    measured window (rehearsal protocol), recall vs the exact mesh oracle
    held across the flip (``recall_pre``/``recall_post``, gated), and the
    causal chain sensor → decision → ``reshard_started`` → completed.
    """
    import numpy as np

    from raft_tpu import stream
    from raft_tpu.control import Controller
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import events as obs_events

    import jax

    ev_before = _events_snap()
    _note("controller ramp: dataset")
    rng = np.random.default_rng(23)
    x = rng.random((n, d), np.float32)
    ramp = rng.random((ramp_steps * ramp_rows, d), np.float32)
    eval_q = rng.random((n_eval, d), np.float32)
    nl = max(n_lists // shards, 8)
    sp = ivf_flat.SearchParams(n_probes=max(n_probes // shards, 1))
    # the watermark trips mid-ramp: base load sits under it, the ramp
    # crosses it
    threshold = (n + ramp_steps * ramp_rows // 2) // shards

    def build(r):
        return ivf_flat.build(ivf_flat.IndexParams(n_lists=nl, seed=0), r)

    def recall_vs_oracle(sm):
        _, ia = sm.search(eval_q, k)
        _, ie = sm.exact_search(eval_q, k)
        ia, ie = np.asarray(ia), np.asarray(ie)
        return float(np.mean([len(set(a.tolist()) & set(b.tolist())) / k
                              for a, b in zip(ia, ie)]))

    def run_window(tag):
        mesh = stream.ShardedMutableIndex(
            x, n_shards=shards, build=build, search_params=sp,
            delta_capacity=delta_capacity, name=f"ramp_{tag}")
        mesh.warm((qbatch, n_eval), ks=(k,))
        jax.block_until_ready(mesh.search(eval_q, k))
        jax.block_until_ready(mesh.exact_search(eval_q, k))
        comp = stream.Compactor(
            mesh, policy=stream.CompactionPolicy(
                delta_fill=None, tombstone_ratio=None,
                reshard_rows_per_shard=threshold))
        ctl = Controller()
        ctl.attach_mesh(mesh, warm_buckets=(qbatch, n_eval), ks=(k,))
        ctl.attach_compactor(comp)
        ctl.arm()
        out = {"failed": 0, "served": 0}

        def serve():
            for b in range(0, n_eval, qbatch):
                try:
                    _, ii = mesh.search(eval_q[b:b + qbatch], k)
                    assert np.asarray(ii).shape[0] > 0
                    out["served"] += 1
                except Exception:
                    out["failed"] += 1

        t0 = time.perf_counter()
        try:
            out["recall_pre"] = recall_vs_oracle(mesh)
            for i in range(ramp_steps):
                mesh.upsert(ramp[i * ramp_rows:(i + 1) * ramp_rows])
                serve()
                comp.run_once()   # the advisory rides every poll
                ctl.step()        # ... and the controller acts on it
            out["recall_post"] = recall_vs_oracle(mesh)
            out["shards"] = mesh.n_shards
        finally:
            ctl.disarm()
        out["wall_s"] = time.perf_counter() - t0
        return mesh, out

    _note("controller ramp: rehearsal")
    run_window("rehearsal")

    _note("controller ramp: measured window")
    with obs_compile.attribution() as rec:
        mesh, out = run_window("measured")
    assert out["failed"] == 0, (
        f"{out['failed']} query batches failed across the ramp window")
    assert out["shards"] == 2 * shards, (
        f"the controller never resharded: {out['shards']} shards after "
        f"the ramp (threshold {threshold})")
    assert rec.compile_s == 0.0, (
        f"measured window compiled {rec.compile_s}s after rehearsal — the "
        "controller's flip minted a program the pre-flip warm missed")
    assert out["recall_post"] >= out["recall_pre"] - 0.02, out
    # the causal chain, straight off the journal (newest = measured run)
    sensor = obs_events.query(kind="reshard_advised",
                              name=mesh.name)[-1]
    dec = obs_events.query(kind="control/decision", name=mesh.name)[-1]
    started = obs_events.query(kind="reshard_started",
                               name=mesh.name)[-1]
    done = obs_events.query(kind="control/action_completed",
                            name=mesh.name)[-1]
    assert sensor["seq"] < dec["seq"] < started["seq"] < done["seq"], (
        sensor["seq"], dec["seq"], started["seq"], done["seq"])
    assert dec["evidence"]["trigger_seq"] == sensor["seq"], dec
    assert started["evidence"]["cause"]["decision_seq"] == dec["seq"], \
        started
    assert done["evidence"]["decision_seq"] == dec["seq"], done

    row = {
        "name": "controller_ramp_100k", "n": n, "d": d,
        "shards_from": shards, "shards_to": out["shards"],
        "queries": out["served"] * qbatch,
        "failed_queries": out["failed"],
        "recall_pre": round(out["recall_pre"], 4),    # gated by compare.py
        "recall_post": round(out["recall_post"], 4),  # gated by compare.py
        "reshard_threshold": threshold,
        "trigger_seq": sensor["seq"],
        "decision_seq": dec["seq"],
        "compile_s_loaded": rec.compile_s,
        "wall_s": round(out["wall_s"], 1),
        "controller_note": "compactor watermark -> reshard_advised -> "
                           "controller admission -> online topology "
                           "double; zero failed queries, zero cold "
                           "compiles, recall held; causal seq chain "
                           "asserted from the journal alone",
    }
    events = _events_delta(ev_before)   # gated by compare.py on presence
    if events is not None:
        row["events"] = events
    rows.append(row)


def _row_tiered(rows, n=100_000, d=128, n_lists=1024, pq_dim=16, k=10,
                n_probes=8, ratio=4, m=1024, bucket=256, waves=3, ncl=2000):
    """Beyond-HBM tiered storage A/B (ISSUE 15 acceptance): the SAME
    corpus served through the refined IVF-PQ pipeline twice — all-HBM
    (``storage="hbm"``: raw rows resident on device) vs tiered
    (``storage="tiered"``: rows in host RAM under a device
    ``memory_budget_bytes`` that the raw-row footprint EXCEEDS, so the
    store provably cannot promote). The acceptance bits ride in the row
    body (a violation converts to an error row):

    - **recall anchor holds**: the tiered twin's refined ids are
      BIT-EQUAL to the all-HBM twin's (tiering moves where rows live,
      never what a query answers), so recall is identical by
      construction and recorded once per twin for the compare.py gate.
    - **zero failed queries, zero cold compiles** across the measured
      waves (rehearsal wave first — the documented warm protocol — then
      compile attribution must stay at 0).
    - **per-tier ledger bytes flat across waves**: the double-buffered
      gather slots allocate once, then steady-state device bytes are
      constant (the slot-ring replacement contract, ledger-provable).
    - the measured **host-hop cost**: tiered vs all-HBM QPS, with the
      host-gather wall (``host_hop_s``) and H2D bytes decomposed per
      wave so the QPS delta is attributable to the hop, not noise.
    """
    import gc

    import jax
    import numpy as np

    from raft_tpu.core.resources import default_resources
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import mem as obs_mem
    from raft_tpu.stream import MutableIndex, TierPolicy

    ev_before = _events_snap()
    _note("tiered: dataset")
    dataset, qsets = _make_clustered(n, d, m, ncl, n_qsets=2, seed=13)
    jax.block_until_ready([dataset] + qsets)
    _note("tiered: ground truth")
    gt = _ground_truth(dataset, qsets[-1][:1000], k=k)
    host_rows = np.asarray(dataset)
    store_bytes = host_rows.nbytes

    _note("tiered: ivf_pq build")
    t0 = time.perf_counter()
    params = ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4, pq_dim=pq_dim,
                                seed=0)
    idx = ivf_pq.build(params, dataset)
    jax.block_until_ready(idx.list_codes)
    build_s = time.perf_counter() - t0
    sp = ivf_pq.SearchParams(n_probes=n_probes, lut_dtype="bfloat16")

    pools = [np.asarray(q) for q in qsets]

    def run_waves(mut, label):
        """Rehearse every (bucket, k) shape once, then measure `waves`
        full passes: per-wave wall, failures, last-wave outputs, and
        compile attribution over the measured (post-rehearsal) window."""
        rehearse = pools[0][:bucket]
        jax.block_until_ready(mut.search_refined(rehearse, k, ratio)[0])
        walls, fails, outs = [], 0, None
        with obs_compile.attribution() as rec:
            for w in range(waves):
                pool = pools[w % len(pools)]
                wave_out = []
                t0 = time.perf_counter()
                for off in range(0, m, bucket):
                    try:
                        _, ids = mut.search_refined(
                            pool[off:off + bucket], k, ratio)
                        wave_out.append(np.asarray(ids))
                    except Exception:  # any loss fails the row's claim
                        fails += 1
                walls.append(time.perf_counter() - t0)
                if w % len(pools) == len(pools) - 1:
                    outs = np.concatenate(wave_out) if wave_out else None
        _note(f"tiered: {label} waves done")
        return walls, fails, outs, rec

    # ---- all-HBM twin ------------------------------------------------------
    m_hbm = MutableIndex(idx, search_params=sp, index_params=params,
                         dataset=host_rows, name="tiered_ab_hbm")
    walls_h, fails_h, out_h, rec_h = run_waves(m_hbm, "all-HBM")
    del m_hbm
    gc.collect()

    # ---- tiered twin under a squeezing device budget -----------------------
    # the budget the corpus EXCEEDS: everything accounted so far plus half
    # the raw-row footprint — the store cannot promote (placement decides
    # cold, hit-rate promotes are refused by headroom), which is the
    # beyond-HBM claim: the corpus serves anyway
    res = default_resources()
    prev_budget = res.memory_budget_bytes
    budget = obs_mem.totals()["device_bytes"] + store_bytes // 2
    res.memory_budget_bytes = budget
    try:
        m_tier = MutableIndex(idx, search_params=sp, index_params=params,
                              dataset=host_rows, name="tiered_ab_tiered",
                              storage="tiered", tier=TierPolicy())
        ts = m_tier.tiered_store
        assert not ts.mirror_resident, (
            "the squeezing budget must keep the store cold — residency "
            f"{ts.residency!r} under budget {budget}")
        hop0 = ts.stats()
        walls_t, fails_t, out_t, rec_t = run_waves(m_tier, "tiered")
        # per-tier ledger bytes flat across waves: steady-state slots only
        levels = []
        for w in range(2):
            jax.block_until_ready(
                m_tier.search_refined(pools[0][:bucket], k, ratio)[0])
            levels.append(dict(ts.tier_bytes()))
        assert levels[0] == levels[-1], (
            f"per-tier bytes must be flat across waves, got {levels}")
        assert not ts.mirror_resident, (
            "hit-rate promote must stay refused under the budget")
        hop1 = ts.stats()
        tier_bytes = ts.tier_bytes()
    finally:
        res.memory_budget_bytes = prev_budget

    assert fails_h == 0 and fails_t == 0, (
        f"zero failed queries required (hbm={fails_h}, tiered={fails_t})")
    assert rec_t.compile_s == 0.0 and rec_t.cache_misses == 0, (
        f"zero cold compiles across refine double-buffer cycles, got "
        f"{rec_t.compile_s}s / {rec_t.cache_misses} misses")
    # the twin's window must be equally hot, or a sneaked compile would
    # deflate qps_hbm and inflate the headline hbm_over_tiered ratio
    assert rec_h.compile_s == 0.0 and rec_h.cache_misses == 0, (
        f"cold compile in the all-HBM twin's measured waves: "
        f"{rec_h.compile_s}s / {rec_h.cache_misses} misses")
    assert out_h is not None and out_t is not None
    assert (out_h == out_t).all(), (
        "tiered refined ids must be bit-equal to the all-HBM twin")
    recall = round(_recall(out_t[:1000], gt), 4)

    qps_h = round(m * waves / sum(walls_h), 1)
    qps_t = round(m * waves / sum(walls_t), 1)
    row = {
        "name": "tiered_100k", "n": n, "k": k, "refine_ratio": ratio,
        "qps": qps_t,
        "qps_hbm": qps_h,
        "hbm_over_tiered": round(qps_h / max(qps_t, 1e-9), 3),
        "recall": recall,            # gated by compare.py
        "recall_hbm": recall,        # bit-equal twins (asserted above)
        "build_s": round(build_s, 1),
        "budget_bytes": int(budget),
        "store_bytes": int(store_bytes),
        "tier_residency": ts.residency,
        "tier_bytes": {t: int(b) for t, b in tier_bytes.items()},
        "host_hop_s": round(hop1["fetch_wall_s"] - hop0["fetch_wall_s"], 4),
        "h2d_bytes": hop1["h2d_bytes"] - hop0["h2d_bytes"],
        "hit_ratio": round(hop1["hit_ratio"], 4),
        "spills": hop1["spills"], "promotes": hop1["promotes"],
        "failed_queries": 0,
        "steady_compile_s": rec_t.compile_s,
        "steady_cache_misses": rec_t.cache_misses,
        "tiered_note": "same corpus, refined pipeline, raw rows exceed "
                       "the device budget: ids bit-equal to the all-HBM "
                       "twin, per-tier bytes flat across waves, zero "
                       "failed queries, zero cold compiles; "
                       "hbm_over_tiered is the measured host-hop cost",
    }
    events = _events_delta(ev_before)   # gated by compare.py on presence
    if events is not None:
        row["events"] = events
    rows.append(row)


def _row_ooc_build(rows, n=100_000, d=128, n_lists=1024, pq_dim=16, k=10,
                   n_probes=8, chunk_rows=16384, ncl=2000):
    """Out-of-core streamed build A/B (ISSUE 19 acceptance): the SAME
    clustered corpus built twice with identical IVF-PQ parameters —
    in-core (whole corpus materialized through the classic build path)
    vs streamed off a temp-file ``.npy`` ``np.memmap`` through
    ``core.chunked.ChunkedReader``. The acceptance bits ride in the row
    body (a violation converts to an error row):

    - **bit-equal indexes**: every array field of the streamed index is
      identical to the in-core twin's, so recall is shared by
      construction — recorded once for the compare.py gate.
    - **peak build device bytes flat across chunks**: the streamed
      twin's measured ledger peak brackets within the ±20% envelope of
      ``obs.mem.plan(streamed=True)``, whose staging term is TWO chunks
      regardless of corpus size — the whole-corpus device copy is gone
      from the build path, so corpus scale buys index bytes only.
    - the measured **streaming cost**: build walls plus device AND host
      ledger peaks for both twins, so the HBM savings (and the host-side
      price of staging) are attributable, not inferred.
    """
    import gc
    import os
    import tempfile

    import jax
    import numpy as np

    from raft_tpu.core import chunked
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs import mem as obs_mem

    ev_before = _events_snap()
    _note("ooc: dataset")
    dataset, qsets = _make_clustered(n, d, 1024, ncl, n_qsets=1, seed=19)
    jax.block_until_ready([dataset] + qsets)
    _note("ooc: ground truth")
    gt = _ground_truth(dataset, qsets[0][:1000], k=k)
    host_rows = np.asarray(dataset)

    params = ivf_pq.IndexParams(n_lists=n_lists, pq_bits=4, pq_dim=pq_dim,
                                seed=0)

    def measured_build(x):
        gc.collect()
        base = obs_mem.totals()
        obs_mem.reset_peak()
        t0 = time.perf_counter()
        idx = ivf_pq.build(params, x)
        jax.block_until_ready(idx.list_codes)
        wall = time.perf_counter() - t0
        tot = obs_mem.totals()
        return (idx, wall, tot["device_peak_bytes"] - base["device_bytes"],
                tot["host_peak_bytes"] - base["host_bytes"])

    _note("ooc: in-core twin build")
    idx_a, wall_a, dev_a, host_a = measured_build(dataset)

    with tempfile.TemporaryDirectory(prefix="raft_tpu_ooc_") as tmp:
        path = os.path.join(tmp, "corpus.npy")
        np.save(path, host_rows)
        reader = chunked.ChunkedReader.from_file(path, chunk_rows=chunk_rows)
        est = obs_mem.plan("ivf_pq", params, n, d, streamed=True,
                           chunk_rows=chunk_rows)
        _note("ooc: streamed twin build")
        idx_b, wall_b, dev_b, host_b = measured_build(reader)

    import dataclasses
    for f in dataclasses.fields(idx_a):
        va, vb = getattr(idx_a, f.name), getattr(idx_b, f.name)
        if hasattr(va, "shape"):
            assert np.array_equal(np.asarray(va), np.asarray(vb)), (
                f"streamed build must be bit-equal to in-core: {f.name}")

    # plan(streamed) is the ADMISSION envelope: the measured ledger peak
    # must stay inside it (ivf_pq's transient trainset scratch is priced
    # by plan but outside the accounted window, so the measurement may
    # legitimately under-run; the two-sided ±20% contract is tier-1 on
    # ivf_flat, whose streamed terms the ledger mirrors exactly)
    assert dev_b <= 1.2 * est["build_peak_bytes"], (
        f"streamed peak {dev_b} above plan {est['build_peak_bytes']} "
        f"+20% — the flat-across-chunks staging claim failed")

    _note("ooc: recall")
    sp = ivf_pq.SearchParams(n_probes=n_probes, lut_dtype="bfloat16")
    _, ids = ivf_pq.search(sp, idx_b, qsets[0][:1000], k)
    recall = round(_recall(np.asarray(ids), gt), 4)

    row = {
        "name": "ooc_build_100k", "n": n, "d": d, "k": k,
        "recall": recall,            # gated by compare.py; shared by the
        "recall_incore": recall,     # bit-equal twins (asserted above)
        "build_s": round(wall_b, 2),
        "build_s_incore": round(wall_a, 2),
        "peak_dev_bytes": int(dev_b),
        "peak_dev_bytes_incore": int(dev_a),
        "peak_host_bytes": int(host_b),
        "peak_host_bytes_incore": int(host_a),
        "plan_dev_bytes": int(est["build_peak_bytes"]),
        "plan_host_bytes": int(est["host_peak_bytes"]),
        "staging_dev_bytes": 2 * chunk_rows * d * 4,
        "n_chunks": reader.n_chunks,
        "corpus_bytes": int(host_rows.nbytes),
        "bit_equal": True,
        "ooc_note": "same corpus, same params, in-core vs memmap-streamed: "
                    "indexes bit-equal, streamed device peak within "
                    "plan(streamed)'s ±20% whose staging term is two "
                    "chunks regardless of corpus size",
    }
    events = _events_delta(ev_before)   # gated by compare.py on presence
    if events is not None:
        row["events"] = events
    rows.append(row)


def _row_quant_funnel(rows, n=100_000, d=128, n_lists=1024, pq_dim=64, k=10,
                      m=1024, bucket=256, waves=3, ncl=2000, repeats=2):
    """Quantization-funnel capacity A/B (ISSUE 16 acceptance): the SAME
    clustered corpus built twice with identical codec parameters — classic
    PQ (``fast_scan="none"``) vs the funnel twin carrying the bit-packed
    1-bit signature tier — then swept over ``tune.funnel_grid`` so the
    recall-vs-QPS-vs-bytes frontier lands in a decision log. The grid HEAD
    is the classic operating point, so ``recall_target="default"`` anchors
    the funnel pin to the classic scan's recall. Acceptance bits ride in
    the row body (a violation converts to an error row):

    - **width-1 bit-equality**: the funnel twin searched at
      ``funnel_widen=1`` routes through the untouched classic scan and
      answers bit-equal to the classic twin (same seed → same codebooks;
      the signature tier is pure addition);
    - **recall anchor holds**: the chosen funnel point's measured recall
      on the held-out query set stays within tolerance of the classic
      anchor (the sweep's choice rule enforces it on the sweep set);
    - **capacity claim**: the funnel's hot-scan bytes per probed row
      (packed signatures + ids, streamed by stage A) price ≥2× more rows
      per HBM byte than the classic scan (unpacked PQ codes + ids) —
      ``bytes_per_row``/``rows_per_hbm_byte`` are the fields
      ``bench/compare.py`` gates on presence;
    - **zero cold compiles** across the measured waves of both twins
      (rehearsal wave first — the documented warm protocol).
    """
    import jax
    import numpy as np

    from raft_tpu import tune
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.tune.apply import search_fn

    _note("quant: dataset")
    dataset, qsets = _make_clustered(n, d, m, ncl, n_qsets=2, seed=13)
    jax.block_until_ready([dataset] + qsets)
    _note("quant: ground truth")
    gt = _ground_truth(dataset, qsets[-1][:1000], k=k)
    pools = [np.asarray(q) for q in qsets]

    base = dict(n_lists=n_lists, pq_bits=4, pq_dim=pq_dim, seed=0)
    _note("quant: classic build")
    t0 = time.perf_counter()
    idx_c = ivf_pq.build(ivf_pq.IndexParams(**base), dataset)
    jax.block_until_ready(idx_c.list_codes)
    build_c = time.perf_counter() - t0
    _note("quant: funnel build (1bit tier)")
    t0 = time.perf_counter()
    idx_f = ivf_pq.build(ivf_pq.IndexParams(fast_scan="1bit", **base),
                         dataset)
    jax.block_until_ready(idx_f.list_sig)
    build_f = time.perf_counter() - t0

    # width-1 bit-equality: widen=1 routes through the classic scan on the
    # same codebooks, so the tier must not change a single answer
    _, ids_f1 = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=8, funnel_widen=1), idx_f,
        pools[0][:bucket], k)
    _, ids_c1 = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=8), idx_c, pools[0][:bucket], k)
    assert (np.asarray(ids_f1) == np.asarray(ids_c1)).all(), (
        "funnel twin at funnel_widen=1 must answer bit-equal to the "
        "classic-PQ twin")

    _note("quant: funnel_grid sweep")
    log = tune.DecisionLog()
    dec = tune.sweep(idx_f, qsets[0], k=k, dataset=dataset, gt=None,
                     grid=tune.funnel_grid(), recall_target="default",
                     repeats=repeats, log=log)
    ev = dec.evidence

    fn_funnel = search_fn(idx_f, dec, dataset=dataset)
    # the classic anchor serves the grid head's operating point on the
    # no-tier twin — the honest bytes/QPS baseline
    fn_classic = search_fn(
        idx_c, {"n_probes": 8, "refine_ratio": 4}, dataset=dataset)

    def run_waves(fn, label):
        """Rehearse the (bucket, k) shape once, then measure ``waves``
        full passes with compile attribution over the measured window."""
        jax.block_until_ready(fn(pools[0][:bucket], k)[0])
        walls, outs = [], None
        with obs_compile.attribution() as rec:
            for w in range(waves):
                pool = pools[w % len(pools)]
                wave_out = []
                t0 = time.perf_counter()
                for off in range(0, m, bucket):
                    _, ids = fn(pool[off:off + bucket], k)
                    wave_out.append(np.asarray(ids))
                walls.append(time.perf_counter() - t0)
                if w % len(pools) == len(pools) - 1:
                    outs = np.concatenate(wave_out)
        _note(f"quant: {label} waves done")
        return walls, outs, rec

    walls_c, out_c, rec_c = run_waves(fn_classic, "classic")
    walls_f, out_f, rec_f = run_waves(fn_funnel, "funnel")
    assert rec_c.compile_s == 0.0 and rec_c.cache_misses == 0, (
        f"cold compile in the classic twin's measured waves: "
        f"{rec_c.compile_s}s / {rec_c.cache_misses} misses")
    assert rec_f.compile_s == 0.0 and rec_f.cache_misses == 0, (
        f"cold compile in the funnel twin's measured waves: "
        f"{rec_f.compile_s}s / {rec_f.cache_misses} misses")

    recall_c = round(_recall(out_c[:1000], gt), 4)
    recall_f = round(_recall(out_f[:1000], gt), 4)
    assert recall_f >= recall_c - 0.02, (
        f"funnel recall {recall_f} broke the classic anchor {recall_c} "
        "on the held-out set")

    # hot-scan bytes per probed row: what stage A streams per candidate.
    # Classic scans the unpacked PQ codes + ids; the funnel scans the
    # packed signatures + ids and touches codes only for the k_widen
    # survivors (gather, not stream).
    bpr_c = int(idx_c.list_codes.shape[2]) + 4
    bpr_f = int(idx_f.list_sig.shape[2]) + 4
    capacity_x = bpr_c / bpr_f
    assert capacity_x >= 2.0, (
        f"funnel must price >=2x rows per HBM byte, got {capacity_x:.2f} "
        f"(classic {bpr_c} B/row vs funnel {bpr_f} B/row)")

    qps_c = round(m * waves / sum(walls_c), 1)
    qps_f = round(m * waves / sum(walls_f), 1)
    rows.append({
        "name": "quant_funnel_100k", "n": n, "k": k,
        "qps": qps_f,
        "qps_classic": qps_c,
        "recall": recall_f,           # gated by compare.py
        "recall_classic": recall_c,   # the anchor, gated too
        "bytes_per_row": bpr_f,       # presence-gated by compare.py
        "rows_per_hbm_byte": round(1.0 / bpr_f, 6),
        "bytes_per_row_classic": bpr_c,
        "rows_per_hbm_byte_classic": round(1.0 / bpr_c, 6),
        "capacity_x": round(capacity_x, 3),
        "build_s": round(build_f, 1),
        "build_classic_s": round(build_c, 1),
        "decision": dec.key, "chosen": dict(dec.params),
        "n_trials": len(ev["trials"]),
        "frontier": ev["frontier"],
        "chosen_qps_over_default": ev["chosen_qps_over_default"],
        "steady_compile_s": rec_f.compile_s,
        "steady_cache_misses": rec_f.cache_misses,
        "quant_note": "same corpus, same codec seed: funnel twin bit-equal "
                      "to classic at width 1, recall anchored to the "
                      "classic operating point by the funnel_grid head, "
                      "capacity_x is hot-scan bytes/row priced classic "
                      "over funnel, frontier recorded in the decision log",
    })


def _row_ivf_flat(rows, dataset, qsets, gt):
    import numpy as np

    from raft_tpu.neighbors import ivf_flat

    _note("ivf_flat build")
    t0 = time.perf_counter()
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=1024, seed=0), dataset)
    import jax
    jax.block_until_ready(idx.list_data)
    build_s = time.perf_counter() - t0
    sp = ivf_flat.SearchParams(n_probes=8)
    qps, out = _measure_qps(
        lambda q: ivf_flat.search(sp, idx, q, 10), qsets,
        qsets[0].shape[0], use_jit=False)
    rows.append({"name": "ivf_flat_1m_p8",
                 "qps": round(qps, 1),
                 "recall": round(_recall(np.asarray(out[1])[:1000], gt), 4),
                 "build_s": round(build_s, 1)})


def _row_cagra(rows, dataset, qsets, gt):
    import numpy as np

    from raft_tpu.neighbors import cagra

    _note("cagra build")
    t0 = time.perf_counter()
    idx = cagra.build(cagra.IndexParams(), dataset)
    import jax
    jax.block_until_ready(idx.graph)
    build_s = time.perf_counter() - t0
    sp = cagra.SearchParams(itopk_size=32)
    qps, out = _measure_qps(
        lambda q: cagra.search(sp, idx, q, 10), qsets,
        qsets[0].shape[0], use_jit=False)
    rows.append({"name": "cagra_1m_itopk32",
                 "qps": round(qps, 1),
                 "recall": round(_recall(np.asarray(out[1])[:1000], gt), 4),
                 "build_s": round(build_s, 1)})


def _render_note(artifact: dict) -> str:
    """Markdown round-note table generated FROM a BENCH_rXX.json artifact
    (VERDICT r5 #7: the r05 BASELINE note described a different session than
    the committed artifact — prose and artifact must be the same bytes).
    Pure stdlib, no jax: runs anywhere, including the doc-writing host.

        python bench.py --note BENCH_r06.json

    Ratio fields that ride IN the rows (fused_over_control, i8_over_f32,
    serve_over_seq) are printed from the rows, never recomputed elsewhere.
    """
    if "parsed" in artifact and isinstance(artifact["parsed"], dict):
        # driver wrapper ({n, cmd, rc, tail, parsed}): the bench's own
        # result line lives under "parsed"
        artifact = artifact["parsed"]
    lines = [
        "| row | QPS | recall | build_s | ratio |",
        "|---|---|---|---|---|",
    ]
    for r in artifact.get("rows", []):
        name = r.get("name", "?")
        if "error" in r:
            lines.append(f"| {name} | ERROR | | | {r['error'][:60]} |")
            continue
        if "qps" not in r:
            continue
        ratio = ""
        for key, label in (("fused_over_control", "fused/control"),
                           ("i8_over_f32", "i8/f32"),
                           ("serve_over_seq", "serve/seq"),
                           ("hbm_over_tiered", "hbm/tiered")):
            if r.get(key) is not None:
                ratio = f"{label} **{r[key]}**"
        rec = r.get("recall")
        lines.append(
            f"| {name} | {r['qps']:,.1f} | "
            f"{'' if rec is None else format(rec, '.4f')} | "
            f"{r.get('build_s', '')} | {ratio} |")
    head = (
        f"Flagship {artifact.get('value', 0):,.1f} {artifact.get('unit', '')}"
        f" (vs_baseline {artifact.get('vs_baseline')}), "
        f"elapsed {artifact.get('elapsed_s')}s, "
        f"metrics_enabled={artifact.get('metrics_enabled')}. "
        "Table generated by `python bench.py --note <artifact>` — the "
        "numbers below ARE the artifact's.")
    return head + "\n\n" + "\n".join(lines)


def _backend_or_exit(rows, timeout_s=150.0, _exit=None):
    """Force backend init under a watchdog; on failure emit a labeled row
    and exit NON-ZERO — a bench with no backend measured nothing.

    Backend init can fail in two ways: raising and HANGING inside device
    discovery — so a try/except alone is not enough; the probe runs in a
    daemon thread and a hang past ``timeout_s`` converts to a labeled row +
    ``os._exit(1)`` (all output is already flushed; atexit has nothing to
    do). ``_exit`` is injectable for the unit test.
    """
    import os
    import threading

    box = {}

    def probe():
        try:
            import jax

            box["n"] = len(jax.devices())
        except BaseException as e:  # labeled, never propagated
            box["err"] = f"{type(e).__name__}: {str(e)[:240]}"

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(timeout_s)
    err = (f"backend init did not return within {timeout_s:.0f}s"
           if t.is_alive() else box.get("err"))
    if err is not None:
        rows.append({"name": "backend", "error": err})
        _emit()
        (_exit or os._exit)(1)


def _row_net_serve(rows, n=100_000, d=64, n_lists=512, k=10, n_probes=16,
                   thread_ladder=(1, 4, 8), per_thread=150, max_batch=64,
                   max_wait_us=2000.0, n_eval=512, ncl=500):
    """Network front door A/B (ISSUE 20): the SAME published service
    driven closed-loop in-process (``svc.search``) and over the loopback
    wire (NetClient -> NetServer -> svc) at each rung of a concurrency
    ladder. Same index, same flush programs, so recall over the wire must
    equal the in-process measurement exactly (both fields gated by
    bench/compare.py); the wire tax is the QPS ratio at the top rung; the
    request p99 decomposes into wire/queue/flush from the serve
    histograms plus the front door's wire-wall histogram. The whole
    serving window — both paths, every rung — runs under compile
    attribution and MUST be compile-free: publish() warmed the bucket
    ladder and the wire path replays the same program set."""
    import threading

    import jax
    import numpy as np

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.net.client import NetClient
    from raft_tpu.net.server import NetServer
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import metrics as obs_metrics
    from raft_tpu.serve import SearchService

    _note("net: dataset")
    dataset, qsets = _make_clustered(n, d, 2000, ncl, n_qsets=1, seed=29)
    jax.block_until_ready([dataset] + qsets)
    pool = np.asarray(qsets[0])
    eval_q = pool[:n_eval]
    _note("net: ground truth")
    gt = _ground_truth(dataset, eval_q, k=k)

    _note("net: ivf_flat build + publish")
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=n_lists, seed=0),
                         dataset)
    jax.block_until_ready(idx.list_data)
    sp = ivf_flat.SearchParams(n_probes=n_probes)

    def serving(queries, k_):
        return ivf_flat.search(sp, idx, queries, k_)

    serving.kind, serving.dim, serving.query_dtype = "ivf_flat", d, "float32"
    svc = SearchService(max_batch=max_batch, max_wait_us=max_wait_us,
                        max_queue_rows=4096)
    svc.publish("net", serving, k=k)  # the warm ladder IS the rehearsal

    failures = []

    def ladder(search_one):
        qps = {}
        for T in thread_ladder:
            def worker(tid):
                for j in range(per_thread):
                    qi = (tid + j * T) % pool.shape[0]
                    try:
                        search_one(pool[qi:qi + 1])
                    except Exception as e:  # pragma: no cover - fails row
                        failures.append(
                            f"{type(e).__name__}: {str(e)[:80]}")
            ws = [threading.Thread(target=worker, args=(t,))
                  for t in range(T)]
            t0 = time.perf_counter()
            for w in ws:
                w.start()
            for w in ws:
                w.join(600)
            qps[str(T)] = round(
                T * per_thread / (time.perf_counter() - t0), 1)
        return qps

    def recall_of(search_batch):
        got = []
        for lo in range(0, n_eval, max_batch):
            _, ids = search_batch(eval_q[lo:lo + max_batch])
            got.append(np.asarray(ids))
        return _recall(np.concatenate(got), gt)

    with NetServer(svc) as srv:
        cli = NetClient(f"http://127.0.0.1:{srv.port}")
        # settle both paths' first flush OUTSIDE the attribution window:
        # publish() compiled the ladder; these replay it from cache
        svc.search("net", pool[:1], k)
        cli.search("net", pool[:1], k)
        with obs_compile.attribution() as rec:
            _note("net: in-process ladder")
            qps_in = ladder(lambda q: svc.search("net", q, k))
            _note("net: wire ladder")
            qps_wire = ladder(lambda q: cli.search("net", q, k))
            recall_in = recall_of(lambda q: svc.search("net", q, k))
            recall_wire = recall_of(lambda q: cli.search("net", q, k))
    svc.shutdown()

    p99 = None
    if _STATE["metrics"]:
        stream_label = f"net.k{k}"
        p99 = {
            "wire_total_ms": round(obs_metrics.quantile(
                "raft_tpu_net_wire_seconds", 0.99,
                route="/v1/search") * 1e3, 3),
            "queue_ms": round(obs_metrics.quantile(
                "raft_tpu_serve_queue_wait_seconds", 0.99,
                stream=stream_label) * 1e3, 3),
            "flush_ms": round(obs_metrics.quantile(
                "raft_tpu_serve_flush_seconds", 0.99,
                stream=stream_label) * 1e3, 3),
        }
    top = str(thread_ladder[-1])
    assert not failures, failures[:3]
    assert rec.cache_misses == 0, (
        f"cold compiles on the serving window: {rec.cache_misses}")
    rows.append({
        "name": "net_serve_100k",
        "qps": qps_wire[top],
        "qps_inproc": qps_in[top],
        "wire_tax": (round(qps_wire[top] / qps_in[top], 3)
                     if qps_in[top] else None),
        "qps_by_threads": {"inproc": qps_in, "wire": qps_wire},
        "recall_inproc": round(recall_in, 4),
        "recall_wire": round(recall_wire, 4),
        "recall_gap": round(recall_wire - recall_in, 4),
        "p99_decomp": p99,
        "compile_s": round(rec.compile_s, 3),
        "cache_misses": rec.cache_misses,
        "threads": list(thread_ladder), "max_batch": max_batch,
        "k": k, "n_probes": n_probes, "n_lists": n_lists,
    })


def _row_net_kill_worker(rows, n=100_000, d=64, k=10, threads=6,
                         duration_s=8.0, kill_after_s=3.0, n_eval=256,
                         max_batch=64):
    """Mesh availability over the wire (ISSUE 20): a 2-shard x 2-replica
    ProcessMesh serves a closed loop through the network front door; one
    worker process is SIGKILLed mid-load. The router's breaker must turn
    the kill into strike->fence->failover with ZERO failed queries (the
    PR 11 semantics crossing process boundaries), post-kill recall must
    stay exact (brute-force workers — any drop means the merge lost a
    shard's candidates), and the surviving fleet reports zero cold
    compiles: each worker warmed its bucket ladder at boot, before the
    front door ever saw traffic."""
    import threading

    import numpy as np

    from raft_tpu.net.client import NetClient
    from raft_tpu.net.mesh import MeshSpec, ProcessMesh
    from raft_tpu.net.server import NetServer
    from raft_tpu.obs import events as obs_events

    _note("net-kill: dataset")
    rng = np.random.default_rng(31)
    dataset = rng.standard_normal((n, d)).astype(np.float32)
    pool = rng.standard_normal((2000, d)).astype(np.float32)
    eval_q = pool[:n_eval]
    gt = _ground_truth(dataset, eval_q, k=k)

    _note("net-kill: boot 2x2 worker mesh (spawn + warm ladders)")
    seq0 = obs_events.last_seq()
    t0 = time.perf_counter()
    mesh = ProcessMesh(dataset, spec=MeshSpec(
        n_shards=2, n_replicas=2, name="corpus", ks=(k,),
        max_batch=max_batch))
    boot_s = time.perf_counter() - t0

    failures, served = [], [0]
    lock = threading.Lock()
    done = threading.Event()
    kill_box = {}
    try:
        with NetServer(mesh, stats=mesh.stats) as srv:
            cli = NetClient(f"http://127.0.0.1:{srv.port}")

            def reader(tid):
                cnt, j = 0, 0
                while not done.is_set():
                    qi = (tid + j * threads) % pool.shape[0]
                    j += 1
                    try:
                        cli.search("corpus", pool[qi:qi + 1], k)
                        cnt += 1
                    except Exception as e:  # pragma: no cover - fails row
                        with lock:
                            failures.append(
                                f"{type(e).__name__}: {str(e)[:80]}")
                with lock:
                    served[0] += cnt

            _note(f"net-kill: {threads}-thread load, kill s0r0 at "
                  f"{kill_after_s:.0f}s")
            ws = [threading.Thread(target=reader, args=(t,))
                  for t in range(threads)]
            t_load = time.perf_counter()
            for w in ws:
                w.start()
            time.sleep(kill_after_s)
            kill_box["pid"] = mesh.kill_worker(0, 0)
            kill_box["at_s"] = round(time.perf_counter() - t_load, 2)
            time.sleep(max(duration_s - kill_after_s, 1.0))
            done.set()
            for w in ws:
                w.join(60)
            load_s = time.perf_counter() - t_load

            got = []
            for lo in range(0, n_eval, max_batch):
                _, ids = cli.search("corpus", eval_q[lo:lo + max_batch], k)
                got.append(np.asarray(ids))
            recall_after = _recall(np.concatenate(got), gt)
            st = mesh.stats()
            health = mesh.health()
    finally:
        mesh.close()

    kinds = [e["kind"] for e in obs_events.query(since_seq=seq0)]
    failovers = kinds.count("net_worker_failover")
    assert not failures, (
        f"{len(failures)} failed queries: {failures[:3]}")
    assert failovers >= 1, "the kill produced no observed failover"
    assert st["cache_misses"] == 0, (
        f"cold compiles in the surviving fleet: {st['cache_misses']}")
    rows.append({
        "name": "net_kill_worker_100k",
        "qps": round(served[0] / load_s, 1),
        "queries": served[0],
        "failed": len(failures),
        "recall_after_kill": round(recall_after, 4),
        "failovers": failovers,
        "fenced": kinds.count("net_worker_fenced"),
        "kill": {"shard": 0, "replica": 0, "pid": kill_box["pid"],
                 "at_s": kill_box["at_s"]},
        "healthy_by_shard": [g["healthy"] for g in health["shards"]],
        "fleet": {"compile_s": st["compile_s"],
                  "cache_misses": st["cache_misses"],
                  "workers_reporting": st["workers"],
                  # the worker processes run on the CPU by design: this
                  # row's QPS is a CPU fleet's, whatever the router ran on
                  "worker_platforms": st["platforms"]},
        "boot_s": round(boot_s, 1),
        "shards": 2, "replicas": 2, "threads": threads,
        "max_batch": max_batch, "k": k,
    })


def _row_guard(rows, name, fn, timeout_s=None, _exit=None):
    """Run one row's body under a watchdog (VERDICT r3 weak #6).

    Exceptions convert to a labeled error row and the bench continues. A
    HANG past the per-row deadline — which a try/except cannot catch —
    converts to a labeled error row, a final emit, and ``os._exit(0)``: a
    wedged device will hang every
    subsequent row too, so the airtight move is to exit with the snapshot
    printed instead of relying on the driver's external kill. The default
    deadline is the remaining soft budget plus a margin (a row that would
    blow the whole budget is not worth waiting on); ``_exit`` is injectable
    for the hang-injection unit test.
    """
    import os
    import threading

    if timeout_s is None:
        timeout_s = max(60.0, SOFT_BUDGET_S + 180.0 - _elapsed())
    box = {}
    start = len(rows)
    obs_before = _obs_snap()
    mem_before = _mem_snap()

    def body():
        try:
            fn()
        except BaseException as e:
            box["err"] = f"{type(e).__name__}: {str(e)[:200]}"

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(timeout_s)
    if not t.is_alive():
        # attribution attaches only on completed scopes; the hang path below
        # exits the process, so a timed-out row's zombie thread can never
        # pollute a later row's delta
        _obs_attach(rows, start, obs_before)
        _mem_attach(rows, start, mem_before)
    if t.is_alive():
        # don't shadow a success row the body already emitted under this
        # name (e.g. the flagship primary row printed before a later mode
        # hung) — consumers key rows by name
        if any(r.get("name") == name for r in rows):
            name = f"{name}_watchdog"
        rows.append({"name": name,
                     "error": f"row hung past {timeout_s:.0f}s watchdog "
                              "(device hang)"})
        _emit()
        (_exit or os._exit)(0)
        return  # only reached under the injected test exit
    if "err" in box:
        if any(r.get("name") == name for r in rows):
            name = f"{name}_error"
        rows.append({"name": name, "error": box["err"]})


def _setup(rows):
    """Shared preamble of _run and --serve: cache, obs subscription, backend
    probe. Each piece degrades to a labeled error row, never a crash."""
    try:
        from raft_tpu.config import enable_compilation_cache

        enable_compilation_cache()
    except Exception as e:  # cache is an optimization, never fatal
        rows.append({"name": "compilation_cache", "error": str(e)[:200]})

    if _STATE["metrics"]:
        try:
            # subscribe to jax.monitoring BEFORE the first compile so every
            # row's obs delta carries compile_s + cache outcomes
            from raft_tpu.obs import compile as _obs_compile

            _obs_compile.install()
        except Exception as e:  # observability is never fatal either
            rows.append({"name": "obs_install", "error": str(e)[:200]})

    _backend_or_exit(rows)


def _run(rows):
    """Bench body. Every row is individually guarded; _run itself may still
    raise only out of the first few lines (jax import), which main()
    converts into a labeled row."""
    _setup(rows)
    import jax

    _note(f"backend: {jax.default_backend()}")

    _note("flagship exact 100k")
    _row_guard(rows, "exact_fused_knn_100k", lambda: _flagship_exact(rows))
    _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "serve_ivf_pq_100k", lambda: _row_serve(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "serve_pipeline_100k",
                   lambda: _row_serve_pipeline(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "serve_churn_ivf_pq_100k",
                   lambda: _row_serve_churn(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "serve_churn_cagra_100k",
                   lambda: _row_serve_churn_cagra(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "serve_shard_churn_100k",
                   lambda: _row_serve_shard(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "canary_smoke_100k",
                   lambda: _row_canary_smoke(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "tune_smoke_10k", lambda: _row_tune_smoke(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "mem_smoke_100k", lambda: _row_mem_smoke(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "fault_smoke_100k",
                   lambda: _row_fault_smoke(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "crash_recovery_100k",
                   lambda: _row_crash_recovery(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "reshard_churn_100k",
                   lambda: _row_reshard_churn(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "controller_drift_100k",
                   lambda: _row_controller_drift(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "controller_ramp_100k",
                   lambda: _row_controller_ramp(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "tiered_100k", lambda: _row_tiered(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "ooc_build_100k", lambda: _row_ooc_build(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "quant_funnel_100k",
                   lambda: _row_quant_funnel(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "net_serve_100k", lambda: _row_net_serve(rows))
        _emit()

    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "net_kill_worker_100k",
                   lambda: _row_net_kill_worker(rows))
        _emit()

    lid_box = {}
    if _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "ivf_pq_1m_lid_pq4x64_r4",
                   lambda: _row_ivf_pq_lid(rows, lid_box))
        _emit()

    if "dataset" in lid_box and _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "ivf_pq_1m_i8", lambda: _row_ivf_pq_i8(
            rows, lid_box["dataset"], lid_box["qsets"]))
        _emit()
    lid_box.clear()  # release the 512 MB LID set before the isotropic draw

    box = {}
    if _elapsed() < SOFT_BUDGET_S:
        def make_dataset():
            _note("isotropic 1M dataset")
            dataset, qsets = _make_1m()
            jax.block_until_ready([dataset] + qsets)
            # ground truth for recall on the first 1000 queries of the LAST
            # set — _measure_qps returns the output for that set
            _note("ground truth 1k queries")
            box["gt"] = _ground_truth(dataset, qsets[-1][:1000])
            box["dataset"], box["qsets"] = dataset, qsets

        _row_guard(rows, "dataset_1m", make_dataset)

    if "gt" in box and _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "ivf_flat_1m_p8", lambda: _row_ivf_flat(
            rows, box["dataset"], box["qsets"], box["gt"]))
        _emit()

    if "gt" in box and _elapsed() < SOFT_BUDGET_S:
        _row_guard(rows, "cagra_1m_itopk32", lambda: _row_cagra(
            rows, box["dataset"], box["qsets"], box["gt"]))


def main(argv=None):
    import signal

    rows = _STATE["rows"]
    argv = sys.argv[1:] if argv is None else argv
    if "--note" in argv:
        # render a round-note table from a committed artifact and exit —
        # never touches jax, so it cannot fail on a broken backend
        path = argv[argv.index("--note") + 1]
        with open(path) as f:
            print(_render_note(json.load(f)))
        return 0
    if "--no-metrics" in argv:
        # the disabled-path proof: every obs touch point reduces to one
        # module-flag check and rows carry no "obs" attribution field
        _STATE["metrics"] = False
        try:
            from raft_tpu import obs

            obs.disable()
        except Exception:
            pass

    def _on_term(signum, frame):  # driver SIGTERM -> the emit path below
        raise SystemExit(f"signal {signum}")

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):  # pragma: no cover - non-main thread
        pass
    try:
        if "--serve-churn" in argv:
            # mutable-lifecycle churn rows only (ISSUE 5/6): the quick loop
            # for iterating on stream/compactor parameters — IVF-PQ (extend
            # folds) and CAGRA (rebuild folds, the build-speed payoff row)
            _setup(rows)
            _row_guard(rows, "serve_churn_ivf_pq_100k",
                       lambda: _row_serve_churn(rows))
            _row_guard(rows, "serve_churn_cagra_100k",
                       lambda: _row_serve_churn_cagra(rows))
        elif "--serve-shard" in argv:
            # sharded serving tier only (ISSUE 9): the iteration loop for
            # the scatter-gather serve path — QPS ladder over shard counts
            # + the staggered-compaction churn window
            _setup(rows)
            _row_guard(rows, "serve_shard_churn_100k",
                       lambda: _row_serve_shard(rows))
        elif "--canary-smoke" in argv:
            # canary overhead loop only (ISSUE 8): sampling-rate QPS A/B +
            # the compile-free-hot-path proof with live quality monitoring
            # on; the heavy drift sweep is bench/drift_sweep.py
            _setup(rows)
            _row_guard(rows, "canary_smoke_100k",
                       lambda: _row_canary_smoke(rows))
        elif "--mem-smoke" in argv:
            # memory-ledger loop proof only (ISSUE 10): publish→retire
            # flat-peak + zero-leak + estimator-accuracy assertions; the
            # regression gate over artifacts is bench/compare.py
            _setup(rows)
            _row_guard(rows, "mem_smoke_100k",
                       lambda: _row_mem_smoke(rows))
        elif "--fault-smoke" in argv:
            # availability loop only (ISSUE 11): replica kill + same-flush
            # failover + breaker heal, then the injected-crash WAL-replay
            # recovery row — the iteration path for fencing/WAL parameters
            _setup(rows)
            _row_guard(rows, "fault_smoke_100k",
                       lambda: _row_fault_smoke(rows))
            _row_guard(rows, "crash_recovery_100k",
                       lambda: _row_crash_recovery(rows))
        elif "--reshard" in argv:
            # elastic-resharding loop only (ISSUE 13): the iteration path
            # for split/merge, carry-over and manifest-commit parameters —
            # the loaded topology-doubling window + the crash-mid-reshard
            # recovery measurement
            _setup(rows)
            _row_guard(rows, "reshard_churn_100k",
                       lambda: _row_reshard_churn(rows))
        elif "--controller" in argv:
            # closed-loop controller only (ISSUE 18): the iteration path
            # for ControlPolicy thresholds — the drift→retune recovery
            # window and the ramp→reshard topology double, each with the
            # causal seq chain asserted off the journal
            _setup(rows)
            _row_guard(rows, "controller_drift_100k",
                       lambda: _row_controller_drift(rows))
            _row_guard(rows, "controller_ramp_100k",
                       lambda: _row_controller_ramp(rows))
        elif "--tiered" in argv:
            # beyond-HBM tiering loop only (ISSUE 15): the iteration path
            # for TierPolicy / refine-hop parameters — the all-HBM vs
            # tiered A/B under a squeezing device budget
            _setup(rows)
            _row_guard(rows, "tiered_100k", lambda: _row_tiered(rows))
        elif "--ooc-build" in argv:
            # out-of-core streamed build loop only (ISSUE 19): the
            # iteration path for chunk_rows / staging parameters — the
            # in-core vs memmap-streamed build A/B with bit-equality and
            # the plan(streamed) peak envelope asserted in the row
            _setup(rows)
            _row_guard(rows, "ooc_build_100k",
                       lambda: _row_ooc_build(rows))
        elif "--quant" in argv:
            # quantization-funnel loop only (ISSUE 16): the iteration path
            # for fast-scan / funnel-width / rotation parameters — the
            # classic-PQ vs funnel-twin capacity A/B with the funnel_grid
            # sweep; the heavy 1M OPQ sweep is the slow-manifest test
            _setup(rows)
            _row_guard(rows, "quant_funnel_100k",
                       lambda: _row_quant_funnel(rows))
        elif "--tune-smoke" in argv:
            # autotune loop proof only (ISSUE 7): the quick iteration
            # path for the tune sweep engine; heavy sweeps are
            # bench/tune_sweep.py
            _setup(rows)
            _row_guard(rows, "tune_smoke_10k",
                       lambda: _row_tune_smoke(rows))
        elif "--net-serve" in argv:
            # network front door only (ISSUE 20): the iteration loop for
            # wire/mesh parameters — the in-process vs over-the-wire
            # closed-loop A/B at identical recall, then the mid-load
            # worker kill with the zero-failed-queries failover proof
            _setup(rows)
            _row_guard(rows, "net_serve_100k",
                       lambda: _row_net_serve(rows))
            _row_guard(rows, "net_kill_worker_100k",
                       lambda: _row_net_kill_worker(rows))
        elif "--serve-pipeline" in argv:
            # host-free flush pipeline A/B only (ISSUE 12): the iteration
            # loop for pipeline_depth / staging parameters — sync vs
            # pipelined per-flush QPS with the queue/flush decomposition
            _setup(rows)
            _row_guard(rows, "serve_pipeline_100k",
                       lambda: _row_serve_pipeline(rows))
        elif "--serve" in argv:
            # serving-layer A/B only (ISSUE 3): the quick loop for
            # iterating on batcher/registry parameters
            _setup(rows)
            _row_guard(rows, "serve_ivf_pq_100k", lambda: _row_serve(rows))
        else:
            _run(rows)
    except BaseException as e:
        # a jax-import or backend-init failure still prints a parseable
        # snapshot, but the run measured nothing: exit non-zero
        rows.append({"name": "fatal",
                     "error": f"{type(e).__name__}: {str(e)[:260]}"})
        _emit()
        return 1
    _emit()
    return 0


if __name__ == "__main__":
    sys.exit(main())

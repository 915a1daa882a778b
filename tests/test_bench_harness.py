"""ANN bench harness smoke test (bench/ann/run.py).

Analogue of the reference harness's CI smoke coverage: a tiny synthetic
config must build, search, compute recall, and emit the CSV.
"""

import csv
import json
import os
import pathlib
import signal
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_harness_end_to_end(tmp_path):
    conf_dir = tmp_path / "conf"
    conf_dir.mkdir()
    conf = {
        "dataset": {
            "name": "tiny",
            "synthetic": {"n": 2000, "dim": 16, "n_queries": 100, "seed": 0},
            "distance": "euclidean",
        },
        "search_basic_param": {"batch_size": 100, "k": 5, "run_count": 1},
        "index": [
            {"name": "bf", "algo": "raft_tpu.brute_force", "build_param": {},
             "search_params": [{}]},
            {"name": "ivf", "algo": "raft_tpu.ivf_flat",
             "build_param": {"n_lists": 8},
             "search_params": [{"n_probes": 8}]},
        ],
    }
    (conf_dir / "tiny.json").write_text(json.dumps(conf))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench/ann/run.py"),
         "--conf", str(conf_dir / "tiny.json"), "--build", "--search"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]

    out_csv = tmp_path / "results" / "tiny.csv"
    assert out_csv.exists(), proc.stdout
    rows = list(csv.DictReader(open(out_csv)))
    assert len(rows) == 2
    by_name = {r["name"]: r for r in rows}
    # brute force IS the ground truth → recall 1.0
    assert float(by_name["bf"]["recall@5"]) == 1.0
    # probing all 8 lists is exhaustive → recall 1.0
    assert float(by_name["ivf"]["recall@5"]) > 0.99
    assert float(by_name["bf"]["qps"]) > 0


def test_row_guard_hang_converts_to_labeled_row():
    """A row body that hangs past the watchdog deadline (a wedged device
    mid-build) must convert into a labeled error row plus an exit-0 request
    — not rely on the driver's external kill."""
    import threading

    import bench

    rows = []
    exit_codes = []
    ev = threading.Event()
    bench._row_guard(rows, "hang_row", ev.wait, timeout_s=0.2,
                     _exit=exit_codes.append)
    ev.set()  # release the stuck daemon thread
    assert exit_codes == [0]
    assert rows and rows[0]["name"] == "hang_row"
    assert "watchdog" in rows[0]["error"]


def test_backend_failure_exits_nonzero(monkeypatch, capsys):
    """A backend that cannot start is a failed run, not an empty one: the
    probe emits a labeled row and exits NON-ZERO."""
    import jax

    import bench

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    monkeypatch.setitem(bench._STATE, "rows", [])
    exit_codes = []
    rows = bench._STATE["rows"]
    bench._backend_or_exit(rows, timeout_s=30, _exit=exit_codes.append)
    assert exit_codes == [1]
    assert rows[-1]["name"] == "backend"
    assert "Unable to initialize backend" in rows[-1]["error"]
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["rows"][-1]["name"] == "backend"


def test_fatal_error_exits_nonzero(monkeypatch, capsys):
    """An exception that escapes every row guard still prints a parseable
    snapshot with a labeled "fatal" row — and main() returns non-zero."""
    import bench

    def broken(rows):
        raise RuntimeError("jax import exploded")

    monkeypatch.setattr(bench, "_run", broken)
    monkeypatch.setitem(bench._STATE, "rows", [])
    # main() installs its SIGTERM handler; leave the worker's as it was
    handler = signal.getsignal(signal.SIGTERM)
    try:
        assert bench.main([]) == 1
    finally:
        signal.signal(signal.SIGTERM, handler)
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["rows"][-1]["name"] == "fatal"
    assert "jax import exploded" in last["rows"][-1]["error"]


def test_row_guard_success_and_error_paths():
    import bench

    rows = []
    bench._row_guard(rows, "ok_row", lambda: None, timeout_s=5)
    assert rows == []

    def boom():
        raise ValueError("boom")

    bench._row_guard(rows, "err_row", boom, timeout_s=5)
    assert rows[0]["name"] == "err_row"
    assert rows[0]["error"].startswith("ValueError")


def test_no_metrics_flag_disables_obs(monkeypatch):
    """`bench.py --no-metrics` must switch the whole obs surface off (the
    disabled-path proof ISSUE 2 asks for): rows then carry no "obs" field
    and the emitted snapshot says metrics_enabled=false."""
    import bench
    from raft_tpu import obs

    called = {}
    monkeypatch.setattr(bench, "_run",
                        lambda rows: called.setdefault("ran", True))
    try:
        rc = bench.main(["--no-metrics"])
        assert rc == 0 and called["ran"]
        assert bench._STATE["metrics"] is False
        assert not obs.enabled()
    finally:
        obs.enable()
        bench._STATE["metrics"] = True


def test_flagship_i8_row_smoke(monkeypatch):
    """The driver-bench i8 rows (this PR's acceptance measurement) must
    produce qps+recall rows, not guarded error rows, when the kernels run —
    a NameError in the row body would silently erase the headline number on
    the TPU driver run. Shrunk shapes, interpret-mode kernels; the shape
    arguments exist on the row functions exactly for this smoke."""
    monkeypatch.setenv("RAFT_TPU_FUSED_KNN_INTERPRET", "1")
    import bench

    rows = []
    bench._flagship_exact(rows, n=4500, d=72, m=150, k=10, n_batches=2)
    by = {r["name"]: r for r in rows if "name" in r}
    assert "exact_fused_knn_100k" in by, rows
    row = by.get("exact_fused_knn_100k_i8")
    assert row is not None and "error" not in row, rows
    # uniform [0,1) quantized onto 1/255 bins: neighbor margins at this
    # scale dwarf the quantization noise
    assert row["recall"] > 0.8, row
    assert row["i8_over_f32"] > 0, row
    assert by["exact_xla_control"]["fused_over_control"] > 0, by


def test_ivf_pq_i8_row_smoke(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_FUSED_KNN_INTERPRET", "1")
    import numpy as np

    import bench

    rng = np.random.default_rng(3)
    centers = rng.random((32, 64)).astype(np.float32) * 10.0
    lab = rng.integers(0, 32, 6000)
    dataset = (centers[lab]
               + 0.3 * rng.standard_normal((6000, 64))).astype(np.float32)
    qsets = []
    for _ in range(3):
        qlab = rng.integers(0, 32, 200)
        qsets.append((centers[qlab] + 0.3 * rng.standard_normal(
            (200, 64))).astype(np.float32))
    import jax.numpy as jnp

    rows = []
    bench._row_ivf_pq_i8(rows, jnp.asarray(dataset),
                         [jnp.asarray(q) for q in qsets],
                         n_lists=32, pq_dim=32)
    row = rows[-1]
    assert row["name"] == "ivf_pq_1m_i8" and "error" not in row, rows
    assert row["recall"] > 0.7, row
    assert row["i8_over_f32"] is None  # no f32 LID row in this smoke


def test_serve_row_smoke(monkeypatch):
    """The --serve bench row (ISSUE 3 acceptance measurement) must produce
    a full row — qps, ratio, latency percentiles, occupancy, and the
    zero-loss zero-cold-compile swap proof — not a guarded error row.
    Shrunk shapes; the real protocol runs on the TPU driver."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_serve(rows, n=3000, d=32, n_lists=16, pq_dim=16, k=5,
                     n_probes=16, threads=3, per_thread=30, seq_queries=24,
                     max_batch=8, max_wait_us=500.0, ncl=32)
    row = rows[-1]
    assert row["name"] == "serve_ivf_pq_100k" and "error" not in row, rows
    assert row["swap"]["failed"] == 0, row
    assert row["swap"]["version"] == 2, row
    # the swap window must not cold-compile: every serving program was
    # warmed at publish and the rebuilt index is HLO-identical
    assert row["swap"]["compile_s"] == 0.0, row
    assert row["swap"]["cache_misses"] == 0, row
    assert row["qps"] > 0 and row["seq_qps"] > 0, row
    assert row["p99_ms"] >= row["p50_ms"] > 0, row
    assert 0 < row["mean_batch_occupancy"] <= 1.0, row
    assert row["recall"] > 0.5, row


def test_serve_flag_runs_only_the_serve_row(monkeypatch):
    """`bench.py --serve` is the parameter-iteration loop: setup + the serve
    row, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_serve",
        lambda rows: rows.append({"name": "serve_ivf_pq_100k", "qps": 1.0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--serve"])
        assert rc == 0 and calls == ["setup"]
        assert any(r.get("name") == "serve_ivf_pq_100k"
                   for r in bench._STATE["rows"])
    finally:
        bench._STATE["rows"].clear()


def test_serve_pipeline_row_smoke(monkeypatch):
    """The --serve-pipeline A/B row (ISSUE 12 acceptance measurement) must
    produce a full row — both modes' per-flush QPS, latency percentiles and
    recall, the queue-wait vs flush decomposition, the dispatch meter, the
    zero-loss/zero-cold-compile proof, and the flat staging-ledger wave
    levels — not a guarded error row. Shrunk shapes; the real A/B runs on
    the TPU driver."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_serve_pipeline(rows, n=3000, d=32, n_lists=16, pq_dim=16,
                              k=5, n_probes=16, threads=3, per_thread=30,
                              max_batch=8, max_wait_us=500.0, ncl=32,
                              depth=2, waves=2)
    row = rows[-1]
    assert row["name"] == "serve_pipeline_100k" and "error" not in row, rows
    # zero failed queries, both modes
    assert row["failed"] == 0, row
    assert row["qps"] > 0 and row["sync_qps"] > 0, row
    assert row["p99_ms"] >= row["p50_ms"] > 0, row
    assert row["sync_p99_ms"] >= row["sync_p50_ms"] > 0, row
    # identical recall: same index, same query pool, both modes measured
    assert row["recall"] > 0.5, row
    assert row["recall"] == pytest.approx(row["sync_recall"], abs=0.02), row
    # the latency decomposition is present for BOTH modes (where a win
    # lands must be readable from the artifact)
    for mode in ("sync", "pipelined"):
        assert row["decomp"][mode]["queue_wait_ms_mean"] >= 0, row
        assert row["decomp"][mode]["flush_ms_mean"] > 0, row
    # the dispatch meter records only in pipelined mode
    assert row["dispatches_per_flush_mean"] >= 1, row
    # zero cold compiles across the pipelined loaded window: publish
    # warmed the ladder, the committed placements, and the stage programs
    assert row["pipeline"]["compile_s"] == 0.0, row
    assert row["pipeline"]["cache_misses"] == 0, row
    assert row["pipeline"]["staging_warmed"] == 4, row  # buckets 1,2,4,8
    # staging: the accounted ledger bytes are FLAT across the post-load
    # waves while donation_frees ADVANCES every wave — the previous query
    # buffer is actually deleted per donated upload (no growth across
    # cycles; a backend ignoring donate_argnums would flatline the frees)
    st = row["staging"]
    assert st["pinned"] and st["uploads"] > 0, row
    assert st["donation_frees"] >= 1, row
    ws = st["by_wave"]
    assert len(ws) == 2, row
    ledger = [w["ledger_bytes"] for w in ws]
    assert -1 not in ledger and len(set(ledger)) == 1, row
    frees = [w["donation_frees"] for w in ws]
    assert frees[1] > frees[0] >= 1, row


def test_serve_pipeline_flag_runs_only_the_pipeline_row(monkeypatch):
    """`bench.py --serve-pipeline` is the pipeline-parameter iteration
    loop: setup + the pipeline A/B row, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_serve_pipeline",
        lambda rows: rows.append({"name": "serve_pipeline_100k",
                                  "qps": 1.0, "recall": 1.0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--serve-pipeline"])
        assert rc == 0 and calls == ["setup"]
        assert any(r.get("name") == "serve_pipeline_100k"
                   for r in bench._STATE["rows"])
    finally:
        bench._STATE["rows"].clear()


def test_render_note_quotes_the_artifact():
    """bench.py --note regenerates the BASELINE round-note table FROM the
    committed artifact (VERDICT r5 #7: the r05 note described a different
    session than BENCH_r05.json) — every number in the output must be a
    number from the artifact, ratios included, with the driver wrapper
    ({rc, tail, parsed}) unwrapped."""
    import bench

    artifact = {
        "rc": 0, "tail": "...",
        "parsed": {
            "metric": "exact brute-force kNN QPS", "value": 192111.3,
            "unit": "QPS", "vs_baseline": 1.734, "elapsed_s": 194.4,
            "rows": [
                {"name": "exact_fused_knn_100k", "qps": 192111.3,
                 "recall": 1.0, "build_s": 0.0},
                {"name": "exact_xla_control", "qps": 137586.3, "recall": 1.0,
                 "build_s": 0.0, "fused_over_control": 1.396},
                {"name": "cagra_1m_itopk32", "qps": 35879.4,
                 "recall": 0.9714, "build_s": 135.6},
                {"name": "ivf_pq_1m_i8", "qps": 30000.0, "recall": 0.97,
                 "build_s": 5.0, "i8_over_f32": 0.87},
                {"name": "broken_row", "error": "TPU fell over"},
            ],
        },
    }
    note = bench._render_note(artifact)
    for needle in ("192,111.3", "137,586.3", "1.396", "35,879.4", "0.9714",
                   "135.6", "i8/f32 **0.87**", "fused/control **1.396**",
                   "vs_baseline 1.734", "broken_row | ERROR",
                   "TPU fell over"):
        assert needle in note, (needle, note)
    # regression guard: the r05 drift was prose saying 162.8k/148.3k/1.098
    for stale in ("162", "148,3", "1.098"):
        assert stale not in note


def test_serve_churn_row_smoke():
    """The --serve-churn bench row (ISSUE 5 acceptance measurement) must
    produce a full row: search qps + latency percentiles, write throughput,
    >= 2 compaction swaps with zero failed queries, mid-churn recall
    bookkeeping, and the rehearsal-backed zero-cold-compile proof. Shrunk
    shapes (toy PQ quantization — the recall PARITY bar applies at driver
    scale; here the gap bound is loose), real protocol on the TPU driver."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_serve_churn(rows, n=2500, d=32, n_lists=16, pq_dim=32, k=5,
                           n_probes=32, threads=3, writer_steps=12,
                           upserts_per_step=24, deletes_per_step=8,
                           delta_capacity=128, compact_fill=0.75,
                           max_batch=8, max_wait_us=500.0, ncl=32, n_eval=64)
    row = rows[-1]
    assert row["name"] == "serve_churn_ivf_pq_100k" and "error" not in row, rows
    assert row["churn"]["failed"] == 0, row
    assert row["churn"]["compactions"] >= 2, row
    # zero cold compiles across the whole loaded window — folds, publish
    # warms, flips and every flush (the rehearsal pre-compiled the epochs)
    assert row["churn"]["compile_s"] == 0.0, row
    assert row["churn"]["cache_misses"] == 0, row
    assert row["qps"] > 0 and row["write_rows_per_s"] > 0, row
    assert row["p99_ms"] >= row["p50_ms"] > 0, row
    # toy-scale PQ: parity only loosely; the 0.01 bar is the 100k row's
    assert abs(row["recall_gap"]) < 0.25, row
    assert row["recall_mut"] > 0.3, row
    # the live recall canary rides this row (ISSUE 8): the estimate exists,
    # its interval is well-formed, and the zero-cold-compile assertion
    # above now ALSO covers the canary's sampling + shadow reranks (they
    # ran inside the attributed window, rehearsal-warmed per epoch)
    c = row["canary"]
    assert c is not None and c["rate"] == 0.05, row
    assert c["reranked"] > 0 and c["seen"] > 0, row
    assert c["wilson_low"] <= c["recall"] <= c["wilson_high"], row
    # toy-scale bracket: the 100k driver row asserts oracle_in_interval
    assert abs(c["recall"] - row["recall_mut"]) < 0.35, row


def test_serve_churn_flag_runs_only_the_churn_rows(monkeypatch):
    """`bench.py --serve-churn` is the stream parameter-iteration loop:
    setup + the two churn rows (IVF-PQ extend folds, CAGRA rebuild folds),
    nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_serve_churn",
        lambda rows: rows.append({"name": "serve_churn_ivf_pq_100k",
                                  "qps": 1.0}))
    monkeypatch.setattr(
        bench, "_row_serve_churn_cagra",
        lambda rows: rows.append({"name": "serve_churn_cagra_100k",
                                  "qps": 1.0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--serve-churn"])
        assert rc == 0 and calls == ["setup"]
        names = {r.get("name") for r in bench._STATE["rows"]}
        assert {"serve_churn_ivf_pq_100k", "serve_churn_cagra_100k"} <= names
    finally:
        bench._STATE["rows"].clear()


def test_serve_churn_cagra_row_smoke():
    """The --serve-churn CAGRA row (ISSUE 6 acceptance measurement): same
    protocol as the IVF-PQ churn smoke, but every compaction is a REBUILD
    (no extend for graphs) — so the row proves the rehearsal covers the
    per-epoch rebuild program set too: >= 2 swaps, zero failed queries,
    zero cold compiles across the loaded window. Shrunk shapes; the
    absolute numbers are the TPU driver row's job."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_serve_churn_cagra(rows, n=2500, d=32, k=5, itopk=16, threads=3,
                                 writer_steps=12, upserts_per_step=24,
                                 deletes_per_step=8, delta_capacity=128,
                                 compact_fill=0.75, max_batch=8,
                                 max_wait_us=500.0, ncl=32, n_eval=64)
    row = rows[-1]
    assert row["name"] == "serve_churn_cagra_100k" and "error" not in row, rows
    assert row["churn"]["failed"] == 0, row
    assert row["churn"]["compactions"] >= 2, row
    # zero cold compiles across the whole loaded window — every rebuild
    # fold, its publish warm + flip, and every flush (rehearsal-compiled)
    assert row["churn"]["compile_s"] == 0.0, row
    assert row["churn"]["cache_misses"] == 0, row
    assert row["qps"] > 0 and row["write_rows_per_s"] > 0, row
    # rebuild compactions actually rebuilt (tombstones reclaimed -> the
    # sealed row count tracks the live set, not a monotone append)
    assert all(w > 0 for w in row["churn"]["compaction_wall_s"]), row
    # exact sealed kind: rebuild-over-live-rows keeps recall at the fresh
    # -oracle point (CAGRA rebuild IS a fresh build over the live rows)
    assert abs(row["recall_gap"]) < 0.05, row


def test_build_ab_table_renders_from_artifact():
    """bench/build_ab.py --table: the BASELINE Round-6 follow-up table is
    generated FROM the artifact (no prose drift) — pure stdlib, no jax."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "build_ab", pathlib.Path(__file__).resolve().parents[1]
        / "bench" / "build_ab.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    artifact = {
        "elapsed_s": 12.3,
        "config": {"n": [1000], "d": 8},
        "rows": [
            {"name": "em_ab_ivf_pq_100k",
             "full": {"warm_s": 10.0, "cold_s": 20.0, "recall": 0.98},
             "minibatch": {"warm_s": 6.0, "cold_s": 9.0, "recall": 0.979},
             "warm_cut": 0.4, "recall_gap": -0.001},
            {"name": "dist_overhead_100k",
             "full": {"single": {"warm_s": 10.0, "cold_s": 20.0},
                      "distributed": {"warm_s": 28.7, "cold_s": 40.0},
                      "warm_overhead": 1.87},
             "minibatch": {"single": {"warm_s": 6.0, "cold_s": 9.0},
                           "distributed": {"warm_s": 6.6, "cold_s": 10.0},
                           "warm_overhead": 0.1}},
            {"name": "cagra_build_ab_1000k", "shards": 8,
             "single": {"warm_s": 135.0, "cold_s": 300.0, "recall": 0.9714},
             "merged": {"warm_s": 50.0, "cold_s": 90.0, "recall": 0.9714},
             "warm_cut": 0.63, "recall_gap": 0.0},
            {"name": "em_ab_ivf_pq_1000k", "error": "RuntimeError: boom"},
        ],
    }
    table = mod.render_table(artifact)
    # every arm's numbers ride verbatim; the header names the generator
    for needle in ("em_ab_ivf_pq_100k", "warm_cut **0.4**", "0.9790",
                   "warm_overhead **0.1**", "cagra_build_ab_1000k",
                   "warm_cut **0.63**", "ERROR", "build_ab.py --table"):
        assert needle in table, (needle, table)
    # a markdown table: header + separator + one line per arm
    assert table.count("|") > 30


def test_canary_smoke_row():
    """The --canary-smoke bench row (ISSUE 8 acceptance measurement): QPS
    at sampling 0% vs 1% vs 5% with the background drainer reranking
    live, the Wilson interval bracketing the offline recall, and ZERO cold
    compiles across the whole monitored window (the canary's oracle was
    warmed at every rerank bucket). Shrunk shapes; absolute overhead
    numbers are the TPU driver row's job."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_canary_smoke(rows, n=2500, d=32, n_lists=16, pq_dim=16, k=5,
                            n_probes=16, threads=3, per_thread=40,
                            rates=(0.0, 0.05, 0.25), max_batch=8,
                            max_wait_us=500.0, ncl=32, n_eval=64)
    row = rows[-1]
    assert row["name"] == "canary_smoke_100k" and "error" not in row, rows
    assert row["failed"] == 0, row
    assert set(row["qps_by_rate"]) == {"0", "0.05", "0.25"}, row
    assert all(v > 0 for v in row["qps_by_rate"].values()), row
    assert row["slowdown_at_5pct"] > 0, row
    # live monitoring must not compile anything, on or off the hot path
    assert row["compile_s"] == 0.0, row
    assert row["cache_misses"] == 0, row
    c = row["canary"]
    assert c["reranked"] > 0 and c["seen"] > 0, row
    assert c["wilson_low"] <= c["recall"] <= c["wilson_high"], row
    # the acceptance bracket: offline truth inside the live interval
    assert c["oracle_in_interval"], row
    assert abs(c["recall"] - row["recall_offline"]) < 0.2, row


def test_canary_smoke_flag_runs_only_the_canary_row(monkeypatch):
    """`bench.py --canary-smoke` is the quality-layer iteration loop: setup
    + the canary row, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_canary_smoke",
        lambda rows: rows.append({"name": "canary_smoke_100k", "qps": 1.0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--canary-smoke"])
        assert rc == 0 and calls == ["setup"]
        assert any(r.get("name") == "canary_smoke_100k"
                   for r in bench._STATE["rows"])
    finally:
        bench._STATE["rows"].clear()


def test_drift_sweep_small_scale():
    """bench/drift_sweep.py at CI scale: the heavytail twin fires the
    detector, the isotropic one stays silent, on both the query-sample and
    compaction-stat feeds (the ISSUE 8 satellite sweep; full scales run on
    the driver)."""
    import importlib.util
    import pathlib

    import pytest

    pytest.importorskip("jax")
    spec = importlib.util.spec_from_file_location(
        "drift_sweep", pathlib.Path(__file__).resolve().parents[1]
        / "bench" / "drift_sweep.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    artifact = mod.run_sweep(mod.SMALL_SCALES)
    assert len(artifact["rows"]) == 2
    by = {r["name"].split("_")[1]: r for r in artifact["rows"]}
    assert by["heavytail"]["ok"] and by["heavytail"]["compaction"]["drifted"]
    assert by["isotropic"]["ok"] and not by["isotropic"]["queries"]["drifted"]
    # drift state is per feed: the query-sample AND compaction-stat feeds
    # each advise once on their own transition
    assert by["heavytail"]["retune_events"] == 2
    assert by["isotropic"]["retune_events"] == 0
    table = mod.render_table(artifact)
    assert "drift_heavytail_2k_d32" in table and "**ok**" in table


def test_tune_smoke_row():
    """The --tune-smoke bench row (ISSUE 7): a tiny-budget sweep must
    produce a full row — chosen vs grid-head operating point with the QPS
    ratio — and the engine's choice rule makes chosen match-or-beat the
    head at equal-or-better recall by construction."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_tune_smoke(rows, n=2000, d=16, ncl=32, n_lists=16, k=5,
                          m=64, repeats=1)
    row = rows[-1]
    assert row["name"] == "tune_smoke_10k" and "error" not in row, rows
    assert row["n_trials"] == 3, row
    assert row["decision"].startswith("ivf_pq/float32/"), row
    assert row["qps"] >= row["default_qps"], row
    assert row["recall"] >= row["recall_target"], row
    assert row["chosen_qps_over_default"] >= 1.0, row


def test_tune_smoke_flag_runs_only_the_tune_row(monkeypatch):
    """`bench.py --tune-smoke` is the autotune iteration loop: setup + the
    tune row, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_tune_smoke",
        lambda rows: rows.append({"name": "tune_smoke_10k", "qps": 1.0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--tune-smoke"])
        assert rc == 0 and calls == ["setup"]
        assert any(r.get("name") == "tune_smoke_10k"
                   for r in bench._STATE["rows"])
    finally:
        bench._STATE["rows"].clear()


def test_serve_shard_row_smoke():
    """The --serve-shard bench row (ISSUE 9 acceptance measurement) must
    produce a full row: a QPS ladder over shard counts, >= 2 STAGGERED
    one-shard-per-cycle compactions with zero failed queries, the
    rehearsal-backed zero-cold-compile proof (canary reranks included),
    and the fresh-oracle recall inside the live canary's interval. Shrunk
    shapes — absolute QPS scaling is the driver row's job (the row carries
    `cores` so the artifact prices the CPU-mesh ceiling in)."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_serve_shard(rows, n=2400, d=32, n_lists=32, k=5, n_probes=16,
                           shard_counts=(1, 2), threads=3, per_thread=20,
                           writer_steps=12, upserts_per_step=24,
                           deletes_per_step=8, delta_capacity=128,
                           compact_fill=0.5, max_batch=8, max_wait_us=500.0,
                           ncl=32, n_eval=64)
    row = rows[-1]
    assert row["name"] == "serve_shard_churn_100k" and "error" not in row, rows
    assert row["churn"]["failed"] == 0, row
    assert row["churn"]["compactions"] >= 2, row
    # staggered: every fold names its shard; with hash-balanced writes the
    # folds walk more than one shard across the window
    shards_folded = row["churn"]["compaction_shards"]
    assert len(shards_folded) == row["churn"]["compactions"], row
    assert len(set(shards_folded)) >= 2, row
    # zero cold compiles across the whole loaded churn window — flushes,
    # staggered folds, publish warms, canary reranks (rehearsal-compiled)
    assert row["churn"]["compile_s"] == 0.0, row
    assert row["churn"]["cache_misses"] == 0, row
    assert set(row["qps_by_shards"]) == {"1", "2"}, row
    assert all(v > 0 for v in row["qps_by_shards"].values()), row
    assert row["cores"] >= 1 and row["shards"] == 2, row
    assert row["qps"] > 0 and row["write_rows_per_s"] > 0, row
    assert row["p99_ms"] >= row["p50_ms"] > 0, row
    # proportional sizing holds recall near the single-device oracle even
    # at toy scale (exhaustive-ish probes)
    assert abs(row["recall_gap"]) < 0.25, row
    c = row["canary"]
    assert c["reranked"] > 0 and c["seen"] > 0, row
    assert c["wilson_low"] <= c["recall"] <= c["wilson_high"], row
    # toy-scale bracket (a ~10-rerank reservoir): the 100k driver row
    # asserts the strict canary.oracle_in_interval acceptance bit
    assert abs(c["recall"] - row["recall_mut"]) < 0.35, row


def test_serve_shard_flag_runs_only_the_shard_row(monkeypatch):
    """`bench.py --serve-shard` is the sharded-tier iteration loop: setup
    + the shard row, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_serve_shard",
        lambda rows: rows.append({"name": "serve_shard_churn_100k",
                                  "qps": 1.0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--serve-shard"])
        assert rc == 0 and calls == ["setup"]
        assert any(r.get("name") == "serve_shard_churn_100k"
                   for r in bench._STATE["rows"])
    finally:
        bench._STATE["rows"].clear()


def test_mem_smoke_row():
    """The --mem-smoke bench row (ISSUE 10): publish→retire cycles with
    flat steady-state peaks, levels returning to baseline + one live
    index, zero steady-state compiles, a clean retirement audit, and the
    plan-vs-measured bracket — every assertion lives IN the row body, so
    a violation converts to an error row; here the small-scale twin must
    come back clean."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_mem_smoke(rows, n=20_000, d=32, n_lists=128, cycles=3)
    row = rows[-1]
    assert row["name"] == "mem_smoke_100k" and "error" not in row, rows
    assert row["cycles"] == 3
    assert row["audit_clean"] is True
    assert row["steady_compile_s"] == 0.0
    assert 0.8 <= row["plan_ratio"] <= 1.2, row
    assert len(row["peak_bytes_by_cycle"]) == 3
    # levels flat: every cycle ends at baseline + exactly one live index
    lv = row["level_bytes_by_cycle"]
    assert max(lv) - min(lv) == 0, row


def test_mem_smoke_flag_runs_only_the_mem_row(monkeypatch):
    """`bench.py --mem-smoke` is the memory-ledger iteration loop: setup
    + the mem row, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_mem_smoke",
        lambda rows: rows.append({"name": "mem_smoke_100k",
                                  "audit_clean": True}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--mem-smoke"])
        assert rc == 0 and calls == ["setup"]
        assert any(r.get("name") == "mem_smoke_100k"
                   for r in bench._STATE["rows"])
    finally:
        bench._STATE["rows"].clear()


def test_rows_carry_mem_field(monkeypatch):
    """Every guarded row scope attaches a "mem" field (peak device/host
    bytes via the ledger) when metrics are on, and none when disabled —
    the same contract as the "obs" attribution field."""
    import bench
    from raft_tpu import obs
    from raft_tpu.obs import mem as obs_mem

    rows = []

    def body():
        t = obs_mem.account("bench_probe", device_bytes=4096)
        obs_mem.release(t)
        rows.append({"name": "probe_row", "qps": 1.0})

    bench._row_guard(rows, "probe_row", body)
    row = next(r for r in rows if r["name"] == "probe_row")
    assert "mem" in row, row
    assert row["mem"]["device_peak_bytes"] >= (
        row["mem"]["device_bytes"])
    assert row["mem"]["device_peak_bytes"] - row["mem"]["device_bytes"] \
        >= 4096, "the scope peak must see the transient allocation"

    obs.disable()
    try:
        rows2 = []
        bench._row_guard(rows2, "probe_row2",
                         lambda: rows2.append({"name": "probe_row2"}))
        assert "mem" not in rows2[0], rows2
    finally:
        obs.enable()


def test_rows_carry_mem_tiers_watermark():
    """A row scope that held a TieredStore carries the per-tier WATERMARK
    under mem.tiers even though the store was a frame local freed before
    attribution attached (the live totals would read empty there); a
    scope without one carries no tiers field."""
    import numpy as np

    import bench
    from raft_tpu.stream.tiered import TieredStore

    rows = []

    def body():
        ts = TieredStore(np.zeros((64, 8), np.float32),
                         name="bench_probe_tier")
        assert ts.tier_bytes()["host"] == 64 * 8 * 4
        rows.append({"name": "tier_probe", "qps": 1.0})

    bench._row_guard(rows, "tier_probe", body)
    row = next(r for r in rows if r["name"] == "tier_probe")
    assert row["mem"]["tiers"]["host"] >= 64 * 8 * 4, row

    rows2 = []
    bench._row_guard(rows2, "plain_probe",
                     lambda: rows2.append({"name": "plain_probe"}))
    assert "tiers" not in rows2[0]["mem"], rows2


def test_fault_smoke_row():
    """The --fault-smoke availability row (ISSUE 11 acceptance): a
    replicated sharded mesh serves a loaded window during which one
    replica is killed and later revived. The row body asserts the
    acceptance bits itself (zero failed queries, breaker strikes
    observed, zero cold compiles after rehearsal); the small-scale twin
    must come back clean and carry the measured recovery."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_fault_smoke(rows, n=4000, d=16, n_lists=32, k=5, n_probes=8,
                           steps=60, qbatch=16, fence_at=15, heal_at=40,
                           delta_capacity=256)
    row = rows[-1]
    assert row["name"] == "fault_smoke_100k" and "error" not in row, rows
    assert row["failed_queries"] == 0, row
    assert row["strikes"] > 0, row
    assert row["compile_s_loaded"] == 0.0, row
    assert row["recovery_s"] > 0, row
    assert row["qps"] > 0 and row["replicas"] == 2, row
    # the event plane saw the fence and the heal (ISSUE 17): the row
    # carries per-kind counts, gated by compare.py on presence
    assert row["events"]["replica_fenced"] >= 1, row
    assert row["events"]["replica_unfenced"] >= 1, row


def test_crash_recovery_row():
    """The --fault-smoke crash-durability row (ISSUE 11 acceptance): an
    injected SimulatedCrash between WAL append and memtable insert, then
    load() + WAL replay + warm(). The row body asserts id-for-id parity
    with an uncrashed twin and a compile-free post-warm window; here the
    small-scale twin must land with recall_recovered == 1.0 (the field
    bench/compare.py gates like every recall field) and the measured
    replay economics."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_crash_recovery(rows, n=4000, d=16, n_lists=32, k=5,
                              n_probes=8, write_steps=10, write_rows=16,
                              delete_rows=4, delta_capacity=512, n_eval=64)
    row = rows[-1]
    assert row["name"] == "crash_recovery_100k" and "error" not in row, rows
    assert row["recall_recovered"] == 1.0, row
    assert row["wal_records"] == 2 * 9 + 1, row  # 9 upsert+delete pairs + 1
    assert row["wal_bytes"] > 0, row
    assert row["recovery_s"] > 0 and row["replay_rows_per_s"] > 0, row
    assert row["compile_s_post_warm"] == 0.0, row


def test_reshard_churn_row():
    """The --reshard elasticity row (ISSUE 13 acceptance): a loaded
    replicated mesh doubles its shard count online with one replica
    killed mid-migration. The row body asserts the acceptance bits itself
    (zero failed queries, strikes observed, zero cold compiles after
    rehearsal, recall held across the flip); the small-scale twin must
    come back clean with the measured crash-mid-reshard recovery."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_reshard_churn(rows, n=4000, d=16, n_lists=32, k=5,
                             n_probes=8, steps=16, qbatch=16, reshard_at=8,
                             write_every=4, write_rows=8,
                             delta_capacity=512, n_eval=32, readers=2)
    row = rows[-1]
    assert row["name"] == "reshard_churn_100k" and "error" not in row, rows
    assert row["failed_queries"] == 0, row
    assert row["shards_from"] == 2 and row["shards_to"] == 4, row
    assert row["strikes"] > 0, row
    assert row["compile_s_loaded"] == 0.0, row
    assert row["rows_moved"] >= 4000, row
    assert row["carried_over"] >= 1, row  # the mid-migration write moved
    assert row["recall_post"] >= row["recall_pre"] - 0.02, row
    assert row["recall_crash_recovered"] == 1.0, row
    assert row["crash_recovery_s"] > 0, row
    assert row["wal_records_replayed"] > 0, row
    assert row["qps"] > 0 and row["replicas"] == 2, row
    # the event plane saw the migration and the mid-flight kill (ISSUE 17)
    assert row["events"]["reshard_started"] >= 1, row
    assert row["events"]["reshard_flip"] >= 1, row
    assert row["events"]["replica_fenced"] >= 1, row


def test_reshard_flag_runs_only_the_reshard_row(monkeypatch):
    """`bench.py --reshard` is the elasticity iteration loop: setup + the
    reshard row, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_reshard_churn",
        lambda rows: rows.append({"name": "reshard_churn_100k",
                                  "failed_queries": 0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--reshard"])
        assert rc == 0 and calls == ["setup"]
        names = {r.get("name") for r in bench._STATE["rows"]}
        assert "reshard_churn_100k" in names
    finally:
        bench._STATE["rows"].clear()


def test_fault_smoke_flag_runs_only_the_fault_rows(monkeypatch):
    """`bench.py --fault-smoke` is the availability iteration loop: setup
    + the two fault rows, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_fault_smoke",
        lambda rows: rows.append({"name": "fault_smoke_100k",
                                  "failed_queries": 0}))
    monkeypatch.setattr(
        bench, "_row_crash_recovery",
        lambda rows: rows.append({"name": "crash_recovery_100k",
                                  "recall_recovered": 1.0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--fault-smoke"])
        assert rc == 0 and calls == ["setup"]
        names = {r.get("name") for r in bench._STATE["rows"]}
        assert {"fault_smoke_100k", "crash_recovery_100k"} <= names
    finally:
        bench._STATE["rows"].clear()


# ---------------------------------------------------------------------------
# bench/compare.py — the artifact regression gate (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def _artifact(rows):
    return {"parsed": {"metric": "m", "value": 1.0, "rows": rows}}


def test_compare_passes_on_identical_artifacts():
    sys.path.insert(0, str(REPO / "bench"))
    import compare

    art = _artifact([{"name": "a", "qps": 100.0, "recall": 0.9}])
    out = compare.compare(art, art)
    assert out["regressions"] == []
    assert out["rows"][0]["status"] == "ok"


def test_compare_flags_qps_and_recall_regressions():
    sys.path.insert(0, str(REPO / "bench"))
    import compare

    old = _artifact([
        {"name": "a", "qps": 100.0, "recall": 0.90},
        {"name": "b", "qps": 100.0, "recall_mut": 0.90},
        {"name": "c", "qps": 100.0},
        {"name": "gone", "qps": 5.0},
    ])
    new = _artifact([
        {"name": "a", "qps": 80.0, "recall": 0.90},     # -20% QPS
        {"name": "b", "qps": 99.0, "recall_mut": 0.85},  # -0.05 recall
        {"name": "c", "error": "boom"},                  # new error row
        {"name": "fresh", "qps": 1.0},
    ])
    out = compare.compare(old, new, qps_tol=0.15, recall_tol=0.01)
    assert sorted(out["regressions"]) == ["a", "b", "c"]
    assert out["only_old"] == ["gone"] and out["only_new"] == ["fresh"]
    # within tolerance → no gate
    ok = compare.compare(old, _artifact([
        {"name": "a", "qps": 90.0, "recall": 0.895},
        {"name": "b", "qps": 100.0, "recall_mut": 0.90},
        {"name": "c", "qps": 100.0},
        {"name": "gone", "qps": 5.0},
    ]), qps_tol=0.15, recall_tol=0.01)
    assert ok["regressions"] == []


def test_compare_gates_on_lost_measurements():
    """Review regression: a QPS/recall field present in the old row but
    missing from the new is a gate failure, not a silent skip — a harness
    bug that drops the measurement must not pass as 'ok'."""
    sys.path.insert(0, str(REPO / "bench"))
    import compare

    old = _artifact([
        {"name": "a", "qps": 100.0, "recall": 0.90},
        {"name": "b", "qps": 100.0, "recall_mut": 0.90},
    ])
    new = _artifact([
        {"name": "a", "qps": 100.0},                    # recall vanished
        {"name": "b", "recall_mut": 0.90},              # qps vanished
    ])
    out = compare.compare(old, new)
    assert sorted(out["regressions"]) == ["a", "b"]
    missing = {(r["name"], c["field"]) for r in out["rows"]
               for c in r["checks"] if c.get("missing")}
    assert missing == {("a", "recall"), ("b", "qps")}
    # a field the NEW artifact gained gates nothing (new rows/fields
    # appear every round)
    ok = compare.compare(new, old)
    assert ok["regressions"] == []


def test_tiered_row():
    """The --tiered bench row (ISSUE 15 acceptance): the same corpus
    served all-HBM vs tiered under a device budget the raw rows exceed.
    Every acceptance bit lives IN the row body (bit-equal ids, flat
    per-tier bytes, zero failed queries, zero cold compiles) — the
    small-scale twin must come back clean with the host-hop cost and
    per-tier attribution recorded."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_tiered(rows, n=20_000, d=32, n_lists=128, pq_dim=16, m=256,
                      bucket=128, waves=3, ncl=200)
    row = rows[-1]
    assert row["name"] == "tiered_100k" and "error" not in row, rows
    assert row["tier_residency"] == "host"
    assert row["store_bytes"] > row["budget_bytes"] - row["tier_bytes"][
        "device"], "the raw rows must exceed the device budget headroom"
    assert row["failed_queries"] == 0
    assert row["steady_compile_s"] == 0.0
    assert row["steady_cache_misses"] == 0
    assert row["recall"] == row["recall_hbm"]  # bit-equal twins
    assert row["tier_bytes"]["host"] == row["store_bytes"]
    assert row["h2d_bytes"] > 0 and row["host_hop_s"] >= 0.0
    assert row["qps"] > 0 and row["qps_hbm"] > 0
    # the row carries the journal's per-kind counts (ISSUE 17) — present
    # whenever metrics are on, gated by compare.py on presence
    assert isinstance(row.get("events"), dict), row


def test_ooc_build_row():
    """The --ooc-build bench row (ISSUE 19 acceptance): the same corpus
    built in-core vs streamed off a temp-file memmap. The hard claims —
    bit-equal indexes, streamed device peak inside plan(streamed)'s
    ±20% envelope — are asserted INSIDE the row body (a violation
    converts to an error row), so the small-scale twin coming back
    clean IS the acceptance check; the row just has to carry the
    attribution fields the compare.py gate and the round notes read."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_ooc_build(rows, n=20_000, d=32, n_lists=128, pq_dim=8,
                         chunk_rows=4096, ncl=200)
    row = rows[-1]
    assert row["name"] == "ooc_build_100k" and "error" not in row, rows
    assert row["bit_equal"] is True
    assert row["recall"] == row["recall_incore"]  # bit-equal twins
    assert row["n_chunks"] == 5
    assert row["peak_dev_bytes"] > 0 and row["plan_dev_bytes"] > 0
    assert row["peak_host_bytes"] > 0 and row["plan_host_bytes"] > 0
    # the staging term is two chunks, independent of the corpus size
    assert row["staging_dev_bytes"] == 2 * 4096 * 32 * 4
    assert row["staging_dev_bytes"] < row["corpus_bytes"]
    assert row["build_s"] > 0 and row["build_s_incore"] > 0
    assert isinstance(row.get("events"), dict), row


def test_ooc_build_flag_runs_only_the_ooc_row(monkeypatch):
    """`bench.py --ooc-build` is the streamed-build iteration loop:
    setup + the ooc row, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_ooc_build",
        lambda rows: rows.append({"name": "ooc_build_100k", "recall": 1.0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--ooc-build"])
        assert rc == 0 and calls == ["setup"]
        assert any(r.get("name") == "ooc_build_100k"
                   for r in bench._STATE["rows"])
    finally:
        bench._STATE["rows"].clear()


def test_tiered_flag_runs_only_the_tiered_row(monkeypatch):
    """`bench.py --tiered` is the beyond-HBM iteration loop: setup + the
    tiered row, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_tiered",
        lambda rows: rows.append({"name": "tiered_100k", "qps": 1.0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--tiered"])
        assert rc == 0 and calls == ["setup"]
        assert any(r.get("name") == "tiered_100k"
                   for r in bench._STATE["rows"])
    finally:
        bench._STATE["rows"].clear()


def test_compare_gates_lost_tier_measurement():
    """The per-tier mem sub-fields gate like recall fields on PRESENCE: a
    tier measurement the old artifact had and the new lost must FAIL (a
    harness bug dropping the attribution cannot pass as 'ok'), while
    byte-level drift between runs gates nothing."""
    sys.path.insert(0, str(REPO / "bench"))
    import compare

    old = _artifact([
        {"name": "t", "qps": 100.0, "recall": 0.9,
         "mem": {"device_bytes": 1, "tiers": {"device": 10, "host": 99}}},
    ])
    drifted = _artifact([
        {"name": "t", "qps": 100.0, "recall": 0.9,
         "mem": {"device_bytes": 5, "tiers": {"device": 77, "host": 1}}},
    ])
    assert compare.compare(old, drifted)["regressions"] == [], (
        "byte drift must not gate — presence does")
    for lost in (
        {"mem": {"device_bytes": 1, "tiers": {"device": 10}}},  # host gone
        {"mem": {"device_bytes": 1}},                           # tiers gone
        {},                                                     # mem gone
    ):
        new = _artifact([{"name": "t", "qps": 100.0, "recall": 0.9, **lost}])
        out = compare.compare(old, new)
        assert out["regressions"] == ["t"], lost
        assert any(c.get("missing") and c["field"].startswith("mem.tiers.")
                   for r in out["rows"] for c in r["checks"]), out
    # tiers the NEW artifact gained gate nothing
    assert compare.compare(_artifact([{"name": "t", "qps": 1.0}]),
                           old)["regressions"] == []


def test_compare_gates_lost_event_measurement():
    """The per-kind ``events`` sub-fields (ISSUE 17) gate like the
    per-tier mem sub-fields on PRESENCE: an event kind the old artifact
    observed and the new lost must FAIL (a fence window that stops
    producing replica_fenced events is a lost measurement), while count
    drift between runs gates nothing."""
    sys.path.insert(0, str(REPO / "bench"))
    import compare

    old = _artifact([
        {"name": "f", "qps": 100.0,
         "events": {"replica_fenced": 1, "replica_unfenced": 1}},
    ])
    drifted = _artifact([
        {"name": "f", "qps": 100.0,
         "events": {"replica_fenced": 7, "replica_unfenced": 3}},
    ])
    assert compare.compare(old, drifted)["regressions"] == [], (
        "count drift must not gate — presence does")
    for lost in (
        {"events": {"replica_fenced": 1}},   # unfenced kind gone
        {},                                  # events field gone
    ):
        new = _artifact([{"name": "f", "qps": 100.0, **lost}])
        out = compare.compare(old, new)
        assert out["regressions"] == ["f"], lost
        assert any(c.get("missing") and c["field"].startswith("events.")
                   for r in out["rows"] for c in r["checks"]), out
    # kinds the NEW artifact gained gate nothing
    assert compare.compare(_artifact([{"name": "f", "qps": 1.0}]),
                           old)["regressions"] == []


def test_compare_table_and_exit_codes(tmp_path, capsys):
    sys.path.insert(0, str(REPO / "bench"))
    import compare

    old = _artifact([{"name": "a", "qps": 100.0, "recall": 0.9}])
    bad = _artifact([{"name": "a", "qps": 10.0, "recall": 0.9}])
    po, pb = tmp_path / "old.json", tmp_path / "bad.json"
    po.write_text(json.dumps(old))
    pb.write_text(json.dumps(bad))
    assert compare.main([str(po), str(po), "--table"]) == 0
    out = capsys.readouterr().out
    assert "PASS" in out and "REGRESSION" not in out
    assert compare.main([str(po), str(pb), "--table"]) == 1
    out = capsys.readouterr().out
    assert "**REGRESSION**" in out and "FAIL: a" in out


def test_compare_bench_r05_vs_itself_passes():
    """The committed BENCH_r05 artifact compared against itself passes
    the gate (the ISSUE 10 acceptance bar for the tool's IO path: real
    driver wrapper, real row vocabulary, rc 0)."""
    sys.path.insert(0, str(REPO / "bench"))
    import compare

    art = json.loads((REPO / "BENCH_r05.json").read_text())
    out = compare.compare(art, art)
    assert out["regressions"] == []
    assert len(out["rows"]) >= 5  # the artifact's named rows all matched
    assert compare.main([str(REPO / "BENCH_r05.json"),
                         str(REPO / "BENCH_r05.json")]) == 0


def test_quant_funnel_row():
    """The --quant bench row (ISSUE 16 acceptance): the same corpus built
    classic vs 1bit-funnel with identical codec seeds, swept over
    tune.funnel_grid. Every acceptance bit lives IN the row body (width-1
    bit-equality, recall anchor, >=2x rows-per-HBM-byte, zero cold
    compiles) — the small-scale twin must come back clean with the
    frontier recorded in the decision evidence."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_quant_funnel(rows, n=20_000, d=64, n_lists=128, pq_dim=32,
                            m=256, bucket=128, waves=3, ncl=200, repeats=1)
    row = rows[-1]
    assert row["name"] == "quant_funnel_100k" and "error" not in row, rows
    assert row["capacity_x"] >= 2.0
    assert row["bytes_per_row"] < row["bytes_per_row_classic"]
    assert row["rows_per_hbm_byte"] > row["rows_per_hbm_byte_classic"]
    assert row["recall"] >= row["recall_classic"] - 0.02
    assert row["steady_compile_s"] == 0.0
    assert row["steady_cache_misses"] == 0
    assert row["qps"] > 0 and row["qps_classic"] > 0
    assert row["n_trials"] >= 5 and row["frontier"], row
    assert row["chosen"]["funnel_widen"] >= 1


def test_quant_flag_runs_only_the_quant_row(monkeypatch):
    """`bench.py --quant` is the funnel iteration loop: setup + the quant
    row, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_quant_funnel",
        lambda rows: rows.append({"name": "quant_funnel_100k", "qps": 1.0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--quant"])
        assert rc == 0 and calls == ["setup"]
        assert any(r.get("name") == "quant_funnel_100k"
                   for r in bench._STATE["rows"])
    finally:
        bench._STATE["rows"].clear()


def test_compare_gates_lost_capacity_measurement():
    """The funnel capacity fields (bytes_per_row / rows_per_hbm_byte)
    gate like recall fields on PRESENCE: a capacity measurement the old
    artifact had and the new lost must FAIL (a harness bug dropping the
    claim cannot pass as 'ok'), while byte-price drift between runs
    gates nothing."""
    sys.path.insert(0, str(REPO / "bench"))
    import compare

    old = _artifact([
        {"name": "q", "qps": 100.0, "recall": 0.9,
         "bytes_per_row": 20, "rows_per_hbm_byte": 0.05},
    ])
    drifted = _artifact([
        {"name": "q", "qps": 100.0, "recall": 0.9,
         "bytes_per_row": 36, "rows_per_hbm_byte": 0.027},
    ])
    assert compare.compare(old, drifted)["regressions"] == [], (
        "byte-price drift must not gate — presence does")
    for lost in (
        {"bytes_per_row": 20},   # rows_per_hbm_byte gone
        {"rows_per_hbm_byte": 0.05},  # bytes_per_row gone
        {},                      # both gone
    ):
        new = _artifact([{"name": "q", "qps": 100.0, "recall": 0.9, **lost}])
        out = compare.compare(old, new)
        assert out["regressions"] == ["q"], lost
        missing = [c["field"] for r in out["rows"] for c in r["checks"]
                   if c.get("missing")]
        assert set(missing) <= {"bytes_per_row", "rows_per_hbm_byte"}, out
        assert missing, out
    # capacity fields the NEW artifact gained gate nothing
    assert compare.compare(_artifact([{"name": "q", "qps": 1.0}]),
                           old)["regressions"] == []


# ---------------------------------------------------------------------------
# bench.py --controller — the closed-loop controller rows (ISSUE 18)
# ---------------------------------------------------------------------------

def test_controller_drift_row():
    """The --controller drift row (ISSUE 18 acceptance): a heavytail
    corpus served at a collapsed operating point recovers through the
    sensor → sweep → warm-republish loop. Every acceptance bit lives IN
    the row body (zero failed queries, zero cold compiles after
    rehearsal, recall recovered, the causal seq chain off the journal) —
    the small-scale twin must come back clean."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_controller_drift(rows, n=6000, d=32, ncl=64, n_lists=64,
                                k=5, m=128, n_eval=64, qbatch=32)
    row = rows[-1]
    assert row["name"] == "controller_drift_100k" and "error" not in row, \
        rows
    assert row["failed_queries"] == 0, row
    assert row["recall_recovered"] > row["pre_retune_at_k"], row
    assert row["retuned_version"] == 2, row
    assert row["compile_s_loaded"] == 0.0, row
    assert row["trigger_seq"] < row["decision_seq"], row
    # the event plane saw the whole loop (gated by compare.py on presence)
    assert row["events"]["retune_advised"] >= 1, row
    assert row["events"]["control/decision"] >= 1, row
    assert row["events"]["control/action_completed"] >= 1, row


def test_controller_ramp_row():
    """The --controller ramp row (ISSUE 18 acceptance): an upsert ramp
    trips the compactor's reshard watermark and the controller doubles
    the topology online. The row body asserts the acceptance bits itself
    (zero failed queries, zero cold compiles, recall held, sensor →
    decision → reshard_started → completed seq chain); the small-scale
    twin must come back clean."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_controller_ramp(rows, n=4000, d=16, n_lists=32, k=5,
                               n_probes=8, qbatch=16, n_eval=32,
                               ramp_steps=4, ramp_rows=64,
                               delta_capacity=512)
    row = rows[-1]
    assert row["name"] == "controller_ramp_100k" and "error" not in row, \
        rows
    assert row["failed_queries"] == 0, row
    assert row["shards_from"] == 2 and row["shards_to"] == 4, row
    assert row["compile_s_loaded"] == 0.0, row
    assert row["recall_post"] >= row["recall_pre"] - 0.02, row
    assert row["trigger_seq"] < row["decision_seq"], row
    assert row["events"]["reshard_advised"] >= 1, row
    assert row["events"]["control/decision"] >= 1, row
    assert row["events"]["reshard_committed"] >= 1, row
    assert row["events"]["control/action_completed"] >= 1, row


def test_controller_flag_runs_only_the_controller_rows(monkeypatch):
    """`bench.py --controller` is the control-plane iteration loop: setup
    + the two controller rows, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_controller_drift",
        lambda rows: rows.append({"name": "controller_drift_100k",
                                  "failed_queries": 0}))
    monkeypatch.setattr(
        bench, "_row_controller_ramp",
        lambda rows: rows.append({"name": "controller_ramp_100k",
                                  "failed_queries": 0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--controller"])
        assert rc == 0 and calls == ["setup"]
        names = {r.get("name") for r in bench._STATE["rows"]}
        assert {"controller_drift_100k", "controller_ramp_100k"} <= names
    finally:
        bench._STATE["rows"].clear()


# ---------------------------------------------------------------------------
# bench.py --net-serve — the network front-door rows (ISSUE 20)
# ---------------------------------------------------------------------------

def test_net_serve_row():
    """The --net-serve A/B row (ISSUE 20 acceptance): the same published
    service driven in-process and over the loopback wire — recall must be
    IDENTICAL across the two paths (same index, same flush programs), the
    QPS ladder and the wire/queue/flush p99 decomposition ride the row,
    and the serving window is compile-free. The shrunk-scale twin must
    come back clean; the row body asserts zero failures and zero cold
    compiles itself."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_net_serve(rows, n=4000, d=16, n_lists=32, n_probes=8, k=5,
                         thread_ladder=(1, 2), per_thread=25, max_batch=16,
                         n_eval=64, ncl=32)
    row = rows[-1]
    assert row["name"] == "net_serve_100k" and "error" not in row, rows
    assert row["recall_wire"] == row["recall_inproc"], row
    assert row["cache_misses"] == 0, row
    assert row["qps"] > 0 and row["qps_inproc"] > 0, row
    assert set(row["qps_by_threads"]) == {"inproc", "wire"}, row
    assert {"wire_total_ms", "queue_ms", "flush_ms"} == \
        set(row["p99_decomp"]), row


def test_net_kill_worker_row():
    """The --net-serve kill row (ISSUE 20 acceptance): a worker process
    SIGKILLed under closed-loop wire load becomes strike→fence→failover
    with ZERO failed queries and exact post-kill recall; the surviving
    fleet reports zero cold compiles. The shrunk 2x2 mesh must come back
    clean (the row body asserts the acceptance bits itself)."""
    import pytest

    pytest.importorskip("jax")
    import bench

    rows = []
    bench._row_net_kill_worker(rows, n=2000, d=16, k=5, threads=3,
                               duration_s=2.5, kill_after_s=1.0,
                               n_eval=32, max_batch=16)
    row = rows[-1]
    assert row["name"] == "net_kill_worker_100k" and "error" not in row, rows
    assert row["failed"] == 0, row
    assert row["failovers"] >= 1, row
    assert row["recall_after_kill"] == 1.0, row
    assert row["fleet"]["cache_misses"] == 0, row
    assert row["healthy_by_shard"] == [1, 2], row


def test_net_serve_flag_runs_only_the_net_rows(monkeypatch):
    """`bench.py --net-serve` is the front-door iteration loop: setup +
    the two net rows, nothing else."""
    import bench

    calls = []
    monkeypatch.setattr(bench, "_setup", lambda rows: calls.append("setup"))
    monkeypatch.setattr(
        bench, "_row_net_serve",
        lambda rows: rows.append({"name": "net_serve_100k",
                                  "recall_wire": 1.0}))
    monkeypatch.setattr(
        bench, "_row_net_kill_worker",
        lambda rows: rows.append({"name": "net_kill_worker_100k",
                                  "failed": 0}))
    monkeypatch.setattr(bench, "_run",
                        lambda rows: calls.append("run"))  # must NOT fire
    try:
        rc = bench.main(["--net-serve"])
        assert rc == 0 and calls == ["setup"]
        names = {r.get("name") for r in bench._STATE["rows"]}
        assert {"net_serve_100k", "net_kill_worker_100k"} <= names
    finally:
        bench._STATE["rows"].clear()

"""Autotuner tests (tier-1 ``tune`` marker): keying, sweep engine, decision
log, serialize /9 round trip + /8 back-compat, threshold pinning, and the
TUNE_r08.json drift pin (ISSUE 7).

The drift pin follows the calibrated-seed-pool template: the committed
artifact's recall numbers were measured on this exact mesh with seeded
generators, so rebuilding a family and re-measuring an operating point
must land within tolerance — QPS is never asserted (wall clock on shared
CI is noise); the matches-or-beats acceptance property is asserted from
the artifact's own numbers, which the choice rule guarantees by
construction and this suite keeps honest."""

import json
import pathlib

import numpy as np
import pytest

from raft_tpu import tune
from raft_tpu.core import serialize
from raft_tpu.core.errors import RaftError
from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.tune import reference
from raft_tpu.tune.apply import search_fn
from raft_tpu.tune.sweep import _ground_truth, _recall

pytestmark = pytest.mark.tune

ARTIFACT = pathlib.Path(__file__).resolve().parents[1] / "TUNE_r08.json"


@pytest.fixture(scope="module")
def small():
    """One small ivf_flat family shared by the engine tests."""
    x, q = reference._clustered(4000, 32, 96, 64, seed=3)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=32, seed=0), x)
    return {"x": x, "q": np.asarray(q), "idx": idx}


# -- keying ------------------------------------------------------------------

def test_shape_family_buckets():
    assert tune.shape_family(12_000, 64) == "10k-d64-bal"
    assert tune.shape_family(950_000, 128, "skew") == "1m-d128-skew"
    assert tune.shape_family(1_000, 48) == "1k-d64-bal"
    assert tune.shape_family(4_096, 33, "clump") == "10k-d32-clump"
    with pytest.raises(RaftError):
        tune.shape_family(100, 10, "weird")


def test_family_of_measures_balance(small):
    assert tune.family_of(small["idx"]) == "10k-d32-bal"
    # the heavytail reference family classifies skew (the r5 signature)
    xs, _ = reference._clustered(4000, 32, 16, 64, seed=5, heavytail=True)
    sidx = ivf_flat.build(ivf_flat.IndexParams(n_lists=32, seed=0), xs)
    assert tune.family_of(sidx).endswith("-skew")


def test_kind_of():
    bf = brute_force.BruteForce().build(np.zeros((8, 4), np.float32))
    assert tune.kind_of(bf) == "brute_force"
    with pytest.raises(RaftError):
        tune.kind_of(object())


# -- decision log ------------------------------------------------------------

def test_decision_log_roundtrip(tmp_path, small):
    dec = tune.Decision(kind="ivf_flat", dtype="float32",
                        family="10k-d32-bal", params={"n_probes": 4},
                        evidence={"recall_target": 0.9})
    log = tune.DecisionLog(meta={"round": "test"})
    log.add(dec)
    path = tmp_path / "log.json"
    log.save(str(path))
    log2 = tune.DecisionLog.load(str(path))
    assert len(log2) == 1 and log2.meta["round"] == "test"
    assert log2.get("ivf_flat", "float32", "10k-d32-bal").params == \
        {"n_probes": 4}
    # exact-family resolve
    assert log2.resolve(small["idx"]).key == dec.key
    # nearest-family fallback: a different-scale entry still resolves
    log3 = tune.DecisionLog()
    far = tune.Decision(kind="ivf_flat", dtype="float32",
                        family="1m-d32-bal", params={"n_probes": 16})
    log3.add(far)
    assert log3.resolve(small["idx"]).key == far.key
    # wrong kind never resolves
    log4 = tune.DecisionLog()
    log4.add(tune.Decision(kind="cagra", dtype="float32",
                           family="10k-d32-bal", params={}))
    assert log4.resolve(small["idx"]) is None


def test_decision_log_rejects_garbage():
    with pytest.raises(RaftError):
        tune.DecisionLog.from_json({"format": "something_else"})
    with pytest.raises(RaftError):
        tune.Decision.from_dict({"dtype": "float32"})


# -- sweep engine ------------------------------------------------------------

def test_sweep_chooses_and_records(small):
    from raft_tpu import obs

    before = obs.to_json()
    dec = tune.sweep(small["idx"], small["q"], k=5, dataset=small["x"],
                     grid=[{"n_probes": 8}, {"n_probes": 4},
                           {"n_probes": 16}],
                     recall_target="default", repeats=1)
    ev = dec.evidence
    assert dec.kind == "ivf_flat" and len(ev["trials"]) == 3
    # the acceptance rule: the grid head (incumbent) is feasible at its
    # own recall, so the chosen point matches-or-beats it on both axes
    assert ev["target_met"]
    assert ev["chosen_qps"] >= ev["default_qps"]
    assert ev["chosen_recall"] >= ev["recall_target"]
    assert ev["frontier"], ev
    assert dec.params in [t["params"] for t in ev["trials"]]
    # every trial is an obs event
    d = obs.delta(before, obs.to_json())
    assert d.get('raft_tpu_tune_trials_total'
                 '{family="10k-d32-bal",kind="ivf_flat"}') == 3


def test_sweep_infeasible_target_takes_best_recall(small):
    dec = tune.sweep(small["idx"], small["q"], k=5, dataset=small["x"],
                     grid=[{"n_probes": 2}, {"n_probes": 8}],
                     recall_target=2.0, repeats=1)
    ev = dec.evidence
    assert not ev["target_met"]
    best = max(t["recall"] for t in ev["trials"] if "recall" in t)
    assert ev["chosen_recall"] == best


def test_sweep_records_failed_arm_as_evidence(small):
    dec = tune.sweep(small["idx"], small["q"], k=5, dataset=small["x"],
                     grid=[{"n_probes": 8}, {"bogus_knob": 1}], repeats=1,
                     recall_target="default")
    trials = dec.evidence["trials"]
    assert "error" in trials[1] and "bogus_knob" in trials[1]["error"]
    assert dec.params == {"n_probes": 8}


def test_sweep_select_k_records_ineligible_on_cpu():
    dec = tune.sweep_select_k(rows=8, cols=(2048,), ks=(5,), repeats=1)
    assert dec.params["wide_cols_min"] == 65536  # the shipped default kept
    assert dec.evidence["pallas_measured"] is False
    errs = [t for t in dec.evidence["trials"] if "error" in t]
    assert errs and "ineligible" in errs[0]["error"]


# -- applying decisions ------------------------------------------------------

def test_tuned_search_params_mapping():
    sp, rr = tune.tuned_search_params(
        "ivf_pq", {"n_probes": 4, "refine_ratio": 8, "lut_dtype": "bfloat16"})
    assert sp.n_probes == 4 and sp.lut_dtype == "bfloat16" and rr == 8
    sp, rr = tune.tuned_search_params("cagra", {"itopk_size": 64})
    assert sp.itopk_size == 64 and rr == 1
    sp, rr = tune.tuned_search_params("brute_force", {})
    assert sp is None and rr == 1
    with pytest.raises(RaftError):  # unknown knob must never half-apply
        tune.tuned_search_params("ivf_flat", {"itopk_size": 32})
    with pytest.raises(RaftError):  # refine is an IVF epilogue only
        tune.tuned_search_params("cagra", {"refine_ratio": 4})


def test_make_searcher_refine_needs_rows(small):
    dec = tune.Decision(kind="ivf_flat", dtype="float32",
                        family="10k-d32-bal",
                        params={"n_probes": 4, "refine_ratio": 4})
    with pytest.raises(RaftError, match="raw rows"):
        tune.make_searcher(small["idx"], dec)
    hook = tune.make_searcher(small["idx"], dec, dataset=small["x"])
    assert hook.kind == "ivf_flat+refine" and hook.tuned == dec.key
    d, i = hook(small["q"][:4], 5)
    assert np.asarray(i).shape == (4, 5)


def test_attach_and_batched_searcher(small, tmp_path):
    idx = small["idx"]
    dec = tune.Decision(kind="ivf_flat", dtype="float32",
                        family="10k-d32-bal", params={"n_probes": 4})
    wrong = tune.Decision(kind="cagra", dtype="float32",
                          family="10k-d32-bal", params={})
    with pytest.raises(RaftError):
        tune.attach(idx, wrong)
    with pytest.raises(RaftError):  # bad knobs fail at pin time
        tune.attach(idx, tune.Decision(
            kind="ivf_flat", dtype="float32", family="10k-d32-bal",
            params={"nope": 1}))
    try:
        tune.attach(idx, dec)
        hook = ivf_flat.batched_searcher(idx)
        assert hook.tuned == dec.key
        # explicit params still win over the attached pin
        hook2 = ivf_flat.batched_searcher(
            idx, ivf_flat.SearchParams(n_probes=8))
        assert not hasattr(hook2, "tuned")
    finally:
        idx.tuned = None


def test_wide_cols_threshold_pin_and_env(monkeypatch):
    import jax.numpy as jnp

    from raft_tpu.matrix.select_k import (set_wide_cols_threshold,
                                          wide_cols_threshold,
                                          wide_dispatch_ok)

    try:
        assert wide_cols_threshold() == 65536
        set_wide_cols_threshold(1024)
        assert wide_cols_threshold() == 1024
        assert wide_dispatch_ok(2048, 10, jnp.float32, backend="tpu")
        set_wide_cols_threshold(None)
        assert not wide_dispatch_ok(2048, 10, jnp.float32, backend="tpu")
        monkeypatch.setenv("RAFT_TPU_WIDE_SELECT_COLS", "4096")
        assert wide_cols_threshold() == 4096
        with pytest.raises(RaftError):
            set_wide_cols_threshold(0)
    finally:
        set_wide_cols_threshold(None)


def test_apply_global_pins_select_threshold():
    from raft_tpu.matrix.select_k import (set_wide_cols_threshold,
                                          wide_cols_threshold)

    log = tune.DecisionLog()
    assert tune.apply_global(log) == {}
    log.add(tune.Decision(kind="select_k", dtype="float32", family="wide",
                          params={"wide_cols_min": 32768}))
    try:
        assert tune.apply_global(log) == {"select_k.wide_cols_min": 32768}
        assert wide_cols_threshold() == 32768
    finally:
        set_wide_cols_threshold(None)


def test_refine_and_ground_truth_follow_index_metric(rng):
    """An inner-product index must be swept against IP ground truth and
    refined by IP score — an L2 epilogue would silently re-rank wrong
    (code-review regression)."""
    d, n = 8, 64
    direction = np.zeros((1, d), np.float32)
    direction[0, 0] = 1.0
    scales = np.linspace(0.1, 10.0, n).astype(np.float32)
    x = scales[:, None] * direction + \
        0.01 * rng.standard_normal((n, d)).astype(np.float32)
    q = direction.copy()  # L2-nearest ~ scale 1.0; IP-max = scale 10
    gt_ip = _ground_truth(x, q, 1, metric="inner_product")
    gt_l2 = _ground_truth(x, q, 1)
    assert gt_ip[0, 0] == n - 1 and gt_l2[0, 0] != n - 1
    idx = ivf_flat.build(ivf_flat.IndexParams(
        n_lists=2, metric="inner_product", seed=0), x)
    fn = search_fn(idx, {"n_probes": 2, "refine_ratio": 4}, dataset=x)
    _, ids = fn(q, 1)
    assert int(np.asarray(ids)[0, 0]) == n - 1


def test_loaded_refine_pin_degrades_without_rows(small, tmp_path):
    """An attached refine_ratio pin must never make the no-params
    batched_searcher of a LOADED index crash: the refine-free remainder
    serves, with a warning (code-review regression)."""
    idx = small["idx"]
    dec = tune.Decision(kind="ivf_flat", dtype="float32",
                        family=tune.family_of(idx),
                        params={"n_probes": 4, "refine_ratio": 4})
    try:
        tune.attach(idx, dec)
        path = tmp_path / "pinned.bin"
        ivf_flat.save(idx, str(path))
        loaded = ivf_flat.load(str(path))
        hook = ivf_flat.batched_searcher(loaded)  # must not raise
        assert hook.kind == "ivf_flat" and hook.tuned == dec.key
        d, i = hook(small["q"][:2], 5)
        assert np.asarray(i).shape == (2, 5)
    finally:
        idx.tuned = None


def test_resolve_never_crosses_balance_class(small):
    """The fallback must not hand a skew-family pin to a balanced index:
    that transfer IS the measured r5 recall collapse (code-review
    regression)."""
    log = tune.DecisionLog()
    log.add(tune.Decision(kind="ivf_flat", dtype="float32",
                          family="10k-d32-skew", params={"n_probes": 32}))
    assert log.resolve(small["idx"]) is None


def test_resolve_tolerates_unstructured_family(small):
    """Hand-authored decisions (from_dict's 'any' family) resolve as a
    last resort instead of crashing the fallback scorer (code-review
    regression)."""
    log = tune.DecisionLog()
    log.add(tune.Decision.from_dict(
        {"kind": "ivf_flat", "params": {"n_probes": 16}}))
    dec = log.resolve(small["idx"])
    assert dec is not None and dec.family == "any"
    # a structured-family entry still wins over the unstructured one
    log.add(tune.Decision(kind="ivf_flat", dtype="float32",
                          family="1m-d32-bal", params={"n_probes": 8}))
    assert log.resolve(small["idx"]).family == "1m-d32-bal"


def test_select_k_sweep_counts_ineligible_trials():
    from raft_tpu import obs

    before = obs.to_json()
    dec = tune.sweep_select_k(rows=8, cols=(1024,), ks=(5,), repeats=1)
    d = obs.delta(before, obs.to_json())
    counted = d.get('raft_tpu_tune_trials_total'
                    '{family="wide",kind="select_k"}')
    assert counted == len(dec.evidence["trials"])


# -- serialize /9 + /8 back-compat ------------------------------------------

def _roundtrip(write, read, tmp_path, name):
    path = tmp_path / name
    with open(path, "wb") as f:
        write(f)
    with open(path, "rb") as f:
        return read(f)


def test_serialize_v9_roundtrip_all_kinds(tmp_path, small):
    x = np.asarray(small["x"])[:600]
    tuned = {"kind": None, "dtype": "float32", "family": "10k-d32-bal",
             "params": {}, "evidence": {"recall_target": 0.9}}

    bf = brute_force.BruteForce().build(x)
    bf.tuned = dict(tuned, kind="brute_force")
    out = _roundtrip(lambda f: brute_force.write_index(f, bf),
                     brute_force.read_index, tmp_path, "bf.bin")
    assert out.tuned == bf.tuned

    fidx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, seed=0), x)
    fidx.tuned = dict(tuned, kind="ivf_flat", params={"n_probes": 4})
    out = _roundtrip(lambda f: ivf_flat.write_index(f, fidx),
                     ivf_flat.read_index, tmp_path, "flat.bin")
    assert out.tuned == fidx.tuned

    pidx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=8, pq_bits=4, pq_dim=16, seed=0), x)
    pidx.tuned = dict(tuned, kind="ivf_pq",
                      params={"n_probes": 4, "refine_ratio": 4})
    out = _roundtrip(lambda f: ivf_pq.write_index(f, pidx),
                     ivf_pq.read_index, tmp_path, "pq.bin")
    assert out.tuned == pidx.tuned

    cidx = cagra.build(cagra.IndexParams(
        graph_degree=8, intermediate_graph_degree=16, seed=0), x[:300])
    cidx.tuned = dict(tuned, kind="cagra", params={"itopk_size": 16})
    out = _roundtrip(lambda f: cagra.write_index(f, cidx),
                     cagra.read_index, tmp_path, "cagra.bin")
    assert out.tuned == cidx.tuned

    # an untuned index writes no decision and reads back None
    cidx.tuned = None
    out = _roundtrip(lambda f: cagra.write_index(f, cidx),
                     cagra.read_index, tmp_path, "cagra2.bin")
    assert out.tuned is None


def test_serialize_v8_files_still_load(tmp_path, monkeypatch, small):
    """A writer pinned to raft_tpu/8 emits true /8 bytes (no tuned
    record); the /9 reader must load them untuned — full /8 read-compat."""
    x = np.asarray(small["x"])[:400]
    fidx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, seed=0), x)
    fidx.tuned = {"kind": "ivf_flat", "dtype": "float32", "family": "f",
                  "params": {"n_probes": 4}}
    monkeypatch.setattr(serialize, "SERIALIZATION_VERSION", "raft_tpu/8")
    path = tmp_path / "v8.bin"
    with open(path, "wb") as f:
        ivf_flat.write_index(f, fidx)
    monkeypatch.undo()
    with open(path, "rb") as f:
        out = ivf_flat.read_index(f)
    assert out.tuned is None
    assert out.data_kind == fidx.data_kind
    np.testing.assert_array_equal(np.asarray(out.list_sizes),
                                  np.asarray(fidx.list_sizes))


def test_version_number_helper():
    assert serialize.version_number("raft_tpu/9") == 9
    assert serialize.version_number(serialize.SERIALIZATION_VERSION) >= 9
    with pytest.raises(ValueError):
        serialize.version_number("garbage")


def test_stream_save_preserves_sealed_tuned(tmp_path, small):
    """The sealed index's pin rides the stream section's embedded
    serializer (docs/streaming.md)."""
    from raft_tpu import stream

    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, seed=0),
                         np.asarray(small["x"])[:400])
    idx.tuned = {"kind": "ivf_flat", "dtype": "float32",
                 "family": "10k-d32-bal", "params": {"n_probes": 4}}
    m = stream.MutableIndex(idx, delta_capacity=16)
    path = tmp_path / "stream.bin"
    stream.save(m, str(path))
    m2 = stream.load(str(path))
    assert m2._state.sealed.tuned == idx.tuned


# -- the committed artifact --------------------------------------------------

def test_artifact_acceptance_properties():
    """TUNE_r08.json: every entry's chosen point matches-or-beats its
    grid-head hand-picked point (QPS at equal-or-better recall) — the
    ROADMAP item-5 done-bar, asserted from the artifact's own numbers."""
    with open(ARTIFACT) as f:
        artifact = json.load(f)
    log = tune.DecisionLog.from_json(artifact)
    assert artifact["meta"]["round"] == reference.ROUND
    kinds = {d.kind for d in log.entries()}
    assert {"ivf_flat", "ivf_pq", "cagra", "select_k"} <= kinds
    # both families of the non-transfer result are pinned separately
    assert log.get("ivf_pq", "float32", "10k-d64-bal") is not None
    assert log.get("ivf_pq", "float32", "10k-d64-skew") is not None
    for dec in log.entries():
        ev = dec.evidence
        if dec.kind == "select_k":
            assert "pallas_measured" in ev and ev["trials"]
            continue
        assert ev["target_met"], dec.key
        assert ev["chosen_qps"] >= ev["default_qps"], dec.key
        assert ev["chosen_recall"] >= ev["recall_target"], dec.key
        assert dec.params in [t["params"] for t in ev["trials"]
                              if "error" not in t], dec.key
        assert ev["default_params"] == ev["trials"][0]["params"], dec.key


def _drift_check(name, tol=0.03):
    """Rebuild a reference family and re-measure the committed chosen and
    default operating points' recall (seeded generators on CPU: the only
    legitimate movement is a code change — which is the point)."""
    with open(ARTIFACT) as f:
        log = tune.DecisionLog.from_json(json.load(f))
    fam = reference.build_family(name)
    idx, q, x, k = (fam["index"], np.asarray(fam["queries"]),
                    fam["dataset"], fam["k"])
    entry = log.resolve(idx, x)
    assert entry is not None, f"no artifact entry resolves for {name}"
    gt = _ground_truth(x, q, k)
    recorded = {json.dumps(t["params"], sort_keys=True): t["recall"]
                for t in entry.evidence["trials"] if "error" not in t}
    for params in (entry.params, entry.evidence["default_params"]):
        fn = search_fn(idx, dict(params), dataset=x)
        _, ids = fn(q, k)
        got = _recall(np.asarray(ids), gt)
        want = recorded[json.dumps(params, sort_keys=True)]
        assert abs(got - want) <= tol, (
            f"{name} drifted: {params} measured {got:.4f} vs committed "
            f"{want:.4f} — regenerate TUNE_r08.json (bench/tune_sweep.py "
            "--cpu-mesh) and record why in CHANGES.md")
        if params == entry.params:
            assert got >= entry.evidence["recall_target"] - tol


def test_artifact_drift_pin_ivf_flat():
    _drift_check("ivf_flat_bal")


def test_artifact_drift_pin_ivf_pq():
    _drift_check("ivf_pq_bal")


@pytest.mark.parametrize("name", ["ivf_pq_skew", "cagra_bal"])
def test_artifact_drift_pin_heavy(name):
    # cagra rebuild + the heavytail family are the slow half (slow manifest)
    _drift_check(name)

"""A stream of writes beside the reads: the schedule follows the seed, the
live-set judge passes a sound index and fails each lost write, and a
traffic mix without writes takes the read-only path unchanged."""

import types

import numpy as np
import pytest

from benchmark import faults, harness, loadgen
from benchmark.data import sift_like
from benchmark.reference import exact_l2, exact_l2_live

from conftest import CHURN, CLOSED, small_root

SEED = 2 ** 33 + 7


def test_write_schedule_follows_the_seed():
    spec = CHURN["writes"]
    a, b, c = (loadgen.write_schedule(spec, 1000, 320, s)
               for s in (SEED, SEED, SEED + 2 ** 32))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    assert np.array_equal(a[0], c[0])  # the inserts are the same rows
    ins, dele = a
    steps, age = spec["steps"], spec["fresh_age_steps"]
    assert ins.shape == (steps, spec["inserts_per_step"])
    assert np.array_equal(ins.reshape(-1), 1000 + np.arange(ins.size))
    per_step = spec["base_deletes_per_step"] + spec["fresh_deletes_per_step"]
    assert dele.shape == (steps, per_step)
    assert np.unique(dele).size == dele.size
    for s in range(steps):
        fresh = dele[s][dele[s] >= 1000]
        if s < age:
            assert fresh.size == 0
        else:
            assert fresh.size == spec["fresh_deletes_per_step"]
            assert np.isin(fresh, ins[s - age]).all()


def test_write_schedule_refuses_more_inserts_than_rows_held():
    with pytest.raises(ValueError):
        loadgen.write_schedule(CHURN["writes"], 1000, 319, SEED)


@pytest.fixture(scope="module")
def tiny():
    corpus, queries = sift_like.generate(
        {"rows": 2000, "dim": 128, "queries": 40, "dtype": "float32",
         "corpus_seed": 0}, SEED)
    return corpus, np.asarray(corpus), np.asarray(queries)


def _judge(tiny, writes, ids, t_s=5.0, t_d=6.0):
    """Judge answers ``ids`` (40, 10) to every query of ``tiny``, each
    submitted at ``t_s`` and answered at ``t_d``, under ``writes``."""
    corpus, host, queries = tiny
    cfg = {"k": 10, "stream": {"held_out": 64, "miss_margin": 2e-5}}
    ids = np.asarray(ids, np.int64)
    d = ((host[ids].astype(np.float64) - queries[:, None]) ** 2).sum(-1)
    m = len(queries)
    rec = loadgen.Record(
        t0=0.0, t1=10.0, start=np.full(1, t_s), done=np.full(1, t_d),
        rows=np.full(1, m), qidx=np.arange(m), ids=ids, dists=d,
        writes=writes)
    return exact_l2_live.judge_record(cfg, corpus, host, queries,
                                      np.arange(m), rec)


def _log(ins_ack, del_ack, dead, call_lead=0.5):
    """One step inserting all 64 held-out rows and deleting ``dead``."""
    return loadgen.WriteLog(
        due=np.zeros(1), ins_ids=(1936 + np.arange(64))[None],
        del_ids=np.asarray(dead, np.int64)[None],
        ins_call=np.array([ins_ack - call_lead]), ins_ack=np.array([ins_ack]),
        del_call=np.array([del_ack - call_lead]), del_ack=np.array([del_ack]))


def _exact(tiny, alive, k=10):
    _, host, queries = tiny
    rows = np.flatnonzero(alive)
    d = ((host[rows][None].astype(np.float64)
          - queries[:, None].astype(np.float64)) ** 2).sum(-1)
    return rows[np.argsort(d, axis=1, kind="stable")[:, :k]]


@pytest.fixture(scope="module")
def dead(tiny):
    """Every query's nearest row, and the first held-out row."""
    nearest = _exact(tiny, np.ones(2000, bool))[:, 0]
    return np.unique(np.append(nearest, 1936))


def _alive(dead, inserted=True):
    alive = np.arange(2000) < (2000 if inserted else 1936)
    alive[dead] = False
    return alive


def test_live_judge_passes_the_live_top_k(tiny, dead):
    n = _judge(tiny, _log(1.0, 2.0, dead), _exact(tiny, _alive(dead)))
    assert n["stale_served"] == 0 and n["missed_fresh"] == 0
    assert n["recall"] == 1.0 and n["full_rows"] == 0
    assert n["dist_err"] < 1e-12 and n["dist_err_fresh"] < 1e-12


def test_live_judge_without_writes_is_exact_l2s_judge_over_the_base_rows(
        tiny):
    # the held-out build's answers judged both ways: the live set is the
    # base rows, so recall and dist_err agree with exact_l2 over them
    corpus, host, queries = tiny
    base = np.arange(2000) < 1936
    shift = np.arange(len(queries)) % 4  # recall 1.0, 0.9, 0.8, 0.7
    top = _exact(tiny, base, 13)
    ids = np.stack([top[j, s:s + 10] for j, s in enumerate(shift)])
    live = _judge(tiny, None, ids)
    judged = np.arange(len(queries))
    ref_ids, ref_d = exact_l2.exact_topk(corpus[:1936], queries, 10)
    d = ((host[ids].astype(np.float64) - queries[:, None]) ** 2).sum(-1)
    want = exact_l2.judge(host[:1936], queries, judged, ref_ids, ref_d,
                          judged, ids, d)
    assert live["recall"] == want["recall"] == 1.0 - 0.1 * shift.mean()
    assert live["dist_err"] == want["dist_err"]
    assert live["rows"] == want["rows"] and live["full_rows"] == 0
    assert live["stale_served"] == 0 and live["missed_fresh"] == 0


def test_write_ms_p95_reads_the_steps_due_to_last_ack():
    from benchmark.metrics import write_ms_p95

    due = np.arange(40, dtype=np.float64)
    late = np.linspace(0.001, 0.040, 40)  # 1..40 ms after the due time
    log = loadgen.WriteLog(
        due=due, ins_ids=np.zeros((40, 0), np.int64),
        del_ids=np.zeros((40, 0), np.int64), ins_call=due,
        ins_ack=due + late / 2, del_call=due + late / 2, del_ack=due + late)
    ctx = types.SimpleNamespace(rec=types.SimpleNamespace(writes=log))
    assert write_ms_p95.read(ctx) == pytest.approx(38.0)  # 38th of 40
    log.del_ack[:3] = np.nan  # three failed calls miss every limit
    assert write_ms_p95.read(ctx) == np.inf
    ctx.rec.writes = None
    assert write_ms_p95.read(ctx) is None


def test_live_judge_catches_stale_and_missed_rows(tiny, dead):
    # the writes were acknowledged before submit: answers that leave the
    # inserted rows out miss them, and answers over every row serve the
    # deleted ones
    log = _log(1.0, 2.0, dead)
    n = _judge(tiny, log, _exact(tiny, _alive(dead, inserted=False)))
    assert n["missed_fresh"] > 0 and n["stale_served"] == 0
    assert n["recall"] < 1.0
    n = _judge(tiny, log, _exact(tiny, np.ones(2000, bool)))
    assert n["stale_served"] >= len(tiny[2])  # each query's nearest


def test_live_judge_allows_writes_that_overlap_the_request(tiny, dead):
    # insert and delete called inside [t_s, t_d]: either outcome passes
    log = _log(5.6, 5.8, dead, call_lead=0.3)
    for alive in (np.arange(2000) < 1936, _alive(dead)):
        n = _judge(tiny, log, _exact(tiny, alive))
        assert n["stale_served"] == 0 and n["missed_fresh"] == 0


def test_live_judge_reads_a_row_the_pool_cannot_cover_in_full(tiny):
    # delete every base row a query's pool holds: its top 10 of the live
    # set lies beyond the pool and is computed over the live set in full
    corpus, host, queries = tiny
    pool_ids, _ = exact_l2.exact_topk(corpus, queries, exact_l2_live.POOL,
                                      pool=2 * exact_l2_live.POOL)
    dead = np.unique(pool_ids[pool_ids < 1936])
    log = loadgen.WriteLog(
        due=np.zeros(1), ins_ids=np.zeros((1, 0), np.int64),
        del_ids=dead[None], ins_call=np.zeros(1), ins_ack=np.zeros(1),
        del_call=np.array([0.5]), del_ack=np.array([1.0]))
    alive = np.arange(2000) < 1936
    alive[dead] = False
    n = _judge(tiny, log, _exact(tiny, alive))
    assert n["full_rows"] == len(queries) and n["recall"] == 1.0
    assert n["stale_served"] == 0 and n["missed_fresh"] == 0


@pytest.mark.parametrize("fault,number", [(None, None),
                                          ("insert_lost", "missed_fresh"),
                                          ("delete_lost", "stale_served")])
def test_stream_cell_judged_against_the_live_set(tmp_path, monkeypatch,
                                                 run_small, fault, number):
    # every query judged, so that a lost write shows in a short CPU window
    root = small_root(tmp_path, "sift1m-ivfpq", CHURN,
                      index={"n_lists": 32}, judged=500, stream=True)
    if fault is not None:
        mod = harness.Bench(root).module("indexes", "ivf_pq")
        build = mod.build
        monkeypatch.setattr(mod, "build",
                            lambda *a: faults.FAULTS[fault](build(*a)))
    out = run_small(root, "small.closed", seconds=2.5)
    w = out["info"]["writes"]
    assert w["steps_done"] == CHURN["writes"]["steps"] and not w["errors"]
    assert out["failed"] == 0
    assert out["attempted"] > 2 * CHURN["writes"]["steps"]
    assert list(out["check"]) == ["dist_err", "dist_err_fresh",
                                  "stale_served", "missed_fresh", "failed"]
    if fault is None:
        assert out["correct"], out["check"]
        # 320 rows inserted, 8 a step deleted again from step 3 on
        assert w["delta_live_rows"] == 320 - 8 * (20 - 3)
        assert w["sealed_republishes"] == CHURN["writes"]["steps"]
    else:
        assert not out["correct"]
        assert out["check"][number]["value"] > 0, out["check"]


def test_read_only_traffic_takes_todays_path(tmp_path, run_small,
                                             monkeypatch):
    root = small_root(tmp_path, "sift1m-ivfpq", CLOSED,
                      index={"n_lists": 32})
    seen = {}
    judge = harness.Cell.judge

    def spy(cell, rec):
        seen["rec"], seen["numbers"] = rec, judge(cell, rec)
        seen["cell"] = cell
        return seen["numbers"]

    monkeypatch.setattr(harness.Cell, "judge", spy)
    out = run_small(root, "small.closed")
    rec, cell = seen["rec"], seen["cell"]
    assert rec.writes is None and "writes" not in out["info"]
    assert set(out["info"]) == {
        "seed", "seconds", "judged_rows", "recall", "errors",
        "programs_in_window", "longest_answer_gap_ms", "gc2_in_window",
        "gc2_seconds", "answers_per_second"}
    assert out["attempted"] == len(rec.done)
    assert out["failed"] == int(np.isnan(rec.done).sum())
    # the numbers are exact_l2's over one fixed corpus
    keep = np.isin(rec.qidx, cell.judged)
    ref_ids, ref_d = exact_l2.exact_topk(
        cell.corpus, cell.queries[cell.judged], cell.cfg["k"])
    want = exact_l2.judge(cell.corpus_host(), cell.queries, cell.judged,
                          ref_ids, ref_d, rec.qidx[keep], rec.ids[keep],
                          rec.dists[keep])
    assert seen["numbers"] == dict(want, failed=out["failed"])

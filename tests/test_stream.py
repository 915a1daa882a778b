"""Mutable-index lifecycle tests (tier-1 ``stream`` marker).

Deterministic by construction: MutableIndex/Compactor take injected clocks
and the compactor is driven via ``run_once()`` — watermark policy, write
visibility and compaction swaps are asserted without wall-clock sleeps.
The two concurrency tests (swap under load, background worker liveness)
use real threads but synchronize on joins/poll deadlines, never timed
sleeps in assertions.
"""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import stream
from raft_tpu.core.errors import RaftError
from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.serve import (IndexRegistry, OverloadedError, SearchService,
                            ServiceClosedError)

pytestmark = pytest.mark.stream


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture
def data(rng):
    return rng.standard_normal((240, 16)).astype(np.float32)


@pytest.fixture
def queries(rng):
    return rng.standard_normal((5, 16)).astype(np.float32)


def wrap_bf(x, **kw):
    return stream.MutableIndex(
        brute_force.BruteForce().build(jnp.asarray(x)), **kw)


def bf_gids(live_mat, live_gids, queries, k):
    """Ground truth over an explicit live-row set, mapped to global ids."""
    _, pos = brute_force.knn(jnp.asarray(live_mat), jnp.asarray(queries), k)
    pos = np.asarray(pos)
    return np.where(pos >= 0, np.asarray(live_gids)[np.clip(pos, 0, None)], -1)


# -- ladder / wrap validation -------------------------------------------------

def test_delta_bucket_ladder():
    assert stream.delta_buckets(64) == (8, 16, 32, 64)
    assert stream.delta_buckets(8) == (8,)
    with pytest.raises(RaftError):
        stream.delta_buckets(48)  # not a power of two
    with pytest.raises(RaftError):
        stream.delta_buckets(4)  # below the floor


def test_wrap_validations(data):
    with pytest.raises(RaftError):
        stream.MutableIndex(object())  # not an index
    pq = ivf_pq.build(ivf_pq.IndexParams(n_lists=8, pq_dim=8, seed=0),
                      jnp.asarray(data))
    with pytest.raises(RaftError, match="retain_vectors"):
        # PQ codes cannot reconstruct rows: a retained store needs dataset=
        stream.MutableIndex(pq, retain_vectors=True)
    with pytest.raises(RaftError, match="sealed rows"):
        stream.MutableIndex(pq, dataset=data[:10])


# -- write visibility ---------------------------------------------------------

def test_upsert_visible_before_compaction(data, queries):
    m = wrap_bf(data, delta_capacity=16)
    new = queries[0:1] + 1e-3  # right on top of query 0
    gid = m.upsert(new)
    assert m.stats()["delta_rows"] == 1 and m.stats()["epoch"] == 0
    _, ids = m.search(queries, 5)
    assert int(np.asarray(ids)[0, 0]) == int(gid[0])
    assert m.size == len(data) + 1


def test_delete_invisible_immediately(data, queries):
    m = wrap_bf(data, delta_capacity=16)
    _, ids0 = m.search(queries, 5)
    nn = int(np.asarray(ids0)[0, 0])
    assert m.delete([nn]) == 1
    _, ids1 = m.search(queries, 5)
    assert nn not in np.asarray(ids1)[0]
    # unknown / already-dead ids are counted no-ops
    assert m.delete([nn, 10_000]) == 0


def test_upsert_same_id_replaces_old_vector(data, queries):
    """upsert = tombstone-old + insert-new: the stale copy never surfaces,
    in either the sealed or the delta layer."""
    m = wrap_bf(data, delta_capacity=16)
    _, ids0 = m.search(queries, 5)
    nn = int(np.asarray(ids0)[1, 0])  # a SEALED row
    far = (queries[1:2] * 0.0) + 100.0
    m.upsert(far, ids=[nn])  # replace with a far-away vector
    d1, ids1 = m.search(queries, 5)
    assert nn not in np.asarray(ids1)[1]  # old copy is dead, new copy is far
    # replace a DELTA row under the same id
    m.upsert(queries[1:2] + 1e-3, ids=[nn])
    _, ids2 = m.search(queries, 5)
    assert int(np.asarray(ids2)[1, 0]) == nn
    assert m.size == len(data)  # one live copy per id throughout


def test_underfilled_search_reports_sentinels(data, queries):
    """Stream inherits the shared filtered-underfill contract: when the
    live rows cannot fill k slots, ids are -1 at +inf."""
    m = wrap_bf(data, delta_capacity=16)
    m.delete(np.arange(len(data)))  # everything sealed is dead
    g = m.upsert(queries[0:1] + 1e-3)  # one live delta row
    d, i = m.search(queries, 5)
    d, i = np.asarray(d), np.asarray(i)
    assert (i[:, 0] == int(g[0])).all()
    assert (i[:, 1:] == -1).all() and np.isinf(d[:, 1:]).all()


def test_delta_full_is_overload(data):
    m = wrap_bf(data, delta_capacity=8)
    m.upsert(data[:8] + 0.5)
    with pytest.raises(OverloadedError):  # DeltaFullError subclasses it
        m.upsert(data[:1])
    with pytest.raises(stream.DeltaFullError):
        m.upsert(data[:1])
    m.compact()
    m.upsert(data[:1] + 0.25)  # admission reopens after the fold


# -- unified search parity ----------------------------------------------------

def test_search_matches_fresh_build_over_live_rows(data, queries, rng):
    """The acceptance bit-match: mutable search over (dataset − deleted +
    inserted) equals a fresh brute-force build over exactly the live rows
    — identical ids (after gid mapping), matching distances — WITHOUT any
    compaction (sealed+delta merge path)."""
    m = wrap_bf(data, delta_capacity=64)
    ins = rng.standard_normal((20, 16)).astype(np.float32)
    gids = m.upsert(ins)
    dele = [3, 17, 44, 101, int(gids[4])]
    m.delete(dele)
    live_mask = np.ones(len(data), bool)
    live_mask[[3, 17, 44, 101]] = False
    ins_mask = np.ones(20, bool)
    ins_mask[4] = False
    live_mat = np.concatenate([data[live_mask], ins[ins_mask]])
    live_g = np.concatenate([np.nonzero(live_mask)[0],
                             np.asarray(gids)[ins_mask]])
    want = bf_gids(live_mat, live_g, queries, 10)
    d, got = m.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got), want)
    dref, _ = brute_force.knn(jnp.asarray(live_mat), jnp.asarray(queries), 10)
    np.testing.assert_allclose(np.asarray(d), np.asarray(dref), rtol=1e-5)


def test_compaction_equals_fresh_build(data, queries, rng):
    """Rebuild compaction folds delta + reclaims tombstones; results stay
    identical to the pre-compaction view and to a fresh build."""
    m = wrap_bf(data, delta_capacity=64)
    gids = m.upsert(rng.standard_normal((10, 16)).astype(np.float32))
    m.delete([0, 1, 2, int(gids[0])])
    d0, i0 = m.search(queries, 8)
    rep = m.compact()
    assert rep["mode"] == "rebuild" and rep["reclaimed"] == 3
    st = m.stats()
    assert st["sealed_dead"] == 0 and st["delta_rows"] == 0
    assert st["epoch"] == 1
    d1, i1 = m.search(queries, 8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d0), rtol=1e-5)


def test_writes_during_fold_survive_the_swap(data, queries):
    """Compaction folds a snapshot prefix; anything written between the
    snapshot and the swap (simulated here by writing right before compact —
    the swap re-reads all alive bits) is preserved."""
    m = wrap_bf(data, delta_capacity=64)
    g1 = m.upsert(queries[0:1] + 1e-3)
    m.compact()
    # post-swap: folded row is sealed now; delete it THROUGH the new layout
    assert m.delete([int(g1[0])]) == 1
    _, ids = m.search(queries, 5)
    assert int(g1[0]) not in np.asarray(ids)[0]


def test_extend_compaction_ivf_flat_parity(data, queries, rng):
    """IVF-Flat extend-compaction: exhaustive probes make the scan exact,
    so pre/post-compaction results match the brute-force ground truth over
    the live rows; tombstoned sealed slots stay masked after the fold."""
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, seed=0),
                         jnp.asarray(data))
    # splitting can leave the index with > n_lists lists; probe them ALL so
    # the scan is exhaustive and the bit-match against brute force holds
    m = stream.MutableIndex(idx, search_params=ivf_flat.SearchParams(n_probes=64),
                            delta_capacity=32, retain_vectors=False)
    ins = rng.standard_normal((6, 16)).astype(np.float32)
    gids = m.upsert(ins)
    m.delete([7, 8])
    live_mat = np.concatenate([np.delete(data, [7, 8], axis=0), ins])
    live_g = np.concatenate([np.delete(np.arange(len(data)), [7, 8]),
                             np.asarray(gids)])
    want = bf_gids(live_mat, live_g, queries, 10)
    _, got0 = m.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got0), want)
    rep = m.compact()
    assert rep["mode"] == "extend"
    assert m.stats()["sealed_dead"] == 2  # extend keeps tombstones masked
    _, got1 = m.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got1), want)


def test_ivf_pq_compaction_recall_parity(data, queries, rng):
    """IVF-PQ (quantized): compacted results keep recall parity with a
    fresh oracle build over the live rows at the same operating point."""
    params = ivf_pq.IndexParams(n_lists=8, pq_dim=16, seed=0)
    sp = ivf_pq.SearchParams(n_probes=64)  # exhaustive even after splits
    idx = ivf_pq.build(params, jnp.asarray(data))
    m = stream.MutableIndex(idx, search_params=sp, delta_capacity=32)
    ins = rng.standard_normal((12, 16)).astype(np.float32)
    gids = m.upsert(ins)
    m.delete(np.arange(10))
    m.compact()  # extend
    live_mat = np.concatenate([data[10:], ins])
    live_g = np.concatenate([np.arange(10, len(data)), np.asarray(gids)])
    want = bf_gids(live_mat, live_g, queries, 10)
    _, got = m.search(queries, 10)
    got = np.asarray(got)
    oracle = ivf_pq.build(params, jnp.asarray(live_mat))
    _, o_pos = ivf_pq.search(sp, oracle, jnp.asarray(queries), 10)
    o_pos = np.asarray(o_pos)
    o_got = np.where(o_pos >= 0, live_g[np.clip(o_pos, 0, None)], -1)

    def rec(ids):
        return np.mean([len(set(a) & set(b)) / 10 for a, b in zip(ids, want)])

    assert abs(rec(got) - rec(o_got)) <= 0.1  # same quantized regime


def test_cagra_rebuild_compaction(data, queries, rng):
    """CAGRA has no extend: compaction rebuilds from the retained rows
    (auto-recovered from the sealed dataset), reclaiming tombstones."""
    idx = cagra.build(cagra.IndexParams(seed=0), jnp.asarray(data))
    m = stream.MutableIndex(idx, search_params=cagra.SearchParams(itopk_size=32),
                            delta_capacity=32)
    assert m.can_rebuild  # store auto-recovered from the sealed dataset
    with pytest.raises(RaftError, match="rebuild"):
        m.compact(mode="extend")
    g = m.upsert(queries[0:1] + 1e-3)
    _, i0 = m.search(queries, 5)
    nn1 = int(np.asarray(i0)[1, 0])
    m.delete([nn1])
    rep = m.compact()
    assert rep["mode"] == "rebuild" and m.stats()["sealed_dead"] == 0
    _, i1 = m.search(queries, 5)
    assert int(np.asarray(i1)[0, 0]) == int(g[0])
    assert nn1 not in np.asarray(i1)[1]


# -- serialization ------------------------------------------------------------

def test_serialize_roundtrip_mutable_state(data, queries, rng, tmp_path):
    """The FULL mutable state — sealed + live delta + tombstones + id map —
    round-trips; the loaded index searches identically and keeps churning
    (delete/upsert/compact all work on the restored state)."""
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, seed=0),
                         jnp.asarray(data))
    m = stream.MutableIndex(idx, search_params=ivf_flat.SearchParams(n_probes=8),
                            delta_capacity=32, dataset=data)
    gids = m.upsert(rng.standard_normal((5, 16)).astype(np.float32))
    m.delete([4, 5, int(gids[2])])
    m.compact()
    g2 = m.upsert(rng.standard_normal((3, 16)).astype(np.float32))
    m.delete([11, int(g2[0])])

    p = str(tmp_path / "m.stream")
    stream.save(m, p)
    m2 = stream.load(p, search_params=ivf_flat.SearchParams(n_probes=8))
    assert m2.size == m.size and m2.kind == "ivf_flat"
    # epoch/age are in-process counters (compaction count, clock base) and
    # restart with the new process; everything structural must match
    sa, sb = m.stats(), m2.stats()
    for key in ("live", "sealed_rows", "sealed_dead", "tombstone_ratio",
                "delta_rows", "delta_fill", "delta_bucket"):
        assert sa[key] == sb[key], key
    da, ia = m.search(queries, 10)
    db, ib = m2.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    np.testing.assert_allclose(np.asarray(da), np.asarray(db), rtol=1e-6)
    # fresh ids continue where the saved state left off; churn keeps working
    g3 = m2.upsert(queries[0:1] + 1e-3)
    assert int(g3[0]) == m._next_id
    _, i3 = m2.search(queries, 5)
    assert int(np.asarray(i3)[0, 0]) == int(g3[0])
    m2.compact()


def test_stream_file_rejects_other_tags(data, tmp_path):
    m = wrap_bf(data)
    p = str(tmp_path / "m.stream")
    stream.save(m, p)
    with pytest.raises(RaftError, match="not an ivf_flat"):
        ivf_flat.load(p)


# -- byte dtypes --------------------------------------------------------------

def test_byte_mutable_index(rng):
    """int8 sealed + int8 delta: the byte contract holds through the
    mutable layer (byte rows required, float rows refused), and the delta
    scan rides the exact byte kNN path."""
    xb = rng.integers(-128, 128, (200, 16), dtype=np.int8)
    idx = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=4, list_dtype="int8", seed=0), xb)
    m = stream.MutableIndex(idx, search_params=ivf_flat.SearchParams(n_probes=16),
                            delta_capacity=16, retain_vectors=False)
    assert m.query_dtype == "int8"
    with pytest.raises(RaftError, match="int8"):
        m.upsert(np.zeros((1, 16), np.float32))
    q = xb[:3]
    g = m.upsert(q[0:1])  # exact duplicate of query 0
    _, ids = m.search(q, 3)
    got = set(np.asarray(ids)[0].tolist())
    assert int(g[0]) in got and 0 in got  # both zero-distance copies win
    m.compact()  # extend path takes byte rows in the original dtype
    _, ids2 = m.search(q, 3)
    assert int(g[0]) in set(np.asarray(ids2)[0].tolist())


# -- compactor watermarks (injected clock) ------------------------------------

def test_compactor_delta_fill_watermark(data):
    clock = FakeClock()
    m = wrap_bf(data, delta_capacity=16, clock=clock)
    comp = stream.Compactor(
        m, policy=stream.CompactionPolicy(delta_fill=0.5,
                                          tombstone_ratio=None), clock=clock)
    assert comp.due() is None and comp.run_once() is None
    m.upsert(data[:8] + 0.5)  # fill 0.5
    assert comp.due() == "delta_fill"
    rep = comp.run_once()
    assert rep["trigger"] == "delta_fill" and rep["folded"] == 8
    assert comp.due() is None
    assert comp.last_report is rep


def test_compactor_age_watermark(data):
    clock = FakeClock()
    m = wrap_bf(data, delta_capacity=64, clock=clock)
    comp = stream.Compactor(
        m, policy=stream.CompactionPolicy(delta_fill=None,
                                          tombstone_ratio=None,
                                          max_age_s=5.0), clock=clock)
    assert comp.due() is None  # empty delta has no age
    m.upsert(data[:1] + 0.5)
    clock.advance(4.9)
    assert comp.due() is None
    clock.advance(0.2)
    assert comp.due() == "age"
    # a Compactor WITHOUT an explicit clock inherits the mutable's — two
    # different time bases would silently disarm max_age_s
    comp2 = stream.Compactor(
        m, policy=stream.CompactionPolicy(delta_fill=None,
                                          tombstone_ratio=None,
                                          max_age_s=5.0))
    assert comp2.due() == "age"
    rep = comp.run_once()
    assert rep["trigger"] == "age" and m.stats()["delta_rows"] == 0
    assert comp.due() is None  # the fold reset the age base


def test_compactor_tombstone_watermark_rebuilds(data):
    clock = FakeClock()
    m = wrap_bf(data, delta_capacity=16, clock=clock)
    comp = stream.Compactor(
        m, policy=stream.CompactionPolicy(delta_fill=None,
                                          tombstone_ratio=0.25), clock=clock)
    m.delete(np.arange(len(data) // 4 + 1))
    assert comp.due() == "tombstone_ratio"
    rep = comp.run_once()
    assert rep["mode"] == "rebuild" and rep["reclaimed"] == len(data) // 4 + 1
    assert m.stats()["sealed_dead"] == 0 and comp.due() is None


def test_compactor_forced_run(data):
    clock = FakeClock()
    m = wrap_bf(data, delta_capacity=16, clock=clock)
    m.upsert(data[:2] + 0.5)
    comp = stream.Compactor(
        m, policy=stream.CompactionPolicy(delta_fill=None,
                                          tombstone_ratio=None), clock=clock)
    assert comp.due() is None
    rep = comp.run_once(force=True)
    assert rep["trigger"] == "forced" and rep["folded"] == 2


def test_compactor_background_thread_liveness(data):
    """Liveness of the real poll loop: a due watermark is picked up without
    any run_once() call. Bounded by a poll deadline, not a timed sleep."""
    import time as _time

    m = wrap_bf(data, delta_capacity=16)
    comp = stream.Compactor(
        m, policy=stream.CompactionPolicy(delta_fill=0.5),
        poll_interval_s=0.01).start()
    try:
        m.upsert(data[:8] + 0.5)
        deadline = _time.monotonic() + 30.0
        while m.stats()["epoch"] == 0 and _time.monotonic() < deadline:
            _time.sleep(0.01)
        assert m.stats()["epoch"] >= 1, "background compactor never fired"
    finally:
        comp.close()


# -- serve integration --------------------------------------------------------

def test_service_write_path_read_your_writes(data, queries):
    clock = FakeClock()
    m = wrap_bf(data, delta_capacity=16, clock=clock)
    svc = SearchService(max_batch=4, clock=clock, start_workers=False)
    svc.publish("m", m, k=5)
    g = svc.upsert("m", queries[0:1] + 1e-3)
    fut = svc.submit("m", queries[:1], 5)
    clock.advance(1.0)
    assert svc.pump() == 1
    _, ids = fut.result(timeout=0)
    assert int(np.asarray(ids)[0, 0]) == int(g[0])  # read-your-writes
    assert svc.delete("m", g) == 1
    fut = svc.submit("m", queries[:1], 5)
    clock.advance(1.0)
    svc.pump()
    assert int(g[0]) not in np.asarray(fut.result(timeout=0)[1])[0]
    # taxonomy: non-mutable names have no write path; closed service fails
    bf2 = brute_force.BruteForce().build(jnp.asarray(data))
    svc.publish("frozen", bf2, k=5, warm=False)
    with pytest.raises(RaftError, match="not a mutable"):
        svc.upsert("frozen", queries[:1])
    svc.shutdown()
    with pytest.raises(ServiceClosedError):
        svc.upsert("m", queries[:1])


def test_republish_plain_index_closes_write_path(data, queries):
    """Republishing a NON-mutable index under a formerly-mutable name must
    close the write path — otherwise upserts would route to an index nobody
    serves (silently lost writes). A hook republish (what the compactor
    publishes after a swap) keeps it open."""
    clock = FakeClock()
    m = wrap_bf(data, delta_capacity=16, clock=clock)
    svc = SearchService(max_batch=4, clock=clock, start_workers=False)
    svc.publish("m", m, k=5)
    svc.upsert("m", queries[:1])
    svc.publish("m", m.searcher(), k=5)  # compactor-style hook republish
    svc.upsert("m", queries[1:2])  # write path survives (marked hook)
    bf2 = brute_force.BruteForce().build(jnp.asarray(data))
    # an UNMARKED bare hook takes the name: writes must stop routing to the
    # orphaned mutable (they would vanish — nobody serves it)
    svc.publish("m", brute_force.batched_searcher(bf2), k=5, warm=False)
    with pytest.raises(RaftError, match="not a mutable"):
        svc.upsert("m", queries[:1])
    svc.publish("m", m, k=5, warm=False)  # mutable republish reopens it
    svc.upsert("m", queries[:1])
    svc.publish("m", bf2, k=5, warm=False)  # plain index closes it again
    with pytest.raises(RaftError, match="not a mutable"):
        svc.upsert("m", queries[:1])
    svc.shutdown()


def test_load_rearms_age_watermark(data, tmp_path):
    """A restored non-empty delta has lost its write timestamps; load must
    re-base the age from load time so max_age_s still fires."""
    clock = FakeClock()
    m = wrap_bf(data, delta_capacity=16, clock=clock)
    m.upsert(data[:2] + 0.5)
    p = str(tmp_path / "m.stream")
    stream.save(m, p)
    clock2 = FakeClock()
    m2 = stream.load(p, clock=clock2)
    comp = stream.Compactor(
        m2, policy=stream.CompactionPolicy(delta_fill=None,
                                           tombstone_ratio=None,
                                           max_age_s=5.0), clock=clock2)
    assert comp.due() is None
    clock2.advance(5.1)
    assert comp.due() == "age"
    assert comp.run_once()["folded"] == 2


def test_service_delta_full_is_overload(data):
    clock = FakeClock()
    m = wrap_bf(data, delta_capacity=8, clock=clock)
    svc = SearchService(max_batch=4, clock=clock, start_workers=False)
    svc.publish("m", m, k=5)
    svc.upsert("m", data[:8] + 0.5)
    with pytest.raises(OverloadedError):
        svc.upsert("m", data[:1])
    svc.shutdown()


def test_publish_mutable_refuses_search_params(data):
    m = wrap_bf(data)
    reg = IndexRegistry(buckets=(1,))
    with pytest.raises(RaftError, match="wrap time"):
        reg.publish("m", m, search_params=object(), warm=False)


def test_registry_lease_pins_pre_compaction_epoch(data, queries):
    """The hot-swap contract: a lease taken before a compaction swap keeps
    serving the pinned (frozen) pre-compaction epoch; the published
    successor serves the folded state."""
    m = wrap_bf(data, delta_capacity=16)
    reg = IndexRegistry(buckets=(4,))
    reg.publish("m", m, k=5)
    g = m.upsert(queries[0:1] + 1e-3)
    with reg.lease("m") as v_old:
        m.compact()
        reg.publish("m", m.searcher(), k=5)
        # the leased (old-epoch) searcher still works mid-swap, serving the
        # frozen pre-compaction view — the upsert is in its delta
        _, ids = v_old.searcher(jnp.asarray(queries[:4]), 5)
        assert int(np.asarray(ids)[0, 0]) == int(g[0])
    assert reg.live_versions("m") == (2,)
    with reg.lease("m") as v_new:
        _, ids = v_new.searcher(jnp.asarray(queries[:4]), 5)
        assert int(np.asarray(ids)[0, 0]) == int(g[0])  # folded, still live


def test_compaction_swap_under_load_loses_nothing(data, queries):
    """The acceptance-critical property: compaction swaps landing mid-load
    (writes + reads in flight) fail zero requests and lose zero writes."""
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, seed=0),
                         jnp.asarray(data))
    m = stream.MutableIndex(idx, search_params=ivf_flat.SearchParams(n_probes=8),
                            delta_capacity=64, retain_vectors=False,
                            name="load")
    svc = SearchService(max_batch=8, max_wait_us=200.0, max_queue_rows=512)
    svc.publish("load", m, k=5)
    m.warm(svc.buckets, ks=(5,))
    comp = stream.Compactor(
        m, publisher=svc, name="load", ks=(5,),
        policy=stream.CompactionPolicy(delta_fill=0.25, tombstone_ratio=None))
    errors, done = [], []
    lock = threading.Lock()

    def reader(tid):
        for j in range(30):
            try:
                _, ids = svc.search("load", data[(tid * 31 + j) % 200:
                                                 (tid * 31 + j) % 200 + 1], 5)
                with lock:
                    done.append(int(np.asarray(ids)[0, 0]))
            except Exception as e:  # any loss is a failure
                with lock:
                    errors.append(repr(e))

    threads = [threading.Thread(target=reader, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    # writer + compactor on this thread: two full fold cycles mid-load
    swaps = 0
    for step in range(40):
        svc.upsert("load", data[step % 100:step % 100 + 2] + 0.5)
        if comp.due():
            comp.run_once()
            swaps += 1
    for t in threads:
        t.join(60)
        assert not t.is_alive(), "reader wedged"
    svc.shutdown()
    assert errors == []
    assert len(done) == 120
    assert swaps >= 2 and m.stats()["epoch"] == swaps


def test_warm_delta_ladder_keeps_hot_path_compile_free(data, queries):
    """The shape discipline the delta bucket ladder exists for: after
    warm(), searches at EVERY delta fill level (and the writes between
    them) trigger zero compiles — asserted via obs compile attribution."""
    import jax

    from raft_tpu.obs import compile as obs_compile

    clock = FakeClock()
    m = wrap_bf(data, delta_capacity=32, clock=clock)
    svc = SearchService(max_batch=4, clock=clock, start_workers=False)
    svc.publish("m", m, k=5)
    rep = m.warm(svc.buckets, ks=(5,))
    assert sorted(rep[5]) == [1, 2, 4]
    with obs_compile.attribution() as rec:
        for step in range(33):  # walks the delta through buckets 8..32
            if step:
                m.upsert(data[step:step + 1] + 0.5)
            fut = svc.submit("m", queries[:2], 5)
            clock.advance(1.0)
            svc.pump()
            fut.result(timeout=0)
    assert rec.compile_s == 0.0 and rec.programs == 0


def test_rebuild_uses_injected_builder(data, queries):
    """builder= (ISSUE 6) replaces the default module.build in REBUILD
    compaction — the hook the sharded CAGRA rebuild rides
    (parallel.cagra.merged_builder). For IVF kinds a builder also satisfies
    can_rebuild without index_params."""
    from raft_tpu.neighbors import ivf_flat

    calls = []

    def builder(rows, res=None):
        calls.append(rows.shape[0])
        return ivf_flat.build(ivf_flat.IndexParams(n_lists=4, seed=0), rows)

    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=4, seed=0),
                         jnp.asarray(data))
    m = stream.MutableIndex(idx, search_params=ivf_flat.SearchParams(n_probes=4),
                            delta_capacity=32, dataset=data, builder=builder)
    assert m.can_rebuild  # builder stands in for index_params
    gids = m.upsert(queries[0:1] + 1e-3)
    m.delete([0, 1])
    rep = m.compact(mode="rebuild")
    assert rep["mode"] == "rebuild" and rep["reclaimed"] == 2
    assert calls == [len(data) - 2 + 1]  # the live-row matrix, once
    # the rebuilt sealed serves: parity vs ground truth over the live rows
    live_mat = np.concatenate([data[2:], np.asarray(queries[0:1] + 1e-3)])
    live_gids = np.concatenate([np.arange(2, len(data)), gids])
    want = bf_gids(live_mat, live_gids, queries, 5)
    _, got = m.search(queries, 5)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_builder_kind_mismatch_rejected(data):
    """A builder returning a different index kind is a configuration error,
    caught at the swap — not a silently corrupted mutable index."""
    from raft_tpu.neighbors import ivf_flat

    def wrong_builder(rows, res=None):
        return brute_force.BruteForce().build(jnp.asarray(rows))

    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=4, seed=0),
                         jnp.asarray(data))
    m = stream.MutableIndex(idx, search_params=ivf_flat.SearchParams(n_probes=4),
                            delta_capacity=32, dataset=data,
                            builder=wrong_builder)
    m.upsert(data[:1] + 1e-3)
    with pytest.raises(RaftError, match="builder returned"):
        m.compact(mode="rebuild")


# -- list-layout tombstones (IVF kinds) --------------------------------------

def _id_space(m, fn):
    """Run ``fn`` with the live epoch's sealed side put back on the id-space
    path — the sealed index itself filtered by ``BitsetFilter`` over its
    slot bitset — so the list-layout answers can be compared against it."""
    from raft_tpu.neighbors import sample_filter

    st = m._state
    saved = st.sealed_view
    st.sealed_view = (st.sealed,
                      sample_filter.BitsetFilter(st.sealed_alive.copy()).mask)
    try:
        return fn()
    finally:
        st.sealed_view = saved


def _churn(m, data, rng):
    """Deletes and upserts of varying batch sizes, kill-then-reveal
    included (existing sealed ids upserted into the delta); returns the
    ids that must never surface."""
    gids = m.upsert(rng.standard_normal((6, 16)).astype(np.float32))
    assert m.delete([3, 17, 44, int(gids[1])]) == 4
    m.upsert(data[[5, 60]] + 50.0, ids=[5, 60])  # kill-then-reveal, far off
    assert m.delete([7]) == 1
    assert m.delete(np.arange(100, 107)) == 7
    return {3, 17, 44, int(gids[1]), 7, *range(100, 107)}


def _assert_same(got, want):
    """Bit for bit on distances (inf included), id for id on ids."""
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


_LIST_KINDS = {
    "ivf_pq.tiled": (ivf_pq, dict(n_lists=8, pq_dim=8, seed=0),
                     dict(n_probes=4)),
    "ivf_pq.funnel": (ivf_pq, dict(n_lists=8, pq_dim=8, seed=0,
                                   fast_scan="1bit"),
                      dict(n_probes=4, funnel_widen=4)),
    "ivf_flat": (ivf_flat, dict(n_lists=8, seed=0), dict(n_probes=4)),
}


@pytest.mark.parametrize("case", sorted(_LIST_KINDS))
def test_list_tombstones_match_id_space_filter(case, data, rng, tmp_path):
    """IVF kinds carry tombstones as -1 in a live ``list_ids`` view. Their
    answers equal the sealed index filtered through ``BitsetFilter`` over
    the same bitset: after a churn script, after an extend compaction and
    after save/load, for search and (IVF-PQ) search_refined."""
    mod, ip, sp = _LIST_KINDS[case]
    idx = mod.build(mod.IndexParams(**ip), jnp.asarray(data))
    m = stream.MutableIndex(idx, search_params=mod.SearchParams(**sp),
                            delta_capacity=32, dataset=data)
    dead = _churn(m, data, rng)
    # queries on top of the dead rows: an unmasked tombstone would win
    q = data[sorted(dead)[:6]] + 1e-3

    def check(mi):
        calls = [lambda: mi.search(q, 10)]
        if mod is ivf_pq:
            calls.append(lambda: mi.search_refined(q, 10, 4))
        for call in calls:
            got = call()
            _assert_same(got, _id_space(mi, call))
            assert not dead & set(np.asarray(got[1]).ravel().tolist())
        return calls[0]()

    check(m)
    assert m.compact(mode="extend")["mode"] == "extend"
    # 11 sealed deletes + 2 sealed copies upserted away stay masked
    assert m.stats()["sealed_dead"] == 13
    check(m)
    assert m.delete([8, 9]) == 2  # through the successor's layout
    dead |= {8, 9}
    check(m)
    p = str(tmp_path / "m.stream")
    stream.save(m, p)
    m2 = stream.load(p, search_params=mod.SearchParams(**sp))
    _assert_same(check(m2), m.search(q, 10))
    m2.delete([10])
    dead.add(10)
    check(m2)


def _sealed_filter_counts(name):
    from raft_tpu.stream import mutable

    out = {}
    for key, v in mutable._c_sealed_filter().series().items():
        labels = dict(key)
        if labels["name"] == name:
            out[labels["layout"]] = v
    return out


@pytest.mark.parametrize("case", sorted(_LIST_KINDS))
def test_list_layout_scan_gathers_no_keep_mask(case, data, rng, monkeypatch):
    """An IVF sealed scan with tombstones runs no id-space filter: the
    per-candidate gather (``apply_id_filter``) is never traced, and every
    sealed scan counts under ``layout="list"``."""
    import jax

    from raft_tpu.neighbors import sample_filter

    mod, ip, sp = _LIST_KINDS[case]
    idx = mod.build(mod.IndexParams(**ip), jnp.asarray(data))
    name = f"list-{case}"
    m = stream.MutableIndex(idx, search_params=mod.SearchParams(**sp),
                            delta_capacity=32, dataset=data, name=name)
    _churn(m, data, rng)

    def no_gather(*a, **kw):
        raise AssertionError("id-space keep-mask gathered in a list scan")

    monkeypatch.setattr(sample_filter, "apply_id_filter", no_gather)
    jax.clear_caches()  # trace afresh, so a gather would be seen
    scans = 0
    for b in (1, 5):
        jax.block_until_ready(m.search(data[:b] + 1e-3, 10))
        scans += 1
        if mod is ivf_pq:
            jax.block_until_ready(m.search_refined(data[:b] + 1e-3, 10, 4))
            scans += 1
    assert _sealed_filter_counts(name) == {"list": scans}


def test_row_offset_kinds_keep_id_space_filter(data, queries):
    """Brute force and CAGRA store row offsets, not lists: their sealed
    scans keep the keep-mask and count under ``layout="ids"``."""
    for name, idx, sp in (
            ("ids-bf", brute_force.BruteForce().build(jnp.asarray(data)),
             None),
            ("ids-cagra", cagra.build(cagra.IndexParams(seed=0),
                                      jnp.asarray(data)),
             cagra.SearchParams(itopk_size=32))):
        m = stream.MutableIndex(idx, search_params=sp, delta_capacity=16,
                                name=name)
        m.delete([0, 1])
        m.search(queries, 5)
        m.search(queries, 5)
        assert _sealed_filter_counts(name) == {"ids": 2}
        assert m._state.sealed_view[1] is not None


def test_list_layout_writes_compile_nothing_after_warm(data, rng):
    """Deletes and upserts of varying batch sizes only re-publish fixed
    shapes: after warm() and one served flush per bucket, a churning
    IVF-PQ index (plain and refined hooks) compiles nothing."""
    import jax

    from raft_tpu.obs import compile as obs_compile

    clock = FakeClock()
    idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=8, pq_dim=8, seed=0),
                       jnp.asarray(data))
    m = stream.MutableIndex(idx, search_params=ivf_pq.SearchParams(n_probes=4),
                            delta_capacity=32, dataset=data, clock=clock)
    svc = SearchService(max_batch=4, clock=clock, start_workers=False)
    svc.publish("m", m, k=5)
    m.warm(svc.buckets, ks=(5,))
    m.warm_refined(svc.buckets, ks=(5,), refine_ratio=4)
    svc.publish("r", m.refined_searcher(4), k=5)
    with obs_compile.attribution() as rec:
        gid = 1000
        for step, (nd, nu) in enumerate(((1, 2), (3, 1), (7, 5), (2, 3))):
            m.delete(np.arange(10 * step, 10 * step + nd))
            # upserts of fresh rows and of live sealed ids (kill-then-reveal)
            m.upsert(rng.standard_normal((nu, 16)).astype(np.float32),
                     ids=np.arange(gid, gid + nu))
            gid += nu
            m.upsert(data[[200 + step]] + 0.5, ids=[200 + step])
            for name in ("m", "r"):
                fut = svc.submit(name, data[:3] + 1e-3, 5)
                clock.advance(1.0)
                svc.pump()
                jax.block_until_ready(fut.result(timeout=0))
    svc.shutdown()
    assert rec.compile_s == 0.0 and rec.programs == 0


def test_list_layout_upsert_never_shows_both_copies(data):
    """Lock-free readers racing kill-then-reveal upserts on a list layout:
    the sealed view is published before the delta, and read after it, so
    no result row ever holds both copies of an upserted id."""
    import os
    import sys

    idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=8, pq_dim=8, seed=0),
                       jnp.asarray(data))
    m = stream.MutableIndex(idx, search_params=ivf_pq.SearchParams(n_probes=64),
                            delta_capacity=64)
    q = data[:48]
    m.search(q, 10)  # compile before the race
    stop = threading.Event()
    bad, done = [], []

    def reader():
        while not stop.is_set():
            ids = np.asarray(m.search(q, 10)[1])
            for row in ids:
                live = row[row >= 0]
                if live.size != np.unique(live).size:
                    bad.append(row.tolist())
            done.append(1)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=reader)
               for _ in range((os.cpu_count() or 4) + 1)]
    try:
        for t in threads:
            t.start()
        for j in range(48):  # each moves a sealed row, unchanged, to the delta
            m.upsert(data[[j]], ids=[j])
    finally:
        stop.set()
        for t in threads:
            t.join(60)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads), "reader wedged"
    assert done and bad == []
    assert m.stats()["sealed_dead"] == 48

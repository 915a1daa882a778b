"""Sharded serving tier tests (tier-1 ``stream`` marker).

The acceptance spine is the parity suite: a 1-shard ShardedMutableIndex
must be BIT-EQUAL to a plain MutableIndex under the same
upsert/delete/compact script (the sharded composition may not change a
single returned id), multi-shard search must match a fresh build over
exactly the live rows, and a compaction swap on ONE shard under live load
must lose nothing. Deterministic by construction: injected clocks,
compactors driven via ``run_once()``/``compact()``, no wall-clock sleeps
in assertions.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import stream
from raft_tpu.core.errors import RaftError
from raft_tpu.neighbors import brute_force, ivf_flat
from raft_tpu.serve import SearchService

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
    return rng.standard_normal((260, 16)).astype(np.float32)


@pytest.fixture
def queries(rng):
    return rng.standard_normal((5, 16)).astype(np.float32)


def bf_build(x):
    return brute_force.BruteForce().build(jnp.asarray(x))


def sharded_bf(data, n_shards, **kw):
    return stream.ShardedMutableIndex(data, n_shards=n_shards,
                                      build=bf_build, **kw)


def bf_gids(live_mat, live_gids, queries, k):
    """Ground truth over an explicit live-row set, mapped to global ids."""
    _, pos = brute_force.knn(jnp.asarray(live_mat), jnp.asarray(queries), k)
    pos = np.asarray(pos)
    return np.where(pos >= 0, np.asarray(live_gids)[np.clip(pos, 0, None)], -1)


# -- routing ------------------------------------------------------------------

def test_shard_of_stable_and_balanced():
    ids = np.arange(100_000)
    s1 = stream.shard_of(ids, 8)
    s2 = stream.shard_of(ids, 8)
    np.testing.assert_array_equal(s1, s2)  # stable across calls/processes
    counts = np.bincount(s1, minlength=8)
    # an avalanche mix over sequential ids stays near-uniform
    assert counts.min() > 0.8 * counts.mean(), counts
    assert counts.max() < 1.2 * counts.mean(), counts
    assert set(np.unique(stream.shard_of(ids[:100], 3))) <= {0, 1, 2}


def test_constructor_validations(data):
    with pytest.raises(RaftError, match="fewer shards"):
        # 4 rows over 16 shards: some shard must come up empty
        sharded_bf(data[:4], 16)
    with pytest.raises(RaftError, match="n_shards"):
        sharded_bf(data, 0)
    with pytest.raises(RaftError, match="devices"):
        sharded_bf(data, 4, devices=jax.devices()[:2])


# -- the parity spine ---------------------------------------------------------

def test_one_shard_parity_bitequal(data, queries, rng):
    """The satellite acceptance bit: the SAME upsert/delete/compact script
    on a 1-shard ShardedMutableIndex and a plain MutableIndex returns
    bit-equal ids (and matching distances) at every step — the sharded
    composition (scan halves + padded one-dispatch merge) may not change
    a single result."""
    clock = FakeClock()
    plain = stream.MutableIndex(bf_build(data), delta_capacity=64,
                                clock=clock)
    shard = sharded_bf(data, 1, delta_capacity=64, clock=clock)

    def check():
        dp, ip = plain.search(queries, 10)
        ds, is_ = shard.search(queries, 10)
        np.testing.assert_array_equal(np.asarray(ip), np.asarray(is_))
        np.testing.assert_allclose(np.asarray(dp), np.asarray(ds),
                                   rtol=1e-6)

    check()
    ins = rng.standard_normal((12, 16)).astype(np.float32)
    g1 = plain.upsert(ins)
    g2 = shard.upsert(ins)
    np.testing.assert_array_equal(g1, g2)  # fresh-id assignment matches
    check()
    for m in (plain, shard):
        m.delete([3, 17, int(g1[4]), 9999])
    check()
    for m in (plain, shard):
        rep = m.compact(mode="rebuild")
        # the two dead SEALED slots reclaim; the dead delta row just
        # doesn't fold (11 of 12 inserted rows were still alive)
        assert rep["reclaimed"] == 2 and rep["folded"] == 11
    check()
    g3, g4 = plain.upsert(ins[:2] + 1.0), shard.upsert(ins[:2] + 1.0)
    np.testing.assert_array_equal(g3, g4)
    check()
    assert plain.size == shard.size


def test_multi_shard_search_matches_fresh_build(data, queries, rng):
    """4 hash-routed shards (uneven sizes by construction), upserts and
    deletes: scatter-gather results equal a fresh brute-force build over
    exactly the live rows — identical global ids, matching distances."""
    shard = sharded_bf(data, 4, delta_capacity=64)
    sizes = [sh._state.id_map.shape[0] for sh in shard.shards]
    assert sum(sizes) == len(data) and len(set(sizes)) > 1, sizes
    ins = rng.standard_normal((20, 16)).astype(np.float32)
    gids = shard.upsert(ins)
    dele = [3, 17, 44, 101, int(gids[4])]
    assert shard.delete(dele) == 5
    live_mask = np.ones(len(data), bool)
    live_mask[[3, 17, 44, 101]] = False
    ins_mask = np.ones(20, bool)
    ins_mask[4] = False
    live_mat = np.concatenate([data[live_mask], ins[ins_mask]])
    live_g = np.concatenate([np.nonzero(live_mask)[0],
                             np.asarray(gids)[ins_mask]])
    want = bf_gids(live_mat, live_g, queries, 10)
    d, got = shard.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got), want)
    dref, _ = brute_force.knn(jnp.asarray(live_mat), jnp.asarray(queries), 10)
    np.testing.assert_allclose(np.asarray(d), np.asarray(dref), rtol=1e-5)
    assert shard.size == len(live_g)


def test_uneven_tiny_corpus_underfill_sentinels(rng):
    """A corpus smaller than k x shards still reports the shared
    underfill contract: live rows first, then id -1 at +inf."""
    data = rng.standard_normal((24, 8)).astype(np.float32)
    q = rng.standard_normal((3, 8)).astype(np.float32)
    shard = sharded_bf(data, 3, delta_capacity=8)
    shard.delete(np.arange(20))  # 4 live rows remain
    d, i = shard.search(q, 10)
    d, i = np.asarray(d), np.asarray(i)
    assert (i[:, 4:] == -1).all() and np.isinf(d[:, 4:]).all()
    assert (i[:, :4] >= 0).all() and np.isfinite(d[:, :4]).all()


def test_exact_search_matches_brute_force(data, queries, rng):
    shard = sharded_bf(data, 4, delta_capacity=32)
    gids = shard.upsert(rng.standard_normal((8, 16)).astype(np.float32))
    shard.delete([0, 1, int(gids[0])])
    # build the live set from the shards' own bookkeeping
    mats, gs = [], []
    for sh in shard.shards:
        st = sh._state
        alive = np.nonzero(st.sealed_alive)[0]
        mats.append(st.store[alive])
        gs.append(st.id_map[alive])
        dal = np.nonzero(st.delta_alive[:st.delta_n])[0]
        mats.append(st.delta[dal])
        gs.append(st.delta_ids[dal])
    live_mat = np.concatenate([m for m in mats if len(m)])
    live_g = np.concatenate([g for g in gs if len(g)])
    want = bf_gids(live_mat, live_g, queries, 10)
    _, got = shard.exact_search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got), want)


# -- writes -------------------------------------------------------------------

def test_upsert_routes_by_hash_and_read_your_writes(data, queries):
    shard = sharded_bf(data, 4, delta_capacity=32)
    g = shard.upsert(queries[0:1] + 1e-3)
    home = int(stream.shard_of(g, 4)[0])
    assert shard.shards[home].stats()["delta_rows"] == 1
    assert all(sh.stats()["delta_rows"] == 0
               for s, sh in enumerate(shard.shards) if s != home)
    _, ids = shard.search(queries, 5)
    assert int(np.asarray(ids)[0, 0]) == int(g[0])
    # upsert under the same id replaces the old copy on its home shard
    far = (queries[0:1] * 0.0) + 100.0
    shard.upsert(far, ids=[int(g[0])])
    _, ids2 = shard.search(queries, 5)
    assert int(g[0]) != int(np.asarray(ids2)[0, 0])
    assert shard.size == len(data) + 1  # one live copy per id


def test_upsert_atomic_across_shards(data):
    """Whole-or-nothing admission: a batch that would overflow ONE home
    shard is refused before ANY row lands on any shard."""
    shard = sharded_bf(data, 2, delta_capacity=8)
    # find ids homing to shard 0 / shard 1
    cand = np.arange(10_000, 30_000)
    homes = stream.shard_of(cand, 2)
    to0, to1 = cand[homes == 0], cand[homes == 1]
    shard.upsert(np.zeros((7, 16), np.float32) + 0.5, ids=to0[:7])
    before = shard.stats()["delta_rows"]
    mixed = np.concatenate([to0[7:9], to1[:3]])  # overflows shard 0
    with pytest.raises(stream.DeltaFullError, match="shard 0"):
        shard.upsert(np.ones((5, 16), np.float32), ids=mixed)
    assert shard.stats()["delta_rows"] == before  # nothing landed anywhere
    shard.upsert(np.ones((3, 16), np.float32), ids=to1[:3])  # still admits


# -- staggered compaction -----------------------------------------------------

def test_staggered_compaction_folds_one_shard_at_a_time(data, queries, rng):
    clock = FakeClock()
    shard = sharded_bf(data, 4, delta_capacity=16, clock=clock)
    comp = stream.Compactor(
        shard, policy=stream.CompactionPolicy(delta_fill=0.5,
                                              tombstone_ratio=None),
        clock=clock)
    assert comp.due() is None
    ins = rng.standard_normal((40, 16)).astype(np.float32)
    gids = shard.upsert(ins)
    folded_shards = []
    while comp.due():
        rep = comp.run_once()
        assert rep["trigger"] == "delta_fill"
        # ONE shard folds per cycle; its siblings' epochs are untouched
        folded_shards.append(rep["shard"])
        assert rep["shard_epoch"] == 1
    assert len(folded_shards) >= 2  # the watermark staggers across shards
    assert len(set(folded_shards)) == len(folded_shards)  # distinct shards
    assert shard.stats()["epoch"] == len(folded_shards)
    # results unchanged by the folds
    live_g = np.concatenate([np.arange(len(data)), gids])
    live_mat = np.concatenate([data, ins])
    want = bf_gids(live_mat, live_g, queries, 10)
    _, got = shard.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_age_trigger_folds_the_stalest_shard_not_the_fullest(data):
    """An age trip must chase the shard holding the OLDEST delta: picking
    the fullest would fold busy shards forever while the quiet shard's
    stale write never seals (and a `while due(): run_once()` loop would
    livelock — due() stays tripped on the min age across shards)."""
    clock = FakeClock()
    shard = sharded_bf(data, 4, delta_capacity=16, clock=clock)
    comp = stream.Compactor(
        shard, policy=stream.CompactionPolicy(delta_fill=None,
                                              tombstone_ratio=None,
                                              max_age_s=5.0), clock=clock)
    cand = np.arange(10_000, 40_000)
    homes = stream.shard_of(cand, 4)
    quiet, busy = cand[homes == 1], cand[homes == 3]
    shard.upsert(np.zeros((1, 16), np.float32), ids=quiet[:1])  # t=0
    clock.advance(3.0)
    shard.upsert(np.ones((5, 16), np.float32), ids=busy[:5])  # fuller, young
    clock.advance(2.5)  # quiet shard is 5.5s stale, busy only 2.5s
    assert comp.due() == "age"
    rep = comp.run_once()
    assert rep["shard"] == 1 and rep["folded"] == 1, rep
    assert comp.due() is None  # the standing trip cleared — no livelock
    clock.advance(3.0)  # now the busy shard's write crosses the horizon
    assert comp.due() == "age"
    assert comp.run_once()["shard"] == 3


def test_tombstone_watermark_picks_dirtiest_shard(data):
    clock = FakeClock()
    shard = sharded_bf(data, 4, delta_capacity=16, clock=clock)
    # tombstone >25% of ONE shard's sealed rows
    victim = 2
    vic_ids = shard.shards[victim]._state.id_map
    shard.delete(vic_ids[:len(vic_ids) // 3 + 1])
    comp = stream.Compactor(
        shard, policy=stream.CompactionPolicy(delta_fill=None,
                                              tombstone_ratio=0.25),
        clock=clock)
    assert comp.due() == "tombstone_ratio"
    rep = comp.run_once()
    assert rep["shard"] == victim and rep["mode"] == "rebuild"
    assert rep["reclaimed"] == len(vic_ids) // 3 + 1
    assert comp.due() is None  # the other shards were never dirty


def test_swap_under_load_on_one_shard_loses_nothing(data, queries):
    """The acceptance-critical property scaled to the mesh: a compaction
    swap of ONE shard landing mid-load (reads + writes in flight on ALL
    shards) fails zero requests and loses zero writes."""
    shard = sharded_bf(data, 4, delta_capacity=64, name="load")
    svc = SearchService(max_batch=8, max_wait_us=200.0, max_queue_rows=512)
    svc.publish("load", shard, k=5)
    shard.warm(svc.buckets, ks=(5,))
    comp = stream.Compactor(
        shard, publisher=svc, name="load", ks=(5,),
        policy=stream.CompactionPolicy(delta_fill=0.125,
                                       tombstone_ratio=None))
    errors, done = [], []
    lock = threading.Lock()

    def reader(tid):
        for j in range(25):
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
    swaps = 0
    for step in range(30):
        svc.upsert("load", data[step % 100:step % 100 + 2] + 0.5)
        while comp.due():
            comp.run_once()
            swaps += 1
    for t in threads:
        t.join(60)
        assert not t.is_alive(), "reader wedged"
    svc.shutdown()
    assert errors == []
    assert len(done) == 100
    assert swaps >= 2 and shard.stats()["epoch"] == swaps
    # staggered: folds landed on more than one shard across the run
    assert len({sh.stats()["epoch"] for sh in shard.shards}) >= 1
    assert sum(sh.stats()["epoch"] for sh in shard.shards) == swaps


# -- device pinning + warm discipline ----------------------------------------

def test_device_pinning_places_shards_apart(data):
    devs = jax.devices()[:4]
    shard = sharded_bf(data, 4, devices=devs, delta_capacity=16)
    placed = [next(iter(sh._state.delta_view[0].devices()))
              for sh in shard.shards]
    assert placed == devs, placed
    # sealed side follows the pin too
    sealed = [next(iter(sh._state.sealed.dataset.devices()))
              for sh in shard.shards]
    assert sealed == devs, sealed
    # results come back mergeable regardless of the pins
    d, i = shard.search(data[:3], 5)
    assert np.asarray(i).shape == (3, 5)


def test_warm_ladder_keeps_sharded_hot_path_compile_free(data, queries):
    """The zero-cold-compile discipline across the mesh: after warm() +
    publish, searches at every per-shard delta fill level, the writes
    between them, and a STAGGERED mid-window shard fold + republish
    trigger zero compiles — asserted via obs compile attribution.
    Device-pinned, so placement is part of what the warm must cover."""
    from raft_tpu.obs import compile as obs_compile

    clock = FakeClock()
    devs = jax.devices()[:2]

    def run(name):
        shard = sharded_bf(data, 2, devices=devs, delta_capacity=16,
                           clock=clock, name=name)
        svc = SearchService(max_batch=4, clock=clock, start_workers=False)
        svc.publish(name, shard, k=5)
        shard.warm(svc.buckets, ks=(5,))
        comp = stream.Compactor(
            shard, publisher=svc, name=name, ks=(5,),
            policy=stream.CompactionPolicy(delta_fill=0.5,
                                           tombstone_ratio=None),
            clock=clock)
        for step in range(24):
            shard.upsert(data[step:step + 1] + 0.5, ids=[1000 + step])
            while comp.due():
                comp.run_once()
            fut = svc.submit(name, queries[:2], 5)
            clock.advance(1.0)
            svc.pump()
            fut.result(timeout=0)
        svc.shutdown()

    run("rehearsal")  # compiles the epoch program set
    with obs_compile.attribution() as rec:
        run("live")  # the same schedule must replay warm
    assert rec.compile_s == 0.0 and rec.programs == 0


# -- serve + obs integration --------------------------------------------------

def test_serve_publish_resolves_sharded_duck_typed(data, queries):
    clock = FakeClock()
    shard = sharded_bf(data, 3, delta_capacity=16, clock=clock)
    svc = SearchService(max_batch=4, clock=clock, start_workers=False)
    rep = svc.publish("mesh", shard, k=5)
    assert rep["version"] == 1
    g = svc.upsert("mesh", queries[0:1] + 1e-3)  # write path opened
    fut = svc.submit("mesh", queries[:1], 5)
    clock.advance(1.0)
    svc.pump()
    assert int(np.asarray(fut.result(timeout=0)[1])[0, 0]) == int(g[0])
    assert svc.delete("mesh", g) == 1
    # a compactor-style hook republish keeps the write path open
    svc.publish("mesh", shard.searcher(), k=5)
    svc.upsert("mesh", queries[1:2])
    with pytest.raises(RaftError, match="wrap time"):
        svc.publish("mesh2", shard, search_params=object(), warm=False)
    svc.shutdown()


def test_canary_oracle_covers_the_mesh(data, queries):
    """obs.quality.exact_oracle resolves a ShardedMutableIndex unchanged;
    for an exact sealed kind the canary's estimate over served results is
    exactly 1.0 (the served pipeline IS the oracle here)."""
    from raft_tpu.obs import quality
    from raft_tpu.serve import bucket_sizes

    clock = FakeClock()
    shard = sharded_bf(data, 3, delta_capacity=16, clock=clock)
    canary = quality.RecallCanary(
        quality.exact_oracle(shard), k=5, sample_rate=1.0,
        buckets=bucket_sizes(4), name="mesh")
    svc = SearchService(max_batch=4, clock=clock, start_workers=False,
                        canary=canary)
    svc.publish("mesh", shard, k=5)
    for lo in range(0, 12, 4):
        fut = svc.submit("mesh", data[lo:lo + 4], 5)
        clock.advance(1.0)
        svc.pump()
        fut.result(timeout=0)
    canary.drain()
    est = canary.estimate()
    assert est["reranked"] > 0
    assert est["recall"] == 1.0, est
    svc.shutdown()


def test_requestlog_per_shard_spans(data, queries):
    """A traced sharded search notes each shard's epoch under its
    ``stream/shard<i>/`` prefix; the stages themselves are device scopes:
    no enqueue-time stream wall is recorded, and the one cross-shard merge
    program carries ``stream.merge`` (around its ``select_k``)."""
    import re

    from raft_tpu.obs import requestlog
    from raft_tpu.stream import sharded

    shard = sharded_bf(data, 2, delta_capacity=16)
    with requestlog.collect() as c:
        shard.search(queries, 5)
    for s in range(2):
        assert c.notes[f"stream/shard{s}/stream_epoch"] == 0
    assert c.notes["stream_shards"] == 2
    assert c.spans == {}
    ds = tuple(jnp.zeros((3, 5), jnp.float32) for _ in range(4))
    ids = tuple(jnp.zeros((3, 5), jnp.int32) for _ in range(4))
    text = sharded._shard_jits()[1].lower(ds, ids, k=5, select_min=True) \
        .as_text(debug_info=True)
    names = {p for loc in re.findall(r'loc\("([^"]*)"', text)
             for p in loc.split("/")}
    assert {"stream.merge", "select_k"} <= names


def test_sharded_stats_and_gauges(data):
    from raft_tpu.obs import metrics

    shard = sharded_bf(data, 4, delta_capacity=16, name="gauges")
    shard.upsert(data[:3] + 0.5)
    st = shard.stats()
    assert st["shards"] == 4 and len(st["per_shard"]) == 4
    assert st["delta_rows"] == 3
    assert st["live"] == len(data) + 3
    # binding-shard semantics: aggregate fill is the max, not the mean
    assert st["delta_fill"] == max(p["delta_fill"] for p in st["per_shard"])
    snap = metrics.to_json()
    assert snap.get('raft_tpu_stream_shards{name="gauges"}') == 4
    # per-shard series report under name/shard<i>; aggregate under the name
    assert 'raft_tpu_stream_delta_rows{name="gauges"}' in snap
    assert any(k.startswith('raft_tpu_stream_delta_rows{name="gauges/shard')
               for k in snap), [k for k in snap if "gauges" in k]


def test_drift_store_interleaves_shards(data):
    shard = sharded_bf(data, 4, delta_capacity=16)
    store = shard._drift_store()
    assert store is not None and store.shape[1] == 16
    assert store.shape[0] == len(data)  # small corpus: everything rides
    none_store = sharded_bf(data, 2, delta_capacity=16,
                            retain_vectors=False)
    assert none_store._drift_store() is None


# -- byte dtypes --------------------------------------------------------------

def test_byte_sharded_index(rng):
    xb = rng.integers(-128, 128, (180, 16), dtype=np.int8)
    shard = stream.ShardedMutableIndex(
        xb, n_shards=2, delta_capacity=16,
        build=lambda x: ivf_flat.build(
            ivf_flat.IndexParams(n_lists=4, list_dtype="int8", seed=0), x),
        search_params=ivf_flat.SearchParams(n_probes=16))
    assert shard.query_dtype == "int8"
    with pytest.raises(RaftError, match="int8"):
        shard.upsert(np.zeros((1, 16), np.float32))
    q = xb[:3]
    g = shard.upsert(q[0:1])  # exact duplicate of query 0
    _, ids = shard.search(q, 3)
    assert int(g[0]) in set(np.asarray(ids)[0].tolist())

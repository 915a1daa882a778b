"""Elastic live resharding tests (tier-1 ``stream`` marker, ISSUE 13).

The acceptance spine: a power-of-two split/merge is an ONLINE topology
change — results before and after the flip are identical to a fresh build
over exactly the live rows (the split locality rule moves every id to a
deterministic successor, so nothing can be lost or duplicated), writes
landing mid-migration carry over at the atomic swap, a replica killed or
staled mid-split never fails a query, and a :class:`SimulatedCrash` at any
of the three reshard fault points recovers — manifest + per-shard WAL
replay — to a state id-for-id equal to an uncrashed twin. Deterministic by
construction: injected clocks, fault callbacks instead of timing races,
no wall-clock sleeps in assertions.
"""

import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import stream
from raft_tpu.core.errors import RaftError
from raft_tpu.neighbors import brute_force
from raft_tpu.serve import SearchService
from raft_tpu.testing import faults

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
    return rng.standard_normal((280, 16)).astype(np.float32)


@pytest.fixture
def queries(rng):
    return rng.standard_normal((5, 16)).astype(np.float32)


def bf_build(x):
    return brute_force.BruteForce().build(jnp.asarray(x))


def sharded_bf(data, n_shards, **kw):
    return stream.ShardedMutableIndex(data, n_shards=n_shards,
                                      build=bf_build, **kw)


def bf_gids(live_mat, live_gids, queries, k):
    _, pos = brute_force.knn(jnp.asarray(live_mat), jnp.asarray(queries), k)
    pos = np.asarray(pos)
    return np.where(pos >= 0, np.asarray(live_gids)[np.clip(pos, 0, None)], -1)


# -- the parity spine ---------------------------------------------------------

def test_split_and_merge_parity_vs_fresh_build(data, queries, rng):
    """Split 2→4 then merge 4→2 after a write script: every topology's
    results are bit-equal to a fresh brute-force build over exactly the
    live rows — AND to a mesh CONSTRUCTED at the target topology — so a
    reshard is observationally a no-op for readers."""
    sm = sharded_bf(data, 2, delta_capacity=64)
    ins = rng.standard_normal((14, 16)).astype(np.float32)
    gids = sm.upsert(ins)
    dele = [3, 17, 101, int(gids[4])]
    assert sm.delete(dele) == 4
    live_mask = np.ones(len(data), bool)
    live_mask[[3, 17, 101]] = False
    ins_mask = np.ones(14, bool)
    ins_mask[4] = False
    live_mat = np.concatenate([data[live_mask], ins[ins_mask]])
    live_g = np.concatenate([np.nonzero(live_mask)[0],
                             np.asarray(gids)[ins_mask]])
    want = bf_gids(live_mat, live_g, queries, 10)

    rep = sm.reshard(4, warm_buckets=(5,))
    assert sm.n_shards == 4 and rep["to"] == 4
    assert rep["rows_moved"] == len(live_g)
    _, got = sm.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got), want)
    # the split locality rule: shard s's ids land on s or s+S only
    for s, sh in enumerate(sm.shards):
        st = sh._state
        lives = np.concatenate([st.id_map[st.sealed_alive],
                                st.delta_ids[:st.delta_n][
                                    st.delta_alive[:st.delta_n]]])
        assert set(np.asarray(stream.shard_of(lives, 4))) <= {s}, s

    # merge back: same results, aggregate size preserved
    sm.reshard(2)
    assert sm.n_shards == 2
    _, got2 = sm.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got2), want)
    assert sm.size == len(live_g)

    # multi-step jump (2 → 8 runs as two committed doublings)
    rep = sm.reshard(8)
    assert sm.n_shards == 8 and len(rep["steps"]) == 2
    _, got3 = sm.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got3), want)


def test_reshard_validations(data, tmp_path):
    sm = sharded_bf(data, 2, delta_capacity=32)
    with pytest.raises(RaftError, match="power-of-two"):
        sm.reshard(3)
    with pytest.raises(RaftError, match="already at"):
        sm.reshard(2)
    with pytest.raises(RaftError, match="n_shards"):
        sm.reshard(0)
    with pytest.raises(RaftError, match="published name"):
        sm.reshard(4, publisher=SearchService(start_workers=False))
    # no retained store: the fold has nothing to rebuild from
    bare = sharded_bf(data, 2, delta_capacity=32, retain_vectors=False)
    with pytest.raises(RaftError, match="retained row store"):
        bare.reshard(4)
    # a split that would leave an empty successor refuses whole (nothing
    # flipped, the donor mesh still serves)
    tiny = sharded_bf(data[:6], 2, delta_capacity=32)
    with pytest.raises(RaftError, match="no live rows|no rows"):
        tiny.reshard(32)
    assert tiny.n_shards == 2 and tiny.size == 6
    # a loaded mesh without build= cannot reshard (but says why)
    sm2 = sharded_bf(data, 2, delta_capacity=32, wal_dir=str(tmp_path))
    del sm2
    rec = stream.ShardedMutableIndex.load(str(tmp_path))
    with pytest.raises(RaftError, match="build recipe"):
        rec.reshard(4)


def test_mid_migration_writes_carry_over(data, queries):
    """Writes landing on an ALREADY-FOLDED donor mid-migration (injected
    deterministically from the reshard/split fault callback, so no timing
    race) carry over at the swap: upserts visible, deletes honored, the
    same contract as compaction's mid-fold writes."""
    sm = sharded_bf(data, 2, delta_capacity=64)
    probe = np.full((2, 16), 7.5, np.float32)
    mid = {}

    def midwrite(ctx):
        # fires as donor 1's fold STARTS — donor 0 is already folded, so
        # writes homed there can only survive via the carry-over
        mid["g"] = sm.upsert(probe, ids=[2000, 2001])
        sm.delete([11])

    with faults.scope():
        faults.inject("reshard/split", callback=midwrite, after=1, times=1)
        rep = sm.reshard(4)
    assert rep["steps"][0]["carried_over"] >= 1
    _, ids = sm.search(probe[:1], 4)
    got = set(np.asarray(ids)[0].tolist())
    assert {2000, 2001} <= got, got
    assert sm.delete([11]) == 0  # the mid-migration delete was honored
    # full parity against the live-row ground truth
    live_mask = np.ones(len(data), bool)
    live_mask[11] = False
    live_mat = np.concatenate([data[live_mask], probe])
    live_g = np.concatenate([np.nonzero(live_mask)[0], [2000, 2001]])
    want = bf_gids(live_mat, live_g, queries, 10)
    _, got = sm.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_reshard_under_load_loses_nothing(data):
    """Readers and writers live on the service while the topology doubles:
    zero failed queries, zero lost writes, and the post-flip mesh serves
    every id the old one did plus everything written mid-migration."""
    sm = sharded_bf(data, 2, delta_capacity=256, name="live")
    svc = SearchService(max_batch=8, max_wait_us=200.0, max_queue_rows=512)
    svc.publish("live", sm, k=5)
    sm.warm(svc.buckets, ks=(5,))
    errors, done = [], []
    lock = threading.Lock()
    stop = threading.Event()

    def reader(tid):
        j = 0
        while not stop.is_set() or j < 25:
            if j >= 25 and stop.is_set():
                break
            try:
                _, ids = svc.search("live", data[(tid * 37 + j) % 200:
                                                 (tid * 37 + j) % 200 + 1], 5)
                with lock:
                    done.append(int(np.asarray(ids)[0, 0]))
            except Exception as e:
                with lock:
                    errors.append(repr(e))
            j += 1

    threads = [threading.Thread(target=reader, args=(t,)) for t in range(3)]
    for t in threads:
        t.start()
    for step in range(8):
        svc.upsert("live", data[step:step + 2] + 0.5, ids=[900 + 2 * step,
                                                           901 + 2 * step])
    rep = sm.reshard(4, publisher=svc, name="live", ks=(5,))
    for step in range(8, 12):
        svc.upsert("live", data[step:step + 2] + 0.5, ids=[900 + 2 * step,
                                                           901 + 2 * step])
    stop.set()
    for t in threads:
        t.join(60)
        assert not t.is_alive(), "reader wedged"
    svc.shutdown()
    assert errors == []
    assert len(done) >= 75
    assert sm.n_shards == 4 and rep["steps"][0]["publish"]["version"] == 2
    # every write (pre-, mid- and post-flip) is live exactly once
    assert sm.size == len(data) + 24
    for gid in range(900, 924):
        row = (gid - 900) // 2 + (gid - 900) % 2
        _, ids = sm.search(data[row:row + 1] + 0.5, 4)
        assert gid in set(np.asarray(ids)[0].tolist()), gid


# -- replicated split ---------------------------------------------------------

def test_replicated_split_twins_in_lockstep_fenced_twin_excluded(data):
    """Splitting a replicated mesh rebuilds R fresh twins per successor in
    lockstep, sourced from a LIVE twin: a stale (write-fenced) twin's
    divergence is excluded — the write it missed is present after the
    split — and the successor groups come up fully healthy (the reshard
    re-replicates, healing staleness)."""
    sm = stream.ShardedMutableIndex(
        data, n_shards=2, replicas=2, build=bf_build, delta_capacity=64,
        name="rs")
    probe = np.full((1, 16), 3.3, np.float32)
    with faults.scope():
        # one twin of shard 0 misses an acknowledged write -> stale
        faults.inject("replica/upsert", RuntimeError("device fault"),
                      match=lambda c: c["replica"] == "rs/shard0/r1",
                      times=1)
        sm.upsert(probe, ids=[5000])
    assert sm.stats()["stale"] == 1
    sm.reshard(4)
    st = sm.stats()
    assert st["shards"] == 4 and st["replicas"] == 8
    assert st["stale"] == 0 and st["healthy"] == 2, st
    for sh in sm.shards:
        assert isinstance(sh, stream.ReplicatedShard)
        assert sh.n_replicas == 2
        # lockstep: both twins answer identically
        d0, i0 = sh.replicas[0].search(probe, 3)
        d1, i1 = sh.replicas[1].search(probe, 3)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    _, ids = sm.search(probe, 3)
    assert 5000 in set(np.asarray(ids)[0].tolist())


def test_replica_killed_mid_split_never_fails_a_query(data):
    """A replica killed while the migration runs (fault injected from the
    reshard/split callback — deterministically mid-migration): reads keep
    failing over to the surviving twin, the reshard completes, zero
    queries fail."""
    sm = stream.ShardedMutableIndex(
        data, n_shards=2, replicas=2, build=bf_build, delta_capacity=64,
        fencing=stream.FencingPolicy(max_consecutive=1, backoff_s=1e9),
        name="kz")
    outcomes = []

    def kill_and_read(ctx):
        faults.inject("replica/search", faults.FaultError("killed"),
                      match=lambda c: c["replica"].startswith("kz/shard0/r0"))
        # reads mid-migration route through the failover pick
        for lo in (0, 40):
            d, i = sm.search(data[lo:lo + 2], 5)
            outcomes.append(np.asarray(i).shape)

    with faults.scope():
        faults.inject("reshard/split", callback=kill_and_read, times=1)
        sm.reshard(4)
    assert outcomes == [(2, 5), (2, 5)]
    assert sm.n_shards == 4
    d, i = sm.search(data[:3], 5)  # post-flip serving intact
    assert np.asarray(i).shape == (3, 5)


# -- crash recovery -----------------------------------------------------------

def _write_script(sm, seed=9):
    r = np.random.default_rng(seed)
    g = sm.upsert(r.standard_normal((10, 16)).astype(np.float32),
                  ids=np.arange(1000, 1010))
    sm.delete([5, 7, 1003])
    return g


def test_kill_mid_reshard_recovers_at_every_fault_point(data, queries,
                                                        tmp_path):
    """THE acceptance bit: a SimulatedCrash at each of reshard/split,
    reshard/flip and reshard/manifest recovers — manifest + per-shard WAL
    replay — to the OLD topology with id-for-id parity against an
    uncrashed twin that never resharded: no acknowledged write lost, no
    write resurrected (the aborted successors' files are ignored)."""
    for point in ("reshard/split", "reshard/flip", "reshard/manifest"):
        d = str(tmp_path / point.replace("/", "_"))
        sm = sharded_bf(data, 2, delta_capacity=64, wal_dir=d)
        _write_script(sm)
        with faults.scope():
            faults.inject(point, faults.SimulatedCrash("kill -9"))
            with pytest.raises(faults.SimulatedCrash):
                sm.reshard(4)
        del sm  # the process is gone; the directory is all that survives
        rec = stream.ShardedMutableIndex.load(d, build=bf_build)
        assert rec.n_shards == 2, point
        twin = sharded_bf(data, 2, delta_capacity=64)
        _write_script(twin)
        dt, it = twin.search(queries, 10)
        dr, ir = rec.search(queries, 10)
        np.testing.assert_array_equal(np.asarray(it), np.asarray(ir), point)
        assert rec.size == twin.size
        assert rec.last_recovery["replayed"] > 0, point


def test_committed_reshard_recovers_to_the_new_topology(data, queries,
                                                        tmp_path):
    """Past the manifest rename the reshard is durable: a crash AFTER the
    commit point recovers to the new topology — with the carry-over
    writes that only ever hit the successor WALs."""
    d = str(tmp_path / "committed")
    sm = sharded_bf(data, 2, delta_capacity=64, wal_dir=d)
    _write_script(sm)

    def midwrite(ctx):  # a write only the successor WALs will hold
        sm.upsert(np.full((1, 16), 9.25, np.float32), ids=[7000])

    with faults.scope():
        faults.inject("reshard/split", callback=midwrite, after=1, times=1)
        sm.reshard(4)
    post_flip = sm.upsert(np.full((1, 16), -9.25, np.float32), ids=[7001])
    dt, it = sm.search(queries, 10)
    del sm
    rec = stream.ShardedMutableIndex.load(d, build=bf_build)
    assert rec.n_shards == 4
    assert rec.last_recovery["topology_epoch"] == 1
    dr, ir = rec.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(it), np.asarray(ir))
    for gid, val in ((7000, 9.25), (int(post_flip[0]), -9.25)):
        _, ids = rec.search(np.full((1, 16), val, np.float32), 3)
        assert gid in set(np.asarray(ids)[0].tolist()), gid


def test_mesh_save_load_and_crash_mid_save(data, queries, tmp_path):
    """Atomic mesh snapshots (satellite): save() routes every per-shard
    snapshot AND the manifest through atomic_write; a crash mid-save — on
    a shard snapshot or on the manifest itself — leaves the previous
    manifest+snapshot set loadable with zero acknowledged-write loss."""
    d = str(tmp_path / "mesh")
    sm = sharded_bf(data, 2, delta_capacity=64, wal_dir=d)
    _write_script(sm)
    want_d, want_i = sm.search(queries, 10)

    # crash on shard 1's snapshot rename: shard 0 already saved (its pair
    # is consistent on its own), manifest still the old one -> loadable
    with faults.scope():
        faults.inject("serialize/atomic-write",
                      faults.SimulatedCrash("kill -9"),
                      match=lambda c: "shard1" in c["path"])
        with pytest.raises(faults.SimulatedCrash):
            sm.save()
    rec = stream.ShardedMutableIndex.load(d, build=bf_build)
    _, ir = rec.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(want_i), np.asarray(ir))

    # crash on the manifest rename: every shard snapshot is new, manifest
    # old — per-shard wal_seq stamps keep each pair consistent
    with faults.scope():
        faults.inject("serialize/atomic-write",
                      faults.SimulatedCrash("kill -9"),
                      match=lambda c: c["path"].endswith("manifest"))
        with pytest.raises(faults.SimulatedCrash):
            sm.save()
    rec = stream.ShardedMutableIndex.load(d, build=bf_build)
    _, ir = rec.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(want_i), np.asarray(ir))

    # clean save + snapshot-only save/load without durability armed
    sm.save()
    rec = stream.ShardedMutableIndex.load(d, build=bf_build)
    assert rec.last_recovery["replayed"] == 0  # snapshots cover the log
    plain = sharded_bf(data, 2, delta_capacity=64)
    _write_script(plain)
    d2 = str(tmp_path / "snaponly")
    plain.save(d2)
    rec2 = stream.ShardedMutableIndex.load(d2)
    assert rec2._wal_dir is None
    _, ir2 = rec2.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(want_i), np.asarray(ir2))


def test_wal_dir_refuses_an_earlier_meshes_directory(data, tmp_path):
    """Constructing a fresh mesh over a wal_dir holding a committed
    manifest must refuse — a fresh epoch-0 manifest would shadow every
    acknowledged write of the earlier life, and a RESHARDED earlier life
    keeps its files under a different epoch that the per-shard WAL probe
    would never even see."""
    d = str(tmp_path / "life1")
    sm = sharded_bf(data, 2, delta_capacity=64, wal_dir=d)
    _write_script(sm)
    del sm
    with pytest.raises(RaftError, match="already holds a mesh manifest"):
        sharded_bf(data, 2, delta_capacity=64, wal_dir=d)
    # the epoch>=1 case (the files live at e1 names, so only the manifest
    # check can catch it): recover, reshard, and try to re-construct
    rec = stream.ShardedMutableIndex.load(d, build=bf_build)
    rec.reshard(4)
    del rec
    with pytest.raises(RaftError, match="already holds a mesh manifest"):
        sharded_bf(data, 2, delta_capacity=64, wal_dir=d)
    # the refused constructions shadowed nothing: the resharded mesh loads
    back = stream.ShardedMutableIndex.load(d, build=bf_build)
    assert back.n_shards == 4


def test_replicated_primary_goes_stale_mid_migration_nothing_lost(data):
    """A replicated donor's PRIMARY twin goes stale mid-migration (a
    write raises past admission on it): later acknowledged group writes
    skip the stale twin, so the commit must read carry-over from a twin
    that received them — the fold-time primary would silently drop
    every write since the staleness event."""
    sm = stream.ShardedMutableIndex(
        data, n_shards=2, replicas=2, build=bf_build, delta_capacity=64,
        name="sg")
    cand = np.arange(10_000, 40_000)
    to0 = cand[stream.shard_of(cand, 2) == 0]

    def midwrite(ctx):
        # fires after donor 0's fold: these writes home on (already
        # folded) shard 0 and can only survive via carry-over. The FIRST
        # write stales r0 — the twin the fold snapshotted — so the
        # second lands only on r1.
        faults.inject("replica/upsert", RuntimeError("dev fault"),
                      match=lambda c: c["replica"] == "sg/shard0/r0",
                      times=1)
        sm.upsert(np.full((1, 16), 4.5, np.float32), ids=[int(to0[0])])
        sm.upsert(np.full((1, 16), -4.5, np.float32), ids=[int(to0[1])])

    with faults.scope():
        faults.inject("reshard/split", callback=midwrite, after=1, times=1)
        sm.reshard(4)
    for gid, val in ((int(to0[0]), 4.5), (int(to0[1]), -4.5)):
        _, ids = sm.search(np.full((1, 16), val, np.float32), 3)
        assert gid in set(np.asarray(ids)[0].tolist()), (gid, ids)


def test_manifest_write_failure_rolls_the_flip_back(data, queries,
                                                    tmp_path):
    """A manifest that fails to LAND (an OSError, not a crash) must not
    leave the mesh flipped in memory while the durable manifest names the
    old topology — reshard() rolls the swap back (donors untouched, still
    logging) and a retry commits cleanly."""
    d = str(tmp_path / "roll")
    sm = sharded_bf(data, 2, delta_capacity=64, wal_dir=d)
    _write_script(sm)
    want_d, want_i = sm.search(queries, 10)
    with faults.scope():
        faults.inject("serialize/atomic-write", OSError("disk full"),
                      match=lambda c: c["path"].endswith("manifest"))
        with pytest.raises(OSError, match="disk full"):
            sm.reshard(4)
    assert sm.n_shards == 2  # the swap rolled back
    _, ir = sm.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(want_i), np.asarray(ir))
    g = sm.upsert(np.full((1, 16), 6.5, np.float32))  # writes still land
    rep = sm.reshard(4)  # the retry reuses the epoch and commits
    assert sm.n_shards == 4 and rep["epoch"] == 1
    _, ids = sm.search(np.full((1, 16), 6.5, np.float32), 3)
    assert int(g[0]) in set(np.asarray(ids)[0].tolist())
    rec = stream.ShardedMutableIndex.load(d, build=bf_build)
    assert rec.n_shards == 4
    _, ids = rec.search(np.full((1, 16), 6.5, np.float32), 3)
    assert int(g[0]) in set(np.asarray(ids)[0].tolist())


def test_per_shard_wal_attribution_and_sawtooth(data, tmp_path):
    """Satellite: per-shard WAL metrics report under name/shard<i>, and
    truncation saw-tooths with each shard's OWN compaction fold — one
    shard's fold resets its log while its sibling's keeps its records."""
    from raft_tpu.obs import metrics

    d = str(tmp_path / "saw")
    sm = sharded_bf(data, 2, delta_capacity=16, wal_dir=d, name="saw")
    cand = np.arange(10_000, 40_000)
    homes = stream.shard_of(cand, 2)
    to0, to1 = cand[homes == 0], cand[homes == 1]
    sm.upsert(np.zeros((6, 16), np.float32), ids=to0[:6])
    sm.upsert(np.ones((3, 16), np.float32), ids=to1[:3])
    snap = metrics.to_json()
    assert snap.get('raft_tpu_wal_appends_total{name="saw/shard0"}') >= 1
    assert snap.get('raft_tpu_wal_appends_total{name="saw/shard1"}') >= 1
    w0, w1 = sm.shards[0]._wal, sm.shards[1]._wal
    assert w0.size_bytes > 0 and w1.size_bytes > 0
    rep = sm.compact(shard=0)  # the fold snapshots + truncates shard 0 only
    assert rep["snapshot"].endswith("shard0.e0.idx")
    assert w0.size_bytes == 0 and w1.size_bytes > 0
    # the truncated shard recovers from its fresh snapshot, the other
    # from snapshot + replay — the mesh as a whole loses nothing
    want_d, want_i = sm.search(data[:4], 10)
    del sm
    rec = stream.ShardedMutableIndex.load(d)
    _, ir = rec.search(data[:4], 10)
    np.testing.assert_array_equal(np.asarray(want_i), np.asarray(ir))


# -- warm / compile discipline ------------------------------------------------

def test_save_serializes_with_a_live_reshard(data, tmp_path):
    """save() must not interleave with a reshard commit (which closes
    donor WALs and flips the epoch under it): a save launched
    mid-migration blocks on the topology lock and lands AFTER the flip,
    writing one consistent post-flip set."""
    d = str(tmp_path / "ser")
    sm = sharded_bf(data, 2, delta_capacity=64, wal_dir=d)
    _write_script(sm)
    box = {}

    def midsave(ctx):
        t = threading.Thread(
            target=lambda: box.setdefault("ok", (sm.save(), True)[1]))
        t.start()
        box["t"] = t

    with faults.scope():
        faults.inject("reshard/split", callback=midsave, after=1, times=1)
        sm.reshard(4)
    box["t"].join(60)
    assert not box["t"].is_alive() and box.get("ok")
    rec = stream.ShardedMutableIndex.load(d, build=bf_build)
    assert rec.n_shards == 4  # the save saw the committed topology, whole
    assert rec.last_recovery["topology_epoch"] == 1


def test_zero_cold_compile_warm_ladder_across_the_flip(data, queries):
    """The zero-cold-compile discipline survives a topology change: after
    the rehearsal run (which compiles both topologies' program sets), an
    identical publish → serve → reshard → serve schedule triggers ZERO
    compiles — the successors' ladders and the new merge shape were
    warmed through the registry's pre-flip seam, never on the hot path."""
    from raft_tpu.obs import compile as obs_compile

    clock = FakeClock()

    def run(name):
        sm = sharded_bf(data, 2, delta_capacity=16, clock=clock, name=name)
        svc = SearchService(max_batch=4, clock=clock, start_workers=False)
        svc.publish(name, sm, k=5)
        sm.warm(svc.buckets, ks=(5,))
        for step in range(4):
            sm.upsert(data[step:step + 1] + 0.5, ids=[600 + step])
            fut = svc.submit(name, queries[:2], 5)
            clock.advance(1.0)
            svc.pump()
            fut.result(timeout=0)
        sm.reshard(4, publisher=svc, name=name, ks=(5,),
                   warm_buckets=svc.buckets)
        for step in range(4, 8):
            sm.upsert(data[step:step + 1] + 0.5, ids=[600 + step])
            fut = svc.submit(name, queries[:2], 5)
            clock.advance(1.0)
            svc.pump()
            fut.result(timeout=0)
        svc.shutdown()

    run("rehearsal")
    with obs_compile.attribution() as rec:
        run("live")
    assert rec.compile_s == 0.0 and rec.programs == 0


# -- compactor advisory -------------------------------------------------------

def test_compactor_reshard_advised_trigger(data):
    """The reshard_advised watermark: a standing once-per-transition
    advisory (the retune_advised discipline — auto_apply False, the fold
    stays manual), cleared when the topology change lands."""
    from raft_tpu.obs import metrics

    clock = FakeClock()
    sm = sharded_bf(data, 2, delta_capacity=32, clock=clock, name="adv")
    comp = stream.Compactor(
        sm, policy=stream.CompactionPolicy(
            delta_fill=None, tombstone_ratio=None,
            reshard_rows_per_shard=100),
        clock=clock)
    before = metrics.to_json().get(
        'raft_tpu_reshard_advised_total{action="split",name="adv"}', 0)
    assert comp.run_once() is None  # no compaction due; advice still lands
    adv = comp.last_advice
    assert adv is not None and adv["action"] == "split"
    assert adv["target"] == 4 and adv["auto_apply"] is False
    after = metrics.to_json().get(
        'raft_tpu_reshard_advised_total{action="split",name="adv"}', 0)
    assert after == before + 1
    comp.run_once()  # standing advice does NOT re-emit
    assert metrics.to_json().get(
        'raft_tpu_reshard_advised_total{action="split",name="adv"}',
        0) == after
    sm.reshard(4)  # the split relieves the watermark (280/4 = 70 < 100)
    comp.run_once()
    assert comp.last_advice is None
    # a compaction report carries the advisory when one is standing
    comp2 = stream.Compactor(
        sm, policy=stream.CompactionPolicy(
            delta_fill=None, tombstone_ratio=None,
            reshard_rows_per_shard=10),
        clock=clock)
    rep = comp2.run_once(force=True)
    assert rep["reshard_advised"]["action"] == "split"
    # merge-side advisory
    comp3 = stream.Compactor(
        sm, policy=stream.CompactionPolicy(
            delta_fill=None, tombstone_ratio=None,
            reshard_min_rows_per_shard=1000),
        clock=clock)
    comp3.run_once()
    assert comp3.last_advice["action"] == "merge"
    assert comp3.last_advice["target"] == 2
    # an ODD shard count never gets merge advice: reshard() only halves
    # even counts, so the advisory would be permanently unactionable
    odd = sharded_bf(data, 3, delta_capacity=32, clock=clock, name="odd")
    comp4 = stream.Compactor(
        odd, policy=stream.CompactionPolicy(
            delta_fill=None, tombstone_ratio=None,
            reshard_min_rows_per_shard=1000),
        clock=clock)
    comp4.run_once()
    assert comp4.last_advice is None


# -- obs: metrics, ledger, healthz --------------------------------------------

def test_reshard_metrics_ledger_and_health_fold(data):
    """New raft_tpu_reshard_* metrics count the migration, the
    stream_shards gauge transitions at the flip, /healthz folds the
    migration state while it runs, and the donor shards' ledger entries
    retire — the audit proves the split's transient double-buffer frees
    once the donors are released."""
    import gc

    from raft_tpu.obs import mem as obs_mem
    from raft_tpu.obs import metrics

    sm = sharded_bf(data, 2, delta_capacity=32, name="met")
    seen = {}

    def observe(ctx):
        from raft_tpu.obs.http import _fold_replica_health

        seen["health"] = sm.health()["reshard"]
        # the exporter-side fold: migration state rides the /healthz body
        # without degrading the verdict (the old topology keeps serving)
        code, body = _fold_replica_health(
            200, {"status": "ready"}, sm.health())
        seen["fold"] = (code, body.get("status"), body.get("reshard"))
        seen["gauge_mid"] = metrics.to_json().get(
            'raft_tpu_stream_shards{name="met"}')

    assert sm.health()["reshard"] is None
    with faults.scope():
        faults.inject("reshard/split", callback=observe, after=1, times=1)
        sm.reshard(4)
    # mid-migration: health folds the migration, the gauge still reports
    # the serving (old) topology
    assert seen["health"]["action"] == "split"
    assert seen["health"]["from"] == 2 and seen["health"]["to"] == 4
    assert seen["health"]["folded_donors"] == 1
    code, verdict, fold = seen["fold"]
    assert code == 200 and verdict == "ready"
    assert fold["action"] == "split" and fold["to"] == 4
    assert seen["gauge_mid"] == 2
    snap = metrics.to_json()
    assert snap.get('raft_tpu_stream_shards{name="met"}') == 4
    assert snap.get('raft_tpu_reshard_migrations_total'
                    '{action="split",name="met",phase="started"}') == 1
    assert snap.get('raft_tpu_reshard_migrations_total'
                    '{action="split",name="met",phase="completed"}') == 1
    assert snap.get(
        'raft_tpu_reshard_rows_moved_total{name="met"}') == len(data)
    assert any(k.startswith("raft_tpu_reshard_seconds") for k in snap)
    assert sm.health()["reshard"] is None  # cleared at the commit
    # donor retirement: with no leases pinning the old topology, the
    # retired entries collect and the audit comes back clean
    gc.collect()
    aud = obs_mem.audit(collect=True)
    leaks = [r for r in aud["retired_unfreed"]
             if r["name"].startswith("met/")]
    assert leaks == [], leaks

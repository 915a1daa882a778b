"""Memory ledger & capacity observability (ISSUE 10, tier-1 ``mem`` marker).

Covers: ledger semantics (account/release/reaccount, weakref auto-release,
peaks, disabled mode), the retirement audits over the serving stack's
correctness-critical free paths (registry retire-after-drain, compaction
swap, sharded staggered fold, ``parallel.release_programs`` — the PR 9
leak class as first-class tests), the footprint estimator's ±20% accuracy
contract at 100k rows for all four index kinds, the
``memory_budget_bytes`` admission gate (whole-or-nothing at
build/publish/upsert), ``/debug/mem`` routing, the
``Resources.workspace_bytes`` attribution pin, and the disabled-mode
overhead smoke.

Deterministic: injected clocks where time matters, ``gc.collect()`` where
liveness matters — no wall sleeps in assertions. Ledger assertions are
RELATIVE (baseline-subtracted) and name-scoped: the ledger is a process
singleton, and other tests' live indexes legitimately appear in it.
"""

import gc
import json
import threading
import urllib.request
import weakref

import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.core import Resources
from raft_tpu.obs import mem as obs_mem
from raft_tpu.serve.errors import MemoryBudgetError, OverloadedError

pytestmark = pytest.mark.mem


def _dev_total():
    gc.collect()
    return obs_mem.totals()["device_bytes"]


def _entries(name=None, component=None):
    return [r for r in obs_mem.breakdown()
            if (name is None or r["name"] == name)
            and (component is None or r["component"] == component)]


# ---------------------------------------------------------------------------
# ledger semantics
# ---------------------------------------------------------------------------

class TestLedger:
    def test_account_release_totals_and_gauges(self):
        led = obs_mem.MemLedger()
        t = led.account("c1", name="n1", device_bytes=100, host_bytes=10)
        t2 = led.account("c1", name="n2", device_bytes=50)
        tot = led.totals()
        assert tot["device_bytes"] == 150 and tot["host_bytes"] == 10
        led.release(t)
        led.release(t)  # idempotent
        tot = led.totals()
        assert tot["device_bytes"] == 50 and tot["host_bytes"] == 0
        assert tot["device_peak_bytes"] == 150  # peak survives the release
        led.release(t2)
        assert led.totals()["allocations"] == 0

    def test_array_nbytes_and_reaccount(self):
        led = obs_mem.MemLedger()
        a = np.zeros((8, 4), np.float32)
        t = led.account("c", device=[a], host=a)
        assert led.totals() == {"device_bytes": 128, "host_bytes": 128,
                                "device_peak_bytes": 128,
                                "host_peak_bytes": 128, "allocations": 1}
        led.reaccount(t, device=[a, a], epoch=3)
        assert led.totals()["device_bytes"] == 256
        assert led.totals()["host_bytes"] == 0
        assert led.breakdown()[0]["epoch"] == 3
        led.reset_peak()
        assert led.totals()["device_peak_bytes"] == 256

    def test_owner_weakref_autorelease(self):
        led = obs_mem.MemLedger()

        class Owner:
            pass

        o = Owner()
        led.account("c", device_bytes=64, owner=o)
        assert led.totals()["device_bytes"] == 64
        del o
        gc.collect()
        assert led.totals()["device_bytes"] == 0

    def test_owner_idempotency_replaces(self):
        led = obs_mem.MemLedger()

        class Owner:
            pass

        o = Owner()
        led.account("c", name="a", device_bytes=64, owner=o)
        led.account("c", name="b", device_bytes=32, owner=o)
        # release-then-insert: a replacement never double-counts, so the
        # peak stays at the larger single entry
        assert led.totals() == {"device_bytes": 32, "host_bytes": 0,
                                "device_peak_bytes": 64,
                                "host_peak_bytes": 0, "allocations": 1}
        assert led.breakdown()[0]["name"] == "b"
        # a DIFFERENT component for the same owner is a separate entry
        led.account("c2", device_bytes=8, owner=o)
        assert led.totals()["allocations"] == 2
        del o
        gc.collect()
        assert led.totals()["allocations"] == 0

    def test_retire_then_audit(self):
        clock_now = [0.0]
        led = obs_mem.MemLedger(clock=lambda: clock_now[0])

        class Owner:
            pass

        o = Owner()
        t = led.account("c", name="x", device_bytes=64, owner=o)
        led.retire(t)
        clock_now[0] = 5.0
        aud = led.audit()
        assert not aud["clean"]
        assert aud["retired_unfreed"][0]["retired_for_s"] == 5.0
        assert aud["retired_unfreed"][0]["name"] == "x"
        del o
        gc.collect()
        aud = led.audit()
        assert aud["clean"] and led.totals()["device_bytes"] == 0

    def test_disabled_mode_noops(self):
        led = obs_mem.MemLedger()
        obs.disable()
        try:
            t = led.account("c", device_bytes=64)
            assert t is None
            led.reaccount(t, device_bytes=1)  # None token no-ops
            led.retire(t)
            led.release(t)
            assert led.totals()["device_bytes"] == 0
        finally:
            obs.enable()


# ---------------------------------------------------------------------------
# retirement audits over the real free paths
# ---------------------------------------------------------------------------

def _small_flat(rng, n=512, d=8, n_lists=8, seed=0):
    from raft_tpu.neighbors import ivf_flat

    x = rng.random((n, d)).astype(np.float32)
    return ivf_flat.build(
        ivf_flat.IndexParams(n_lists=n_lists, kmeans_n_iters=2, seed=seed), x)


class TestRetirementAudit:
    def test_registry_retire_after_drain_frees_bytes(self, rng):
        """THE acceptance audit: a published-then-retired serve version's
        accounted device bytes return to the pre-publish baseline —
        weakref-verified (release only ever happens through the owner
        weakref), injected clock, no wall sleeps."""
        from raft_tpu.serve import IndexRegistry

        clock_now = [0.0]
        baseline = _dev_total()
        reg = IndexRegistry(buckets=(1, 4), clock=lambda: clock_now[0])
        idx1 = _small_flat(rng, seed=1)
        reg.publish("aud1", idx1, k=3)
        v1_bytes = _dev_total() - baseline
        assert v1_bytes > 0, "the build must be accounted"
        wr_idx = weakref.ref(idx1)
        del idx1  # the registry version now holds the only reference
        assert _dev_total() - baseline == v1_bytes  # published = pinned

        # hold a lease (an in-flight flush) across the swap: v1 must NOT
        # free while draining
        with reg.lease("aud1") as v1:
            clock_now[0] = 1.0
            idx2 = _small_flat(rng, seed=2)
            reg.publish("aud1", idx2, k=3)
            gc.collect()
            assert wr_idx() is not None, "leased version freed early"
            assert not obs_mem.audit()["clean"] or v1.leases >= 0
        # lease drained → retire-after-drain ran → v1's bytes free
        del v1
        gc.collect()
        v2_bytes = int(sum(x.nbytes for x in idx2.tree_flatten()[0]))
        assert wr_idx() is None, "retired version still pinned after drain"
        assert _dev_total() - baseline == v2_bytes, (
            "retired version's device bytes did not return to the "
            "pre-publish baseline")
        assert obs_mem.audit(collect=True)["clean"]

    def test_pinned_searcher_shows_as_leak(self, rng):
        """Negative control — the PR 9 class: something (here a deliberate
        strong ref, there the ProgramCache) pins a retired version's
        searcher; the audit must SEE it, and see it clear."""
        from raft_tpu.serve import IndexRegistry

        reg = IndexRegistry(buckets=(1, 4))
        reg.publish("aud2", _small_flat(rng, seed=3), k=3)
        pin = reg.active("aud2").searcher  # the leak: a strong reference
        reg.publish("aud2", _small_flat(rng, seed=4), k=3)
        aud = obs_mem.audit(collect=True)
        leaks = [r for r in aud["retired_unfreed"]
                 if r["component"] == "serve/version" and r["name"] == "aud2"]
        assert leaks, "a pinned retired searcher must surface in the audit"
        del pin
        aud = obs_mem.audit(collect=True)
        assert not [r for r in aud["retired_unfreed"]
                    if r["component"] == "serve/version"
                    and r["name"] == "aud2"]

    def test_compact_swap_frees_pre_epoch(self, rng):
        """MutableIndex.compact(): the pre-swap epoch's stream arrays and
        replaced sealed store free once the last pinned hook drops —
        accounted bytes return to exactly the live state's entries."""
        from raft_tpu.neighbors import brute_force
        from raft_tpu.stream import MutableIndex

        baseline = _dev_total()
        bf = brute_force.BruteForce().build(
            rng.random((64, 8)).astype(np.float32))
        m = MutableIndex(bf, delta_capacity=32, name="aud3",
                         clock=lambda: 0.0)
        del bf  # the mutable owns the sealed index now
        m.upsert(rng.random((20, 8)).astype(np.float32))
        hook = m.searcher()  # a lease-pinned epoch-0 hook
        m.compact(mode="rebuild")
        aud = obs_mem.audit(collect=True)
        assert [r for r in aud["retired_unfreed"] if r["name"] == "aud3"], (
            "pinned pre-compaction epoch must show in the audit")
        del hook
        aud = obs_mem.audit(collect=True)
        assert not [r for r in aud["retired_unfreed"]
                    if r["name"] == "aud3"]
        # totals == exactly the live entries (old epoch fully gone)
        live = sum(r["device_bytes"] for r in _entries(name="aud3"))
        assert _dev_total() - baseline == live
        epochs = {(r["component"], r["epoch"])
                  for r in _entries(name="aud3")}
        assert epochs == {("stream", 1), ("index/brute_force", 1)}
        del m
        gc.collect()
        assert _dev_total() - baseline == 0

    def test_sharded_fold_frees_one_shard(self, rng):
        """ShardedMutableIndex staggered fold: only the folded shard's
        epoch advances; its pre-fold entries free; the sibling shard's
        entries are untouched; shard attribution rides the ledger."""
        from raft_tpu.neighbors import brute_force
        from raft_tpu.stream import ShardedMutableIndex

        baseline = _dev_total()
        x = rng.random((96, 8)).astype(np.float32)
        sm = ShardedMutableIndex(
            x, n_shards=2, delta_capacity=32, name="aud4",
            build=lambda rows: brute_force.BruteForce().build(rows),
            clock=lambda: 0.0)
        sm.upsert(rng.random((16, 8)).astype(np.float32))
        shards = {r["shard"] for r in _entries(component="stream")
                  if r["name"].startswith("aud4/")}
        assert shards == {0, 1}, "per-shard ledger attribution missing"
        report = sm.compact(mode="rebuild")
        folded = report["shard"]
        gc.collect()
        assert obs_mem.audit(collect=True)["clean"]
        for s in range(2):
            eps = {r["epoch"] for r in _entries(name=f"aud4/shard{s}",
                                                component="stream")}
            assert eps == ({1} if s == folded else {0}), (s, folded, eps)
        live = sum(r["device_bytes"] for r in obs_mem.breakdown()
                   if r["name"].startswith("aud4/"))
        assert _dev_total() - baseline == live
        del sm
        gc.collect()
        assert _dev_total() - baseline == 0

    def test_release_programs_frees_accounted_comms(self, rng):
        """parallel.release_programs as a ledger-audited free path: an
        allocation owned by a retired Comms frees only after the program
        cache releases it — accounted bytes return to the pre-op
        baseline (the PR 9 fix, generalized into the audit)."""
        import jax
        from jax.sharding import Mesh

        from raft_tpu import parallel
        from raft_tpu.comms import Comms

        baseline = _dev_total()
        x = rng.random((64, 8)).astype(np.float32)
        q = rng.random((4, 8)).astype(np.float32)
        c = Comms(Mesh(np.array(jax.devices()[:2]), ("data",)), "data")
        d, i = parallel.knn.knn(c, x, q, k=3)
        c.sync_stream(d, i)
        # attribute the mesh's working set to the communicator: the entry
        # must live exactly as long as the comms does
        tok = obs_mem.account("comms", name="aud5", device=[d, i], owner=c)
        pinned = _dev_total() - baseline
        assert pinned > 0
        obs_mem.retire(tok)
        ref = weakref.ref(c)
        del d, i, c
        gc.collect()
        assert ref() is not None, "sanity: the program cache pins the comms"
        aud = obs_mem.audit(collect=True)
        assert [r for r in aud["retired_unfreed"] if r["name"] == "aud5"], (
            "the cache-pinned comms must surface in the audit")
        parallel.release_programs(ref())
        gc.collect()
        assert ref() is None
        assert _dev_total() - baseline == 0, (
            "accounted bytes did not return to the pre-op baseline")
        assert not [r for r in obs_mem.audit()["retired_unfreed"]
                    if r["name"] == "aud5"]


# ---------------------------------------------------------------------------
# footprint estimator accuracy (acceptance: ±20% at 100k, tier-1)
# ---------------------------------------------------------------------------

def _measured_index_bytes(index):
    kind, leaves = obs_mem._index_kind_and_leaves(index)
    assert kind is not None
    return int(sum(x.nbytes for x in leaves))


def _plan_params(d):
    """Per-kind build params sized so tier-1 stays CPU-cheap while the
    arrays being estimated stay 100k-scale."""
    from raft_tpu.neighbors import cagra, ivf_flat, ivf_pq

    return {
        "brute_force": None,
        "ivf_flat": ivf_flat.IndexParams(n_lists=256, kmeans_n_iters=4),
        "ivf_pq": ivf_pq.IndexParams(n_lists=256, pq_bits=4,
                                     pq_dim=max(d // 2, 1),
                                     kmeans_n_iters=4),
        "cagra": cagra.IndexParams(intermediate_graph_degree=32,
                                   graph_degree=16, build_n_probes=8),
    }


def _build_kind(kind, params, x):
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq

    if kind == "brute_force":
        return brute_force.BruteForce().build(x)
    mod = {"ivf_flat": ivf_flat, "ivf_pq": ivf_pq, "cagra": cagra}[kind]
    return mod.build(params, x)


def _assert_plan_brackets(kind, params, idx, n, d):
    measured = _measured_index_bytes(idx)
    est = obs_mem.plan(kind, params, n, d)["index_bytes"]
    assert abs(est - measured) <= 0.20 * measured, (
        f"{kind}: plan {est} vs measured {measured} "
        f"({est / measured:.3f}x) outside the ±20% contract")


@pytest.mark.parametrize("kind", ["brute_force", "ivf_flat", "ivf_pq"])
def test_plan_within_20pct_at_100k(rng, kind):
    """obs.mem.plan() vs the measured ledger at 100k rows (the ISSUE 10
    accuracy bar; CAGRA's case is split below, the 1M cases ride the slow
    manifest). Real builds at a CPU-cheap dim — the IVF padded-list
    capacity model is the part with real slack."""
    import jax

    n, d = 100_000, 16
    params = _plan_params(d)[kind]
    idx = _build_kind(kind, params, rng.random((n, d)).astype(np.float32))
    jax.block_until_ready(jax.tree_util.tree_leaves(
        idx if kind != "brute_force" else idx.dataset))
    _assert_plan_brackets(kind, params, idx, n, d)


def test_plan_cagra_within_20pct_at_100k(rng):
    """The CAGRA leg of the 100k accuracy bar. A CagraIndex's allocation
    is SHAPE-exact — dataset (n, d) + graph (n, graph_degree) int32; the
    knn-graph self-search that fills the graph runs minutes on the CPU
    mesh and cannot change a byte of it. So tier-1 runs the real build
    at 4k (pinning that the pipeline's output matches the plan exactly)
    and measures the 100k LAYOUT through the same ledger hook; the full
    100k build rides the slow manifest."""
    import jax

    from raft_tpu.neighbors import cagra

    d = 16
    params = _plan_params(d)["cagra"]
    small = _build_kind("cagra", params,
                        rng.random((4096, d)).astype(np.float32))
    jax.block_until_ready(small.graph)
    est_small = obs_mem.plan("cagra", params, 4096, d)["index_bytes"]
    assert est_small == _measured_index_bytes(small), (
        "cagra plan must be exact against the real build pipeline")

    n = 100_000
    idx = cagra.CagraIndex(
        dataset=jax.numpy.asarray(rng.random((n, d)).astype(np.float32)),
        graph=jax.numpy.zeros((n, params.graph_degree), jax.numpy.int32))
    tok = obs_mem.account_index(idx, name="plan_cagra_100k")
    try:
        _assert_plan_brackets("cagra", params, idx, n, d)
        entry = [r for r in _entries(name="plan_cagra_100k")][0]
        assert entry["device_bytes"] == _measured_index_bytes(idx)
    finally:
        obs_mem.release(tok)


def test_plan_fast_scan_tier_within_20pct_at_100k(rng):
    """ISSUE 16 satellite: plan() prices the fast-scan signature tier
    (list_sig + sig_scales) inside the same ±20% contract — the packed
    tier rides the padded-list capacity model, so its per-array slack is
    the same slack as list_codes, and the decode scales are exact."""
    import dataclasses

    import jax

    n, d = 100_000, 16
    base = _plan_params(d)["ivf_pq"]
    params = dataclasses.replace(base, fast_scan="1bit")
    idx = _build_kind("ivf_pq", params,
                      rng.random((n, d)).astype(np.float32))
    jax.block_until_ready(jax.tree_util.tree_leaves(idx))
    assert idx.has_fast_scan
    _assert_plan_brackets("ivf_pq", params, idx, n, d)
    with_tier = obs_mem.plan("ivf_pq", params, n, d)["breakdown"]
    without = obs_mem.plan("ivf_pq", base, n, d)["breakdown"]
    sig = int(np.asarray(idx.list_sig).nbytes)
    assert abs(with_tier["list_sig"] - sig) <= 0.20 * sig, (
        with_tier["list_sig"], sig)
    assert with_tier["sig_scales"] == int(np.asarray(idx.sig_scales).nbytes)
    assert set(with_tier) - set(without) == {"list_sig", "sig_scales"}


@pytest.mark.slow
def test_plan_cagra_full_build_at_100k(rng):
    """The full 100k CAGRA build vs the plan (slow manifest — the
    self-search is minutes on the CPU mesh)."""
    import jax

    n, d = 100_000, 16
    params = _plan_params(d)["cagra"]
    idx = _build_kind("cagra", params, rng.random((n, d)).astype(np.float32))
    jax.block_until_ready(idx.graph)
    _assert_plan_brackets("cagra", params, idx, n, d)


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["brute_force", "ivf_flat", "ivf_pq"])
def test_plan_within_20pct_at_1m(rng, kind):
    """The 1M-row estimator case (slow manifest): the IVF padded-list
    models at the scale ROADMAP 2's tiering planning actually targets."""
    import jax

    n, d = 1_000_000, 16
    params = _plan_params(d)[kind]
    idx = _build_kind(kind, params, rng.random((n, d)).astype(np.float32))
    jax.block_until_ready(jax.tree_util.tree_leaves(
        idx if kind != "brute_force" else idx.dataset))
    _assert_plan_brackets(kind, params, idx, n, d)


def test_plan_breakdown_and_unknown_kind():
    from raft_tpu.core.errors import RaftError

    p = obs_mem.plan("brute_force", None, 1000, 32)
    assert p["breakdown"] == {"dataset": 1000 * 32 * 4, "norms": 1000 * 4}
    assert p["index_bytes"] == 1000 * 32 * 4 + 1000 * 4
    assert p["build_peak_bytes"] >= p["index_bytes"]
    assert obs_mem.plan("brute_force", None, 1000, 32,
                        dtype="int8")["index_bytes"] == 1000 * 32 + 1000 * 4
    with pytest.raises(RaftError):
        obs_mem.plan("nope", None, 10, 10)


# ---------------------------------------------------------------------------
# memory_budget_bytes admission gate (whole-or-nothing)
# ---------------------------------------------------------------------------

class TestBudgetGate:
    def test_build_refused_before_any_work(self, rng):
        from raft_tpu.neighbors import ivf_flat

        x = rng.random((512, 8)).astype(np.float32)
        res = Resources(memory_budget_bytes=16)
        with pytest.raises(MemoryBudgetError) as ei:
            ivf_flat.build(ivf_flat.IndexParams(n_lists=8), x, res=res)
        assert ei.value.site == "build"
        assert isinstance(ei.value, OverloadedError)
        assert ei.value.budget_bytes == 16
        assert ei.value.need_bytes > 0

    def test_publish_refused_zero_partial_state(self, rng):
        """Over-budget publish: no version minted, the name stays
        unpublished, the service's write-path routing is untouched —
        the PR 9 cross-shard whole-or-nothing contract at the registry."""
        from raft_tpu.core.errors import RaftError
        from raft_tpu.serve import IndexRegistry

        reg = IndexRegistry(buckets=(1, 4))
        idx = _small_flat(rng, seed=9)
        # the index is already ledger-accounted, so the budget must sit
        # below the CURRENT totals to trip at publish (the publish-time
        # gate exists for exactly this: budgets set after builds land)
        res = Resources(memory_budget_bytes=1)
        with pytest.raises(MemoryBudgetError) as ei:
            reg.publish("gated", idx, k=3, res=res)
        assert ei.value.site == "publish"
        assert "gated" not in reg.names()
        with pytest.raises(RaftError):
            reg.active("gated")
        assert not [r for r in obs_mem.breakdown()
                    if r["component"] == "serve/version"
                    and r["name"] == "gated"]
        # and the same publish admits once the budget allows it
        reg.publish("gated", idx, k=3,
                    res=Resources(memory_budget_bytes=None))
        assert reg.active("gated").version == 1

    def test_publish_counts_unaccounted_index_bytes(self, rng):
        """An index the ledger has never seen (obs was disabled at build)
        gates on its MEASURED bytes — the gate cannot be dodged by
        building in the dark."""
        from raft_tpu.serve import IndexRegistry

        obs.disable()
        try:
            idx = _small_flat(rng, seed=10)
        finally:
            obs.enable()
        need = obs_mem.unaccounted_index_bytes(idx)
        assert need == _measured_index_bytes(idx)
        reg = IndexRegistry(buckets=(1, 4))
        used = obs_mem.totals()["device_bytes"]
        with pytest.raises(MemoryBudgetError):
            reg.publish("gated2", idx, k=3,
                        res=Resources(memory_budget_bytes=used + need - 1))

    def test_dark_published_indexes_accumulate(self, rng):
        """Review regression: an admitted dark-built (obs-disabled) index
        must JOIN the ledger at publish — otherwise a second dark publish
        gates against a total that never learned about the first and the
        budget is quietly exceeded."""
        from raft_tpu.serve import IndexRegistry

        obs.disable()
        try:
            a = _small_flat(rng, seed=20)
            b = _small_flat(rng, seed=21)
        finally:
            obs.enable()
        need = _measured_index_bytes(a)
        used = obs_mem.totals()["device_bytes"]
        res = Resources(memory_budget_bytes=used + need + need // 2)
        reg = IndexRegistry(buckets=(1, 4))
        reg.publish("dark_a", a, k=3, res=res)  # fits
        assert obs_mem.unaccounted_index_bytes(a) == 0, (
            "an admitted publish must account its index")
        with pytest.raises(MemoryBudgetError):
            reg.publish("dark_b", b, k=3, res=res)  # a's bytes now count

    def test_owner_map_pruned_on_release(self):
        """Review regression: releasing an owned entry must drop its
        owner-map key — the leak-detection module must not itself leak a
        mapping per publish→retire cycle."""

        class Owner:
            pass

        led = obs_mem.MemLedger()
        keep = Owner()
        led.account("c", device_bytes=1, owner=keep)
        for _ in range(16):
            o = Owner()
            led.account("c", device_bytes=1, owner=o)
            del o
            gc.collect()
        assert len(led._owners) == 1  # only the live owner's mapping
        assert led.totals()["allocations"] == 1

    def test_upsert_refused_nothing_written(self, rng):
        from raft_tpu.neighbors import brute_force
        from raft_tpu.stream import MutableIndex

        m = MutableIndex(
            brute_force.BruteForce().build(
                rng.random((32, 8)).astype(np.float32)),
            delta_capacity=64, name="gate_up", clock=lambda: 0.0)
        m.upsert(rng.random((7, 8)).astype(np.float32))  # bucket 8, 1 free
        before = m.stats()
        used = obs_mem.totals()["device_bytes"]
        res = Resources(memory_budget_bytes=used)  # zero headroom
        with pytest.raises(MemoryBudgetError) as ei:
            # 9 rows grow the delta bucket 8 → 16: real device growth
            m.upsert(rng.random((9, 8)).astype(np.float32), res=res)
        assert ei.value.site == "upsert"
        assert m.stats() == before, "a refused upsert wrote state"
        # a write that does NOT grow the bucket passes the same budget
        m.upsert(rng.random((1, 8)).astype(np.float32), res=res)
        assert m.stats()["delta_rows"] == 8

    def test_sharded_upsert_whole_or_nothing(self, rng):
        """Cross-shard: the summed bucket growth gates BEFORE any shard
        writes — one over-budget sibling means no shard lands a row."""
        from raft_tpu.neighbors import brute_force
        from raft_tpu.stream import ShardedMutableIndex

        x = rng.random((64, 8)).astype(np.float32)
        sm = ShardedMutableIndex(
            x, n_shards=2, delta_capacity=64, name="gate_sh",
            build=lambda rows: brute_force.BruteForce().build(rows),
            clock=lambda: 0.0)
        before = [sh.stats() for sh in sm.shards]
        used = obs_mem.totals()["device_bytes"]
        with pytest.raises(MemoryBudgetError):
            sm.upsert(rng.random((40, 8)).astype(np.float32),
                      res=Resources(memory_budget_bytes=used))
        assert [sh.stats() for sh in sm.shards] == before, (
            "a refused cross-shard upsert left partial state")

    def test_sharded_upsert_forwards_res_to_shards(self, rng):
        """Review regression: the caller's res must reach the per-shard
        upserts — a stricter ambient default budget would otherwise admit
        at the hoisted gate and refuse mid-write on shard 1, breaking
        whole-or-nothing."""
        from raft_tpu.core.resources import default_resources
        from raft_tpu.neighbors import brute_force
        from raft_tpu.stream import ShardedMutableIndex

        x = rng.random((64, 8)).astype(np.float32)
        sm = ShardedMutableIndex(
            x, n_shards=2, delta_capacity=64, name="gate_fw",
            build=lambda rows: brute_force.BruteForce().build(rows),
            clock=lambda: 0.0)
        dflt = default_resources()
        assert dflt.memory_budget_bytes is None  # suite invariant
        dflt.memory_budget_bytes = 1  # a hostile ambient budget
        try:
            out = sm.upsert(rng.random((40, 8)).astype(np.float32),
                            res=Resources(memory_budget_bytes=None))
            assert len(out) == 40
            assert sum(sh.stats()["delta_rows"] for sh in sm.shards) == 40
        finally:
            dflt.memory_budget_bytes = None

    def test_brute_force_gate_sizes_from_host_view(self, rng):
        """Review regression: the brute-force build gate prices the f32
        STORED bytes from the host view (before any device upload) — an
        f64 numpy input must not double the gate's ask — plus the 4 bytes
        a row of the norms the index holds."""
        from raft_tpu.neighbors import brute_force

        x64 = rng.random((256, 8))  # float64 host array
        used = obs_mem.totals()["device_bytes"]
        need_f32 = 256 * 8 * 4 + 256 * 4
        idx = brute_force.BruteForce().build(
            x64, res=Resources(memory_budget_bytes=used + need_f32))
        assert str(idx.dataset.dtype) == "float32"
        used = obs_mem.totals()["device_bytes"]  # idx is accounted now
        with pytest.raises(MemoryBudgetError):
            brute_force.BruteForce().build(
                rng.random((256, 8)),
                res=Resources(memory_budget_bytes=used + need_f32 - 1))

    def test_service_paths_carry_res(self, rng):
        """SearchService.publish/upsert thread the budget through to the
        same gates (the serve admission taxonomy end to end)."""
        from raft_tpu.neighbors import brute_force
        from raft_tpu.serve import SearchService
        from raft_tpu.stream import MutableIndex

        svc = SearchService(max_batch=4, start_workers=False,
                            clock=lambda: 0.0)
        m = MutableIndex(
            brute_force.BruteForce().build(
                rng.random((32, 8)).astype(np.float32)),
            delta_capacity=64, name="gate_svc", clock=lambda: 0.0)
        svc.publish("gate_svc", m, k=3)
        used = obs_mem.totals()["device_bytes"]
        with pytest.raises(MemoryBudgetError):
            svc.upsert("gate_svc", rng.random((9, 8)).astype(np.float32),
                       res=Resources(memory_budget_bytes=used))
        with pytest.raises(MemoryBudgetError):
            svc.publish("gate_svc2", _small_flat(rng, seed=11), k=3,
                        res=Resources(memory_budget_bytes=1))
        assert "gate_svc2" not in svc.registry.names()
        svc.shutdown()

    def test_armed_budget_requires_obs_enabled(self, rng):
        """Review regression: under obs.disable() the ledger stops
        accounting, so an armed budget would compare every admission
        against a frozen total and silently enforce nothing (three dark
        builds each see 0 used and all admit) — the gate fails loudly
        instead."""
        from raft_tpu.core.errors import RaftError
        from raft_tpu.neighbors import brute_force

        obs.disable()
        try:
            with pytest.raises(RaftError, match="disabled"):
                obs_mem.gate(Resources(memory_budget_bytes=1 << 30), 0,
                             site="publish")
            with pytest.raises(RaftError, match="disabled"):
                brute_force.BruteForce().build(
                    rng.random((32, 8)).astype(np.float32),
                    res=Resources(memory_budget_bytes=1 << 30))
            obs_mem.gate(Resources(), 0, site="publish")  # unarmed: no-op
        finally:
            obs.enable()

    def test_sharded_upsert_immune_to_concurrent_growth(self, rng,
                                                        monkeypatch):
        """Review regression: ledger growth landing between the hoisted
        cross-shard admit and shard s's write (another name's publish, a
        fold's double-buffer) must not refuse mid-write and leave a
        partial cross-shard upsert — the per-shard upserts run with the
        budget stripped, so admission is decided exactly once."""
        from raft_tpu.neighbors import brute_force
        from raft_tpu.stream import ShardedMutableIndex

        x = rng.random((64, 8)).astype(np.float32)
        sm = ShardedMutableIndex(
            x, n_shards=2, delta_capacity=64, name="gate_race",
            build=lambda rows: brute_force.BruteForce().build(rows),
            clock=lambda: 0.0)
        orig, tokens, hoisted = obs_mem.gate, [], []

        def racing_gate(res, need, **kw):
            orig(res, need, **kw)
            if not hoisted and getattr(
                    res, "memory_budget_bytes", None) is not None:
                hoisted.append(kw.get("site"))
                # the admit landed; now a "concurrent publish" eats the
                # entire remaining headroom before any shard writes
                tokens.append(obs_mem.account(
                    "test/race", name="gate_race", device_bytes=1 << 30))

        monkeypatch.setattr(obs_mem, "gate", racing_gate)
        try:
            budget = obs_mem.totals()["device_bytes"] + (1 << 30)
            out = sm.upsert(rng.random((40, 8)).astype(np.float32),
                            res=Resources(memory_budget_bytes=budget))
            assert hoisted == ["upsert"]  # the race actually fired
            assert len(out) == 40
            assert sum(sh.stats()["delta_rows"] for sh in sm.shards) == 40
        finally:
            for t in tokens:
                obs_mem.release(t)

    def test_duck_typed_mutable_without_res_kwarg(self, rng):
        """Review regression: serve resolves mutables duck-typed, so a
        custom hook whose ``upsert`` takes no ``res=`` must still write
        through ``SearchService.upsert`` — and an ARMED budget against it
        fails loudly instead of silently going unenforced."""
        from raft_tpu.core.errors import RaftError
        from raft_tpu.neighbors import brute_force
        from raft_tpu.serve import SearchService
        from raft_tpu.stream import MutableIndex

        class LegacyMutable:  # the pre-ledger duck shape
            def __init__(self, inner):
                self._inner = inner

            def searcher(self):
                return self._inner.searcher()

            def upsert(self, rows, ids=None):
                return self._inner.upsert(rows, ids)

        m = MutableIndex(
            brute_force.BruteForce().build(
                rng.random((32, 8)).astype(np.float32)),
            delta_capacity=64, name="gate_duck", clock=lambda: 0.0)
        svc = SearchService(max_batch=4, start_workers=False,
                            clock=lambda: 0.0)
        svc.publish("gate_duck", LegacyMutable(m), k=3)
        out = svc.upsert("gate_duck", rng.random((5, 8)).astype(np.float32))
        assert len(out) == 5 and m.stats()["delta_rows"] == 5
        with pytest.raises(RaftError, match="res="):
            svc.upsert("gate_duck",
                       rng.random((2, 8)).astype(np.float32),
                       res=Resources(memory_budget_bytes=1 << 40))
        svc.shutdown()


# ---------------------------------------------------------------------------
# /debug/mem endpoint + routing (404 contract preserved)
# ---------------------------------------------------------------------------

class TestDebugMemEndpoint:
    def _get(self, port, path):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    def test_debug_mem_routes_and_404_contract(self):
        exp = obs.MetricsExporter(port=0)
        try:
            code, body = self._get(exp.port, "/debug/mem")
            assert code == 200
            payload = json.loads(body)
            # "tiers" (ISSUE 15) registers once a TieredStore has lived
            # in the process — an extra registered section, not a route
            assert set(payload) - {"tiers"} == {"totals", "by_component",
                                                "top", "audit", "hbm"}
            assert payload["totals"]["device_bytes"] >= 0
            assert isinstance(payload["audit"]["retired_unfreed"], list)
            # the 404 contract survives, and names the new endpoint
            code, body = self._get(exp.port, "/debug/memx")
            assert code == 404 and "/debug/mem" in body
            code, _ = self._get(exp.port, "/metrics")
            assert code == 200
        finally:
            exp.stop()

    def test_debug_mem_reflects_ledger(self):
        t = obs_mem.account("http_probe", name="probe",
                            device_bytes=12345)
        exp = obs.MetricsExporter(port=0)
        try:
            _, body = self._get(exp.port, "/debug/mem")
            payload = json.loads(body)
            assert "http_probe" in payload["by_component"]
            assert payload["by_component"]["http_probe"][
                "device_bytes"] == 12345
        finally:
            exp.stop()
            obs_mem.release(t)

    def test_debug_payload_top_bound(self):
        toks = [obs_mem.account("payload_probe", name=f"p{i}",
                                device_bytes=i + 1) for i in range(5)]
        try:
            payload = obs_mem.debug_payload(top=2)
            assert len(payload["top"]) <= 2
        finally:
            for t in toks:
                obs_mem.release(t)


# ---------------------------------------------------------------------------
# workspace_bytes attribution (the docstring-audit satellite)
# ---------------------------------------------------------------------------

class TestWorkspaceAttribution:
    def test_brute_force_tile_honors_and_records_budget(self, rng):
        """The XLA tiled brute-force path reads Resources.workspace_bytes
        (the docstring's claim, now pinned): a smaller budget yields a
        smaller recorded workspace, and the recorded bytes never exceed
        the budget it was sized under (beyond the 8-row tile floor)."""
        from raft_tpu.neighbors.brute_force import knn

        x = rng.random((300, 12)).astype(np.float32)
        q = rng.random((64, 12)).astype(np.float32)

        def recorded(ws):
            knn(x, q, k=3, metric="l1",  # l1 never routes to the fused path
                res=Resources(workspace_bytes=ws))
            snap = obs.snapshot()["raft_tpu_mem_workspace_bytes"]["series"]
            return [s["value"] for s in snap
                    if s["labels"].get("op") == "brute_force.knn"][0]

        small_budget = 300 * 14 * 4 * 16
        small = recorded(small_budget)
        big = recorded(64 << 20)
        assert small <= small_budget, (
            "recorded workspace exceeds the budget the tile was sized "
            f"under: {small} > {small_budget}")
        assert small < big, (small, big)


# ---------------------------------------------------------------------------
# overhead (pytest.ini obs_overhead marker)
# ---------------------------------------------------------------------------

@pytest.mark.obs_overhead
def test_disabled_ledger_hot_path_is_one_flag_check():
    """obs.disable() must reduce account() to a single module-flag check:
    per-call added cost vs a trivial call under 5 us (same bound and slack
    discipline as the instrument-decorator smoke)."""
    import time

    def raw():
        return None

    obs.disable()
    try:
        n = 20000
        for _ in range(200):
            raw(), obs_mem.account("ov", device_bytes=1)
        t0 = time.perf_counter()
        for _ in range(n):
            raw()
        t_raw = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n):
            obs_mem.account("ov", device_bytes=1)
        t_led = time.perf_counter() - t0
    finally:
        obs.enable()
    per_call = (t_led - t_raw) / n
    assert per_call < 5e-6, f"disabled account() {per_call * 1e6:.2f} us/call"
    assert obs_mem.totals()["allocations"] >= 0  # and recorded nothing new
    assert not [r for r in obs_mem.breakdown() if r["component"] == "ov"]


# ---------------------------------------------------------------------------
# hbm stats (CPU backend: documented absence, ledger fallback)
# ---------------------------------------------------------------------------

def test_hbm_stats_cpu_fallback_contract():
    """On the CPU test platform memory_stats() reports nothing usable —
    hbm_stats() must return a dict (possibly empty) and never raise; the
    ledger gauges are the documented fallback."""
    out = obs_mem.hbm_stats()
    assert isinstance(out, dict)
    for stats in out.values():
        assert set(stats) <= {"bytes_in_use", "peak_bytes_in_use",
                              "bytes_limit"}

"""Online quality observability (ISSUE 8): recall canary + Wilson interval,
family-drift detection, SLO burn rates, request-level tracing, and the
routed HTTP endpoints.

Deterministic throughout: injected clocks (no wall-clock sleeps in
assertions), seeded canary sampling, and the tune/reference data generator
for the drift families. Tests that read the DEFAULT registry diff
to_json() snapshots, same as test_obs.py.
"""

import json
import math
import urllib.error
import urllib.request

import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.obs import quality, requestlog, slo


@pytest.fixture(autouse=True)
def _metrics_enabled():
    obs.enable()
    yield
    obs.enable()


# ---------------------------------------------------------------------------
# Wilson interval
# ---------------------------------------------------------------------------


@pytest.mark.quality
class TestWilson:
    def test_golden_values(self):
        # classic reference point: 95/100 at z=1.96 -> (0.888, 0.978)
        lo, hi = quality.wilson_interval(95, 100)
        assert lo == pytest.approx(0.8882, abs=5e-4)
        assert hi == pytest.approx(0.9785, abs=5e-4)

    def test_stays_in_unit_interval_at_extremes(self):
        assert quality.wilson_interval(0, 50)[0] == 0.0
        lo, hi = quality.wilson_interval(50, 50)
        assert hi == 1.0 and 0.9 < lo < 1.0  # p=1 still gets a real lower CI

    def test_no_trials_is_vacuous(self):
        assert quality.wilson_interval(0, 0) == (0.0, 1.0)

    def test_narrows_with_samples(self):
        w100 = quality.wilson_interval(90, 100)
        w10000 = quality.wilson_interval(9000, 10000)
        assert (w10000[1] - w10000[0]) < (w100[1] - w100[0]) / 5


# ---------------------------------------------------------------------------
# canary core (fake oracle: exact bookkeeping, no device work)
# ---------------------------------------------------------------------------


def _fake_oracle(answers, dim=4):
    """Oracle returning fixed ids regardless of the query — lets the test
    pin the exact match count."""

    def fn(queries, k):
        q = np.asarray(queries)
        ids = np.tile(np.asarray(answers[:k], np.int32), (q.shape[0], 1))
        return np.zeros((q.shape[0], k), np.float32), ids

    fn.dim = dim
    fn.query_dtype = "float32"
    return fn


@pytest.mark.quality
class TestCanaryCore:
    def test_estimate_and_interval_with_known_overlap(self):
        # oracle says (0,1,2,3); served ids overlap 3 of 4 -> recall 0.75
        canary = quality.RecallCanary(
            _fake_oracle([0, 1, 2, 3]), k=4, sample_rate=1.0,
            buckets=(1, 2, 4), name="t-core", seed=0)
        q = np.zeros((20, 4), np.float32)
        served = np.tile(np.array([0, 1, 2, 99], np.int32), (20, 1))
        before = obs.to_json()
        assert canary.offer(q, served) == 20
        assert canary.drain() == 20
        est = canary.estimate()
        assert est["recall"] == pytest.approx(0.75)
        assert est["scored_slots"] == 80 and est["reranked"] == 20
        assert est["wilson_low"] < 0.75 < est["wilson_high"]
        assert canary.in_interval(0.75)
        assert not canary.in_interval(0.2)
        d = obs.delta(before, obs.to_json())
        assert d['raft_tpu_quality_canary_sampled_total{name="t-core"}'] == 20
        assert d['raft_tpu_quality_canary_reranked_total{name="t-core"}'] == 20
        # per-query recall histogram: 0.75 lands in the (0.7, 0.8] ratio
        # bucket, with labels preserved in the flattened view
        key = ('raft_tpu_quality_canary_recall_bucket'
               '{le="0.8",name="t-core"}')
        assert d[key] == 20, d
        assert obs.quantile("raft_tpu_quality_canary_recall", 0.5,
                            name="t-core") == pytest.approx(0.75, abs=0.06)

    def test_zero_rate_is_one_compare(self):
        canary = quality.RecallCanary(_fake_oracle([0]), k=1,
                                      sample_rate=0.0, name="t-off")
        assert canary.offer(np.zeros((8, 4), np.float32),
                            np.zeros((8, 1), np.int32)) == 0
        assert canary.pending() == 0 and canary.drain() == 0

    def test_reservoir_bounds_memory_and_counts_drops(self):
        canary = quality.RecallCanary(
            _fake_oracle([0, 1]), k=2, sample_rate=1.0, reservoir=8,
            buckets=(1, 2, 4, 8), name="t-res", seed=1)
        before = obs.to_json()
        canary.offer(np.zeros((50, 4), np.float32),
                     np.zeros((50, 2), np.int32))
        assert canary.pending() == 8  # bounded
        d = obs.delta(before, obs.to_json())
        assert d['raft_tpu_quality_canary_dropped_total{name="t-res"}'] == 42
        assert canary.drain() == 8

    def test_sampling_rate_is_respected(self):
        canary = quality.RecallCanary(
            _fake_oracle([0]), k=1, sample_rate=0.1, reservoir=10_000,
            name="t-rate", seed=7)
        kept = canary.offer(np.zeros((5000, 4), np.float32),
                            np.zeros((5000, 1), np.int32))
        assert 350 < kept < 650  # ~500 expected; seeded, so stable

    def test_padded_tail_results_are_discarded(self):
        # 3 queries through a (1,2,4) ladder: one bucket-4 dispatch padded
        # by a repeated row; the estimate must count exactly 3 queries
        canary = quality.RecallCanary(
            _fake_oracle([5, 6]), k=2, sample_rate=1.0, buckets=(1, 2, 4),
            name="t-pad", seed=0)
        canary.offer(np.zeros((3, 4), np.float32),
                     np.tile(np.array([5, 9], np.int32), (3, 1)))
        assert canary.drain() == 3
        est = canary.estimate()
        assert est["scored_slots"] == 6
        assert est["recall"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# canary end-to-end: exact oracle over a MutableIndex + the service tap
# ---------------------------------------------------------------------------


def _small_stack(rng, n=600, d=16, k=5, delta_capacity=64, **svc_kw):
    from raft_tpu import stream
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.serve import SearchService

    x = rng.random((n, d), dtype=np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, seed=0), x)
    m = stream.MutableIndex(
        idx, search_params=ivf_flat.SearchParams(n_probes=8), dataset=x,
        index_params=ivf_flat.IndexParams(n_lists=8, seed=0),
        delta_capacity=delta_capacity, name="q")
    svc = SearchService(max_batch=8, start_workers=False, **svc_kw)
    svc.publish("q", m, k=k)
    return x, m, svc


@pytest.mark.quality
def test_exact_search_matches_fresh_brute_force(rng):
    """MutableIndex.exact_search IS the exact kNN over the live rows:
    bit-equal ids vs a fresh brute-force scan of exactly the live set,
    across upserts, deletes and a compaction."""
    from raft_tpu.neighbors.brute_force import knn

    x, m, svc = _small_stack(rng)
    q = rng.random((16, 16), dtype=np.float32)
    new = rng.random((10, 16), dtype=np.float32)
    gids = m.upsert(new)
    m.delete(np.arange(7))

    def oracle_ids():
        live = np.concatenate([x[7:], new])
        live_gids = np.concatenate([np.arange(7, 600), gids])
        _, pos = knn(live, q, 5)
        return live_gids[np.asarray(pos)]

    _, got = m.exact_search(q, 5)
    np.testing.assert_array_equal(np.asarray(got), oracle_ids())
    m.compact()  # fold the delta; exact view must be unchanged
    _, got2 = m.exact_search(q, 5)
    np.testing.assert_array_equal(np.asarray(got2), oracle_ids())


@pytest.mark.quality
def test_exact_search_requires_store(rng):
    from raft_tpu import stream
    from raft_tpu.core.errors import RaftError
    from raft_tpu.neighbors import ivf_flat

    x = rng.random((200, 8), dtype=np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=4, seed=0), x)
    m = stream.MutableIndex(idx, retain_vectors=False, name="nostore")
    with pytest.raises(RaftError, match="retained row store"):
        m.exact_search(x[:2], 3)


@pytest.mark.quality
def test_canary_through_service_brackets_offline_recall(rng):
    """The full tap: SearchService(canary=) samples flushes, the drain
    reranks against the live corpus, and the offline recall of the same
    served pipeline lands inside the Wilson interval."""
    x, m, svc = _small_stack(rng)
    canary = quality.RecallCanary(
        quality.exact_oracle(m), k=5, sample_rate=1.0, reservoir=512,
        buckets=(1, 2, 4, 8), name="q", seed=3)
    svc._canary = canary  # wired post-construction to reuse _small_stack
    q = rng.random((48, 16), dtype=np.float32)
    futs = [svc.submit("q", q[i:i + 1], 5) for i in range(48)]
    while svc.pump(force=True):
        pass
    served = np.concatenate([np.asarray(f.result()[1]) for f in futs])
    assert canary.pending() == 48
    assert canary.drain() == 48
    # offline truth on the same queries (corpus unchanged since serving)
    _, oids = m.exact_search(q, 5)
    oids = np.asarray(oids)
    offline = float(np.mean([
        len(set(served[i]) & set(oids[i])) / 5 for i in range(48)]))
    est = canary.estimate()
    assert est["recall"] == pytest.approx(offline, abs=1e-9)
    assert canary.in_interval(offline)


@pytest.mark.quality
def test_canary_tap_only_samples_its_own_name(rng):
    """A service serving several names must not feed another stream's
    results to the canary's oracle."""
    from raft_tpu.neighbors import brute_force
    from raft_tpu.serve import SearchService

    x = rng.random((100, 8), dtype=np.float32)
    y = rng.random((100, 8), dtype=np.float32)
    bf_x = brute_force.BruteForce().build(x)
    bf_y = brute_force.BruteForce().build(y)
    canary = quality.RecallCanary(
        quality.exact_oracle(bf_x, dataset=x), k=3, sample_rate=1.0,
        buckets=(1, 2), name="xname")
    svc = SearchService(max_batch=2, start_workers=False, canary=canary)
    svc.publish("xname", bf_x, k=3)
    svc.publish("other", bf_y, k=3)
    fx = svc.submit("xname", x[:1], 3)
    fy = svc.submit("other", y[:1], 3)
    while svc.pump(force=True):
        pass
    fx.result(), fy.result()
    assert canary.pending() == 1  # only the xname flush was offered


@pytest.mark.quality
def test_canary_under_churn_tracks_oracle_with_zero_compiles(rng):
    """The ISSUE 8 integration bar: upserts + deletes + a mid-load
    compaction swap under an injected clock; the canary's estimate tracks
    a fresh-oracle measurement within its Wilson interval, and the whole
    monitored window — sampling, drains, the swap — attributes ZERO cold
    compiles (rehearsal-warmed, same discipline as the churn bench)."""
    from raft_tpu import stream
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.neighbors.brute_force import knn
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.serve import SearchService


    n, d, k, cap = 600, 16, 5, 64
    x = rng.random((n, d), dtype=np.float32)
    churn = rng.random((96, d), dtype=np.float32)
    q = rng.random((32, d), dtype=np.float32)
    ip = ivf_flat.IndexParams(n_lists=8, seed=0)
    sp = ivf_flat.SearchParams(n_probes=8)
    steps, ups, dels = 6, 16, 4

    def schedule(m, svc, canary, sample_box=None):
        for step in range(steps):
            lo, dlo = step * ups, step * dels
            m.upsert(churn[lo:lo + ups], ids=n + np.arange(lo, lo + ups))
            m.delete(np.arange(dlo, dlo + dels))
            if m.stats()["delta_fill"] >= 0.75:
                m.compact()
                svc.publish("churn", m.searcher(), k=k)
                canary.warm()
            # serve a few queries at warmed bucket shapes; the flush tap
            # samples them, the drain reranks immediately (the corpus is
            # frozen between offer and drain, so the estimate is clean)
            qs = q[(step * 8) % 32:(step * 8) % 32 + 8]
            fut = svc.submit("churn", qs, k)
            while svc.pump(force=True):
                pass
            if sample_box is not None:
                sample_box.append((np.asarray(fut.result()[1]),
                                   qs, m.size))
            else:
                fut.result()
            canary.drain()

    def build_stack(name):
        m = stream.MutableIndex(ivf_flat.build(ip, x), search_params=sp,
                                dataset=x, index_params=ip,
                                delta_capacity=cap, name=name)
        canary = quality.RecallCanary(
            quality.exact_oracle(m), k=k, sample_rate=1.0, reservoir=512,
            buckets=(1, 2, 4, 8), name="churn", seed=5)
        svc = SearchService(max_batch=8, start_workers=False, canary=canary)
        svc.publish("churn", m, k=k)
        m.warm(svc.buckets, ks=(k,))
        canary.warm()
        return m, canary, svc

    # rehearsal: compiles every epoch's program set (deterministic schedule)
    m0, canary0, svc0 = build_stack("rehearsal")
    schedule(m0, svc0, canary0)
    del m0, canary0, svc0

    # the attributed live window
    m, canary, svc = build_stack("live")
    samples = []
    with obs_compile.attribution() as rec:
        schedule(m, svc, canary, samples)
    assert rec.compile_s == 0.0 and rec.cache_misses == 0, rec.summary()

    est = canary.estimate()
    assert est["reranked"] == steps * 8 and est["scored_slots"] > 0
    # fresh-oracle offline recall: every step's served results vs a fresh
    # exact kNN over exactly that step's live rows (an independent
    # implementation of the canary's oracle — the bar is the BRACKETING:
    # the live estimate's Wilson interval must contain the offline truth
    # measured over the same window)
    matched = scored = 0
    for step, (served, qs, _) in enumerate(samples):
        del_done, ins_done = (step + 1) * dels, (step + 1) * ups
        live = np.concatenate([x[del_done:], churn[:ins_done]])
        live_gids = np.concatenate([np.arange(del_done, n),
                                    n + np.arange(ins_done)])
        _, pos = knn(live, qs, k)
        gt = live_gids[np.asarray(pos)]
        for i in range(len(qs)):
            matched += len(set(served[i]) & set(gt[i]))
            scored += k
    offline = matched / scored
    assert canary.in_interval(offline), (est, offline)
    # and the estimate itself is quality signal, not noise: uniform data
    # at k=5 has tight f32 margins, so the served recall sits high but
    # below 1.0 — the canary resolves that gap online
    assert 0.85 < est["recall"] <= 1.0, est


# ---------------------------------------------------------------------------
# drift detection (tier-1 acceptance: heavytail fires, isotropic silent)
# ---------------------------------------------------------------------------


@pytest.mark.quality
class TestDrift:
    def _rows(self, heavytail, n=2000, d=32, ncl=64):
        from raft_tpu.tune.reference import _clustered

        x, _ = _clustered(n, d, 8, ncl, seed=29 if heavytail else 23,
                          heavytail=heavytail)
        return np.asarray(x)

    def test_heavytail_fires_isotropic_stays_silent(self):
        from raft_tpu.tune import shape_family

        pinned = shape_family(2000, 32, "bal")
        iso, hot = self._rows(False), self._rows(True)
        before = obs.to_json()

        det = quality.DriftDetector(pinned, name="drift-iso", min_rows=256)
        det.offer_rows(iso[:1024])
        rep = det.check()
        assert rep is not None and not rep["drifted"], rep
        assert det.events == []

        det2 = quality.DriftDetector(pinned, name="drift-hot", min_rows=256)
        det2.offer_rows(hot[:1024])
        rep2 = det2.check()
        assert rep2 is not None and rep2["drifted"], rep2
        assert rep2["observed"].endswith("-skew")
        assert len(det2.events) == 1
        ev = det2.events[0]
        assert ev["event"] == "retune_advised"
        assert ev["auto_apply"] is False  # never auto-apply across classes
        d = obs.delta(before, obs.to_json())
        assert d.get(
            'raft_tpu_quality_retune_advised_total{name="drift-hot"}') == 1
        assert d.get(
            'raft_tpu_quality_family_drift{name="drift-hot"}') == 1.0
        # gauge stays 0 for the silent twin (delta drops unchanged zeros —
        # read the snapshot instead)
        snap = obs.snapshot()["raft_tpu_quality_family_drift"]["series"]
        by = {s["labels"]["name"]: s["value"] for s in snap}
        assert by["drift-iso"] == 0.0

    def test_event_fires_once_per_transition(self):
        from raft_tpu.tune import shape_family

        hot = self._rows(True)
        det = quality.DriftDetector(shape_family(2000, 32, "bal"),
                                    name="drift-once", min_rows=128)
        det.offer_rows(hot[:512])
        det.check()
        det.check()  # still drifted: no second event
        assert len(det.events) == 1
        iso = self._rows(False)
        det2 = quality.DriftDetector(shape_family(2000, 32, "bal"),
                                     name="drift-flap", min_rows=128)
        det2.offer_rows(hot[:512])
        det2.check()  # query feed drifts
        rep = det2.check(rows=iso, n_rows=2000, dim=32, source="compaction")
        assert not rep["drifted"]  # the corpus feed itself is clean...
        # ...but drift state is PER FEED: a clean corpus check must not
        # clear the standing query-side drift (the early-warning case)
        assert det2.drifted()
        assert len(det2.events) == 1  # and must not re-arm the event
        det2.offer_rows(hot[:512])
        det2.check()  # query feed still drifted: no new event
        assert len(det2.events) == 1
        det2.check(rows=hot, n_rows=2000, dim=32, source="compaction")
        assert len(det2.events) == 2  # corpus-feed transition: new event

    def test_below_min_rows_withholds_judgement(self):
        det = quality.DriftDetector("10k-d32-bal", min_rows=256)
        det.offer_rows(np.zeros((10, 32), np.float32))
        assert det.check() is None

    def test_corpus_feed_sees_size_decade_drift(self):
        iso = self._rows(False)
        det = quality.DriftDetector("100k-d32-bal", name="drift-size")
        rep = det.check(rows=iso, n_rows=2000, dim=32, source="compaction")
        assert rep["drifted"] and rep["observed"].startswith("1k-")

    def test_compactor_feeds_corpus_stats(self, rng):
        from raft_tpu import stream
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.tune import shape_family

        x = rng.random((600, 16), dtype=np.float32)
        idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, seed=0), x)
        m = stream.MutableIndex(
            idx, search_params=ivf_flat.SearchParams(n_probes=8), dataset=x,
            index_params=ivf_flat.IndexParams(n_lists=8, seed=0),
            delta_capacity=64, name="dc")
        det = quality.DriftDetector(shape_family(600, 16, "bal"), name="dc")
        comp = stream.Compactor(m, drift=det)
        m.upsert(rng.random((8, 16), dtype=np.float32))
        report = comp.run_once(force=True)
        assert report["drift"] is not None
        assert report["drift"]["source"] == "compaction"
        assert not report["drift"]["drifted"]  # same family: silent


# ---------------------------------------------------------------------------
# SLO tracker: golden burn-rate math + status transitions (injected clock)
# ---------------------------------------------------------------------------


@pytest.mark.quality
class TestSLO:
    def _tracker(self, **pol):
        clk = [0.0]
        policy = slo.SLOPolicy(slot_s=30.0, windows_s=(300.0, 3600.0), **pol)
        return clk, slo.SLOTracker(policy, name="t", clock=lambda: clk[0])

    def test_burn_rate_golden(self):
        clk, t = self._tracker()
        for _ in range(950):
            t.record_admission(True)
        for _ in range(50):
            t.record_admission(False)
        # bad fraction 0.05 over a 0.001 budget -> burn exactly 50
        assert t.burn_rate("availability", 300.0) == pytest.approx(50.0)
        assert t.burn_rate("availability", 3600.0) == pytest.approx(50.0)
        # latency: 99 under the bound + 1 over at target 0.99 -> burn 1.0
        for _ in range(99):
            t.record_request(0.01, 0.05)
        t.record_request(0.5, 0.05)
        assert t.burn_rate("latency", 300.0) == pytest.approx(1.0)
        # quality: 450/500 matched at floor 0.9 -> miss 0.1 / budget 0.1
        t.record_quality(450, 500)
        assert t.burn_rate("quality", 300.0) == pytest.approx(1.0)

    def test_window_expiry_under_injected_clock(self):
        clk, t = self._tracker()
        for _ in range(10):
            t.record_admission(False)
        assert t.burn_rate("availability", 300.0) > 0
        clk[0] = 400.0  # past the short window, inside the long one
        assert t.burn_rate("availability", 300.0) == 0.0
        assert t.burn_rate("availability", 3600.0) > 0
        clk[0] = 4000.0  # everything expired
        assert t.burn_rate("availability", 3600.0) == 0.0

    def test_ready_to_degraded_on_recall_burn(self):
        """The acceptance transition: /healthz flips ready -> degraded when
        the recall SLO burn rate crosses the threshold."""
        clk, t = self._tracker(degraded_burn=1.0, failing_burn=100.0)
        assert t.status() == "ready"  # no events, no burn
        t.record_quality(990, 1000)   # miss 0.01 < budget 0.1: fine
        assert t.status() == "ready"
        t.record_quality(500, 1000)   # cumulative miss ~0.255: burn ~2.5
        assert t.status() == "degraded"
        code, body = t.healthz()
        assert code == 200 and body["status"] == "degraded"
        assert body["objectives"]["quality"]["burn_rates"]["300s"] > 1.0

    def test_failing_maps_to_503(self):
        clk, t = self._tracker(failing_burn=5.0)
        for _ in range(100):
            t.record_admission(False)
        code, body = t.healthz()
        assert code == 503 and body["status"] == "failing"

    def test_multiwindow_and_rule(self):
        """A burst that only the short window still sees must NOT degrade
        once the long window has diluted below threshold — and vice versa:
        stale long-window badness with a clean short window stays ready."""
        clk, t = self._tracker(degraded_burn=1.0, failing_burn=1000.0)
        t.record_quality(0, 200)      # total miss in slot 0
        clk[0] = 600.0                # outside 300s, inside 3600s
        t.record_quality(1000, 1000)  # clean current slot
        rates = t.burn_rates()["quality"]
        # long window: 200 bad / 1200 -> burn ~1.67; short window: clean
        assert rates["300s"] < 1.0 <= rates["3600s"]
        assert t.status() == "ready"

    def test_burn_gauges_published(self):
        before = obs.to_json()
        clk, t = self._tracker()
        t.record_quality(0, 10)
        t.status()
        d = obs.delta(before, obs.to_json())
        key = 'raft_tpu_slo_burn_rate{objective="quality",window="300s"}'
        assert d.get(key, 0) == pytest.approx(10.0)
        assert d.get('raft_tpu_slo_status{name="t"}', 0) == 2.0  # failing
        assert d.get('raft_tpu_slo_events_total'
                     '{objective="quality",outcome="bad"}') == 10.0

    def test_policy_validation(self):
        with pytest.raises(Exception, match="multiple"):
            slo.SLOTracker(slo.SLOPolicy(slot_s=30.0, windows_s=(100.0,)))
        with pytest.raises(Exception, match="targets"):
            slo.SLOTracker(slo.SLOPolicy(availability_target=1.5))


# ---------------------------------------------------------------------------
# request log: rid threading, spans, exemplars
# ---------------------------------------------------------------------------


@pytest.mark.quality
class TestRequestLog:
    def test_spans_thread_through_service_and_stream(self, rng):
        clk = [0.0]
        rl = requestlog.RequestLog(capacity=32, clock=lambda: clk[0])
        x, m, svc = _small_stack(rng, request_log=rl)
        fut = svc.submit("q", x[:2], 5)
        while svc.pump(force=True):
            pass
        fut.result()
        entries = rl.recent()
        assert len(entries) == 1
        e = entries[0]
        assert e["rid"].startswith("req-") and e["outcome"] == "ok"
        assert e["stream"] == "q.k5" and e["rows"] == 2 and e["bucket"] == 2
        for span in ("queue", "flush", "serve/lease", "serve/dispatch",
                     "serve/complete", "serve/search"):
            assert span in e["spans_ms"], e["spans_ms"]
        # the stream's stages are device scopes in the profiler trace
        # (stream.delta, stream.merge, ...), not host walls here
        assert not [s for s in e["spans_ms"] if s.startswith("stream")]
        # the flush leased version 1 of the epoch-0 mutable
        assert e["notes"]["version"] == 1
        assert e["notes"]["stream_epoch"] == 0
        assert e["total_ms"] >= e["spans_ms"]["flush"]

    def test_expired_requests_are_traced_and_burn_latency(self, rng):
        clk = [0.0]
        rl = requestlog.RequestLog(clock=lambda: clk[0])
        tracker = slo.SLOTracker(clock=lambda: clk[0])
        x, m, svc = _small_stack(rng, request_log=rl, slo=tracker,
                                 clock=lambda: clk[0])
        svc.submit("q", x[:1], 5, timeout_s=0.5)
        clk[0] = 1.0  # expire in queue
        svc.pump(force=True)
        e = rl.recent()[-1]
        assert e["outcome"] == "expired"
        assert e["spans_ms"]["queue"] == pytest.approx(1000.0)
        assert "flush" not in e["spans_ms"]
        # an expired request is a latency-bad SLO outcome: a saturated
        # service shedding at the deadline must burn budget, not stay
        # 'ready' over the surviving minority
        assert tracker.burn_rate("latency", 300.0) > 0

    def test_ring_slowest_and_exemplars(self):
        clk = [0.0]
        rl = requestlog.RequestLog(capacity=4, clock=lambda: clk[0])
        for i, total in enumerate((0.002, 0.030, 0.004, 0.0007, 0.009)):
            rid = rl.begin("s", 1)
            rl.complete(rid, stream="s", rows=1, bucket=1,
                        spans={"queue": total / 2, "flush": total / 2})
        assert len(rl.recent()) == 4  # capacity-bounded: the oldest fell off
        slowest = rl.slowest(2)
        assert slowest[0]["total_ms"] == pytest.approx(30.0)
        ex = rl.exemplars()
        # 0.03s lands in the le=0.05 latency bucket; the exemplar links it
        assert ex["0.05"]["rid"] == slowest[0]["rid"]
        payload = rl.to_json()
        assert set(payload) == {"capacity", "in_flight", "recent", "slowest",
                                "exemplars"}
        assert payload["in_flight"] == []  # everything begun was completed

    def test_in_flight_visible_until_completed(self):
        clk = [0.0]
        rl = requestlog.RequestLog(capacity=4, in_flight_capacity=4,
                                   clock=lambda: clk[0])
        rid = rl.begin("s", 2)
        inf = rl.in_flight()
        assert inf == [{"rid": rid, "stream": "s", "rows": 2,
                        "admitted_at": 0.0}]
        rl.complete(rid, stream="s", rows=2, spans={"queue": 0.001})
        assert rl.in_flight() == []
        # never-completed rids are evicted past in_flight_capacity (a cap
        # sized to the serve queue bound, so only leaked entries go)
        stale = rl.begin("s", 1)
        for _ in range(4):
            rl.begin("s", 1)
        assert stale not in {e["rid"] for e in rl.in_flight()}
        assert len(rl.in_flight()) == 4

    def test_none_rid_is_noop(self):
        rl = requestlog.RequestLog()
        rl.complete(None, stream="s", rows=1, spans={"queue": 1.0})
        assert rl.recent() == []


# ---------------------------------------------------------------------------
# HTTP endpoints: explicit routing (ISSUE 8 satellite)
# ---------------------------------------------------------------------------


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, resp.headers.get("Content-Type", ""), \
                resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type", ""), e.read().decode()


@pytest.mark.quality
class TestHttpRouting:
    def test_routes_and_404(self):
        clk = [0.0]
        tracker = slo.SLOTracker(clock=lambda: clk[0])
        rl = requestlog.RequestLog(clock=lambda: clk[0])
        rid = rl.begin("s", 1)
        rl.complete(rid, stream="s", rows=1, spans={"queue": 0.001,
                                                    "flush": 0.002})
        obs.counter("raft_tpu_items_total", "rows").inc(1, op="route-test")
        with obs.MetricsExporter(port=0, slo=tracker, request_log=rl) as exp:
            base = f"http://127.0.0.1:{exp.port}"
            code, ctype, body = _get(base + "/metrics")
            assert code == 200 and ctype.startswith("text/plain")
            assert 'raft_tpu_items_total{op="route-test"}' in body
            code, ctype, body = _get(base + "/healthz")
            assert code == 200 and ctype.startswith("application/json")
            assert json.loads(body)["status"] == "ready"
            code, _, body = _get(base + "/debug/requests")
            assert code == 200
            payload = json.loads(body)
            assert payload["recent"][0]["rid"] == rid
            assert payload["exemplars"]
            # the satellite: unknown paths 404 loudly — a scrape-config
            # typo must not silently receive the exposition format
            for bad in ("/", "/metrcs", "/metrics/extra", "/debug"):
                code, _, body = _get(base + bad)
                assert code == 404, bad
                assert "/metrics, /healthz, /debug/requests" in body

    def test_healthz_503_on_failing_and_no_sources(self):
        clk = [0.0]
        tracker = slo.SLOTracker(
            slo.SLOPolicy(failing_burn=5.0), clock=lambda: clk[0])
        for _ in range(50):
            tracker.record_admission(False)
        with obs.MetricsExporter(port=0, slo=tracker) as exp:
            base = f"http://127.0.0.1:{exp.port}"
            code, _, body = _get(base + "/healthz")
            assert code == 503 and json.loads(body)["status"] == "failing"
            code, _, _ = _get(base + "/debug/requests")
            assert code == 404  # no request log attached
        with obs.MetricsExporter(port=0) as exp:
            code, _, body = _get(f"http://127.0.0.1:{exp.port}/healthz")
            assert code == 200
            assert json.loads(body)["note"] == "no SLO tracker attached"


# ---------------------------------------------------------------------------
# metrics satellites: ratio buckets + to_json bucket flattening
# ---------------------------------------------------------------------------


@pytest.mark.quality
class TestMetricsSatellites:
    def test_both_bucket_families_and_quantiles(self):
        """The satellite's unit test: a latency-ladder histogram and a 0-1
        ratio histogram side by side, with quantile() correct on each."""
        reg = obs.Registry()
        lat = reg.histogram("lat_seconds")  # DEFAULT_BUCKETS
        ratio = reg.histogram("recall_ratio", buckets=obs.RATIO_BUCKETS)
        for v in (0.003, 0.004, 0.020):
            lat.observe(v, op="x")
        for v in (0.93, 0.97, 0.97, 0.50):
            ratio.observe(v, op="x")
        # latency median lands in (0.0025, 0.005]
        assert 0.0025 <= lat.quantile(0.5, op="x") <= 0.005
        # ratio median lands in (0.9, 0.95] — a latency ladder would have
        # dumped all four into (0.25, 0.5]/(0.5, 1.0] and reported ~garbage
        assert 0.9 <= ratio.quantile(0.5, op="x") <= 0.95
        assert 0.95 <= ratio.quantile(0.9, op="x") <= 0.99
        assert obs.RATIO_BUCKETS[-1] == 1.0  # nothing above the unit range
        snap = reg.snapshot()["recall_ratio"]["series"][0]
        assert snap["buckets"]["1.0"] == 4 and snap["buckets"]["+Inf"] == 4

    def test_rebucketing_conflict_raises(self):
        reg = obs.Registry()
        reg.histogram("h", buckets=(0.5, 1.0))
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("h", buckets=obs.RATIO_BUCKETS)
        reg.histogram("h", buckets=(0.5, 1.0))  # same ladder: fine

    def test_to_json_flattens_buckets_with_labels(self):
        """The BENCH-artifact satellite: histogram series flatten with
        their label sets preserved — per-bucket keys carry the series
        labels PLUS le, and delta() subtracts them."""
        reg = obs.Registry()
        h = reg.histogram("r", buckets=(0.5, 1.0))
        h.observe(0.3, name="a", kind="x")
        h.observe(0.9, name="b", kind="x")
        j = reg.to_json()
        assert j['r_bucket{kind="x",le="0.5",name="a"}'] == 1
        assert j['r_bucket{kind="x",le="0.5",name="b"}'] == 0
        assert j['r_bucket{kind="x",le="1.0",name="b"}'] == 1
        assert j['r_bucket{kind="x",le="+Inf",name="a"}'] == 1
        assert j['r_sum{kind="x",name="a"}'] == pytest.approx(0.3)
        before = dict(j)
        h.observe(0.4, name="a", kind="x")
        d = obs.delta(before, reg.to_json())
        assert d['r_bucket{kind="x",le="0.5",name="a"}'] == 1
        assert d['r_count{kind="x",name="a"}'] == 1

    def test_math_helpers_stay_finite(self):
        # quantile on the ratio family's +Inf bucket reports the last
        # finite bound (1.0), never inf
        reg = obs.Registry()
        h = reg.histogram("r", buckets=obs.RATIO_BUCKETS)
        h.observe(1.0)
        assert math.isfinite(h.quantile(0.99))

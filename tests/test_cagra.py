"""CAGRA tests — recall acceptance vs brute force (reference analogue:
cpp/test/neighbors/ann_cagra.cuh)."""

import numpy as np
import pytest
from scipy.spatial import distance as sp_dist

from raft_tpu.neighbors import cagra
from raft_tpu.random import make_blobs


def _recall(got_ids, true_ids):
    hits = 0
    for g, t in zip(got_ids, true_ids):
        hits += len(set(g.tolist()) & set(t.tolist()))
    return hits / true_ids.size


@pytest.fixture(scope="module")
def data():
    # uniform data, like real ANN benchmark distributions: on well-separated
    # blobs a kNN graph has no inter-cluster edges, so graph traversal cannot
    # cross clusters lacking an entry point (the reference's CAGRA has the
    # same property — it's inherent to graph ANN, not an implementation bug)
    rng = np.random.default_rng(0)
    x = rng.random((4000, 24)).astype(np.float32)
    q = rng.random((60, 24)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def index(data):
    x, _ = data
    return cagra.build(
        cagra.IndexParams(intermediate_graph_degree=48, graph_degree=24, seed=0), x
    )


class TestBuild:
    def test_graph_shape_and_validity(self, index, data):
        x, _ = data
        g = np.asarray(index.graph)
        assert g.shape == (4000, 24)
        assert g.min() >= 0 and g.max() < 4000
        # no self-edges
        assert not (g == np.arange(4000)[:, None]).any()

    def test_knn_graph_quality(self, data):
        """Intermediate kNN graph edges should largely be true neighbors."""
        x, _ = data
        params = cagra.IndexParams(intermediate_graph_degree=16, graph_degree=8, seed=0)
        g = np.asarray(cagra.build_knn_graph(params, x))
        true_i = np.argsort(sp_dist.cdist(x[:200], x, "sqeuclidean"), 1)[:, 1:17]
        rec = _recall(g[:200], true_i)
        assert rec > 0.8, rec

    def test_optimize_degree(self, data):
        x, _ = data
        params = cagra.IndexParams(intermediate_graph_degree=32, graph_degree=16, seed=0)
        g = cagra.build_knn_graph(params, x)
        opt = np.asarray(cagra.optimize(g, 16))
        assert opt.shape == (4000, 16)
        assert opt.min() >= 0


class TestSearch:
    def test_recall(self, index, data):
        x, q = data
        d, i = cagra.search(cagra.SearchParams(itopk_size=64), index, q, k=10)
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        rec = _recall(np.asarray(i), true_i)
        assert rec > 0.9, rec

    def test_distances_are_exact_for_found_ids(self, index, data):
        x, q = data
        d, i = cagra.search(cagra.SearchParams(itopk_size=64), index, q, k=5)
        full = sp_dist.cdist(q, x, "sqeuclidean")
        got = np.take_along_axis(full, np.asarray(i), 1)
        np.testing.assert_allclose(np.asarray(d), got, atol=1e-2, rtol=1e-3)

    def test_wider_beam_improves_recall(self, index, data):
        x, q = data
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        recalls = []
        for itopk in (16, 64, 128):
            _, i = cagra.search(cagra.SearchParams(itopk_size=itopk), index, q, k=10)
            recalls.append(_recall(np.asarray(i), true_i))
        assert recalls[-1] >= recalls[0]
        assert recalls[-1] > 0.95, recalls

    def test_search_width(self, index, data):
        x, q = data
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        _, i = cagra.search(cagra.SearchParams(itopk_size=64, search_width=4), index, q, k=10)
        assert _recall(np.asarray(i), true_i) > 0.9


class TestSerialize:
    def test_roundtrip(self, tmp_path, index, data):
        _, q = data
        p = str(tmp_path / "cagra.bin")
        cagra.save(index, p)
        idx2 = cagra.load(p)
        d1, i1 = cagra.search(cagra.SearchParams(itopk_size=32), index, q, k=5)
        d2, i2 = cagra.search(cagra.SearchParams(itopk_size=32), idx2, q, k=5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


class TestSeedPool:
    def test_seeded_entries_on_clustered_data(self):
        """Scored seed-pool entries must recover recall on well-separated
        clusters, where purely random entries (seed_pool=0, the reference's
        seeding) start in the wrong basin and the pruned graph has no
        cross-cluster edges to escape through."""
        x, _ = make_blobs(3000, 24, n_clusters=30, cluster_std=0.5, seed=2)
        x = np.asarray(x)
        idx = cagra.build(
            cagra.IndexParams(intermediate_graph_degree=24, graph_degree=12, seed=0), x
        )
        q = x[:150]
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        _, i_seeded = cagra.search(cagra.SearchParams(itopk_size=32), idx, q, k=10)
        rec_seeded = _recall(np.asarray(i_seeded), true_i)
        _, i_rand = cagra.search(cagra.SearchParams(itopk_size=32, seed_pool=0), idx, q, k=10)
        rec_rand = _recall(np.asarray(i_rand), true_i)
        assert rec_seeded > 0.9, (rec_seeded, rec_rand)
        assert rec_seeded >= rec_rand

    def test_search_seed_contract(self, index, data):
        """Same seed → bitwise-identical results; a different seed draws a
        different entry pool (VERDICT r3 weak #3) but stays a valid search."""
        x, q = data
        sp0 = cagra.SearchParams(itopk_size=32, seed=0)
        d1, i1 = cagra.search(sp0, index, q, k=10)
        d2, i2 = cagra.search(sp0, index, q, k=10)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
        _, i3 = cagra.search(cagra.SearchParams(itopk_size=32, seed=3),
                             index, q, k=10)
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        assert _recall(np.asarray(i3), true_i) > 0.9


class TestFusedHop:
    """The fused Pallas hop kernel (ops/cagra_hop.py, VERDICT r4 #1) must
    reproduce the XLA hop loop: same beam semantics (dedup, lowest-id ties,
    visited tracking; the arena is sorted once after the loop), so same
    neighbor sets and distances up to summation order."""

    @pytest.mark.parametrize("impl", ["fused_arena"])
    def test_matches_xla_loop(self, index, data, monkeypatch, impl):
        monkeypatch.setenv("RAFT_TPU_CAGRA_HOP_INTERPRET", "1")
        x, q = data
        d_x, i_x = cagra.search(
            cagra.SearchParams(itopk_size=32, hop_impl="xla"), index, q, k=10)
        d_f, i_f = cagra.search(
            cagra.SearchParams(itopk_size=32, hop_impl=impl), index, q, k=10)
        i_x, i_f = np.asarray(i_x), np.asarray(i_f)
        # id sets match except where summation-order ULP noise reorders
        # near-ties at the beam boundary
        overlap = np.mean([len(set(i_x[r]) & set(i_f[r])) / 10
                           for r in range(i_x.shape[0])])
        assert overlap > 0.99, overlap
        np.testing.assert_allclose(np.sort(np.asarray(d_f), 1),
                                   np.sort(np.asarray(d_x), 1),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("impl", ["fused_arena"])
    def test_recall_on_clustered(self, monkeypatch, impl):
        monkeypatch.setenv("RAFT_TPU_CAGRA_HOP_INTERPRET", "1")
        x, _ = make_blobs(3000, 24, n_clusters=30, cluster_std=0.5, seed=2)
        x = np.asarray(x)
        idx = cagra.build(cagra.IndexParams(
            intermediate_graph_degree=24, graph_degree=12, seed=0), x)
        q = x[:150]
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        _, ids = cagra.search(cagra.SearchParams(
            itopk_size=32, hop_impl=impl), idx, q, k=10)
        rec = _recall(np.asarray(ids), true_i)
        assert rec > 0.9, rec

    def test_fused_sqrt_metric(self, data, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_CAGRA_HOP_INTERPRET", "1")
        import dataclasses

        from raft_tpu.distance.types import DistanceType

        x, q = data
        idx = cagra.build(cagra.IndexParams(
            intermediate_graph_degree=24, graph_degree=12,
            metric="euclidean", seed=0), x)
        assert idx.metric in (DistanceType.L2SqrtExpanded,
                              DistanceType.L2SqrtUnexpanded)
        d_f, i_f = cagra.search(cagra.SearchParams(
            itopk_size=32, hop_impl="fused_arena"), idx, q, k=5)
        d_true = np.sqrt(((q[:, None, :] - x[np.asarray(i_f)]) ** 2).sum(-1))
        np.testing.assert_allclose(np.asarray(d_f), d_true, rtol=1e-4,
                                   atol=1e-4)

    @pytest.mark.parametrize("impl", ["fused_arena"])
    def test_matches_xla_loop_width2(self, index, data, monkeypatch, impl):
        """search_width=2: two picks per hop, candidate block 2*deg — must
        still track the XLA loop."""
        monkeypatch.setenv("RAFT_TPU_CAGRA_HOP_INTERPRET", "1")
        _, q = data
        d_x, i_x = cagra.search(cagra.SearchParams(
            itopk_size=32, search_width=2, hop_impl="xla"), index, q, k=10)
        d_f, i_f = cagra.search(cagra.SearchParams(
            itopk_size=32, search_width=2, hop_impl=impl), index, q, k=10)
        i_x, i_f = np.asarray(i_x), np.asarray(i_f)
        overlap = np.mean([len(set(i_x[r]) & set(i_f[r])) / 10
                           for r in range(i_x.shape[0])])
        assert overlap > 0.95, overlap
        np.testing.assert_allclose(np.sort(np.asarray(d_f), 1),
                                   np.sort(np.asarray(d_x), 1),
                                   rtol=1e-4, atol=1e-4)

    def test_eligibility_guard(self, index, data):
        from raft_tpu.core import RaftError

        _, q = data
        # itopk 64 + 3*24 = 136 > 128: pool does not fit one register row
        with pytest.raises(RaftError, match="hop_impl='fused_arena'"):
            cagra.search(cagra.SearchParams(
                itopk_size=64, search_width=3, hop_impl="fused_arena"),
                index, q, k=5)

    @pytest.mark.parametrize("impl", ["fused", "fused_arena_smem"])
    def test_deleted_hop_impls_rejected(self, impl):
        """The sorted-extraction and r05 arena hops were deleted: naming
        them fails loudly with the accepted values, never falls back."""
        from raft_tpu.core import RaftError

        with pytest.raises(RaftError,
                           match="'auto', 'xla' or 'fused_arena'"):
            cagra.resolve_hop_impl(
                cagra.SearchParams(itopk_size=32, hop_impl=impl), 12, 24)


class TestSeedPoolAuto:
    """The measured seed_pool autotune (VERDICT r4 #4): the build reads the
    clump scale off the knn graph's neighbor-distance jump profile and sizes
    the entry pool to the local-mode count."""

    @staticmethod
    def _clumpy(n_clumps, clump, d, scale, rng):
        centers = rng.random((n_clumps, d)).astype(np.float32)
        x = (np.repeat(centers, clump, axis=0)
             + scale * rng.standard_normal((n_clumps * clump, d))
             .astype(np.float32))
        return x

    def test_detects_clumps_and_sizes_pool(self):
        """65536 points in 16384 4-point near-duplicate clumps, knn graph =
        3 clump-mates + 5 far points: jump at position 3 → ~16k modes →
        pool 32768 (> the 16384 default the isotropic path keeps)."""
        rng = np.random.default_rng(0)
        n_clumps, clump, d = 16384, 4, 8
        x = self._clumpy(n_clumps, clump, d, 1e-3, rng)
        n = n_clumps * clump
        i = np.arange(n)
        mates = (i // clump)[:, None] * clump + np.arange(clump)[None, :]
        mates = np.stack(
            [mates[r][mates[r] != r] for r in range(0, n)], axis=0)
        far = rng.integers(0, n, (n, 5))
        g = np.concatenate([mates, far], axis=1).astype(np.int32)
        pool = cagra.estimate_seed_pool(x, g, seed=0)
        assert pool == 32768, pool

    def test_isotropic_keeps_default(self):
        """Uniform data + random graph: no >=2x jump — hint 0 (default pool;
        a bigger pool on isotropic data is a pure QPS loss, r02)."""
        rng = np.random.default_rng(1)
        n, d = 8192, 16
        x = rng.random((n, d)).astype(np.float32)
        g = rng.integers(0, n, (n, 8)).astype(np.int32)
        assert cagra.estimate_seed_pool(x, g, seed=0) == 0

    def test_small_modes_keep_default(self):
        """Clumpy but few modes: 2*modes <= 16384 — the default pool already
        covers them, hint stays 0."""
        rng = np.random.default_rng(2)
        x = self._clumpy(512, 16, 8, 1e-3, rng)
        n = 512 * 16
        i = np.arange(n)
        mates = (i // 16)[:, None] * 16 + np.arange(16)[None, :]
        mates = np.stack(
            [mates[r][mates[r] != r][:7] for r in range(n)], axis=0)
        far = rng.integers(0, n, (n, 5))
        g = np.concatenate([mates, far], axis=1).astype(np.int32)
        assert cagra.estimate_seed_pool(x, g, seed=0) == 0

    def test_auto_resolves_to_hint(self, index, data):
        """seed_pool=-1 (default) must search exactly like an explicit pool
        equal to the index hint."""
        import dataclasses

        _, q = data
        idx2 = dataclasses.replace(index, seed_pool_hint=2048)
        d_auto, i_auto = cagra.search(
            cagra.SearchParams(itopk_size=32), idx2, q, k=10)
        d_exp, i_exp = cagra.search(
            cagra.SearchParams(itopk_size=32, seed_pool=2048), index, q, k=10)
        np.testing.assert_array_equal(np.asarray(i_auto), np.asarray(i_exp))
        np.testing.assert_array_equal(np.asarray(d_auto), np.asarray(d_exp))

    def test_hint_survives_serialization(self, tmp_path, index):
        import dataclasses

        idx2 = dataclasses.replace(index, seed_pool_hint=32768)
        p = str(tmp_path / "cagra_hint.bin")
        cagra.save(idx2, p)
        assert cagra.load(p).seed_pool_hint == 32768


class TestBuildProbesAuto:
    def test_auto_adopts_cheap_probes_on_clustered_data(self, caplog):
        """The measured build_n_probes auto (chunk-0 p=32 vs p=8/16 edge
        overlap) must adopt a cheap setting on clustered data — where the
        full-build A/B showed identical recall — and keep the graph good."""
        import logging

        x, _ = make_blobs(3000, 24, n_clusters=30, cluster_std=0.5, seed=4)
        x = np.asarray(x)
        params = cagra.IndexParams(
            intermediate_graph_degree=16, graph_degree=8,
            build_chunk=1000, seed=0)
        with caplog.at_level(logging.INFO, logger="raft_tpu"):
            g = np.asarray(cagra.build_knn_graph(params, x))
        assert g.shape == (3000, 16)
        assert any("build_n_probes auto" in r.message for r in caplog.records)
        true_i = np.argsort(sp_dist.cdist(x[:200], x, "sqeuclidean"), 1)[:, 1:17]
        rec = _recall(g[:200], true_i)
        assert rec > 0.8, rec


class TestByteDatasets:
    """int8/uint8 datasets end-to-end (reference: the dtype-generic
    cagra::index<T> int8_t/uint8_t instantiations). The index stores native
    bytes — uint8 shifted by -128 into the s8 domain, L2-invariant — and the
    hop paths upcast to f32 at the tile level, where every 8-bit integer is
    exact, so byte results are checked against the f64 image of the same
    bytes rather than a loosened threshold."""

    @pytest.fixture(scope="class")
    def idata(self):
        # uniform bytes (see the module fixture's note on blobs vs graphs)
        rng = np.random.default_rng(11)
        xu = rng.integers(0, 256, (3000, 24), dtype=np.uint8)
        qu = rng.integers(0, 256, (50, 24), dtype=np.uint8)
        return xu, qu

    @pytest.fixture(scope="class")
    def u8_index(self, idata):
        xu, _ = idata
        return cagra.build(cagra.IndexParams(
            intermediate_graph_degree=48, graph_degree=24, seed=0), xu)

    def test_native_byte_storage(self, u8_index):
        import jax.numpy as jnp

        assert u8_index.data_kind == "uint8"
        assert u8_index.dataset.dtype == jnp.int8  # shifted s8 bytes

    def test_recall_and_exact_distances(self, u8_index, idata):
        xu, qu = idata
        d, i = cagra.search(cagra.SearchParams(itopk_size=64), u8_index, qu, k=10)
        d2 = ((qu[:, None, :].astype(np.float64)
               - xu[None].astype(np.float64)) ** 2).sum(-1)
        true_i = np.argsort(d2, 1)[:, :10]
        rec = _recall(np.asarray(i), true_i)
        assert rec > 0.9, rec
        # the -128 shift is L2-invariant and 8-bit values are exact in f32:
        # reported distances are the true integer byte-domain distances
        got = np.take_along_axis(d2, np.asarray(i), 1)
        np.testing.assert_allclose(np.asarray(d), got, rtol=1e-6)

    def test_int8_matches_uint8_shifted(self, u8_index, idata):
        """uint8 ingestion = the pre-shifted int8 build, bit for bit."""
        xu, qu = idata
        xs = (xu.astype(np.int16) - 128).astype(np.int8)
        qs = (qu.astype(np.int16) - 128).astype(np.int8)
        idx = cagra.build(cagra.IndexParams(
            intermediate_graph_degree=48, graph_degree=24, seed=0), xs)
        assert idx.data_kind == "int8"
        np.testing.assert_array_equal(np.asarray(idx.graph),
                                      np.asarray(u8_index.graph))
        _, i_s = cagra.search(cagra.SearchParams(itopk_size=64), idx, qs, k=10)
        _, i_u = cagra.search(cagra.SearchParams(itopk_size=64), u8_index, qu, k=10)
        np.testing.assert_array_equal(np.asarray(i_s), np.asarray(i_u))

    def test_float_queries_on_uint8_index(self, u8_index, idata):
        _, qu = idata
        _, i_b = cagra.search(cagra.SearchParams(itopk_size=64), u8_index, qu, k=10)
        _, i_f = cagra.search(cagra.SearchParams(itopk_size=64), u8_index,
                              qu.astype(np.float32), k=10)
        np.testing.assert_array_equal(np.asarray(i_b), np.asarray(i_f))

    def test_query_dtype_guard(self, u8_index, idata):
        from raft_tpu.core import RaftError

        _, qu = idata
        qs = (qu.astype(np.int16) - 128).astype(np.int8)
        with pytest.raises(RaftError, match="stores uint8"):
            cagra.search(cagra.SearchParams(itopk_size=64), u8_index, qs, k=10)

    def test_fused_hop_matches_xla_on_bytes(self, u8_index, idata, monkeypatch):
        """The Pallas hop takes int8 candidate blocks (quarter the DMA
        bytes) and upcasts in-kernel — must track the XLA loop."""
        monkeypatch.setenv("RAFT_TPU_CAGRA_HOP_INTERPRET", "1")
        _, qu = idata
        d_x, i_x = cagra.search(cagra.SearchParams(
            itopk_size=32, hop_impl="xla"), u8_index, qu, k=10)
        d_f, i_f = cagra.search(cagra.SearchParams(
            itopk_size=32, hop_impl="fused_arena"), u8_index, qu, k=10)
        i_x, i_f = np.asarray(i_x), np.asarray(i_f)
        overlap = np.mean([len(set(i_x[r]) & set(i_f[r])) / 10
                           for r in range(i_x.shape[0])])
        assert overlap > 0.95, overlap
        np.testing.assert_allclose(np.sort(np.asarray(d_f), 1),
                                   np.sort(np.asarray(d_x), 1),
                                   rtol=1e-4, atol=1e-4)

    def test_roundtrip_preserves_bytes(self, tmp_path, u8_index, idata):
        import jax.numpy as jnp

        _, qu = idata
        p = str(tmp_path / "cagra_u8.bin")
        cagra.save(u8_index, p)
        idx2 = cagra.load(p)
        assert idx2.data_kind == "uint8"
        assert idx2.dataset.dtype == jnp.int8
        d1, i1 = cagra.search(cagra.SearchParams(itopk_size=32), u8_index, qu, k=5)
        d2, i2 = cagra.search(cagra.SearchParams(itopk_size=32), idx2, qu, k=5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_seed_pool_log_reports_calibrated_threshold(caplog):
    """The seed_pool autotune logs must interpolate the threshold constant
    actually applied (_SEED_JUMP_RATIO = 2.0), not a stale literal
    (ADVICE r5: the success log said "jump >=4x" while the rule was 2.0)."""
    import logging

    rng = np.random.default_rng(0)
    # >= 4096 rows and >= 8 graph columns: the autotune's lower bound —
    # smaller inputs return the default pool without logging
    x = rng.random((4200, 16)).astype(np.float32)
    params = cagra.IndexParams(intermediate_graph_degree=16, graph_degree=8,
                               build_chunk=2100, seed=0)
    g = cagra.build_knn_graph(params, np.asarray(x))
    with caplog.at_level(logging.INFO, logger="raft_tpu"):
        cagra.estimate_seed_pool(x, g, seed=0)
    msgs = [r.getMessage() for r in caplog.records
            if "seed_pool auto" in r.getMessage()]
    assert msgs, "autotune logged nothing"
    want = ">=%.0fx" % cagra._SEED_JUMP_RATIO
    assert all("4x" not in m or want == ">=4x" for m in msgs), msgs
    assert any(want in m for m in msgs), (want, msgs)


@pytest.mark.slow
def test_shard_local_vs_global_graph_recall_64k():
    """VERDICT r5 item 10: quantify the recall cost of shard-local CAGRA
    graphs (parallel.cagra.build — one independent graph per dataset shard,
    merged over ICI at search) vs ONE global graph over the same rows, at a
    realistic scale on the 8-device mesh: 64k rows / 8 shards of 8k.

    Expectation (docs/using_comms.md "Shard-local CAGRA graphs" records the
    measured numbers): the merged result's recall does NOT degrade vs the
    global graph — each true neighbor lives in exactly one shard, the beam
    searches its 8x-smaller graph with the SAME itopk (an easier problem),
    and the allgather+select_k merge is exact over the per-shard top-k. The
    cost is compute (S beams per query + the merge), not recall; per-shard
    graphs stop being acceptable only when a shard falls below the point
    where graph search beats brute force (~thousands of rows), not for
    recall reasons.
    """
    import jax
    from jax.sharding import Mesh

    from raft_tpu.comms.comms import Comms
    from raft_tpu.neighbors import brute_force
    from raft_tpu.parallel import cagra as pcagra

    n, d, m, k = 65536, 64, 256, 10
    rng = np.random.default_rng(7)
    # clustered (the regime where entry-point coverage matters; uniform data
    # would hide shard effects behind an easy neighbor structure)
    centers = rng.random((256, d)).astype(np.float32) * 10.0
    lab = rng.integers(0, 256, n)
    x = (centers[lab] + 0.5 * rng.standard_normal((n, d))).astype(np.float32)
    qlab = rng.integers(0, 256, m)
    q = (centers[qlab] + 0.5 * rng.standard_normal((m, d))).astype(np.float32)

    _, gt = brute_force.knn(x, q, k)
    gt = np.asarray(gt)

    params = cagra.IndexParams(seed=0)
    sp = cagra.SearchParams(itopk_size=32)

    g_idx = cagra.build(params, x)
    _, g_ids = cagra.search(sp, g_idx, q, k)
    recall_global = _recall(np.asarray(g_ids), gt)

    comms = Comms(Mesh(np.array(jax.devices()[:8]), ("data",)), "data")
    s_idx = pcagra.build(comms, params, x)
    assert s_idx.n_shards == 8 and s_idx.rows_per_shard == n // 8
    _, s_ids = pcagra.search(comms, sp, s_idx, q, k)
    recall_sharded = _recall(np.asarray(s_ids), gt)

    # sanity floors + the documented relationship: shard-local graphs hold
    # recall at this scale (gap bound loose enough for seed noise; the
    # measured r06 gap is recorded in docs/using_comms.md)
    assert recall_global > 0.85, recall_global
    assert recall_sharded > 0.85, recall_sharded
    assert recall_sharded >= recall_global - 0.03, (
        recall_sharded, recall_global)


@pytest.mark.slow
def test_build_select_impl_pallas_matches_xla():
    """IndexParams.build_select_impl routes the build self-search's
    k = gpu_top_k + 1 candidate selects through the wide-k Pallas selector
    (the r05-commissioned call site, VERDICT r5 #3). Both impls must produce
    the IDENTICAL knn graph — the selector is exact with lax.top_k tie
    semantics — and this exercises the two-wide-instances-per-program
    composition (per-chunk + final merge) end to end through ivf_pq."""
    rng = np.random.default_rng(5)
    x = np.asarray(make_blobs(800, 16, n_clusters=10, cluster_std=0.6,
                              seed=3)[0])
    graphs = {}
    for impl in ("xla", "pallas"):
        params = cagra.IndexParams(
            intermediate_graph_degree=48, graph_degree=16, refine_rate=2.0,
            build_n_probes=8, build_chunk=800, build_select_impl=impl,
            seed=0)
        graphs[impl] = np.asarray(cagra.build_knn_graph(params, x))
    np.testing.assert_array_equal(graphs["xla"], graphs["pallas"])


class TestSampleFilter:
    """`sample_filter=` parity with brute_force/ivf_pq (ISSUE 5 satellite):
    mask epilogue on candidate scores before the beam select, same
    resolve_filter/validate_filter_covers contract, shared -1/+inf
    underfill sentinel."""

    def test_filtered_matches_filtered_brute_force(self, index, data):
        from raft_tpu.neighbors import brute_force

        x, q = data
        keep = np.ones(x.shape[0], bool)
        keep[::2] = False  # drop half the rows
        d, i = cagra.search(cagra.SearchParams(itopk_size=64), index, q, 10,
                            sample_filter=keep)
        i = np.asarray(i)
        assert (i[i >= 0] % 2 == 1).all()  # only kept rows surface
        _, ref = brute_force.knn(x, q, 10, sample_filter=keep)
        assert _recall(i, np.asarray(ref)) > 0.9

    def test_bitset_filter_object(self, index, data):
        from raft_tpu.neighbors import BitsetFilter

        x, q = data
        keep = np.zeros(x.shape[0], bool)
        keep[:100] = True
        _, i = cagra.search(cagra.SearchParams(itopk_size=64), index, q, 10,
                            sample_filter=BitsetFilter(keep))
        i = np.asarray(i)
        assert ((i < 100) | (i == -1)).all()

    def test_underfill_sentinels(self, index, data, check_filter_underfill):
        x, q = data
        alive = [5, 77, 1234]
        keep = np.zeros(x.shape[0], bool)
        keep[alive] = True
        d, i = cagra.search(cagra.SearchParams(itopk_size=64), index, q, 10,
                            sample_filter=keep)
        check_filter_underfill(d, i, alive, select_min=True)

    def test_filter_cover_validated(self, index, data):
        from raft_tpu.core.errors import RaftError

        x, q = data
        with pytest.raises(RaftError, match="cover"):
            cagra.search(cagra.SearchParams(), index, q, 10,
                         sample_filter=np.ones(x.shape[0] - 1, bool))

    @pytest.mark.parametrize("impl", ["fused_arena"])
    def test_fused_hop_filter_matches_xla(self, index, data, monkeypatch,
                                          impl):
        monkeypatch.setenv("RAFT_TPU_CAGRA_HOP_INTERPRET", "1")
        x, q = data
        keep = np.ones(x.shape[0], bool)
        keep[:x.shape[0] // 2] = False
        d_x, i_x = cagra.search(
            cagra.SearchParams(itopk_size=32, hop_impl="xla"), index, q, 10,
            sample_filter=keep)
        d_f, i_f = cagra.search(
            cagra.SearchParams(itopk_size=32, hop_impl=impl), index, q, 10,
            sample_filter=keep)
        i_x, i_f = np.asarray(i_x), np.asarray(i_f)
        assert (i_f[i_f >= 0] >= x.shape[0] // 2).all()
        overlap = np.mean([len(set(i_x[r]) & set(i_f[r])) / 10
                           for r in range(i_x.shape[0])])
        assert overlap > 0.95, overlap


class TestMergedShardedBuild:
    """parallel.cagra.build_merged (ISSUE 6): per-shard graphs concatenated
    into ONE plain CagraIndex — every single-chip consumer takes it
    unchanged, and the scored seed pool (spanning all shards) keeps recall
    at parity with a global build (the r06 64k/8 measured result)."""

    @pytest.fixture(scope="class")
    def mdata(self):
        rng = np.random.default_rng(3)
        centers = rng.random((16, 16)).astype(np.float32) * 10
        lab = rng.integers(0, 16, 2000)
        x = (centers[lab] + 0.3 * rng.standard_normal((2000, 16))).astype(
            np.float32)
        return x

    @pytest.fixture(scope="class")
    def merged(self, mdata):
        import jax
        from jax.sharding import Mesh

        from raft_tpu.comms.comms import Comms
        from raft_tpu.parallel import cagra as pcagra

        comms = Comms(Mesh(np.array(jax.devices()[:8]), ("data",)), "data")
        params = cagra.IndexParams(intermediate_graph_degree=16,
                                   graph_degree=8, build_chunk=1024, seed=0)
        return pcagra.build_merged(comms, params, mdata)

    def test_structure_and_shard_locality(self, mdata, merged):
        from raft_tpu.parallel import cagra as pcagra

        n = mdata.shape[0]
        assert merged.dataset.shape == (n, 16)
        assert merged.graph.shape == (n, 8)
        g = np.asarray(merged.graph)
        assert g.min() >= 0 and g.max() < n
        # uneven shards allowed (2000 / 8 = 250): edges stay within their
        # owning shard's global row range — no cross-shard edges by
        # construction
        for lo, hi in pcagra._shard_bounds(n, 8):
            assert g[lo:hi].min() >= lo and g[lo:hi].max() < hi, (lo, hi)
        # the merged dataset preserves the original row order
        np.testing.assert_array_equal(np.asarray(merged.dataset), mdata)

    def test_search_recall_parity_vs_single(self, mdata, merged):
        from raft_tpu.neighbors import brute_force

        params = cagra.IndexParams(intermediate_graph_degree=16,
                                   graph_degree=8, build_chunk=1024, seed=0)
        single = cagra.build(params, mdata)
        q = mdata[:64]
        _, gt = brute_force.knn(mdata, q, 5)
        gt = np.asarray(gt)
        sp = cagra.SearchParams(itopk_size=16)

        def rec(idx):
            _, ids = cagra.search(sp, idx, q, 5)
            return _recall(np.asarray(ids), gt)

        r_merged, r_single = rec(merged), rec(single)
        assert r_merged > 0.8, r_merged
        assert r_merged >= r_single - 0.03, (r_merged, r_single)

    def test_uneven_rows_and_degree_bound(self, mdata):
        import jax
        from jax.sharding import Mesh

        from raft_tpu.comms.comms import Comms
        from raft_tpu.core import RaftError
        from raft_tpu.parallel import cagra as pcagra

        comms = Comms(Mesh(np.array(jax.devices()[:8]), ("data",)), "data")
        # 2001 rows over 8 shards: bounds cover every row exactly once
        bounds = pcagra._shard_bounds(2001, 8)
        assert bounds[0] == (0, 251) and bounds[-1] == (1751, 2001)
        assert sum(hi - lo for lo, hi in bounds) == 2001
        # graph_degree must fit the SMALLEST shard
        with pytest.raises(RaftError):
            pcagra.build_merged(
                comms, cagra.IndexParams(intermediate_graph_degree=16,
                                         graph_degree=8, seed=0),
                mdata[:40])  # 5-row shards < graph_degree

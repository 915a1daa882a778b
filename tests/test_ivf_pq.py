"""IVF-PQ tests — recall-threshold acceptance vs brute force
(reference analogue: cpp/test/neighbors/ann_ivf_pq.cuh, pylibraft
test_ivf_pq.py)."""

import numpy as np
import pytest
from scipy.spatial import distance as sp_dist

from raft_tpu.neighbors import ivf_pq, refine
from raft_tpu.random import make_blobs


def _recall(got_ids, true_ids):
    hits = 0
    for g, t in zip(got_ids, true_ids):
        hits += len(set(g.tolist()) & set(t.tolist()))
    return hits / true_ids.size


@pytest.fixture(scope="module")
def data():
    x, _ = make_blobs(6000, 32, n_clusters=60, cluster_std=2.0, seed=0)
    q, _ = make_blobs(80, 32, n_clusters=60, cluster_std=2.0, seed=1)
    return np.asarray(x), np.asarray(q)


class TestBuild:
    def test_index_structure(self, data):
        x, _ = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=8, seed=0), x)
        # hot lists may split into capacity-bounded sub-lists sharing a center
        # (_list_utils.split_oversized), so n_lists is a lower bound
        assert idx.n_lists >= 32
        assert idx.capacity <= max(2 * 6000 // 32 + 8, 16)
        assert idx.pq_dim == 8
        assert idx.pq_len == 4  # 32 / 8
        assert idx.size == 6000
        assert idx.codebooks.shape == (8, 16, 4)  # 2**pq_bits=16 (TPU default 4)

    def test_pq_bits(self, data):
        x, _ = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8, pq_bits=4, seed=0), x)
        assert idx.codebooks.shape[1] == 16
        assert np.asarray(idx.list_codes).max() < 16

    def test_default_pq_dim(self, data):
        x, _ = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, seed=0), x)
        # bits-aware heuristic: equal code bytes to the reference default
        # (d/2 dims at 8 bits == d dims at 4 bits == d/2 bytes)
        assert idx.pq_dim == 32  # d at the pq_bits=4 default

    def test_rotation_is_orthonormal(self, data):
        x, _ = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=8, force_random_rotation=True, seed=0), x
        )
        r = np.asarray(idx.rotation)
        np.testing.assert_allclose(r @ r.T, np.eye(r.shape[0]), atol=1e-4)

    def test_bad_pq_bits(self, data):
        from raft_tpu.core import RaftError

        with pytest.raises(RaftError):
            ivf_pq.build(ivf_pq.IndexParams(pq_bits=16), data[0])


class TestSearch:
    def test_recall_all_probes(self, data):
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=32, seed=0), x)
        _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), idx, q, k=10)
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        rec = _recall(np.asarray(i), true_i)
        assert rec > 0.8, rec  # PQ-lossy exact-probe recall

    def test_recall_grows_with_probes(self, data):
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=64, pq_dim=32, seed=0), x)
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        recalls = [
            _recall(np.asarray(ivf_pq.search(ivf_pq.SearchParams(n_probes=p), idx, q, 10)[1]), true_i)
            for p in (2, 8, 32, 64)
        ]
        assert recalls[-1] >= recalls[0]
        assert recalls[-1] > 0.75, recalls

    def test_refine_recovers_exact_ranking(self, data):
        """The reference pipeline: ivf_pq search k0 > k → exact refine → k
        (pylibraft ivf_pq+refine pattern, CAGRA build dependency)."""
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=32, seed=0), x)
        _, cand = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), idx, q, k=40)
        d, i = refine(x, q, np.asarray(cand), k=10)
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        rec = _recall(np.asarray(i), true_i)
        assert rec > 0.9, rec

    def test_per_cluster_codebooks(self, data):
        x, q = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, codebook_kind="per_cluster", seed=0), x
        )
        # one codebook per list (sub-lists share their parent's codebook)
        assert idx.codebooks.shape[0] == idx.n_lists >= 16
        _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=idx.n_lists), idx, q, k=10)
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        rec = _recall(np.asarray(i), true_i)
        # pq_dim=8 on d=32 is 4x compression; ~0.55 matches per_subspace at the
        # same ratio (codebook kinds are quality-equivalent here)
        assert rec > 0.45, rec

    def test_inner_product(self, data):
        x, q = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=32, pq_dim=32, metric="inner_product", seed=0), x
        )
        _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), idx, q, k=10)
        true_i = np.argsort(-(q @ x.T), 1)[:, :10]
        rec = _recall(np.asarray(i), true_i)
        assert rec > 0.7, rec

    def test_bf16_lut(self, data):
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=16, seed=0), x)
        _, i32 = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), idx, q, k=10)
        _, i16 = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=32, lut_dtype="bfloat16"), idx, q, k=10
        )
        # bf16 LUT must stay close to f32 ranking
        overlap = np.mean([
            len(set(a.tolist()) & set(b.tolist())) / 10
            for a, b in zip(np.asarray(i32), np.asarray(i16))
        ])
        assert overlap > 0.85, overlap


class TestExtend:
    def test_extend(self, data):
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8, seed=0), x[:5000])
        idx = ivf_pq.extend(idx, x[5000:], np.arange(5000, 6000, dtype=np.int32))
        assert idx.size == 6000
        ids = np.asarray(idx.list_ids)
        assert sorted(ids[ids >= 0].tolist()) == list(range(6000))

    def test_build_empty_then_extend(self, data):
        x, _ = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=8, add_data_on_build=False, seed=0), x
        )
        assert idx.size == 0
        idx = ivf_pq.extend(idx, x)
        assert idx.size == 6000


class TestSerialize:
    def test_roundtrip(self, tmp_path, data):
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8, seed=0), x)
        p = str(tmp_path / "pq.bin")
        ivf_pq.save(idx, p)
        idx2 = ivf_pq.load(p)
        d1, i1 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx, q, k=5)
        d2, i2 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx2, q, k=5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


class TestPq8Split:
    """pq_bits=8 nibble-split (two-stage 4+4-bit residual VQ per subspace):
    the scan separates the 256-entry LUT into two 16-entry stage LUTs plus a
    precomputed per-vector cross term (list_consts). No reference analogue —
    the reference's smem-gather LUT (detail/ivf_pq_compute_similarity-inl.cuh)
    is bits-insensitive; on TPU the one-hot contraction axis shrinks 8x."""

    def test_structure(self, data):
        x, _ = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8, pq_bits=8, seed=0), x)
        assert idx.pq_split
        assert idx.codebooks.shape == (8, 32, 4)  # 2 stages x 16 entries
        assert idx.list_consts.shape == (idx.n_lists, idx.capacity)
        # codes use the full byte (hi/lo nibbles)
        assert np.asarray(idx.list_codes).max() > 15

    def test_scores_are_exact_composed_distances(self, data):
        """Reported distance == ||q - center - R^T(cb1[hi]+cb2[lo])||^2 —
        verifies the separated LUTs + cross-term constant reassemble the
        joint score exactly (up to f32 accumulation)."""
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8, pq_bits=8, seed=0), x)
        d, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=idx.n_lists), idx, q[:8], k=5)
        d, i = np.asarray(d), np.asarray(i)
        cb = np.asarray(idx.codebooks)
        codes = np.asarray(idx.list_codes)
        lids = np.asarray(idx.list_ids)
        rot = np.asarray(idx.rotation)
        cen = np.asarray(idx.centers)
        for r in range(8):
            for c in range(5):
                l, p = np.argwhere(lids == i[r, c])[0]
                cd = codes[l, p]
                dec = np.concatenate(
                    [cb[s, cd[s] >> 4] + cb[s, 16 + (cd[s] & 15)]
                     for s in range(idx.pq_dim)])
                recon = cen[l] + rot.T @ dec
                # f32 accumulation of large cancelling terms (||r||^2 bias +
                # stage LUTs + cross consts) skews ~0.1% relative vs the
                # numpy double-precision recompute
                np.testing.assert_allclose(
                    d[r, c], ((q[r] - recon) ** 2).sum(), rtol=5e-3, atol=1e-2)

    def test_recall_beats_pq4_same_pq_dim(self, data):
        """8 bits via 4+4 residual stages should rank at least as well as the
        single-stage 4-bit codebook at the SAME pq_dim (so pq8 spends twice
        the code bytes) — the added stage must buy quality."""
        x, q = data
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        r8 = _recall(np.asarray(ivf_pq.search(
            ivf_pq.SearchParams(n_probes=32),
            ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=16, pq_bits=8, seed=0), x),
            q, 10)[1]), true_i)
        r4 = _recall(np.asarray(ivf_pq.search(
            ivf_pq.SearchParams(n_probes=32),
            ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=16, pq_bits=4, seed=0), x),
            q, 10)[1]), true_i)
        assert r8 >= r4 - 0.02, (r8, r4)

    def test_joint_flag_off(self, data):
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_dim=8, pq_bits=8, pq8_split=False, seed=0), x)
        assert not idx.pq_split
        assert idx.codebooks.shape == (8, 256, 4)
        assert idx.list_consts.shape == (idx.n_lists, 0)
        _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=16), idx, q, k=10)
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        # pq_dim=8 on d=32 is 4x compression with a 6k-row trainset for 256
        # codes/subspace; ~0.53 matches the per-cluster fixture at this ratio
        assert _recall(np.asarray(i), true_i) > 0.45

    def test_inner_product_defaults_to_joint(self, data):
        # metric-aware auto: the Minkowski coarseness costs IP ranking far
        # more than L2 (review-measured recall@5 0.375 joint vs 0.075 split
        # on tight clusters), so pq8_split=None resolves to joint for IP
        x, _ = data
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_dim=16, pq_bits=8, metric="inner_product", seed=0), x)
        assert not idx.pq_split
        assert idx.codebooks.shape[1] == 256

    def test_inner_product_split_forced(self, data):
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_dim=16, pq_bits=8, pq8_split=True,
            metric="inner_product", seed=0), x)
        assert idx.pq_split
        # IP scoring is exactly separable: no consts stored
        assert idx.list_consts.shape == (idx.n_lists, 0)
        _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=16), idx, q, k=10)
        true_i = np.argsort(-(q @ x.T), 1)[:, :10]
        assert _recall(np.asarray(i), true_i) > 0.6

    def test_extend_carries_consts(self, data):
        x, _ = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8, pq_bits=8, seed=0),
                           x[:5000])
        idx2 = ivf_pq.extend(idx, x[5000:], np.arange(5000, 6000, dtype=np.int32))
        assert idx2.size == 6000
        # every stored vector has its const where its id lives
        lids = np.asarray(idx2.list_ids)
        consts = np.asarray(idx2.list_consts)
        assert consts.shape == lids.shape
        # re-extending the same rows reproduces identical consts for old rows
        l, p = np.argwhere(lids == 0)[0]
        lids1 = np.asarray(idx.list_ids)
        l1, p1 = np.argwhere(lids1 == 0)[0]
        np.testing.assert_allclose(consts[l, p], np.asarray(idx.list_consts)[l1, p1],
                                   rtol=1e-6)

    def test_per_cluster_split(self, data):
        """per_cluster codebooks x nibble-split: stage training on the pooled
        per-cluster subvectors and the per-cluster cross-consts gather
        (_pq_cross_consts labels branch) compose with the split scan."""
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_dim=8, pq_bits=8, codebook_kind="per_cluster",
            seed=0), x)
        assert idx.pq_split
        assert idx.codebooks.shape == (idx.n_lists, 32, 4)
        assert idx.list_consts.shape == (idx.n_lists, idx.capacity)
        _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=idx.n_lists), idx, q, k=10)
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        assert _recall(np.asarray(i), true_i) > 0.4

    def test_roundtrip_split(self, tmp_path, data):
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8, pq_bits=8, seed=0), x)
        p = str(tmp_path / "pq8.bin")
        ivf_pq.save(idx, p)
        idx2 = ivf_pq.load(p)
        assert idx2.pq_split
        d1, i1 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx, q, k=5)
        d2, i2 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx2, q, k=5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


class TestCodebookAuto:
    """codebook_kind='auto' trial-trains per-cluster codebooks on the largest
    clusters and adopts them only when they quantize markedly better
    (reference leaves PER_CLUSTER opt-in, ivf_pq_build.cuh:424; the auto mode
    + advisory log are TPU-side additions)."""

    def _lid_data(self, n=6000, d=32, ncl=24, idim=3, seed=5):
        """Cluster-structured residuals: each cluster's points deviate from
        its center inside a private low-dim subspace — per-cluster codebooks'
        best case."""
        rng = np.random.default_rng(seed)
        centers = rng.uniform(0, 10, (ncl, d)).astype(np.float32)
        bases = rng.normal(size=(ncl, idim, d)).astype(np.float32)
        bases /= np.linalg.norm(bases, axis=-1, keepdims=True)
        lab = rng.integers(0, ncl, n)
        z = rng.normal(size=(n, idim)).astype(np.float32)
        return (centers[lab] + np.einsum("ni,nid->nd", z, bases[lab])).astype(np.float32)

    def test_auto_picks_per_cluster_on_structured_residuals(self):
        x = self._lid_data()
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=24, pq_dim=8, codebook_kind="auto", seed=0), x)
        assert idx.codebook_kind == "per_cluster"
        assert idx.codebooks.shape[0] == idx.n_lists

    def test_auto_keeps_per_subspace_on_shared_residuals(self):
        # iid gaussian data has no per-cluster residual structure (measured
        # trial ratio ~0.98 vs ~0.83 on blob data, threshold 0.9) — note
        # even make_blobs data legitimately profits from per-cluster books
        # when n_lists < n_blobs (each list pools several blobs), so the
        # negative control must be structureless
        rng = np.random.default_rng(11)
        x = rng.standard_normal((6000, 32)).astype(np.float32)
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=32, pq_dim=8, codebook_kind="auto", seed=0), x)
        assert idx.codebook_kind == "per_subspace"

    def test_default_build_runs_no_trial(self, caplog):
        # the trial is opt-in via codebook_kind="auto": plain per_subspace
        # builds (including internal ones like CAGRA's knn-graph IVF-PQ,
        # which expose no codebook knob) must not pay for it or log advice
        import logging

        x = self._lid_data()
        with caplog.at_level(logging.INFO, logger="raft_tpu"):
            idx = ivf_pq.build(
                ivf_pq.IndexParams(n_lists=24, pq_dim=8, seed=0), x)
        assert idx.codebook_kind == "per_subspace"
        assert not any("codebook" in r.message for r in caplog.records)

    def test_auto_logs_its_decision(self, caplog):
        import logging

        x = self._lid_data()
        with caplog.at_level(logging.INFO, logger="raft_tpu"):
            ivf_pq.build(ivf_pq.IndexParams(
                n_lists=24, pq_dim=8, codebook_kind="auto", seed=0), x)
        assert any("auto codebooks" in r.message for r in caplog.records)


@pytest.mark.slow
def test_int8_lut(rng):
    """int8 LUT (the reference's fp8 smem-LUT analogue, detail/fp_8bit.cuh):
    per-(query,probe) symmetric quantization must track the f32 LUT ranking
    closely at full probe coverage."""
    import jax.numpy as jnp
    from scipy.spatial import distance as sp_dist

    x = rng.random((3000, 32)).astype(np.float32)
    q = rng.random((20, 32)).astype(np.float32)
    idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=16, seed=0), jnp.asarray(x))
    d32, i32 = ivf_pq.search(ivf_pq.SearchParams(n_probes=16), idx, q, 10)
    d8, i8 = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, lut_dtype="int8"), idx, q, 10)
    i32, i8 = np.asarray(i32), np.asarray(i8)
    overlap = np.mean([len(set(i32[r]) & set(i8[r])) / 10 for r in range(20)])
    assert overlap > 0.8, overlap
    # both should be decent vs exact ground truth
    gt = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), axis=1)[:, :10]
    rec8 = np.mean([len(set(i8[r]) & set(gt[r])) / 10 for r in range(20)])
    rec32 = np.mean([len(set(i32[r]) & set(gt[r])) / 10 for r in range(20)])
    assert rec8 > rec32 - 0.1, (rec8, rec32)


def _probed_lists(idx, q, n_probes, inner=False):
    """(m, n_probes) probed list ids, by the coarse select in float64."""
    cen = np.asarray(idx.centers, np.float64)
    qd = np.asarray(q, np.float64)
    cd = -(qd @ cen.T) if inner else (
        (cen * cen).sum(1)[None, :] - 2.0 * qd @ cen.T)
    return np.argsort(cd, axis=1, kind="stable")[:, :n_probes]


def _decode_list(idx, qrot, lst, lut_dtype):
    """(ids, float64 ||q_rot - c_rot - decode(code)||^2, LUT error bound)
    for every stored row of list ``lst``: the one-hot scan's score,
    recomputed from the stored codes (pq4: one codeword per subspace;
    nibble-split pq8: the hi and lo stage codewords summed). The bound is
    what rounding the LUT to ``lut_dtype`` may add: each summed entry moves
    by at most 2^-8 of its magnitude (bfloat16) or half the symmetric int8
    step amax/127."""
    ids = np.asarray(idx.list_ids)[lst]
    live = ids >= 0
    codes = np.asarray(idx.list_codes)[lst][live].astype(np.int64)
    cb = np.asarray(idx.codebooks, np.float64)         # (pq_dim, K, pq_len)
    sub = np.arange(idx.pq_dim)
    if idx.pq_split:
        dec = cb[sub, codes >> 4] + cb[sub, 16 + (codes & 15)]
    else:
        dec = cb[sub, codes]                           # (n, pq_dim, pq_len)
    resid = (qrot - np.asarray(idx.centers_rot, np.float64)[lst]
             ).reshape(idx.pq_dim, 1, idx.pq_len)
    dist = ((resid[:, 0][None] - dec) ** 2).sum(axis=(1, 2))
    lut = (cb * cb).sum(-1) - 2.0 * (resid * cb).sum(-1)  # (pq_dim, K)
    n_terms = idx.pq_dim * (2 if idx.pq_split else 1)
    bound = {"float32": 0.0, "bfloat16": 2.0 ** -8,
             "int8": 1.0 / 254.0}[lut_dtype] * n_terms * np.abs(lut).max()
    return ids[live], dist, bound


@pytest.fixture(scope="module")
def onehot_indexes(data):
    x, _ = data
    return {bits: ivf_pq.build(ivf_pq.IndexParams(
        n_lists=32, pq_dim=8, pq_bits=bits, seed=0), x) for bits in (4, 8)}


class TestOneHotScan:
    """The one-hot MXU contraction is the only scan: its scores are the
    decoded distances of the stored codes, in every LUT dtype and in the
    nibble-split pq8 form, and its filter epilogue never serves a banned
    row."""

    @pytest.mark.parametrize("lut", ["float32", "bfloat16", "int8"])
    @pytest.mark.parametrize("bits", [4, 8])
    def test_scores_match_numpy_decode(self, data, onehot_indexes, bits,
                                       lut):
        _, q = data
        idx = onehot_indexes[bits]
        assert idx.codebook_kind == "per_subspace"
        assert idx.pq_split == (bits == 8)
        n_probes, k = 8, 10
        d, i = ivf_pq.search(ivf_pq.SearchParams(
            n_probes=n_probes, lut_dtype=lut), idx, q, k)
        d, i = np.asarray(d, np.float64), np.asarray(i)
        qrot = np.asarray(q, np.float64) @ np.asarray(idx.rotation,
                                                      np.float64).T
        probes = _probed_lists(idx, q, n_probes)
        for r in range(len(q)):
            parts = [_decode_list(idx, qrot[r], lst, lut)
                     for lst in probes[r]]
            ids = np.concatenate([p[0] for p in parts])
            want = np.concatenate([p[1] for p in parts])
            tol = (max(p[2] for p in parts)
                   # f32 accumulation of large cancelling terms (||r||^2
                   # bias, LUT entries, pq8 cross constants)
                   + 1e-2 + 1e-5 * want.max())
            by_id = dict(zip(ids.tolist(), want.tolist()))
            assert (i[r] >= 0).all(), r
            for c in range(k):
                assert i[r, c] in by_id, (r, c)   # served from a probed list
                assert abs(d[r, c] - by_id[i[r, c]]) <= tol, (r, c, lut)
            # tie-robust top-k: every served id is within the scan's error
            # of the true k-th decoded distance, and every id clearly
            # inside the true top k is served
            kth = np.sort(want)[k - 1]
            served = set(i[r].tolist())
            assert all(by_id[j] <= kth + 2 * tol for j in served), r
            assert set(ids[want < kth - 2 * tol].tolist()) <= served, r

    @pytest.mark.parametrize("metric", ["sqeuclidean", "inner_product"])
    def test_sample_filter_never_serves_banned(self, data, metric):
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=32, pq_dim=8, metric=metric, seed=0), x)
        keep = np.ones(len(x), bool)
        keep[::3] = False
        k = 10
        _, got = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx, q, k,
                               sample_filter=keep)
        got = np.asarray(got)
        banned = set(np.nonzero(~keep)[0].tolist())
        assert not (set(got[got >= 0].ravel().tolist()) & banned)
        # each row fills to k while its probed lists hold k surviving rows
        lids = np.asarray(idx.list_ids)
        probes = _probed_lists(idx, q, 8, inner=metric == "inner_product")
        for r, row in enumerate(got):
            ids = lids[probes[r]]
            surviving = int(keep[ids[ids >= 0]].sum())
            assert (row >= 0).sum() == min(k, surviving), (r, surviving)

    def test_split_consts_validated(self, data):
        import dataclasses

        from raft_tpu.core import RaftError
        import jax.numpy as jnp

        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_dim=8, pq_bits=8, seed=0), x)
        assert idx.pq_split
        broken = dataclasses.replace(
            idx, list_consts=jnp.zeros((idx.n_lists, 0), jnp.float32))
        with pytest.raises(RaftError, match="list_consts"):
            ivf_pq.search(ivf_pq.SearchParams(n_probes=8), broken, q, 10)
        with pytest.raises(RaftError, match="list_consts"):
            ivf_pq.extend(broken, x[:8])


class TestByteDatasets:
    """int8/uint8 dataset ingestion end-to-end (reference: the dedicated
    ivf_pq int8_t/uint8_t instantiations, cpp/src/neighbors/ivf_pq_build_*
    — BigANN-class byte data is PQ's home regime). All PQ math runs on the
    exact f32 image of the bytes (uint8 shifted by -128, L2-invariant), so
    recall bars match the float tests'."""

    @pytest.fixture(scope="class")
    def idata(self):
        rng = np.random.default_rng(5)
        # clustered bytes: blob centers + noise, clipped to [0, 255]
        centers = rng.integers(40, 215, (24, 32))
        lab = rng.integers(0, 24, 4000)
        x = np.clip(centers[lab] + rng.normal(0, 12, (4000, 32)), 0, 255)
        qlab = rng.integers(0, 24, 60)
        q = np.clip(centers[qlab] + rng.normal(0, 12, (60, 32)), 0, 255)
        return x.astype(np.uint8), q.astype(np.uint8)

    @pytest.mark.parametrize("dt", [np.uint8, np.int8])
    def test_build_search_recall(self, idata, dt):
        xu, qu = idata
        x = xu if dt == np.uint8 else (xu.astype(np.int16) - 128).astype(np.int8)
        q = qu if dt == np.uint8 else (qu.astype(np.int16) - 128).astype(np.int8)
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=32, seed=0), x)
        assert idx.data_kind == dt.__name__
        _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), idx, q, k=10)
        d2 = ((q[:, None, :].astype(np.float64)
               - x[None].astype(np.float64)) ** 2).sum(-1)
        true_i = np.argsort(d2, 1)[:, :10]
        rec = _recall(np.asarray(i), true_i)
        assert rec > 0.8, rec  # PQ-lossy exact-probe recall, float parity

    def test_signed_and_shifted_agree(self, idata):
        """uint8 ingestion = the pre-shifted int8 build, identical ids."""
        xu, qu = idata
        xs = (xu.astype(np.int16) - 128).astype(np.int8)
        qs = (qu.astype(np.int16) - 128).astype(np.int8)
        ip = ivf_pq.IndexParams(n_lists=16, pq_dim=8, seed=0)
        _, i_u = ivf_pq.search(ivf_pq.SearchParams(n_probes=16),
                               ivf_pq.build(ip, xu), qu, 10)
        _, i_s = ivf_pq.search(ivf_pq.SearchParams(n_probes=16),
                               ivf_pq.build(ip, xs), qs, 10)
        np.testing.assert_array_equal(np.asarray(i_u), np.asarray(i_s))

    def test_refine_pipeline(self, idata):
        """Byte PQ search k0 > k feeding an exact byte refine — the
        reference's standard BigANN operating point."""
        xu, qu = idata
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=32, pq_dim=32, seed=0), xu)
        _, cand = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), idx, qu, k=40)
        _, i = refine(xu, qu, np.asarray(cand), k=10)
        d2 = ((qu[:, None, :].astype(np.float64)
               - xu[None].astype(np.float64)) ** 2).sum(-1)
        true_i = np.argsort(d2, 1)[:, :10]
        rec = _recall(np.asarray(i), true_i)
        assert rec > 0.9, rec

    def test_float_queries_on_uint8_index(self, idata):
        xu, qu = idata
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8, seed=0), xu)
        _, i_b = ivf_pq.search(ivf_pq.SearchParams(n_probes=16), idx, qu, 10)
        _, i_f = ivf_pq.search(ivf_pq.SearchParams(n_probes=16), idx,
                               qu.astype(np.float32), 10)
        np.testing.assert_array_equal(np.asarray(i_b), np.asarray(i_f))

    def test_extend_dtype_guard(self, idata):
        from raft_tpu.core import RaftError

        xu, _ = idata
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8, seed=0),
                           xu[:3000])
        # a plain astype would wrap the domain mod 256 — must be rejected
        with pytest.raises(RaftError, match="stores uint8"):
            ivf_pq.extend(idx, (xu[3000:].astype(np.int16) - 128).astype(np.int8))
        idx2 = ivf_pq.extend(idx, xu[3000:])
        assert int(np.asarray(idx2.list_sizes).sum()) == len(xu)
        assert idx2.data_kind == "uint8"

    def test_uint8_inner_product_rejected(self, idata):
        from raft_tpu.core import RaftError

        xu, _ = idata
        with pytest.raises(RaftError, match="inner_product"):
            ivf_pq.build(ivf_pq.IndexParams(
                n_lists=16, pq_dim=8, metric="inner_product", seed=0), xu)

    def test_roundtrip_preserves_kind(self, tmp_path, idata):
        xu, qu = idata
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8, seed=0), xu)
        p = str(tmp_path / "pq_u8.bin")
        ivf_pq.save(idx, p)
        idx2 = ivf_pq.load(p)
        assert idx2.data_kind == "uint8"
        d1, i1 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx, qu, 5)
        d2, i2 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx2, qu, 5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


class TestResidualScaleNorm:
    """Per-list residual scale normalization (IndexParams.residual_scale
    _norm — the heavytail remedy, VERDICT r5 #2): codes encode r/s_list,
    search folds s_list back in (s^2 for L2, s for IP), so scores stay the
    exact ||r - s*decode||^2 and recall on scale-skewed data recovers."""

    @staticmethod
    def _skewed(rng, n=6000, ncl=32, d=16, q=200):
        """Lognormal per-cluster residual scales — the heavytail family's
        defining symmetry break, at test scale."""
        centers = rng.random((ncl, d)).astype(np.float32) * 10
        scales = rng.lognormal(np.log(0.25), 0.8, ncl).astype(np.float32)
        lab = rng.integers(0, ncl, n)
        x = (centers[lab] + rng.normal(0, 1, (n, d)).astype(np.float32)
             * scales[lab][:, None])
        qs = x[:q] + rng.normal(0, 0.01, (q, d)).astype(np.float32)
        true_i = np.argsort(sp_dist.cdist(qs, x, "sqeuclidean"), 1)[:, :10]
        return x, qs, true_i

    def test_recall_recovers_on_scale_skewed_data(self, rng):
        x, q, true_i = self._skewed(rng)
        recs = {}
        for norm in (False, True):
            idx = ivf_pq.build(ivf_pq.IndexParams(
                n_lists=32, pq_bits=4, pq_dim=8, residual_scale_norm=norm,
                seed=0), x)
            _, ids = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx,
                                   q, 10)
            recs[norm] = _recall(np.asarray(ids), true_i)
        # the in-session 100k heavytail A/B measured bare +0.18 absolute;
        # at test scale the gap is smaller but must not invert
        assert recs[True] >= recs[False] - 0.01, recs
        assert recs[True] > 0.5, recs

    def test_scores_are_exact_scaled_decode(self, rng):
        """Returned distances must equal the manual ||r - s*decode||^2
        reconstruction — the folding (r/s into the LUT dots, s^2 back out,
        raw-r bias) is exact algebra, not an approximation."""
        x, q, _ = self._skewed(rng, n=2000, ncl=16)
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_bits=4, pq_dim=8, residual_scale_norm=True,
            seed=0), x)
        assert idx.scale_normed and idx.list_scales.shape[0] == idx.n_lists
        d_got, i_got = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=idx.n_lists), idx, q[:8], 5)
        d_got, i_got = np.asarray(d_got), np.asarray(i_got)
        # locate each hit's (list, slot) to read its code back
        ids_h = np.asarray(idx.list_ids)
        codes_h = np.asarray(idx.list_codes)
        cb = np.asarray(idx.codebooks)           # (pq_dim, 16, pq_len)
        centers_rot = np.asarray(idx.centers_rot)
        scales = np.asarray(idx.list_scales)
        qrot = q[:8] @ np.asarray(idx.rotation).T
        for r in range(8):
            for c in range(5):
                hit = i_got[r, c]
                l, s = np.argwhere(ids_h == hit)[0]
                code = codes_h[l, s]             # (pq_dim,)
                decode = np.stack([cb[j, code[j]] for j in range(len(code))])
                resid = (qrot[r] - centers_rot[l]).reshape(decode.shape)
                want = float(((resid - scales[l] * decode) ** 2).sum())
                np.testing.assert_allclose(d_got[r, c], want, rtol=2e-3,
                                           atol=2e-3)

    def test_pq8_split_consts_carry_scale(self, rng):
        """pq_split stores the 2*cb1·cb2 cross term per vector; with scale
        norm it must arrive s^2-folded — search on an all-lists probe would
        misrank otherwise."""
        x, q, true_i = self._skewed(rng, n=3000, ncl=16)
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_bits=8, pq_dim=8, residual_scale_norm=True,
            seed=0), x)
        assert idx.pq_split and idx.scale_normed
        _, ids = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx, q, 10)
        assert _recall(np.asarray(ids), true_i) > 0.5

    def test_extend_save_load_roundtrip(self, rng, tmp_path):
        x, q, _ = self._skewed(rng, n=4000, ncl=16)
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_bits=4, pq_dim=8, residual_scale_norm=True,
            seed=0), x[:3000])
        idx = ivf_pq.extend(idx, x[3000:])
        assert idx.list_scales.shape[0] == idx.n_lists
        p = str(tmp_path / "pq_scaled.bin")
        ivf_pq.save(idx, p)
        idx2 = ivf_pq.load(p)
        np.testing.assert_allclose(np.asarray(idx2.list_scales),
                                   np.asarray(idx.list_scales))
        d1, i1 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx, q, 5)
        d2, i2 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx2, q, 5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_load_pre_v7_defaults_disabled(self, rng, tmp_path):
        """A file without residual_scale_norm loads with the (0,) disabled
        sentinel — older indexes never normalized, so that is exact."""
        x, _, _ = self._skewed(rng, n=2000, ncl=16)
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_bits=4, pq_dim=8, seed=0), x)
        p = str(tmp_path / "pq_plain.bin")
        ivf_pq.save(idx, p)
        idx2 = ivf_pq.load(p)
        assert not idx2.scale_normed
        assert idx2.list_scales.shape == (0,)

    def test_per_cluster_composes(self, rng):
        x, q, true_i = self._skewed(rng, n=4000, ncl=16)
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_bits=4, pq_dim=8, codebook_kind="per_cluster",
            residual_scale_norm=True, seed=0), x)
        assert idx.codebook_kind == "per_cluster" and idx.scale_normed
        _, ids = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), idx, q, 10)
        assert _recall(np.asarray(ids), true_i) > 0.5

    def test_inner_product_scale_fold(self, rng):
        """IP folds s (not s^2): returned scores must equal the manual
        q_rot · (c_rot + s*decode) reconstruction exactly (recall is the
        wrong probe here — pq4's IP ranking is coarse regardless of the
        fold, see IndexParams.pq8_split notes)."""
        x, q, _ = self._skewed(rng, n=3000, ncl=16)
        idx = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=16, pq_bits=4, pq_dim=8, metric="inner_product",
            residual_scale_norm=True, seed=0), x)
        d_got, i_got = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=idx.n_lists), idx, q[:8], 5)
        d_got, i_got = np.asarray(d_got), np.asarray(i_got)
        ids_h = np.asarray(idx.list_ids)
        codes_h = np.asarray(idx.list_codes)
        cb = np.asarray(idx.codebooks)
        crot = np.asarray(idx.centers_rot)
        sc = np.asarray(idx.list_scales)
        qrot = q[:8] @ np.asarray(idx.rotation).T
        for r in range(8):
            for c in range(5):
                l, s = np.argwhere(ids_h == i_got[r, c])[0]
                code = codes_h[l, s]
                dec = np.stack([cb[j, code[j]]
                                for j in range(len(code))]).reshape(-1)
                want = float(qrot[r] @ (crot[l] + sc[l] * dec))
                np.testing.assert_allclose(d_got[r, c], want, rtol=2e-3,
                                           atol=2e-3)


class TestFilterUnderfill:
    """Shared filtered-underfill contract (ISSUE 5 satellite): when fewer
    than k rows survive the filter, ids are -1 at +inf (L2) / -inf (IP) —
    same checker as brute_force/ivf_flat/cagra."""

    def test_underfill_sentinels(self, data, check_filter_underfill):
        x, q = data
        idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=16, seed=0), x)
        alive = [44, 1023, 5020]
        keep = np.zeros(x.shape[0], bool)
        keep[alive] = True
        d, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=64), idx, q, 10,
                             sample_filter=keep)
        check_filter_underfill(d, i, alive, select_min=True)

    def test_underfill_sentinels_inner_product(self, data,
                                               check_filter_underfill):
        x, q = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16,
                               metric="inner_product", seed=0), x)
        alive = [3, 997]
        keep = np.zeros(x.shape[0], bool)
        keep[alive] = True
        d, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=64), idx, q, 10,
                             sample_filter=keep)
        check_filter_underfill(d, i, alive, select_min=False)


class TestMinibatchEm:
    """Mini-batch coarse EM (ISSUE 6): the 100k IVF-PQ recall anchor must
    hold within tolerance vs full EM at the BENCH operating point shape
    (pq4). Heavy 1M cases live in the slow manifest."""

    def test_minibatch_recall_parity_100k(self):
        import dataclasses

        from raft_tpu.neighbors import brute_force

        n, d, k = 100_000, 32, 10
        x, _ = make_blobs(n, d, n_clusters=500, cluster_std=1.0, seed=9)
        x = np.asarray(x)
        q = x[:300]
        _, gt = brute_force.knn(x, q, k)
        gt = np.asarray(gt)
        base = ivf_pq.IndexParams(n_lists=256, pq_bits=4, pq_dim=16, seed=0,
                                  kmeans_batch_rows=8192)
        sp = ivf_pq.SearchParams(n_probes=8, lut_dtype="bfloat16")
        recs = {}
        for mode in ("full", "minibatch"):
            idx = ivf_pq.build(
                dataclasses.replace(base, kmeans_train_mode=mode), x)
            _, ids = ivf_pq.search(sp, idx, q, k)
            recs[mode] = _recall(np.asarray(ids), gt)
            del idx
        # absolute recall here is set by the shrunk pq4x16 quantizer on
        # d=32 (same convention as the churn smoke: the anchor VALUE is the
        # driver-scale row's job); the bar that matters is PARITY
        assert recs["minibatch"] > 0.3, recs
        assert recs["minibatch"] >= recs["full"] - 0.03, recs


class TestQuantFunnel:
    """Quantization funnel (ISSUE 16): OPQ learned rotation, score-aware
    (anisotropic) codebooks, and the bit-packed fast-scan pre-filter tier
    (binary widen → exact-PQ rerank → caller refine). The load-bearing
    contracts: funnel_widen=1 is BIT-EQUAL to a no-tier twin at the same
    seed (the tier changes WHERE candidates come from, never what width-1
    answers), filtered candidates keep their sentinels through every
    stage, and the raft_tpu/13 codec record round-trips with /12
    read-compat both directions."""

    @pytest.fixture(scope="class")
    def twins(self, data):
        """Classic / 1bit-funnel twin builds at the same seed — identical
        codebooks by construction (signature encoding consumes no RNG)."""
        x, _ = data
        base = dict(n_lists=16, pq_dim=16, seed=0)
        classic = ivf_pq.build(ivf_pq.IndexParams(**base), x)
        funnel = ivf_pq.build(
            ivf_pq.IndexParams(fast_scan="1bit", **base), x)
        return classic, funnel

    def test_structure(self, twins):
        classic, funnel = twins
        assert funnel.has_fast_scan and funnel.fast_scan == "1bit"
        # d_rot=32 → ceil(32/8)=4 packed sign-bit bytes per slot
        assert funnel.list_sig.shape == (funnel.n_lists, funnel.capacity, 4)
        assert funnel.list_sig.dtype == np.uint8
        assert funnel.sig_scales.shape == (funnel.n_lists,)
        assert not classic.has_fast_scan and classic.fast_scan == "none"
        assert classic.list_sig.shape == (classic.n_lists, 0, 0)

    def test_structure_4bit(self, data):
        x, _ = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, fast_scan="4bit",
                               seed=0), x)
        # d_rot=32 → ceil(32/2)=16 packed nibble bytes per slot
        assert idx.list_sig.shape == (idx.n_lists, idx.capacity, 16)
        assert idx.fast_scan == "4bit"

    def test_width1_bit_equal_classic(self, twins, data):
        """The acceptance anchor: funnel_widen=1 routes the classic scan
        untouched — ids AND distances bit-equal to the no-tier twin."""
        _, q = data
        classic, funnel = twins
        dc, ic = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), classic,
                               q, k=10)
        df, if_ = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=8, funnel_widen=1), funnel, q, k=10)
        np.testing.assert_array_equal(np.asarray(ic), np.asarray(if_))
        np.testing.assert_array_equal(np.asarray(dc), np.asarray(df))

    def test_funnel_recall_1bit(self, twins, data):
        """Widened 1bit funnel holds the classic scan's recall: the binary
        tier only has to RANK the true top-k into the top W·k per chunk."""
        x, q = data
        classic, funnel = twins
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        _, ic = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), classic,
                              q, k=10)
        _, if_ = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=32, funnel_widen=16), funnel,
            q, k=10)
        rec_c = _recall(np.asarray(ic), true_i)
        rec_f = _recall(np.asarray(if_), true_i)
        # the anchor is RELATIVE: this coarse pq4x16 codec tops out ~0.43
        # on d=32 blobs, and the widened funnel must track it
        assert rec_f > 0.3, rec_f
        assert rec_f >= rec_c - 0.05, (rec_f, rec_c)

    def test_funnel_recall_4bit_narrower_widen(self, data):
        """4bit's lower estimator variance holds the anchor at half the
        width the 1bit sizing rule starts from (the docs' W=4 start)."""
        x, q = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, fast_scan="4bit",
                               seed=0), x)
        classic = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, seed=0), x)
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        _, ic = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), classic,
                              q, k=10)
        _, i4 = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=32, funnel_widen=4), idx, q, k=10)
        rec_c = _recall(np.asarray(ic), true_i)
        rec_4 = _recall(np.asarray(i4), true_i)
        assert rec_4 >= rec_c - 0.05, (rec_4, rec_c)

    def test_inner_product_funnel(self, data):
        x, q = data
        base = dict(n_lists=16, pq_dim=16, metric="inner_product", seed=0)
        classic = ivf_pq.build(ivf_pq.IndexParams(**base), x)
        funnel = ivf_pq.build(
            ivf_pq.IndexParams(fast_scan="1bit", **base), x)
        true_i = np.argsort(-(q @ x.T), 1)[:, :10]
        _, ic = ivf_pq.search(ivf_pq.SearchParams(n_probes=32), classic,
                              q, k=10)
        _, if_ = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=32, funnel_widen=8), funnel, q, k=10)
        rec_c = _recall(np.asarray(ic), true_i)
        rec_f = _recall(np.asarray(if_), true_i)
        assert rec_f >= rec_c - 0.1, (rec_f, rec_c)
        # width 1 stays bit-equal under IP too
        dc, ic1 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), classic,
                                q, k=5)
        df, if1 = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=8, funnel_widen=1), funnel, q, k=5)
        np.testing.assert_array_equal(np.asarray(ic1), np.asarray(if1))
        np.testing.assert_array_equal(np.asarray(dc), np.asarray(df))

    def test_funnel_restrictions(self, twins, data):
        """Every invalid funnel combination fails loudly at the entry
        point, not as silent quality loss deep in the scan."""
        from raft_tpu.core import RaftError

        _, q = data
        classic, funnel = twins
        with pytest.raises(RaftError, match="fast[-_ ]?scan"):
            ivf_pq.search(ivf_pq.SearchParams(n_probes=8, funnel_widen=2),
                          classic, q, k=10)
        with pytest.raises(RaftError):
            ivf_pq.search(ivf_pq.SearchParams(n_probes=8, funnel_widen=0),
                          funnel, q, k=10)
        with pytest.raises(RaftError, match="int8"):
            ivf_pq.search(
                ivf_pq.SearchParams(n_probes=8, funnel_widen=2,
                                    lut_dtype="int8"), funnel, q, k=10)

    def test_extend_carries_sig(self, data):
        """extend() encodes signatures for the new rows through the same
        per-list scales — the grown twin stays bit-equal to a grown
        classic twin at width 1, and the widened funnel still serves."""
        x, q = data
        base = dict(n_lists=16, pq_dim=16, seed=0)
        ids = np.arange(5000, 6000, dtype=np.int32)
        f = ivf_pq.build(
            ivf_pq.IndexParams(fast_scan="1bit", **base), x[:5000])
        f = ivf_pq.extend(f, x[5000:], ids)
        c = ivf_pq.build(ivf_pq.IndexParams(**base), x[:5000])
        c = ivf_pq.extend(c, x[5000:], ids)
        assert f.size == 6000 and f.has_fast_scan
        assert f.list_sig.shape == (f.n_lists, f.capacity, 4)
        dc, ic = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), c, q, k=10)
        df, if_ = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=8, funnel_widen=1), f, q, k=10)
        np.testing.assert_array_equal(np.asarray(ic), np.asarray(if_))
        np.testing.assert_array_equal(np.asarray(dc), np.asarray(df))
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        _, iw = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=32, funnel_widen=8), f, q, k=10)
        assert _recall(np.asarray(iw), true_i) > 0.3  # coarse-codec anchor

    def test_underfill_sentinels_funnel(self, data, check_filter_underfill):
        """Filtered candidates keep their -1/±inf sentinel through the
        binary stage, the PQ rerank and the final merge (same shared
        checker as the classic path)."""
        x, q = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, fast_scan="1bit",
                               seed=0), x)
        alive = [44, 1023, 5020]
        keep = np.zeros(x.shape[0], bool)
        keep[alive] = True
        d, i = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=64, funnel_widen=4), idx, q, 10,
            sample_filter=keep)
        check_filter_underfill(d, i, alive, select_min=True)

    def test_underfill_sentinels_funnel_inner_product(
            self, data, check_filter_underfill):
        x, q = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, fast_scan="1bit",
                               metric="inner_product", seed=0), x)
        alive = [3, 997]
        keep = np.zeros(x.shape[0], bool)
        keep[alive] = True
        d, i = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=64, funnel_widen=4), idx, q, 10,
            sample_filter=keep)
        check_filter_underfill(d, i, alive, select_min=False)

    def test_filter_fills_k_when_enough_survive(self, data,
                                                check_filter_underfill):
        """The other side of the underfill contract: with >= k survivors
        the funnel must FILL every slot from the alive set — a binary
        stage that silently narrowed the pool would leak sentinels here."""
        x, q = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, fast_scan="1bit",
                               seed=0), x)
        alive = list(range(100, 140))  # 40 survivors >= k=10
        keep = np.zeros(x.shape[0], bool)
        keep[alive] = True
        d, i = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=idx.n_lists, funnel_widen=8),
            idx, q, 10, sample_filter=keep)
        check_filter_underfill(d, i, alive, select_min=True)

    # -- serialize: raft_tpu/13 codec record, /12 read-compat ---------------

    def test_serialize_13_roundtrip(self, tmp_path, data):
        """The /13 codec record (rotation_kind, codebook_loss, fast_scan,
        list_sig, sig_scales) round-trips and the loaded funnel serves
        bit-equal at the widened point too."""
        x, q = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, rotation="opq",
                               fast_scan="1bit", seed=0), x)
        p = str(tmp_path / "funnel13.bin")
        ivf_pq.save(idx, p)
        idx2 = ivf_pq.load(p)
        assert idx2.rotation_kind == "opq"
        assert idx2.fast_scan == "1bit" and idx2.has_fast_scan
        np.testing.assert_array_equal(np.asarray(idx.list_sig),
                                      np.asarray(idx2.list_sig))
        np.testing.assert_array_equal(np.asarray(idx.sig_scales),
                                      np.asarray(idx2.sig_scales))
        sp = ivf_pq.SearchParams(n_probes=8, funnel_widen=4)
        d1, i1 = ivf_pq.search(sp, idx, q, k=5)
        d2, i2 = ivf_pq.search(sp, idx2, q, k=5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))

    def test_serialize_12_read_compat_both_directions(self, tmp_path, data,
                                                      monkeypatch):
        """(a) Bytes written by a writer PINNED to raft_tpu/12 (pre-codec
        layout) load in this build as a classic index — no tier, classic
        search bit-equal; (b) this build's /13 bytes of a NO-tier index
        read back classic too (the record is present but empty)."""
        from raft_tpu.core import RaftError
        from raft_tpu.core import serialize as core_serialize

        x, q = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, fast_scan="1bit",
                               seed=0), x)
        old_path = str(tmp_path / "v12.bin")
        monkeypatch.setattr(core_serialize, "SERIALIZATION_VERSION",
                            "raft_tpu/12")
        ivf_pq.save(idx, old_path)
        monkeypatch.undo()
        assert core_serialize.version_number(
            core_serialize.SERIALIZATION_VERSION) >= 13
        old = ivf_pq.load(old_path)
        assert old.fast_scan == "none" and not old.has_fast_scan
        assert old.list_sig.shape == (old.n_lists, 0, 0)
        d1, i1 = ivf_pq.search(
            ivf_pq.SearchParams(n_probes=8, funnel_widen=1), idx, q, k=5)
        d2, i2 = ivf_pq.search(ivf_pq.SearchParams(n_probes=8), old, q, k=5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
        # the tier did NOT survive the /12 bytes: widening must refuse
        with pytest.raises(RaftError):
            ivf_pq.search(ivf_pq.SearchParams(n_probes=8, funnel_widen=4),
                          old, q, k=5)

        classic = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, seed=0), x)
        new_path = str(tmp_path / "v13_no_tier.bin")
        ivf_pq.save(classic, new_path)
        back = ivf_pq.load(new_path)
        assert back.fast_scan == "none" and not back.has_fast_scan

    # -- OPQ rotation (funnel stage a) --------------------------------------

    def test_opq_rotation_orthonormal(self, data):
        x, _ = data
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, rotation="opq",
                               seed=0), x)
        assert idx.rotation_kind == "opq"
        r = np.asarray(idx.rotation)
        np.testing.assert_allclose(r @ r.T, np.eye(r.shape[0]), atol=1e-4)

    def test_opq_recall_holds_baseline(self, data):
        """OPQ must never cost recall (it is a no-op by construction on
        isotropic data; blobs sit close to that regime)."""
        x, q = data
        true_i = np.argsort(sp_dist.cdist(q, x, "sqeuclidean"), 1)[:, :10]
        recs = {}
        for rot in ("none", "opq"):
            idx = ivf_pq.build(
                ivf_pq.IndexParams(n_lists=16, pq_dim=16, rotation=rot,
                                   seed=0), x)
            _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=16), idx,
                                 q, k=10)
            recs[rot] = _recall(np.asarray(i), true_i)
        assert recs["opq"] >= recs["none"] - 0.05, recs

    # -- anisotropic codebooks (funnel stage b) -----------------------------

    def test_anisotropic_ip_recall(self, data):
        """Score-aware codebooks target inner-product serving: recall at
        the IP operating point must hold the plain-loss baseline."""
        x, q = data
        true_i = np.argsort(-(q @ x.T), 1)[:, :10]
        recs = {}
        for loss in ("l2", "anisotropic"):
            idx = ivf_pq.build(
                ivf_pq.IndexParams(n_lists=16, pq_dim=16,
                                   metric="inner_product",
                                   codebook_loss=loss, seed=0), x)
            _, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=16), idx,
                                 q, k=10)
            recs[loss] = _recall(np.asarray(i), true_i)
        assert recs["anisotropic"] >= recs["l2"] - 0.05, recs

    def test_anisotropic_rejects_split_pq8(self, data):
        """The split-pq8 codebook's two stages share one proxy EM — the
        anisotropic weighting cannot thread through it and must refuse."""
        from raft_tpu.core import RaftError

        with pytest.raises(RaftError, match="anisotropic"):
            ivf_pq.build(
                ivf_pq.IndexParams(n_lists=16, pq_dim=8, pq_bits=8,
                                   pq8_split=True,
                                   codebook_loss="anisotropic", seed=0),
                data[0])

    # -- stream embedding + tiered/sharded composition ----------------------

    @pytest.fixture(scope="class")
    def small_corpus(self):
        r = np.random.default_rng(7)
        X = r.standard_normal((2048, 16)).astype(np.float32)
        Q = r.standard_normal((32, 16)).astype(np.float32)
        return X, Q

    def test_stream_embedded_13_roundtrip(self, small_corpus, tmp_path):
        """A funnel index embedded in a stream file rides the /13 codec
        record: the reloaded sealed index keeps the tier and the widened
        funnel pin serves bit-equal."""
        from raft_tpu import stream

        X, Q = small_corpus
        params = ivf_pq.IndexParams(n_lists=32, pq_bits=4, pq_dim=8,
                                    fast_scan="1bit", seed=0)
        sp = ivf_pq.SearchParams(n_probes=8, funnel_widen=4)
        sealed = ivf_pq.build(params, X)
        m = stream.MutableIndex(sealed, search_params=sp,
                                index_params=params, dataset=X,
                                name="funnel13")
        path = str(tmp_path / "funnel13.stream")
        stream.save(m, path)
        rec = stream.load(path, search_params=sp)
        assert rec._state.sealed.has_fast_scan
        assert rec._state.sealed.fast_scan == "1bit"
        d1, i1 = m.search(Q, 10)
        d2, i2 = rec.search(Q, 10)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))

    def test_wrap_refuses_funnel_pin_without_tier(self, small_corpus):
        """The funnel/tier mismatch fails at WRAP time, not on a serving
        thread mid-request."""
        from raft_tpu import stream
        from raft_tpu.core import RaftError

        X, _ = small_corpus
        params = ivf_pq.IndexParams(n_lists=32, pq_bits=4, pq_dim=8, seed=0)
        sealed = ivf_pq.build(params, X)
        with pytest.raises(RaftError, match="fast[-_ ]?scan"):
            stream.MutableIndex(
                sealed, search_params=ivf_pq.SearchParams(n_probes=8,
                                                          funnel_widen=4),
                index_params=params, dataset=X, name="funnel_guard")

    def test_tiered_composition_width1_bit_equal(self, small_corpus):
        """ISSUE 16 acceptance: the funnel index under tiered storage at
        width 1 answers bit-equal (ids AND distances) to the all-HBM
        classic-PQ twin — composition changes placement, never answers."""
        from raft_tpu import stream

        X, Q = small_corpus
        base = dict(n_lists=32, pq_bits=4, pq_dim=8, seed=0)
        classic = ivf_pq.build(ivf_pq.IndexParams(**base), X)
        funnel = ivf_pq.build(
            ivf_pq.IndexParams(fast_scan="1bit", **base), X)
        a = stream.MutableIndex(
            classic, search_params=ivf_pq.SearchParams(n_probes=8),
            index_params=ivf_pq.IndexParams(**base), dataset=X,
            storage="hbm", name="cmp_hbm_classic")
        b = stream.MutableIndex(
            funnel,
            search_params=ivf_pq.SearchParams(n_probes=8, funnel_widen=1),
            index_params=ivf_pq.IndexParams(fast_scan="1bit", **base),
            dataset=X, storage="tiered",
            tier=stream.TierPolicy(oracle_chunk=512, auto_promote=False),
            name="cmp_tiered_funnel")
        da, ia = a.search(Q, 10)
        db, ib = b.search(Q, 10)
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
        np.testing.assert_array_equal(np.asarray(da), np.asarray(db))
        dra, ira = a.search_refined(Q, 10, 4)
        drb, irb = b.search_refined(Q, 10, 4)
        np.testing.assert_array_equal(np.asarray(ira), np.asarray(irb))
        np.testing.assert_array_equal(np.asarray(dra), np.asarray(drb))

    def test_sharded_composition_width1_bit_equal(self, small_corpus):
        """The sharded half of the composition acceptance: per-shard
        funnel builds at width 1 scatter-gather to the same ids as the
        classic-build sharded twin."""
        from raft_tpu import stream

        X, Q = small_corpus
        base = dict(n_lists=8, pq_bits=4, pq_dim=8, seed=0)
        a = stream.ShardedMutableIndex(
            X, n_shards=2,
            build=lambda x: ivf_pq.build(ivf_pq.IndexParams(**base), x),
            search_params=ivf_pq.SearchParams(n_probes=8),
            name="shard_classic")
        b = stream.ShardedMutableIndex(
            X, n_shards=2,
            build=lambda x: ivf_pq.build(
                ivf_pq.IndexParams(fast_scan="1bit", **base), x),
            search_params=ivf_pq.SearchParams(n_probes=8, funnel_widen=1),
            name="shard_funnel")
        da, ia = a.search(Q, 10)
        db, ib = b.search(Q, 10)
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
        np.testing.assert_array_equal(np.asarray(da), np.asarray(db))


def test_funnel_sweep_1m_opq():
    """Heavy 1M funnel sweep (slow manifest; the ISSUE 16 capacity bar at
    the 1M recall anchor): an OPQ+1bit index swept over tune.funnel_grid
    must pin a widened operating point that holds the classic anchor,
    with the recall-vs-QPS frontier in the decision evidence, at >= 2x
    rows per hot-scan HBM byte."""
    from raft_tpu import tune
    from raft_tpu.neighbors import brute_force

    n, d, k = 1_000_000, 32, 10
    x, _ = make_blobs(n, d, n_clusters=1000, cluster_std=1.0, seed=9)
    x = np.asarray(x)
    q = x[:256]
    _, gt = brute_force.knn(x, q, k)
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=1024, pq_bits=4, pq_dim=16,
                           rotation="opq", fast_scan="1bit", seed=0,
                           kmeans_batch_rows=8192), x)
    assert idx.has_fast_scan and idx.rotation_kind == "opq"
    log = tune.DecisionLog()
    dec = tune.sweep(idx, q, k=k, dataset=x, gt=np.asarray(gt),
                     grid=tune.funnel_grid(), recall_target="default",
                     repeats=1, log=log)
    ev = dec.evidence
    assert ev["target_met"], ev
    assert len(ev["trials"]) >= 5 and ev["frontier"], ev
    # the hot-scan capacity bar: classic streams pq_dim+4 B/row, the
    # funnel sig_words+4 (1bit at d_rot=32 -> 4 packed bytes)
    bpr_classic = int(idx.list_codes.shape[2]) + 4
    bpr_funnel = int(idx.list_sig.shape[2]) + 4
    assert bpr_classic / bpr_funnel >= 2.0, (bpr_classic, bpr_funnel)

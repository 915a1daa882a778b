"""The BIGANN cell at a small size on the CPU: a sound run reads
``correct``, a moved id or the control does not; the byte generator's
corpus is a mixture of 32-dim Gaussian clusters mapped into bytes and its
queries one set the seed orders; the blocked byte reference answers
as ``exact_l2`` does on the same rows cast to float32; a tie at the k-th
distance counts as a hit; and the work count gives the numbers worked out
by hand."""

import numpy as np
import pytest

import conftest
from conftest import CLOSED, small_root
from test_faults import _broken, answer_altered

from benchmark import harness
from benchmark.data import bigann_like
from benchmark.reference import exact_l2, exact_l2_bytes


@pytest.fixture
def root(tmp_path, monkeypatch):
    # the refine of byte rows is exact in float32: a sound run's
    # dist_err reads 0 at any size
    monkeypatch.setitem(conftest.SMALL_LIMITS, "bigann10m-ivfpq",
                        {"dist_err": 1e-7, "failed": 0})
    return small_root(tmp_path, "bigann10m-ivfpq", CLOSED,
                      index={"n_lists": 64})


@pytest.mark.parametrize("fault", [None, answer_altered])
def test_moved_id_is_not_correct(root, monkeypatch, run_small, fault):
    if fault is not None:
        _broken(monkeypatch, root, "ivf_pq_bytes", fault)
    out = run_small(root, "small.closed")
    assert out["correct"] == (fault is None), out["check"]
    assert out["failed"] == 0, out["info"]
    if fault is None:
        assert out["check"]["dist_err"]["value"] == 0.0
        assert out["info"]["recall"] > 0.85, out["info"]


def test_control_is_not_correct(root, run_small):
    out = run_small(root, "small.closed", control=True)
    assert not out["correct"], out["check"]
    assert out["info"]["recall"] == 1.0, out["info"]


SPEC = {"rows": 20000, "dim": 128, "queries": 200, "dtype": "uint8",
        "corpus_seed": 0}
SEED = 2 ** 33 + 1


@pytest.fixture(scope="module")
def small():
    corpus, queries = bigann_like.generate(SPEC, SEED)
    return corpus, np.asarray(corpus), np.asarray(queries)


def test_bytes_are_a_32_dim_mixture_mapped(small):
    _, host, q = small
    assert host.dtype == np.uint8 and q.dtype == np.uint8
    # 20k rows are one cluster: a unit-variance draw along each of
    # INTRINSIC_DIM unit directions, scaled by 20, spans INTRINSIC_DIM
    # directions of total variance 32 * 20**2; the other directions hold
    # the rounding's 1/12 of a byte step squared (one a little more, where
    # the clip at 0 bites)
    assert max(1, SPEC["rows"] // bigann_like.ROWS_PER_CLUSTER) == 1
    dims = bigann_like.INTRINSIC_DIM
    eig = np.linalg.eigvalsh(np.cov(host.astype(np.float64), rowvar=False))
    assert abs(eig[-dims:].sum() / (dims * 400) - 1) < 0.05, eig[-dims:].sum()
    rest = eig[:-dims]
    assert eig[-dims] > 50 and rest.max() < 1, eig[-dims - 1:]
    assert abs(np.median(rest) - 1 / 12) < 0.01, np.median(rest)
    # non-negative bytes, as BIGANN's, and the clip touches almost nothing
    assert ((host == 0) | (host == 255)).mean() < 1e-3


@pytest.mark.parametrize("x", [-2.0, -1.0, -0.975, 0.0, 0.525, 11.7, 12.0])
def test_byte_map(x):
    import jax.numpy as jnp

    got = int(bigann_like.to_bytes(jnp.float32(x)))
    assert got == min(255, max(0, int(np.rint(20.0 * x + 20.0))))


def test_queries_are_one_set_the_seed_orders():
    spec = dict(SPEC, rows=1000, queries=50)
    (xa, a), (xb, b), (xc, c) = (
        (np.asarray(x), np.asarray(q)) for x, q in
        (bigann_like.generate(spec, s) for s in (5, 5, 5 + 2 ** 32)))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.array_equal(np.unique(a, axis=0), np.unique(c, axis=0))
    assert np.array_equal(xa, xc)


@pytest.mark.parametrize("rows", [4096, exact_l2_bytes.BLOCK])
def test_blocked_reference_equals_exact_l2(small, rows):
    import jax.numpy as jnp

    corpus, host, q = small
    ids, d = exact_l2_bytes.exact_topk(corpus, q, 10, rows=rows)
    want_ids, want_d = exact_l2.exact_topk(
        jnp.asarray(host, jnp.float32), q.astype(np.float32), 10)
    assert np.array_equal(ids, want_ids)
    assert np.array_equal(d, want_d)


def test_tie_at_kth_counts_as_hit():
    import jax.numpy as jnp

    # squared distances from the origin: 1, 4, 4, 9, 9
    host = np.array([[1, 0], [0, 2], [2, 0], [3, 0], [0, 3]], np.uint8)
    q = np.zeros((1, 2), np.uint8)
    ref_ids, ref_d = exact_l2_bytes.exact_topk(jnp.asarray(host), q, 2)
    assert ref_ids.tolist() == [[0, 1]] and ref_d.tolist() == [[1.0, 4.0]]
    judged = np.array([0])

    def judge(mod, ids, d):
        return mod.judge(host, q, judged, ref_ids, ref_d, judged,
                         np.array([ids]), np.array([d], np.float64))

    tie = judge(exact_l2_bytes, [0, 2], [1.0, 4.0])
    assert tie["recall"] == 1.0 and tie["dist_err"] == tie["rank_gap"] == 0
    assert judge(exact_l2, [0, 2], [1.0, 4.0])["recall"] == 0.5
    beyond = judge(exact_l2_bytes, [0, 3], [1.0, 9.0])
    assert beyond["recall"] == 0.5 and beyond["rank_gap"] > 0


def test_flush_work_by_hand():
    work = harness.Bench().module("work", "ivf_pq_bytes")
    cfg = {"k": 10, "index": {"n_probes": 1, "pq_dim": 2, "pq_bits": 4,
                              "refine_ratio": 4}}
    # centres in the s8 domain: bytes (0, 0), (128, 128), (255, 255)
    info = {"centers": np.array([[-128, -128], [0, 0], [127, 127]],
                                np.float32),
            "list_sizes": np.array([5, 7, 11])}
    queries = np.array([[0, 0], [130, 120]], np.uint8)
    flops, nbytes = work.flush_work(cfg, info, queries)
    # shifted, the queries probe lists 0 and 1 (unshifted: 1 and 2):
    # centres 3 * 2 * 4 = 24 bytes, codes (5 + 7) * 2 * 4 / 8 = 12, the
    # refine rows 2 * 10 * 4 * 2 at a byte each = 160
    assert nbytes == 24 + 12 + 160
    # coarse 2 * 2 * 3 * 2 = 24, LUT 2 * 2 * 1 * 16 * 2 = 128
    assert flops == 24 + 128


@pytest.mark.parametrize("kind,want", [("ivf_pq_bytes", 50.0),
                                       ("ivf_pq", None)])
def test_roofline_divides_by_busy_time(kind, want):
    """The scan's loop ``while`` op overlaps the operations it runs: the
    share divides by their union (``busy_s``), not by their sum."""
    read = harness.Bench().module("metrics",
                                  "ivfpq_bytes_search_roofline").read
    ctx = harness.Context(
        cell="c", cfg={"index": {"kind": kind}}, traffic={}, rec=None,
        hist={}, numbers={}, setup_s=0.0, build_s=None, setup_compile_s=0.0,
        window_programs=0, trace={"busy_s": 1.0, "op_s": 2.0},
        trace_flushes=100, least_flush_s=5e-3)
    got = read(ctx)
    assert got is None if want is None else got == pytest.approx(want)
    ctx.trace = None
    assert read(ctx) is None

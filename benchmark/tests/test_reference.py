"""The plain reference and the comparison: the reference's own answers pass,
degraded ones fail the configurations' limits."""

import json
import os

import numpy as np
import pytest

from benchmark.data import sift_like
from benchmark.reference import exact_l2

from conftest import ROOT


def limits(config):
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        return json.load(f)["limits"]


@pytest.fixture(scope="module")
def small():
    corpus, queries = sift_like.generate(
        {"rows": 20000, "dim": 128, "queries": 200, "dtype": "float32",
         "corpus_seed": 0}, 2 ** 33 + 1)
    q = np.asarray(queries)
    ref_ids, ref_d = exact_l2.exact_topk(corpus, q, 10)
    return corpus, np.asarray(corpus), q, np.arange(len(q)), ref_ids, ref_d


def judge(small, ids, dists):
    corpus, host, q, judged, ref_ids, ref_d = small
    return exact_l2.judge(host, q, judged, ref_ids, ref_d, judged, ids, dists)


def test_queries_follow_every_bit_of_the_seed_and_the_corpus_stays():
    spec = {"rows": 1000, "dim": 128, "queries": 4, "dtype": "float32",
            "corpus_seed": 0}
    (xa, a), (xb, b), (xc, c) = (
        (np.asarray(x), np.asarray(q)) for x, q in
        (sift_like.generate(spec, s) for s in (5, 5, 5 + 2 ** 32)))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.array_equal(xa, xc)


def test_reference_passes_itself(small):
    *_, ref_ids, ref_d = small
    n = judge(small, ref_ids, ref_d)
    assert n["dist_err"] < 1e-12 and n["rank_gap"] == 0.0
    assert n["recall"] == 1.0 and n["rows"] == len(ref_ids)


def test_shuffled_id_column_fails(small):
    *_, ref_ids, ref_d = small
    ids = ref_ids.copy()
    ids[:, 3] = np.random.default_rng(0).permutation(ids[:, 3])
    n = judge(small, ids, ref_d)
    for config in ("sift1m-ivfpq", "sift1m-exact"):
        assert n["dist_err"] > limits(config)["dist_err"]


def test_bf16_queries_fail(small):
    import jax.numpy as jnp

    corpus, host, q, judged, ref_ids, ref_d = small
    qb = np.asarray(jnp.asarray(q).astype(jnp.bfloat16).astype(jnp.float32))
    ids, d = exact_l2.exact_topk(corpus, qb, 10)
    n = judge(small, ids, d)
    for config in ("sift1m-ivfpq", "sift1m-exact"):
        assert n["dist_err"] > limits(config)["dist_err"]


def test_reference_in_bf16_fails(small):
    corpus, *_ = small
    d, ids = exact_l2.bf16_search(corpus)(small[2], 10)
    n = judge(small, np.asarray(ids), np.asarray(d))
    assert n["dist_err"] > limits("sift1m-ivfpq")["dist_err"]


def test_invalid_or_repeated_ids_fail(small):
    *_, ref_ids, ref_d = small
    ids = ref_ids.copy()
    ids[0, 0] = -1
    ids[1, 1] = ids[1, 0]
    n = judge(small, ids, ref_d)
    assert n["dist_err"] == np.inf and n["rank_gap"] == np.inf

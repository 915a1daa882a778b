"""CAGRA on the served path: a ``MutableIndex`` over a CAGRA index, published
into ``SearchService`` through its ``searcher()`` hook, answers exactly as
``cagra.search`` called directly — with no writes, and after a delete (the
sealed keep-mask path)."""

import numpy as np
import pytest

from raft_tpu.neighbors import cagra
from raft_tpu.serve import SearchService
from raft_tpu.stream import MutableIndex


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(1)
    return (rng.random((2000, 16), dtype=np.float32),
            rng.random((16, 16), dtype=np.float32))


@pytest.fixture(scope="module")
def index(data):
    return cagra.build(cagra.IndexParams(intermediate_graph_degree=32,
                                         graph_degree=16, seed=0), data[0])


def _served(mut, queries, k):
    """One full flush of ``queries`` through the service."""
    svc = SearchService(max_batch=len(queries), start_workers=False)
    try:
        svc.publish("cagra", mut.searcher(), k=k)
        fut = svc.submit("cagra", queries, k)
        svc.pump(force=True)
        return fut.result(timeout=0)
    finally:
        svc.shutdown()


@pytest.mark.parametrize("impl", ["xla", "fused_arena"])
@pytest.mark.parametrize("delete", [False, True])
def test_served_answers_as_direct_search(monkeypatch, index, data, impl,
                                         delete):
    monkeypatch.setenv("RAFT_TPU_CAGRA_HOP_INTERPRET", "1")  # the kernel on the CPU
    x, q = data
    sp = cagra.SearchParams(itopk_size=32, hop_impl=impl)
    mut = MutableIndex(index, search_params=sp, retain_vectors=False,
                       name="cagra")
    keep = None
    if delete:
        victim = int(np.asarray(cagra.search(sp, index, q, 10)[1])[0, 0])
        assert mut.delete([victim]) == 1
        keep = np.ones(len(x), bool)
        keep[victim] = False
    want_d, want_i = cagra.search(sp, index, q, 10, sample_filter=keep)
    got_d, got_i = _served(mut, q, 10)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    if delete:
        assert victim not in np.asarray(got_i)

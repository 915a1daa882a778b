"""IVF-PQ over a uint8 corpus through the served path, at 20k x 128 with
64 lists: ``SearchService`` -> ``MutableIndex.refined_searcher`` ->
``ivf_pq`` answers what ``ivf_pq.search`` plus ``refine`` called directly
answer, with distances a float64 computation reproduces exactly and a
recall up to ties the test states; a scan split into several sequential
steps by a small workspace answers what one step answers; the scan-plan
counter counts one increment per search dispatch under the plan's labels.
"""

import numpy as np
import pytest

N, D, LISTS, PROBES, K, RATIO = 20_000, 128, 64, 8, 10, 4


def _bytes(rng, n):
    """Rows of low intrinsic dimension, as SIFT's are (256 clusters, each
    spread in a random 8-dim subspace, plus a little noise), mapped into
    bytes by rint and a clip. One generator: the corpus and the queries
    share its clusters."""
    centers = rng.uniform(40, 215, (256, D))
    bases = rng.standard_normal((256, 8, D)) / np.sqrt(D)
    lab = rng.integers(0, 256, n)
    z = 20.0 * rng.standard_normal((n, 8))
    x = (centers[lab] + np.einsum("ni,nid->nd", z, bases[lab])
         + 2.0 * rng.standard_normal((n, D)))
    return np.clip(np.rint(x), 0, 255).astype(np.uint8)


@pytest.fixture(scope="module")
def data():
    rows = _bytes(np.random.default_rng(7), N + 256)
    return rows[:N], rows[N:]


@pytest.fixture(scope="module")
def index(data):
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_pq

    params = ivf_pq.IndexParams(n_lists=LISTS, pq_dim=64, pq_bits=4,
                                kmeans_trainset_fraction=0.1, seed=0)
    return ivf_pq.build(params, jnp.asarray(data[0]))


SP = dict(n_probes=PROBES, lut_dtype="bfloat16")


def _direct(index, corpus, queries, res=None):
    """``ivf_pq.search`` at the refine width, then ``refine``, per 64-row
    block (the service's flush shape)."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_pq, refine

    sp = ivf_pq.SearchParams(**SP)
    ds, ids = [], []
    for s in range(0, len(queries), 64):
        q = jnp.asarray(queries[s:s + 64])
        _, cand = ivf_pq.search(sp, index, q, K * RATIO, res=res)
        d, i = refine(jnp.asarray(corpus), q, cand, K)
        ds.append(np.asarray(d))
        ids.append(np.asarray(i))
    return np.concatenate(ds), np.concatenate(ids)


@pytest.fixture(scope="module")
def served(data, index):
    """Every query answered through the service, 64 rows a request."""
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.serve import SearchService
    from raft_tpu.stream import MutableIndex

    corpus, queries = data
    mut = MutableIndex(index, search_params=ivf_pq.SearchParams(**SP),
                       dataset=corpus, name="bytes")
    hook = mut.refined_searcher(RATIO)
    assert hook.query_dtype == "uint8"
    svc = SearchService(max_batch=64, max_wait_us=1000)
    try:
        svc.publish("bytes", hook, k=K)
        with obs_compile.attribution() as rec:
            out = [svc.submit("bytes", queries[s:s + 64], K).result(60)
                   for s in range(0, len(queries), 64)]
    finally:
        svc.shutdown()
    d = np.concatenate([np.asarray(o[0]) for o in out])
    i = np.concatenate([np.asarray(o[1]) for o in out])
    return d, i, rec.programs


def test_served_path_equals_search_plus_refine(data, index, served):
    d, i, programs = served
    dd, di = _direct(index, *data)
    assert np.array_equal(i, di)
    assert np.array_equal(d, dd)
    assert programs == 0, "the uint8 warm-up left a program to compile"


def test_served_distances_are_exact(data, served):
    corpus, queries = data
    d, i, _ = served
    assert (i >= 0).all() and (i < N).all()
    exact = ((corpus[i].astype(np.float64)
              - queries[:, None, :].astype(np.float64)) ** 2).sum(-1)
    assert np.array_equal(d.astype(np.float64), exact)


def test_served_recall_up_to_ties(data, served):
    corpus, queries = data
    _, i, _ = served
    x, q = corpus.astype(np.float64), queries.astype(np.float64)
    full = (q * q).sum(1)[:, None] - 2.0 * q @ x.T + (x * x).sum(1)[None]
    kth = np.sort(full, axis=1)[:, K - 1:K]
    got = np.take_along_axis(full, i, axis=1)
    recall = float((got <= kth).sum(1).mean() / K)
    assert recall >= 0.9, recall


def test_several_scan_steps_answer_as_one(data, index):
    from raft_tpu.core import Resources
    from raft_tpu.neighbors._list_utils import (plan_search_tiles,
                                                pq_scan_bytes_per_probe_row)

    per_row = pq_scan_bytes_per_probe_row(index.capacity, index.pq_dim, 16)
    small = Resources(workspace_bytes=16 * per_row)
    one = plan_search_tiles(64, PROBES, K * RATIO, index.capacity, per_row,
                            Resources().workspace_bytes, 128)
    many = plan_search_tiles(64, PROBES, K * RATIO, index.capacity, per_row,
                             small.workspace_bytes, 128)
    assert one == (64, PROBES) and many == (16, 1)
    corpus, queries = data
    d1, i1 = _direct(index, corpus, queries)
    dm, im = _direct(index, corpus, queries, res=small)
    assert np.array_equal(i1, im)
    assert np.array_equal(d1, dm)


def _plan_counts():
    from raft_tpu.obs import metrics

    snap = metrics.snapshot().get("raft_tpu_ivf_pq_scan_plan_total", {})
    return {(s["labels"]["query_tile"], s["labels"]["probe_chunk"]):
            s["value"] for s in snap.get("series", [])}


def test_scan_plan_counter_counts_each_dispatch(data, index):
    import jax.numpy as jnp

    from raft_tpu.core import Resources
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors._list_utils import pq_scan_bytes_per_probe_row

    per_row = pq_scan_bytes_per_probe_row(index.capacity, index.pq_dim, 16)
    small = Resources(workspace_bytes=16 * per_row)
    sp = ivf_pq.SearchParams(**SP)
    q = jnp.asarray(data[1][:64])
    before = _plan_counts()
    for _ in range(3):
        ivf_pq.search(sp, index, q, K)
    ivf_pq.search(sp, index, q, K, res=small)
    after = _plan_counts()
    grew = {key: after[key] - before.get(key, 0.0) for key in after
            if after[key] != before.get(key, 0.0)}
    assert grew == {("64", str(PROBES)): 3.0, ("16", "1"): 1.0}

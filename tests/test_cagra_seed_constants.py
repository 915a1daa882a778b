"""CAGRA seed constants: the corpus norms, the seed pool and its rows depend
only on the index and the search seed, so ``cagra.search`` draws them once
per index and seed and reuses them. The cached constants serve the same
bits as constants computed inside the search program (the distributed
driver's form), and they live and die with the index's dataset."""

import functools
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.neighbors import cagra
from raft_tpu.obs import metrics
from raft_tpu.random.rng import as_key
from raft_tpu.stream import MutableIndex

POOL = 512  # > n_init (32): the scored-pool path


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)
    return (rng.random((2000, 16), dtype=np.float32),
            rng.random((16, 16), dtype=np.float32))


def _build(x):
    return cagra.build(cagra.IndexParams(intermediate_graph_degree=32,
                                         graph_degree=16, seed=0), x)


@pytest.fixture(scope="module")
def index(data):
    return _build(data[0])


def _count(result: str) -> float:
    snap = metrics.snapshot().get("raft_tpu_cagra_seed_constants_total")
    if snap is None:
        return 0.0
    return sum(s["value"] for s in snap["series"]
               if s["labels"].get("result") == result)


def _plan(index, sp):
    return cagra.seed_plan(index.size, sp.itopk_size, sp.search_width,
                           index.graph_degree,
                           cagra.resolve_seed_pool(sp, index.seed_pool_hint))


def _cached(index, sp):
    """The cache's entry for ``sp`` (a lookup that the search already made
    counts a hit)."""
    return cagra._SEEDS.get(index.dataset, sp.seed, *_plan(index, sp))


@functools.partial(jax.jit, static_argnames=("pool", "n_init", "k", "itopk",
                                             "max_iter", "width", "impl"))
def _inline(index, queries, key, keep, pool, n_init, k, itopk, max_iter,
            width, impl):
    """The constants computed inside the search program, as the
    distributed step computes them."""
    seeds = cagra._seed_constants(index.dataset, key, pool, n_init)
    return cagra._cagra_search(index, queries, seeds, k, itopk, max_iter,
                               width, False, impl, keep)


def test_pool_ids_are_the_seeded_draw(index):
    sp = cagra.SearchParams(itopk_size=32, seed_pool=POOL, seed=11)
    pool, n_init = _plan(index, sp)
    assert (pool, n_init) == (POOL, 32)
    seeds = _cached(index, sp)
    want = jax.random.choice(as_key(11), index.size, (POOL,), replace=False)
    np.testing.assert_array_equal(np.asarray(seeds.ids), np.asarray(want))
    x = np.asarray(index.dataset)
    ids = np.asarray(seeds.ids)
    np.testing.assert_array_equal(np.asarray(seeds.pool_vecs), x[ids])
    np.testing.assert_allclose(np.asarray(seeds.dn2),
                               np.sum(x.astype(np.float64) ** 2, axis=1),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(seeds.pool_dn2),
                                  np.asarray(seeds.dn2)[ids])


def test_plain_entries_when_the_pool_scores_nothing(index):
    sp = cagra.SearchParams(itopk_size=32, seed_pool=0, seed=4)
    assert _plan(index, sp) == (0, 32)
    seeds = _cached(index, sp)
    assert seeds.pool_vecs is None and seeds.pool_dn2 is None
    want = jax.random.choice(as_key(4), index.size, (32,), replace=False)
    np.testing.assert_array_equal(np.asarray(seeds.ids), np.asarray(want))


@pytest.mark.parametrize("impl", ["xla", "fused_arena"])
@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("seed_pool", [POOL, 0])
def test_cached_search_serves_the_inline_bits(monkeypatch, index, data, impl,
                                              filtered, seed_pool):
    monkeypatch.setenv("RAFT_TPU_CAGRA_HOP_INTERPRET", "1")  # the kernel on the CPU
    x, q = data
    sp = cagra.SearchParams(itopk_size=32, hop_impl=impl,
                            seed_pool=seed_pool, seed=7)
    keep = None
    if filtered:
        keep = np.ones(len(x), bool)
        keep[::5] = False
    got_d, got_i = cagra.search(sp, index, q, 10, sample_filter=keep)
    again_d, again_i = cagra.search(sp, index, q, 10, sample_filter=keep)
    pool, n_init = _plan(index, sp)
    want_d, want_i = _inline(
        index, jnp.asarray(q), as_key(7),
        None if keep is None else jnp.asarray(keep), pool=pool,
        n_init=n_init, k=10, itopk=32,
        max_iter=cagra.resolve_max_iterations(sp), width=1, impl=impl)
    for got in ((got_d, got_i), (again_d, again_i)):
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want_i))
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want_d))
    if filtered:
        assert not np.isin(np.asarray(got_i), np.flatnonzero(~keep)).any()


def test_first_search_misses_then_hits(data):
    x, q = data
    idx = _build(x)
    sp = cagra.SearchParams(itopk_size=32, seed_pool=POOL)
    miss, hit = _count("miss"), _count("hit")
    cagra.search(sp, idx, q, 10)
    assert (_count("miss") - miss, _count("hit") - hit) == (1, 0)
    for _ in range(3):
        cagra.search(sp, idx, q[:4], 5)  # other shapes share the entry
    assert (_count("miss") - miss, _count("hit") - hit) == (1, 3)


def test_seed_or_pool_makes_a_new_entry(index):
    base = cagra.SearchParams(itopk_size=32, seed_pool=POOL, seed=21)
    a = _cached(index, base)
    miss = _count("miss")
    b = _cached(index, cagra.SearchParams(itopk_size=32, seed_pool=POOL,
                                          seed=22))
    c = _cached(index, cagra.SearchParams(itopk_size=32, seed_pool=2 * POOL,
                                          seed=21))
    assert _count("miss") - miss == 2
    assert not np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
    assert c.ids.shape == (2 * POOL,)
    assert _cached(index, base) is a  # the first entry is still held


def test_entries_per_dataset_are_bounded(index):
    for s in range(cagra._SeedCache.PER_DATASET + 3):
        _cached(index, cagra.SearchParams(itopk_size=32, seed_pool=POOL,
                                          seed=100 + s))
    slot = cagra._SEEDS._by_data[id(index.dataset)]
    assert len(slot[1]) == cagra._SeedCache.PER_DATASET


def test_rebuilt_index_gets_fresh_constants(data):
    x, q = data
    sp = cagra.SearchParams(itopk_size=32, seed_pool=POOL)
    a, b = _build(x), _build(x + 1.0)
    cagra.search(sp, a, q, 10)
    miss = _count("miss")
    got_d, got_i = cagra.search(sp, b, q, 10)
    assert _count("miss") - miss == 1
    np.testing.assert_allclose(
        np.asarray(_cached(b, sp).dn2),
        np.sum((x + 1.0).astype(np.float64) ** 2, axis=1), rtol=1e-6)
    # the same seed draws the same ids from the same-sized corpus
    np.testing.assert_array_equal(np.asarray(_cached(a, sp).ids),
                                  np.asarray(_cached(b, sp).ids))


def test_compaction_swap_answers_as_its_new_sealed_index(data):
    x, q = data
    sp = cagra.SearchParams(itopk_size=32, seed_pool=POOL)
    idx = _build(x)
    mut = MutableIndex(idx, search_params=sp, retain_vectors=True,
                       delta_capacity=32, name="seeds")
    mut.search(q, 10)
    assert mut.delete(list(range(0, 2000, 9))) > 0
    assert mut.compact(mode="rebuild")["mode"] == "rebuild"
    st = mut._state
    sealed = st.sealed
    assert sealed.dataset is not idx.dataset
    got_d, got_i = mut.search(q, 10)
    want_d, want_i = cagra.search(sp, sealed, q, 10,
                                  sample_filter=st.sealed_alive)
    want_i = np.where(np.asarray(want_i) >= 0,
                      st.id_map[np.maximum(np.asarray(want_i), 0)], -1)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    live = np.asarray(sealed.dataset, np.float64)
    np.testing.assert_allclose(np.asarray(_cached(sealed, sp).dn2),
                               np.sum(live ** 2, axis=1), rtol=1e-6)


def test_dropped_index_frees_its_entry(data):
    x, q = data
    idx = _build(x)
    cagra.search(cagra.SearchParams(itopk_size=32, seed_pool=POOL), idx,
                 q, 10)
    ident = id(idx.dataset)
    assert ident in cagra._SEEDS._by_data
    (entry,) = cagra._SEEDS._by_data[ident][1].values()
    norms = weakref.ref(entry.dn2)
    del idx, entry
    gc.collect()
    assert ident not in cagra._SEEDS._by_data
    assert norms() is None


def test_concurrent_lookups_compute_each_entry_once(data):
    import sys
    import threading

    idx = _build(data[0])
    seeds = (31, 32, 33)
    got = {s: [] for s in seeds}
    miss = _count("miss")

    def worker(w):
        for i in range(20):
            s = seeds[(w + i) % len(seeds)]
            got[s].append(cagra._SEEDS.get(idx.dataset, s, POOL, 32))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert _count("miss") - miss == len(seeds)
    for s in seeds:
        assert len(got[s]) > 0 and all(e is got[s][0] for e in got[s])

"""Held row norms of a brute-force index: ``BruteForce`` computes the row
operand its fused search subtracts (an L2 metric's ``|y|^2``, a uint8 inner
product's row bias) once per dataset array and holds it with that array, so
a served flush runs one program: the keep-mask fold, the kernel, the query
norms and the distances. The held norms serve the bits the function API
computes per call, and a dataset that is replaced gets norms of its own."""

import functools
import importlib
import re

import jax
import numpy as np
import pytest

from raft_tpu.neighbors import brute_force
from raft_tpu.obs import mem as obs_mem
from raft_tpu.obs import metrics
from raft_tpu.stream import MutableIndex

# the module (``raft_tpu.ops`` exports its function under the same name)
fk = importlib.import_module("raft_tpu.ops.fused_knn")

N, D, M, K = 4500, 128, 16, 10  # fused-eligible; 4500 % 4096 leaves a ragged block


@pytest.fixture(autouse=True)
def _kernel_on_cpu(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_FUSED_KNN_INTERPRET", "1")


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(29)
    return (rng.random((N, D), dtype=np.float32),
            rng.random((M, D), dtype=np.float32))


def _count(result: str) -> float:
    snap = metrics.snapshot().get("raft_tpu_brute_force_norms_total")
    if snap is None:
        return 0.0
    return sum(s["value"] for s in snap["series"]
               if s["labels"].get("result") == result)


def _counts():
    return _count("miss"), _count("hit")


def _held(dataset):
    return brute_force._NORMS.held(dataset)


def _assert_same(got, want):
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _mapped(st, ids):
    ids = np.asarray(ids)
    return np.where(ids >= 0, st.id_map[np.maximum(ids, 0)], -1)


@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("metric,dtype", [
    ("sqeuclidean", np.float32), ("euclidean", np.float32),
    ("inner_product", np.float32), ("sqeuclidean", np.uint8),
    ("inner_product", np.uint8)])
def test_index_search_is_the_function_api(data, metric, dtype, filtered):
    x, q = data
    if dtype == np.uint8:
        x, q = (x * 255).astype(np.uint8), (q * 255).astype(np.uint8)
    keep = None
    if filtered:
        keep = np.ones(N, bool)
        keep[::3] = False
    idx = brute_force.BruteForce(metric=metric).build(x)
    got = idx.search(q, K, sample_filter=keep)
    _assert_same(got, brute_force.knn(idx.dataset, q, K, metric,
                                      sample_filter=keep))
    if keep is not None:
        assert not np.isin(np.asarray(got[1]), np.flatnonzero(~keep)).any()


def test_mutable_searcher_is_the_function_api(data):
    x, q = data
    idx = brute_force.BruteForce().build(x)
    mut = MutableIndex(idx, retain_vectors=False, delta_capacity=32,
                       name="bf_norms_served")
    assert mut.delete(list(range(0, N, 7))) > 0
    st = mut._state
    got_d, got_i = mut.searcher()(q, K)
    want_d, want_i = brute_force.knn(st.sealed.dataset, q, K,
                                     sample_filter=st.sealed_alive)
    np.testing.assert_array_equal(np.asarray(got_i), _mapped(st, want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


def test_one_miss_per_corpus_then_hits(data):
    x, q = data
    miss, hit = _counts()
    idx = brute_force.BruteForce().build(x)
    assert _counts() == (miss + 1, hit)
    for rows in (M, 4, M):
        idx.search(q[:rows], K)
    assert _counts() == (miss + 1, hit + 3)
    brute_force.knn(idx.dataset, q, K)  # the function API holds nothing
    assert _counts() == (miss + 1, hit + 3)
    (held,) = _held(idx.dataset)
    np.testing.assert_array_equal(
        np.asarray(held), np.asarray(brute_force._row_operand(idx.dataset,
                                                              kind="l2")))


def test_ledger_accounts_the_norms(data):
    x, _ = data
    idx = brute_force.BruteForce().build(x)
    kind, leaves = obs_mem._index_kind_and_leaves(idx)
    assert kind == "brute_force"
    assert sum(a.nbytes for a in leaves) == N * D * 4 + N * 4
    assert obs_mem.plan("brute_force", None, N, D)["index_bytes"] == (
        N * D * 4 + N * 4)


def _replace_by_device_put(idx, tmp_path):
    idx.dataset = jax.device_put(idx.dataset, jax.devices()[1])
    return idx, jax.devices()[1]


def _replace_by_load(idx, tmp_path):
    path = str(tmp_path / "bf.bin")
    brute_force.save(idx, path)
    return brute_force.load(path), jax.devices()[0]


_REPLACE = {"device_put": _replace_by_device_put, "load": _replace_by_load}


@pytest.mark.parametrize("how", sorted(_REPLACE))
def test_replaced_dataset_gets_fresh_norms(data, tmp_path, how):
    x, q = data
    idx = brute_force.BruteForce().build(x)
    old = idx.dataset
    idx, device = _REPLACE[how](idx, tmp_path)
    miss, hit = _counts()
    got = idx.search(q, K)
    # device_put: the first search misses; load: the load did
    assert _counts() == ((miss + 1, hit) if how == "device_put"
                         else (miss, hit + 1))
    assert idx.dataset is not old
    (held,) = _held(idx.dataset)
    assert held.devices() == {device}
    _assert_same(got, brute_force.knn(idx.dataset, q, K))


def test_mutable_device_pin_holds_norms_on_its_device(data):
    x, q = data
    dev = jax.devices()[2]
    mut = MutableIndex(brute_force.BruteForce().build(x),
                       retain_vectors=False, delta_capacity=32, device=dev,
                       name="bf_norms_pinned")
    sealed = mut._state.sealed
    (held,) = _held(sealed.dataset)
    assert held.devices() == {dev}
    miss, hit = _counts()
    got_d, got_i = mut.search(q, K)
    assert _counts() == (miss, hit + 1)
    want_d, want_i = brute_force.knn(sealed.dataset, q, K)
    np.testing.assert_array_equal(np.asarray(got_i),
                                  _mapped(mut._state, want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


def test_compaction_rebuild_holds_fresh_norms(data):
    x, q = data
    mut = MutableIndex(brute_force.BruteForce().build(x),
                       retain_vectors=True, delta_capacity=32,
                       name="bf_norms_compact")
    old = mut._state.sealed.dataset
    assert mut.delete(list(range(0, N, 5))) > 0
    miss = _count("miss")
    assert mut.compact(mode="rebuild")["mode"] == "rebuild"
    assert _count("miss") == miss + 1  # the rebuilt index's build
    st = mut._state
    assert st.sealed.dataset is not old
    (held,) = _held(st.sealed.dataset)
    np.testing.assert_array_equal(
        np.asarray(held),
        np.asarray(brute_force._row_operand(st.sealed.dataset, kind="l2")))
    got_d, got_i = mut.search(q, K)
    want_d, want_i = brute_force.knn(st.sealed.dataset, q, K,
                                     sample_filter=st.sealed_alive)
    np.testing.assert_array_equal(np.asarray(got_i), _mapped(st, want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    assert not np.isin(np.asarray(got_i), np.arange(0, N, 5)).any()


def test_sealed_scan_is_one_program(data, monkeypatch):
    """A served flush computes no norms and hands the kernel's program the
    held norms; that program, lowered for the chip, pads no ``(n, d)``
    operand and reduces none (the norms are not in it)."""
    x, q = data
    mut = MutableIndex(brute_force.BruteForce().build(x),
                       retain_vectors=False, delta_capacity=32,
                       name="bf_norms_program")
    mut.search(q, K)  # warm
    calls = []

    def record(*a, **kw):
        calls.append((a, kw))
        return impl(*a, **kw)

    def no_norms(*a, **kw):
        raise AssertionError("a served flush computed corpus norms")

    impl = fk._fused_knn_impl
    monkeypatch.setattr(fk, "_fused_knn_impl", record)
    monkeypatch.setattr(brute_force, "_row_operand", no_norms)
    mut.search(q, K)
    (args, kw), = calls
    dataset, _, yn, keep = args[:4]
    assert dataset is mut._state.sealed.dataset
    assert yn is _held(dataset)[0] and keep is not None

    static = dict(zip(("k", "l2", "mode", "qt", "nblk"), args[4:9]),
                  interpret=False, **kw)
    spec = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args[:4]]
    text = jax.jit(functools.partial(impl, **static)).trace(*spec).lower(
        lowering_platforms=("tpu",)).as_text()
    corpus = f"tensor<{N}x{D}xf32>"
    for line in text.splitlines():
        if re.search(r"stablehlo\.(pad|reduce|multiply)\b", line):
            assert corpus not in line, line
    assert re.search(r"tpu_custom_call\(%\w+, %arg0,", text), (
        "the corpus is not the kernel's operand as it is")


def test_concurrent_lookups_compute_once(data):
    """Searches on many threads share one computation per array: one miss,
    one held operand, whatever the interleaving."""
    import sys
    import threading

    x, _ = data
    ds = jax.device_put(x)
    miss, hit = _counts()
    got, n_threads = [], 32
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: got.append(brute_force._NORMS.get(ds, "l2")))
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert len(got) == n_threads and all(g is got[0] for g in got)
    assert _counts() == (miss + 1, hit + n_threads - 1)

"""Out-of-core streamed build (ISSUE 19, tier-1 ``ooc`` marker).

Covers the chunked-corpus build path end to end: the PARITY CONTRACT
(an index built from a temp-file ``np.memmap`` through
``core.chunked.ChunkedReader`` is BIT-EQUAL to its in-core twin — same
PRNG trainset, same list ranks, same codes), the ``build_stream``
admission gates (host AND device budgets refuse whole-or-nothing
BEFORE the coarse trainer or any staged chunk spends a byte), the
``extend()`` full-materialization fix (large host batches auto-route
through the chunked path), the warm-build discipline (a second
streamed build compiles nothing), ``obs.mem.plan(streamed=True)``
accuracy against the measured ledger peak at 100k, and the stream
layer's composition seams (tiered mmap adoption, rebuild compaction
and sharded folds taking ``ooc_chunk_rows``).

Deterministic: seeded data, explicit ``seed=`` build params, ledger
assertions RELATIVE (baseline-subtracted) — the ledger is a process
singleton and other tests' live indexes legitimately appear in it.
"""

import dataclasses
import gc

import numpy as np
import pytest

from raft_tpu.core import Resources, chunked
from raft_tpu.obs import compile as obs_compile
from raft_tpu.obs import mem as obs_mem
from raft_tpu.obs import metrics
from raft_tpu.serve.errors import MemoryBudgetError

pytestmark = pytest.mark.ooc


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _index_arrays(ix):
    return {f.name: np.asarray(getattr(ix, f.name))
            for f in dataclasses.fields(ix)
            if hasattr(getattr(ix, f.name), "shape")}


def _assert_bit_equal(a, b, context=""):
    """Every array field of two index dataclasses identical — shape AND
    bytes. The streamed build's whole claim is that chunking changes
    WHERE rows pass through, never what lands in the index."""
    fa, fb = _index_arrays(a), _index_arrays(b)
    assert fa.keys() == fb.keys()
    bad = [k for k in fa
           if fa[k].shape != fb[k].shape or not np.array_equal(fa[k], fb[k])]
    assert not bad, f"fields diverged {context}: {bad}"


def _ooc_chunks_total(kind=None):
    snap = metrics.snapshot().get("raft_tpu_build_ooc_chunks_total")
    if snap is None:
        return 0
    return sum(s["value"] for s in snap["series"]
               if kind is None or s["labels"].get("kind") == kind)


def _dev_total():
    gc.collect()
    return obs_mem.totals()["device_bytes"]


def _staging_entries():
    return [r for r in obs_mem.breakdown()
            if r["component"] == "build/staging"]


# ---------------------------------------------------------------------------
# parity: memmap-streamed build bit-equal to the in-core twin
# ---------------------------------------------------------------------------

def test_memmap_parity_ivf_flat(rng, tmp_path):
    """ISSUE 19 acceptance: an IVF-Flat index built from a raw-binary
    ``np.memmap`` corpus in ~5 chunks is bit-equal to the in-core build
    of the same rows — every field, including the order-sensitive list
    layout. Also pins the ooc metrics family: per-chunk counters tick
    and the chunk-rows gauge reflects the reader."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_flat

    n, d = 20_000, 32
    data = rng.standard_normal((n, d)).astype(np.float32)
    raw = tmp_path / "corpus.f32"
    data.tofile(raw)

    params = ivf_flat.IndexParams(n_lists=64, seed=3)
    incore = ivf_flat.build(params, jnp.asarray(data))

    before = _ooc_chunks_total(kind="ivf_flat")
    reader = chunked.ChunkedReader.from_file(
        raw, dtype=np.float32, shape=(n, d), chunk_rows=4096)
    assert reader.n_chunks == 5
    streamed = ivf_flat.build(params, reader)

    _assert_bit_equal(incore, streamed, "(ivf_flat memmap vs in-core)")
    assert _ooc_chunks_total(kind="ivf_flat") >= before + reader.n_chunks
    snap = metrics.snapshot()
    assert snap["raft_tpu_build_ooc_chunk_rows"]["series"], (
        "the chunk-rows gauge must be set by the streamed build")
    staged = sum(s["value"] for s in
                 snap["raft_tpu_build_ooc_staged_bytes_total"]["series"])
    assert staged > 0


def test_npy_memmap_parity_ivf_pq(rng, tmp_path):
    """The IVF-PQ leg of the parity contract, through the ``.npy``
    mmap door: coarse centers, OPQ rotation, codebooks, per-list codes
    and ids all bit-equal — the residual-encode pass is chunk-order
    independent by construction."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_pq

    n, d = 20_000, 32
    data = rng.standard_normal((n, d)).astype(np.float32)
    path = tmp_path / "corpus.npy"
    np.save(path, data)

    params = ivf_pq.IndexParams(n_lists=64, pq_dim=8, seed=5)
    incore = ivf_pq.build(params, jnp.asarray(data))
    streamed = ivf_pq.build(
        params, chunked.ChunkedReader.from_file(path, chunk_rows=4096))
    _assert_bit_equal(incore, streamed, "(ivf_pq .npy vs in-core)")


def test_u8bin_memmap_parity_ivf_pq_uint8(rng, tmp_path):
    """The IVF-PQ parity contract for a byte corpus read from a raw
    ``.u8bin``-style file, as a BIGANN deployment builds: the s8 shift and
    f32 upcast per chunk, the trainset gather (a 10% trainset, so it runs)
    and the per-chunk encode and fill give the in-core build's centres,
    codebooks, codes and ids, bit for bit."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_pq

    n, d = 20_000, 32
    centers = rng.uniform(30, 225, (64, d))
    data = np.clip(np.rint(centers[rng.integers(0, 64, n)]
                           + 10.0 * rng.standard_normal((n, d))),
                   0, 255).astype(np.uint8)
    raw = tmp_path / "base.u8bin"
    data.tofile(raw)

    params = ivf_pq.IndexParams(n_lists=64, pq_dim=16,
                                kmeans_trainset_fraction=0.1, seed=4)
    incore = ivf_pq.build(params, jnp.asarray(data))
    reader = chunked.ChunkedReader.from_file(
        raw, dtype=np.uint8, shape=(n, d), chunk_rows=4096)
    streamed = ivf_pq.build(params, reader)
    assert incore.data_kind == streamed.data_kind == "uint8"
    _assert_bit_equal(incore, streamed, "(ivf_pq uint8 .u8bin vs in-core)")


def test_memmap_parity_brute_force_uint8(rng, tmp_path):
    """Dataset-resident kinds stream too: brute force materializes the
    reader chunk-by-chunk into ONE device array — bit-equal rows, and
    the s8-shift for uint8 corpora applied identically."""
    from raft_tpu.neighbors import brute_force

    n, d = 10_000, 16
    data = rng.integers(0, 256, (n, d), dtype=np.uint8)
    raw = tmp_path / "corpus.u8"
    data.tofile(raw)

    incore = brute_force.BruteForce().build(data)
    streamed = brute_force.BruteForce().build(
        chunked.ChunkedReader.from_file(raw, dtype=np.uint8, shape=(n, d),
                                        chunk_rows=3000))
    assert np.array_equal(np.asarray(incore.dataset),
                          np.asarray(streamed.dataset))


def test_memmap_parity_cagra(rng, tmp_path):
    """CAGRA parity (slow: the knn-graph self-search dominates): the
    streamed dataset materialization feeds the same graph pipeline, so
    dataset AND graph come back bit-equal."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import cagra

    n, d = 4096, 16
    data = rng.standard_normal((n, d)).astype(np.float32)
    path = tmp_path / "corpus.npy"
    np.save(path, data)

    params = cagra.IndexParams(intermediate_graph_degree=16,
                               graph_degree=8)
    incore = cagra.build(params, jnp.asarray(data))
    streamed = cagra.build(
        params, chunked.ChunkedReader.from_file(path, chunk_rows=1000))
    assert np.array_equal(np.asarray(incore.dataset),
                          np.asarray(streamed.dataset))
    assert np.array_equal(np.asarray(incore.graph),
                          np.asarray(streamed.graph))


# ---------------------------------------------------------------------------
# admission gates: whole-or-nothing, before anything spends
# ---------------------------------------------------------------------------

def test_host_budget_refuses_before_any_chunk(rng):
    """ISSUE 19 satellite: an armed ``host_budget_bytes`` the staging +
    trainset peak exceeds refuses at ``site="build_stream/host"``
    BEFORE the coarse trainer or any staged chunk lands — ledger device
    bytes untouched, no staging entry, no chunk counter tick."""
    from raft_tpu.neighbors import ivf_flat, ivf_pq

    data = rng.standard_normal((4000, 16)).astype(np.float32)
    res = Resources(host_budget_bytes=1 << 10)
    for mod, params in ((ivf_flat, ivf_flat.IndexParams(n_lists=16)),
                        (ivf_pq, ivf_pq.IndexParams(n_lists=16, pq_dim=4))):
        dev0, chunks0 = _dev_total(), _ooc_chunks_total()
        staging0 = len(_staging_entries())
        with pytest.raises(MemoryBudgetError) as ei:
            mod.build(params, chunked.ChunkedReader(data, chunk_rows=1000),
                      res=res)
        assert ei.value.site == "build_stream/host", ei.value.site
        assert _dev_total() == dev0
        assert _ooc_chunks_total() == chunks0
        assert len(_staging_entries()) == staging0


def test_device_budget_refuses_streamed_build(rng):
    """The device half of the gate: the streamed build prices its peak
    (index + staged slots + labels) against ``memory_budget_bytes`` and
    refuses at ``site="build_stream"`` whole-or-nothing."""
    from raft_tpu.neighbors import ivf_flat

    data = rng.standard_normal((4000, 16)).astype(np.float32)
    res = Resources(memory_budget_bytes=1 << 10)
    dev0 = _dev_total()
    with pytest.raises(MemoryBudgetError) as ei:
        ivf_flat.build(ivf_flat.IndexParams(n_lists=16),
                       chunked.ChunkedReader(data, chunk_rows=1000), res=res)
    assert ei.value.site == "build_stream", ei.value.site
    assert _dev_total() == dev0


# ---------------------------------------------------------------------------
# extend(): the full-materialization fix
# ---------------------------------------------------------------------------

def test_extend_auto_wraps_large_host_batches(rng, monkeypatch):
    """The regression the fix exists for: a host ndarray batch past
    ``_STREAM_EXTEND_BYTES`` must take the chunked path (per-chunk
    assign + scatter — chunk counters tick) and still come back
    bit-equal to the in-core extend of a twin index. Patching the
    ivf_flat threshold covers ivf_pq too — its extend imports the same
    module global."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_flat, ivf_pq

    n, d = 4000, 16
    data = rng.standard_normal((n, d)).astype(np.float32)
    batch = rng.standard_normal((1500, d)).astype(np.float32)
    monkeypatch.setattr(ivf_flat, "_STREAM_EXTEND_BYTES", 1 << 12)

    for mod, params in (
            (ivf_flat, ivf_flat.IndexParams(n_lists=32, seed=8)),
            (ivf_pq, ivf_pq.IndexParams(n_lists=32, pq_dim=8, seed=9))):
        kind = mod.__name__.rsplit(".", 1)[-1]
        base_a = mod.build(params, jnp.asarray(data))
        base_b = mod.build(params, jnp.asarray(data))
        # jnp input is not an ndarray -> stays on the in-core path
        incore = mod.extend(base_a, jnp.asarray(batch))
        before = _ooc_chunks_total(kind=kind)
        streamed = mod.extend(base_b, batch)
        assert _ooc_chunks_total(kind=kind) > before, (
            f"{kind}: the oversized host batch must stream")
        _assert_bit_equal(incore, streamed, f"({kind} auto-wrapped extend)")


def test_extend_small_batches_stay_in_core(rng):
    """Batches under the threshold keep the one-shot path — no chunk
    counter tick, no behavior change for the common small append."""
    from raft_tpu.neighbors import ivf_flat

    data = rng.standard_normal((3000, 16)).astype(np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=16, seed=1), data)
    before = _ooc_chunks_total(kind="ivf_flat")
    ivf_flat.extend(idx, rng.standard_normal((64, 16)).astype(np.float32))
    assert _ooc_chunks_total(kind="ivf_flat") == before


# ---------------------------------------------------------------------------
# warm-build discipline: the chunked loop must not sync or recompile
# ---------------------------------------------------------------------------

def test_second_streamed_build_compiles_nothing(rng):
    """ISSUE 19 satellite (dispatch-attribution guard): with shapes
    warm, a whole streamed ivf_pq rebuild — stage, assign, residual
    encode, scatter — launches ZERO fresh XLA programs. A per-chunk
    host round-trip or shape wobble would show up here first."""
    from raft_tpu.neighbors import ivf_pq

    data = rng.standard_normal((8000, 16)).astype(np.float32)
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=4, seed=2)
    reader = chunked.ChunkedReader(data, chunk_rows=2000)
    first = ivf_pq.build(params, reader)
    with obs_compile.attribution() as rec:
        second = ivf_pq.build(params, reader)
    assert rec.programs == 0, (
        f"warm streamed rebuild compiled {rec.programs} programs "
        f"({rec.compile_s:.3f}s)")
    _assert_bit_equal(first, second, "(streamed rebuild determinism)")


# ---------------------------------------------------------------------------
# plan(streamed=True) accuracy
# ---------------------------------------------------------------------------

def test_plan_streamed_within_20pct_at_100k(rng):
    """ISSUE 19 satellite: the streamed-mode estimate vs the measured
    ledger peak of a REAL chunked build at 100k rows, same ±20%
    contract as the in-core estimator suite (test_obs_mem). plan()
    slightly overestimates by design — the labels scratch it prices is
    transient and partially outside the accounted window."""
    import jax

    from raft_tpu.neighbors import ivf_flat

    n, d, cr = 100_000, 16, 8192
    params = ivf_flat.IndexParams(n_lists=256, kmeans_n_iters=4)
    data = rng.random((n, d)).astype(np.float32)

    est = obs_mem.plan("ivf_flat", params, n, d, streamed=True,
                       chunk_rows=cr)
    assert est["host_peak_bytes"] > 0, "streamed plan must price host"

    baseline = _dev_total()
    obs_mem.reset_peak()
    idx = ivf_flat.build(params, chunked.ChunkedReader(data, chunk_rows=cr))
    jax.block_until_ready(jax.tree_util.tree_leaves(idx))
    measured = obs_mem.totals()["device_peak_bytes"] - baseline
    assert measured > 0
    assert abs(est["build_peak_bytes"] - measured) <= 0.20 * measured, (
        f"streamed plan {est['build_peak_bytes']} vs measured {measured} "
        f"({est['build_peak_bytes'] / measured:.3f}x) outside ±20%")


# ---------------------------------------------------------------------------
# stream-layer composition: tiered adoption, compaction, sharded folds
# ---------------------------------------------------------------------------

def test_tiered_store_adopts_mmap_corpus(rng, tmp_path):
    """A ``MutableIndex(dataset=reader, storage="tiered")`` over an
    mmap corpus ADOPTS the mapping as its cold tier in place: residency
    "disk", ZERO host bytes accounted (pages are disk-backed), and the
    refine hop serves straight off it."""
    import jax.numpy as jnp

    from raft_tpu import stream
    from raft_tpu.neighbors import ivf_pq

    n, d = 4000, 24
    data = rng.standard_normal((n, d)).astype(np.float32)
    path = tmp_path / "corpus.npy"
    np.save(path, data)

    reader = chunked.ChunkedReader.from_file(path, chunk_rows=900)
    params = ivf_pq.IndexParams(n_lists=16, seed=1)
    sealed = ivf_pq.build(params, reader)
    mi = stream.MutableIndex(sealed, dataset=reader, index_params=params,
                             storage="tiered", name="ooc_tiered_adopt")
    ts = mi.tiered_store
    assert ts.residency == "disk"
    tb = ts.tier_bytes()
    assert tb["host"] == 0 and tb["device"] == 0
    assert tb["disk"] == n * d * 4
    _, ids = mi.search_refined(jnp.asarray(data[:8]), 5, 4)
    assert np.asarray(ids).shape == (8, 5)


def test_compact_rebuild_takes_ooc_chunk_rows(rng):
    """Rebuild compaction through the chunked reader is bit-equal to
    the in-core fold: same live rows, same sealed result — the
    compactor only changes how rows travel to the builder."""
    from raft_tpu import stream
    from raft_tpu.neighbors import ivf_flat

    n, d = 2500, 16
    data = rng.standard_normal((n, d)).astype(np.float32)
    extra = rng.standard_normal((50, d)).astype(np.float32)
    params = ivf_flat.IndexParams(n_lists=16, seed=2)

    def make(name):
        m = stream.MutableIndex(ivf_flat.build(params, data),
                                dataset=data, index_params=params,
                                name=name)
        m.upsert(extra)
        m.delete(np.arange(10))
        return m

    m_incore, m_ooc = make("ooc_cmp_a"), make("ooc_cmp_b")
    m_incore.compact(mode="rebuild")
    m_ooc.compact(mode="rebuild", ooc_chunk_rows=777)
    _assert_bit_equal(m_incore._state.sealed, m_ooc._state.sealed,
                      "(rebuild compact via reader)")


def test_compact_ooc_chunk_rows_requires_rebuild(rng):
    """The knob is rebuild-only — extend-mode compaction never re-reads
    the corpus, so accepting the argument there would lie."""
    from raft_tpu import stream
    from raft_tpu.core.errors import RaftError
    from raft_tpu.neighbors import ivf_flat

    data = rng.standard_normal((2000, 16)).astype(np.float32)
    params = ivf_flat.IndexParams(n_lists=16, seed=4)
    m = stream.MutableIndex(ivf_flat.build(params, data), dataset=data,
                            index_params=params, name="ooc_mode_guard")
    with pytest.raises(RaftError):
        m.compact(mode="extend", ooc_chunk_rows=512)


def test_sharded_builds_from_reader_and_ooc_compacts(rng, tmp_path):
    """The mesh seam: a ShardedMutableIndex takes the reader directly
    (per-shard rows gathered via ``take`` — only the home shard's pages
    are touched), serves, and per-shard rebuild folds forward
    ``ooc_chunk_rows``."""
    import jax.numpy as jnp

    from raft_tpu import stream
    from raft_tpu.neighbors import ivf_flat

    n, d = 2500, 16
    data = rng.standard_normal((n, d)).astype(np.float32)
    path = tmp_path / "corpus.npy"
    np.save(path, data)
    params = ivf_flat.IndexParams(n_lists=16, seed=6)

    sm = stream.ShardedMutableIndex(
        chunked.ChunkedReader.from_file(path, chunk_rows=900),
        n_shards=2, build=lambda rows: ivf_flat.build(params, rows),
        index_params=params)
    _, ids = sm.search(jnp.asarray(data[:4]), 5)
    assert np.asarray(ids).shape == (4, 5)
    rep = sm.compact(mode="rebuild", shard=0, ooc_chunk_rows=512)
    assert rep["mode"] == "rebuild" and rep["shard"] == 0


# ---------------------------------------------------------------------------
# 10M-class (slow manifest)
# ---------------------------------------------------------------------------

def test_ooc_build_10m_class(rng, tmp_path):
    """The scale the subsystem exists for (slow manifest): a 10M-row
    uint8 corpus — 320 MB, deliberately bigger than any single staged
    allocation by orders of magnitude — streamed off disk. The measured
    device peak must stay INSIDE the streamed plan's +20% admission
    envelope (whose staging term is two chunks — corpus size shows up
    as index bytes, never as a whole-corpus staging copy; the plan's
    transient label scratch sits partly outside the accounted window,
    so the bound is one-sided at this scale), and the result must
    serve."""
    import jax

    from raft_tpu.neighbors import ivf_flat

    n, d, cr = 10_000_000, 32, 262_144
    raw = tmp_path / "corpus10m.u8"
    mm = np.memmap(raw, dtype=np.uint8, mode="w+", shape=(n, d))
    chunk = 1_000_000
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        mm[s:e] = rng.integers(0, 256, (e - s, d), dtype=np.uint8)
    mm.flush()
    del mm

    params = ivf_flat.IndexParams(n_lists=1024, kmeans_n_iters=4,
                                  kmeans_trainset_fraction=0.02, seed=0)
    reader = chunked.ChunkedReader.from_file(raw, dtype=np.uint8,
                                             shape=(n, d), chunk_rows=cr)
    est = obs_mem.plan("ivf_flat", params, n, d, dtype="uint8",
                       streamed=True, chunk_rows=cr)
    baseline = _dev_total()
    obs_mem.reset_peak()
    idx = ivf_flat.build(params, reader)
    jax.block_until_ready(jax.tree_util.tree_leaves(idx))
    measured = obs_mem.totals()["device_peak_bytes"] - baseline
    assert 0 < measured <= 1.2 * est["build_peak_bytes"], (
        f"10M streamed peak {measured} above plan "
        f"{est['build_peak_bytes']} +20%")

    q = rng.integers(0, 256, (4, d), dtype=np.uint8)
    _, ids = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), idx, q, 10)
    assert np.asarray(ids).shape == (4, 10)

"""The one span API (``raft_tpu.core.tracing``): host spans land on the
profiler's host timeline and in the request-log collector; device stages
are named scopes that ride the HLO metadata of the served programs and open
no host annotation."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core import tracing
from raft_tpu.obs import requestlog


def _host_events(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    files = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(files) == 1, files
    pd = ProfileData.from_file(files[0])
    return [e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def test_range_lands_in_collector_and_on_host_plane(tmp_path):
    x = jnp.ones((8, 8))
    jax.block_until_ready(x + 1)  # compile outside the traced span
    jax.profiler.start_trace(str(tmp_path))
    try:
        with requestlog.collect() as col:
            with tracing.range("serve/flush/%d", 64):
                jax.block_until_ready(x + 1)
    finally:
        jax.profiler.stop_trace()
    assert set(col.spans) == {"serve/flush/64"}
    assert col.spans["serve/flush/64"] > 0
    assert "serve/flush/64" in _host_events(str(tmp_path))


def test_range_without_collector_or_profiler_is_a_noop():
    with tracing.range("serve/stage"):
        pass  # no collector open: nothing recorded, nothing raised
    with requestlog.collect() as col:
        tracing.disable()
        try:
            with tracing.range("serve/stage"):
                pass
        finally:
            tracing.enable()
    # the request-log wall does not depend on the profiler switch
    assert set(col.spans) == {"serve/stage"}


def test_range_records_its_wall_when_the_body_raises():
    with requestlog.collect() as col:
        with pytest.raises(ValueError):
            with tracing.range("serve/complete"):
                raise ValueError("materialize failed")
    assert "serve/complete" in col.spans


def test_scope_opens_no_trace_annotation(monkeypatch):
    opened = []

    class Recorder:
        def __init__(self, name, **kw):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    with requestlog.collect() as col:
        with tracing.scope("stream.merge"):
            jax.block_until_ready(jnp.ones(4) * 2)
        assert opened == [] and col.spans == {}
        with tracing.range("serve/resolve"):
            pass
    assert opened == ["serve/resolve"]


def test_scope_names_ops_inside_jit_only():
    def f(x):
        with tracing.scope("stream.delta"):
            return x * 2

    assert "stream.delta" in _names(_lowered(jax.jit(f), jnp.ones(4)))
    # a scope opened around a top-level jit does not reach its operations
    with tracing.scope("stream.delta"):
        text = _lowered(jax.jit(lambda x: x * 2), jnp.ones(4))
    assert "stream.delta" not in _names(text)


def _lowered(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).as_text(debug_info=True)


def _names(text: str) -> set:
    """Every component of every op-name location in lowered text (the
    name stack the profiler later reports as an operation's ``tf_op``)."""
    return {part for loc in re.findall(r'loc\("([^"]*)"', text)
            for part in loc.split("/")}


# -- the served programs carry their stage names ----------------------------

def _spy(monkeypatch, module, name, store):
    orig = getattr(module, name)

    def spy(*a, **kw):
        store.append(_lowered(orig, *a, **kw))
        return orig(*a, **kw)

    monkeypatch.setattr(module, name, spy)


@pytest.fixture(scope="module")
def pq_index():
    from raft_tpu.neighbors import ivf_pq

    x = np.random.default_rng(0).random((2000, 16), dtype=np.float32)
    return x, ivf_pq.build(ivf_pq.IndexParams(n_lists=8, pq_dim=8, seed=0),
                           x)


def test_pq_search_names_its_stages(monkeypatch, pq_index):
    from raft_tpu.neighbors import ivf_pq

    x, index = pq_index
    texts: list = []
    _spy(monkeypatch, ivf_pq, "_pq_search", texts)
    keep = np.ones(len(x), bool)
    keep[::7] = False
    ivf_pq.search(ivf_pq.SearchParams(n_probes=4), index, x[:8], 5,
                  sample_filter=keep)
    (text,) = texts
    assert {"ivf_pq.search.coarse", "ivf_pq.search.lut", "ivf_pq.search.scan",
            "sample_filter", "select_k"} <= _names(text)


def test_refine_gather_and_rerank_name_refine(pq_index):
    from raft_tpu.distance.types import DistanceType
    from raft_tpu.neighbors.refine import _refine_gathered
    from raft_tpu.stream import tiered

    x, _ = pq_index
    rows = jnp.asarray(x)
    slots = jnp.asarray(np.arange(40, dtype=np.int32).reshape(4, 10))
    gather = tiered._tier_jits()[0]  # mirror_gather's program
    assert "refine" in _names(_lowered(gather, rows, slots))
    text = _lowered(_refine_gathered, gather(rows, slots), rows[:4], slots,
                    k=5, metric=DistanceType.L2Expanded)
    assert "refine" in _names(text)


@pytest.mark.parametrize("which,stage", [(0, "stream.map_ids"),
                                         (1, "stream.merge")])
def test_stream_programs_name_their_stage(which, stage):
    from raft_tpu.stream import mutable

    fn = mutable._jits()[which]
    ids = jnp.asarray(np.arange(20, dtype=np.int32).reshape(4, 5))
    if which == 0:
        text = _lowered(fn, ids, jnp.arange(100, dtype=jnp.int32))
    else:
        d = jnp.ones((4, 5), jnp.float32)
        text = _lowered(fn, d, ids, d, ids, k=5, select_min=True)
    assert stage in _names(text)
    if which == 1:
        assert "select_k" in _names(text)  # the merge's select, innermost


def test_fused_knn_names_pad_and_kernel():
    from raft_tpu.ops.fused_knn import _fused_knn_impl

    x = jnp.ones((4096, 64), jnp.float32)
    q = jnp.ones((8, 64), jnp.float32)
    text = _lowered(_fused_knn_impl, x, q, jnp.ones((4096,)), None, k=4,
                    l2=True, mode="f32", qt=8, nblk=1024, interpret=True)
    # the kernel is named by its pallas_call(name=...)
    assert {"fused_knn.pad", "fused_knn"} <= _names(text)


# -- CAGRA search: seed, hops, select ------------------------------------------

CAGRA_STAGES = {"cagra.search.seed", "cagra.search.hops",
                "cagra.search.select"}


@pytest.fixture(scope="module")
def cagra_index():
    from raft_tpu.neighbors import cagra

    x = np.random.default_rng(0).random((2000, 16), dtype=np.float32)
    return x, cagra.build(cagra.IndexParams(intermediate_graph_degree=32,
                                            graph_degree=16, seed=0), x)


@pytest.mark.parametrize("impl", ["xla", "fused_arena"])
def test_cagra_search_names_its_stages(monkeypatch, cagra_index, impl):
    from raft_tpu.neighbors import cagra

    monkeypatch.setenv("RAFT_TPU_CAGRA_HOP_INTERPRET", "1")  # the kernel on the CPU
    x, index = cagra_index
    texts: list = []
    _spy(monkeypatch, cagra, "_cagra_search", texts)
    keep = np.ones(len(x), bool)
    keep[::7] = False
    cagra.search(cagra.SearchParams(itopk_size=32, hop_impl=impl), index,
                 x[:8], 5, sample_filter=keep)
    (text,) = texts
    names = _names(text)
    assert CAGRA_STAGES <= names
    # the kernel and the arena's closing select_k, innermost in their stages
    assert ("cagra_hop" in names) == ("select_k" in names) == (impl != "xla")
    # the seed stage scores the pool (GEMM, top_k) and re-scores the seeds;
    # the pool draw and the corpus norms come in as arguments
    seed_ops = {loc.split("cagra.search.seed/", 1)[1]
                for loc in re.findall(r'loc\("([^"]*)"', text)
                if "cagra.search.seed/" in loc}
    assert {"md,sd->ms/dot_general", "top_k",
            "md,med->me/dot_general"} <= seed_ops
    assert "cagra.seed_constants" not in names
    assert not any("threefry" in n or "random" in n for n in names)


@pytest.mark.parametrize("impl", ["xla", "fused_arena"])
def test_cagra_stages_change_no_result_bit(monkeypatch, cagra_index, impl):
    """The same search traced with every stage a no-op serves the same
    bits: a stage is metadata only."""
    import contextlib
    import functools

    from raft_tpu.neighbors import cagra

    monkeypatch.setenv("RAFT_TPU_CAGRA_HOP_INTERPRET", "1")
    x, index = cagra_index
    sp = cagra.SearchParams(itopk_size=32, hop_impl=impl)
    staged = cagra.search(sp, index, x[:16], 10)
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    body = cagra._cagra_search.__wrapped__
    # a new function object: jit would reuse the staged trace of ``body``
    plain_fn = jax.jit(functools.wraps(body)(lambda *a, **kw: body(*a, **kw)),
                       static_argnames=("k", "itopk", "max_iter",
                                        "search_width", "sqrt_out",
                                        "hop_impl"))
    texts: list = []
    monkeypatch.setattr(cagra, "_cagra_search", plain_fn)
    _spy(monkeypatch, cagra, "_cagra_search", texts)
    plain = cagra.search(sp, index, x[:16], 10)
    assert not CAGRA_STAGES & _names(texts[0])
    for a, b in zip(staged, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

"""The stage reduction (``stages.py``) on traces recorded on a TPU v5e:
``sift1m-ivfpq.batch64.xplane.pb.gz`` from before the program named its
stages, and one ``<cell>.stages.xplane.pb.gz`` per cell with the stage
scopes (0.15 s of each cell, recorded by ``stages_run.py``)."""

import gzip
import os
import shutil

import pytest

from benchmark import stages, trace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
UNNAMED = os.path.join(FIXTURES, "sift1m-ivfpq.batch64.xplane.pb.gz")
CELLS = ("sift1m-ivfpq.batch64", "sift1m-exact.batch64")


def named(cell: str) -> str:
    return os.path.join(FIXTURES, f"{cell}.stages.xplane.pb.gz")


def _raw(path: str, tmp_path) -> str:
    out = tmp_path / os.path.basename(path)[:-3]
    with gzip.open(path, "rb") as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(out)


def test_scan_scope_of_the_unnamed_trace():
    """Before the stage scopes the one in-jit scope around the whole scan
    held 94.9% of device time: the reader finds it."""
    r = stages.reduce(UNNAMED)
    share = dict(r["stages"])["ivf_pq.search.scan"] / r["op_s"]
    assert 0.94 <= share <= 0.96


def test_existing_reduction_unchanged_on_the_unnamed_trace(tmp_path):
    """``trace.reduce`` — what every accepted device metric reads — gives
    the numbers it gave when the fixture was recorded."""
    r = trace.reduce(_raw(UNNAMED, tmp_path))
    assert r["window_s"] == pytest.approx(0.150737653, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.146848043, rel=1e-9)
    assert r["op_s"] == pytest.approx(0.146848043, rel=1e-9)
    assert r["idle_pct"] == pytest.approx(2.5803838, rel=1e-6)
    assert r["device_ops"][0][0].startswith("jit__pq_search %fusion.1")


@pytest.mark.parametrize("path", [UNNAMED] + [named(c) for c in CELLS])
def test_stages_and_unattributed_add_up_to_op_s(path, tmp_path):
    r = stages.reduce(path)
    ref = trace.reduce(_raw(path, tmp_path))
    assert r["window_s"] == pytest.approx(ref["window_s"], rel=1e-9)
    # picoseconds here, whole nanoseconds in ProfileData
    assert r["op_s"] == pytest.approx(ref["op_s"], rel=1e-4)
    total = sum(t for _, t in r["stages"]) + r["unattributed_s"]
    assert total == pytest.approx(r["op_s"], rel=1e-9)
    assert [t for _, t in r["stages"]] == sorted(
        (t for _, t in r["stages"]), reverse=True)


@pytest.mark.parametrize("cell", CELLS)
def test_named_trace_attributes_its_device_time(cell):
    r = stages.reduce(named(cell))
    assert r["unattributed_s"] <= 0.05 * r["op_s"], r["unattributed"]
    t = dict(r["stages"])
    if cell.startswith("sift1m-ivfpq"):
        # the keep-mask gather of every probed slot leads (PERF.md §5)
        assert r["stages"][0][0] == "sample_filter"
        assert {"ivf_pq.search.scan", "select_k", "refine",
                "stream.merge"} <= set(t)
    else:
        prep = t["brute_force.norms"] + t["fused_knn.pad"]
        assert prep > t["fused_knn"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_idle_gaps_name_a_serve_span(cell):
    """Every idle gap of the named traces over 0.5 ms falls under a host
    step of a flush — away from the window's edges, where the step's span
    may have opened before the trace started or closed after it stopped
    (the profiler then drops it). A flush takes under 10 ms."""
    r = stages.reduce(named(cell))
    assert any(g[0].startswith(stages.SERVE_SPAN) for g in r["idle_gaps"])
    for serve, _, seconds, start in r["idle_gaps"]:
        if (seconds > 5e-4 and start >= 0.01
                and start + seconds <= r["window_s"] - 0.01):
            assert serve.startswith(stages.SERVE_SPAN), r["idle_gaps"]


def test_stage_of_takes_the_innermost_known_stage():
    op = "jit(merge)/stream.merge/jit(_select_k)/select_k/top_k"
    assert stages.stage_of(op, "jit_merge") == "select_k"
    assert stages.stage_of("jit(integer_pow)/integer_pow",
                           "jit_integer_pow") == "brute_force.norms"
    assert stages.stage_of("", "jit__pq_search") is None


def test_read_space_matches_profile_data(tmp_path):
    """The wire-format reader sees the host events ``ProfileData`` sees,
    at the same times (a CPU trace)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(stages.WINDOW):
            jax.block_until_ready(x @ x)
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(dp, f) for dp, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    want = sorted((p.name, e.name, e.start_ns, e.duration_ns)
                  for p in ProfileData.from_file(path).planes
                  for line in p.lines for e in line.events)
    got = sorted((p["name"], n, s / 1000, (e - s) / 1000)
                 for p in stages.read_space(path)
                 for events in p["lines"].values()
                 for s, e, n, _ in events)
    assert len(got) == len(want) > 0
    assert [g[:2] for g in got] == [w[:2] for w in want]
    for g, w in zip(got, want):
        assert g[2] == pytest.approx(w[2], abs=1e-3)
        assert g[3] == pytest.approx(w[3], abs=1e-3)

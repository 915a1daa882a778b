"""The CAGRA cell at a small size on the CPU: a sound run reads ``correct``,
a moved id or the bf16 control does not, and the roofline's work count
gives the numbers its docstring works by hand."""

import numpy as np
import pytest

import conftest
from conftest import CLOSED, small_root
from test_faults import _broken, answer_altered

from benchmark import harness


@pytest.fixture
def root(tmp_path, monkeypatch):
    # the limits the other small cells use: the sound path's distances
    # read about 1e-7 of the row's scale here (XLA hop loop on the CPU)
    monkeypatch.setitem(conftest.SMALL_LIMITS, "sift1m-cagra",
                        {"dist_err": 1e-6, "failed": 0})
    return small_root(tmp_path, "sift1m-cagra", CLOSED)


@pytest.mark.parametrize("fault", [None, answer_altered])
def test_moved_id_is_not_correct(root, monkeypatch, run_small, fault):
    if fault is not None:
        _broken(monkeypatch, root, "cagra", fault)
    out = run_small(root, "small.closed")
    assert out["correct"] == (fault is None), out["check"]
    assert out["failed"] == 0, out["info"]
    if fault is None:
        assert out["info"]["recall"] > 0.9, out["info"]


def test_control_is_not_correct(root, run_small):
    out = run_small(root, "small.closed", control=True)
    assert not out["correct"], out["check"]


def test_flush_work_by_hand():
    work = harness.Bench().module("work", "cagra")
    cfg = {"index": {"itopk_size": 32, "search_width": 1, "graph_degree": 32},
           "data": {"rows": 1_000_000, "dim": 128, "dtype": "float32"}}
    flops, nbytes = work.flush_work(cfg, {}, np.zeros((64, 128)))
    assert nbytes == 33_554_432 + 33_816_576 == 67_371_008
    assert flops == 1_073_741_824 + 16_777_216 == 1_090_519_040
    # a corpus smaller than the pool is read whole
    cfg["data"]["rows"] = 20_000
    flops, nbytes = work.flush_work(cfg, {}, np.zeros((64, 128)))
    assert nbytes == 20_000 * 128 * 4 + 33_816_576


@pytest.mark.parametrize("kind,want", [("cagra", 1.0), ("ivf_pq", None)])
def test_roofline_divides_by_busy_time(kind, want):
    """The hop loop's ``while`` op overlaps the operations it runs: the
    share divides by their union (``busy_s``), not by their sum."""
    read = harness.Bench().module("metrics", "cagra_search_roofline").read
    ctx = harness.Context(
        cell="c", cfg={"index": {"kind": kind}}, traffic={}, rec=None,
        hist={}, numbers={}, setup_s=0.0, build_s=None, setup_compile_s=0.0,
        window_programs=0, trace={"busy_s": 2.0, "op_s": 2.5},
        trace_flushes=250, least_flush_s=80e-6)
    got = read(ctx)
    assert got is None if want is None else got == pytest.approx(want)
    ctx.trace = None
    assert read(ctx) is None

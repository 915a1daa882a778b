"""The trace reduction, on a trace recorded on the chip (my chip call 2,
PR 22: 0.15 s of sift1m-ivfpq.batch64), and the peaks table."""

import gzip
import os
import shutil

import pytest

from benchmark import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "sift1m-ivfpq.batch64.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(str(path))


def test_window_and_busy_time(reduced):
    assert 0.1 < reduced["window_s"] < 0.3
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["op_s"] >= reduced["busy_s"] * 0.999
    assert 0 <= reduced["idle_pct"] < 100
    assert reduced["chips"] == 1


def test_breakdown_names_programs(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= trace.TOP
    assert ops[0][0].startswith("jit_") and ops[0][1] > 0
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert len(reduced["idle_gaps"]) <= trace.TOP


def test_peaks_table():
    p = trace.peaks("TPU v5 lite")
    assert p["bytes_per_s"] == 819e9 and p["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        trace.peaks("TPU v99")

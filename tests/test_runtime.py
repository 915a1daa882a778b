"""Native host runtime tests (cpp/runtime.cpp via ctypes).

Analogue of the reference's runtime-library smoke coverage: binary IO
roundtrips (bench/ann dataset.h format), host refine vs numpy reference
(test/neighbors/refine.cu host path), merge_parts vs select over the
concatenation. Tests exercise the native path when a toolchain is present
and the numpy fallback otherwise.
"""

import numpy as np
import pytest

from raft_tpu import runtime


def test_bin_roundtrip(tmp_path, rng):
    x = rng.random((37, 9)).astype(np.float32)
    p = str(tmp_path / "data.fbin")
    runtime.write_bin(p, x)
    n, d = runtime.bin_info(p)
    assert (n, d) == (37, 9)
    back = runtime.load_bin(p)
    np.testing.assert_array_equal(back, x)
    chunk = runtime.read_bin_chunk(p, 10, 5)
    np.testing.assert_array_equal(chunk, x[10:15])


def test_bin_u8_and_dataset_stream(tmp_path, rng):
    x = (rng.random((64, 7)) * 255).astype(np.uint8)
    p = str(tmp_path / "data.u8bin")
    runtime.write_bin(p, x)
    ds = runtime.BinDataset(p)
    assert len(ds) == 64 and ds.dim == 7 and ds.dtype == np.uint8
    got = np.concatenate([c for _, c in ds.chunks(20)])
    np.testing.assert_array_equal(got, x)
    np.testing.assert_array_equal(ds[8:24], x[8:24])


def test_refine_host_l2(rng):
    n, d, m, k_in, k = 200, 12, 9, 20, 6
    x = rng.random((n, d)).astype(np.float32)
    q = rng.random((m, d)).astype(np.float32)
    cand = np.stack([rng.choice(n, k_in, replace=False) for _ in range(m)]).astype(np.int32)
    dists, idx = runtime.refine_host(x, q, cand, k)
    # reference: exact distances over candidates, ascending
    d2 = ((q[:, None, :].astype(np.float64) - x[cand]) ** 2).sum(-1)
    order = np.argsort(d2, axis=1)[:, :k]
    want_i = np.take_along_axis(cand, order, axis=1)
    want_d = np.take_along_axis(d2, order, axis=1)
    np.testing.assert_array_equal(idx, want_i)
    np.testing.assert_allclose(dists, want_d, rtol=1e-4, atol=1e-4)


def test_refine_host_invalid_ids(rng):
    n, d, m = 50, 4, 3
    x = rng.random((n, d)).astype(np.float32)
    q = rng.random((m, d)).astype(np.float32)
    cand = np.full((m, 5), -1, np.int32)
    cand[:, 0] = 7
    dists, idx = runtime.refine_host(x, q, cand, 3)
    assert (idx[:, 0] == 7).all()
    assert (idx[:, 1:] == -1).all()
    assert np.isinf(dists[:, 1:]).all()


def test_refine_host_inner_product(rng):
    n, d, m, k = 100, 8, 5, 4
    x = rng.random((n, d)).astype(np.float32)
    q = rng.random((m, d)).astype(np.float32)
    cand = np.stack([rng.choice(n, 10, replace=False) for _ in range(m)]).astype(np.int32)
    dists, idx = runtime.refine_host(x, q, cand, k, metric="inner_product")
    ip = np.einsum("md,mkd->mk", q, x[cand])
    order = np.argsort(-ip, axis=1)[:, :k]
    np.testing.assert_array_equal(idx, np.take_along_axis(cand, order, axis=1))
    np.testing.assert_allclose(dists, np.take_along_axis(ip, order, axis=1), rtol=1e-4)


def test_merge_parts_host(rng):
    n_parts, m, k = 4, 7, 5
    d = rng.random((n_parts, m, k)).astype(np.float32)
    ids = rng.integers(0, 10_000, (n_parts, m, k)).astype(np.int32)
    out_d, out_i = runtime.merge_parts_host(d, ids, k)
    flat_d = np.moveaxis(d, 0, 1).reshape(m, -1)
    flat_i = np.moveaxis(ids, 0, 1).reshape(m, -1)
    order = np.argsort(flat_d, axis=1)[:, :k]
    np.testing.assert_allclose(out_d, np.take_along_axis(flat_d, order, axis=1))
    # ids may differ on exact ties; distances are the contract
    assert out_i.shape == (m, k)


def test_native_available_or_fallback():
    # informational: record which path the suite exercised
    assert runtime.available() in (True, False)


def test_native_library_rebuilds_when_stale(tmp_path, monkeypatch):
    """A built library older than runtime.cpp or the Makefile is stale: a
    leftover .so must not outlive a change to the files it is built from."""
    import os
    import shutil

    from raft_tpu.runtime import native

    cpp = tmp_path / "cpp"
    cpp.mkdir()
    for f in ("runtime.cpp", "Makefile"):
        shutil.copy(native._CPP_DIR / f, cpp / f)
    so = cpp / native._SO_PATH.name
    monkeypatch.setattr(native, "_CPP_DIR", cpp)
    monkeypatch.setattr(native, "_SO_PATH", so)
    assert native._stale()  # missing
    so.write_bytes(b"")
    os.utime(cpp / "runtime.cpp", (100, 100))
    os.utime(cpp / "Makefile", (100, 100))
    os.utime(so, (200, 200))
    assert not native._stale()
    for f in ("runtime.cpp", "Makefile"):
        os.utime(cpp / f, (300, 300))
        assert native._stale(), f
        os.utime(cpp / f, (100, 100))


def test_make_fbin_roundtrip(tmp_path):
    """bench/ann/make_fbin.py writes chunked big-ANN files the native loader
    reads back intact (the no-network stand-in for downloading SIFT-1M)."""
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    r = subprocess.run(
        [sys.executable, str(repo / "bench/ann/make_fbin.py"), "--out",
         str(tmp_path), "--n", "300000", "--n-queries", "50", "--dim", "16",
         "--clusters", "10"],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr

    from raft_tpu.runtime import bin_info, load_bin, read_bin_chunk

    base = str(tmp_path / "base-300000x16.fbin")
    assert bin_info(base) == (300000, 16)
    rows = read_bin_chunk(base, 299_990, 10)
    assert rows.shape == (10, 16)
    q = load_bin(str(tmp_path / "query-50x16.fbin"))
    assert q.shape == (50, 16)
    # chunk boundary continuity: rows on either side of the 200k chunk edge
    a = read_bin_chunk(base, 199_999, 2)
    assert np.isfinite(a).all() and a.std() > 0

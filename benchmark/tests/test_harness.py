"""The harness runs a new cell from new files alone, and refuses to run
where it should."""

import hashlib
import os
import shutil
import subprocess
import sys

from conftest import CLOSED, OPEN, ROOT, small_root


def _tree_hash(path):
    h = hashlib.sha256()
    for dp, dns, fs in sorted(os.walk(path)):
        dns[:] = sorted(d for d in dns if d != "__pycache__")
        for f in sorted(fs):
            with open(os.path.join(dp, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    return h.hexdigest()


def test_new_cell_runs_from_new_files_only(tmp_path, run_small):
    before = _tree_hash(os.path.join(ROOT, "benchmark"))
    root = small_root(tmp_path, "sift1m-ivfpq", CLOSED,
                      index={"n_lists": 32})
    out = run_small(root, "small.closed")
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"qps", "p99_ms", "recall_at_10",
                                   "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert _tree_hash(os.path.join(ROOT, "benchmark")) == before


def test_open_loop_over_the_wire(tmp_path, run_small):
    root = small_root(tmp_path, "sift1m-exact", OPEN)
    out = run_small(root, "small.open")
    assert out["correct"], out["check"]
    assert out["metrics"]["qps"]["value"] > 0
    assert out["attempted"] == round(OPEN["rate"] * 1.5)


def _run_py(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "sift1m-exact.batch64", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_accelerator_no_result():
    p = _run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and not p.stdout.strip()
    assert "no accelerator" in p.stderr


def test_benchmark_alone_is_no_system(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and not p.stdout.strip()

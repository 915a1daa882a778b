"""Warm-build story: serialize-after-build and the persistent jit cache.

VERDICT item: 1M builds are cold-jit dominated (IVF-Flat 120 s / CAGRA 320 s
cold vs seconds warm); repeat users need a path that skips both compile and
build. docs/warm_builds.md documents the workflow; these tests pin it.
"""

import time

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu.neighbors import ivf_flat


@pytest.mark.slow
def test_load_is_much_faster_than_build(tmp_path, rng):
    x = jnp.asarray(rng.random((20_000, 32)).astype(np.float32))
    t0 = time.perf_counter()
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=64, seed=0), x)
    import jax

    jax.block_until_ready(idx.list_data)
    build_s = time.perf_counter() - t0

    path = str(tmp_path / "warm.bin")
    ivf_flat.save(idx, path)
    t0 = time.perf_counter()
    idx2 = ivf_flat.load(path)
    jax.block_until_ready(idx2.list_data)
    load_s = time.perf_counter() - t0

    assert load_s * 5 < build_s, (load_s, build_s)
    d1, i1 = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), idx, x[:16], 5)
    d2, i2 = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), idx2, x[:16], 5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_warmup_entry_point(tmp_path):
    """raft_tpu.warmup must run the real build+search pipeline at the given
    shapes under the persistent cache and report timings (VERDICT r4 #6 —
    the AOT first-touch story; small shapes here). The warmup itself runs in a subprocess:
    enable_compilation_cache permanently redirects this process's jax cache
    config, and the cache dir is a tmp_path deleted after the test."""
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    cache = tmp_path / "warmcache"
    code = f"""
import sys
sys.path.insert(0, {str(repo)!r})
from raft_tpu.core.platform import force_virtual_cpu
force_virtual_cpu(1)
import raft_tpu
from raft_tpu.neighbors import ivf_flat
out = raft_tpu.warmup("ivf_flat", n=2000, d=16, queries=64,
                      index_params=ivf_flat.IndexParams(n_lists=16, seed=0),
                      cache_dir={str(cache)!r})
assert out["build_s"] > 0 and out["search_s"] > 0, out
import os
assert os.path.isdir(out["cache_dir"]), out
print("WARMUP_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=360)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WARMUP_OK" in r.stdout

    # the kind guard needs no jax work and is safe in-process
    import raft_tpu
    from raft_tpu.core import RaftError

    with pytest.raises(RaftError, match="unknown index kind"):
        raft_tpu.warmup("flann", n=100, d=8)


def test_enable_compilation_cache_populates_dir(tmp_path):
    """The cache helper must configure jax to persist entries to disk. Run in
    a subprocess so this process's jax config/caches are untouched."""
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    cache = tmp_path / "jitcache"
    code = f"""
import sys
sys.path.insert(0, {str(repo)!r})
from raft_tpu.core.platform import force_virtual_cpu
force_virtual_cpu(1)
import raft_tpu.config
p = raft_tpu.config.enable_compilation_cache({str(cache)!r})
import jax, jax.numpy as jnp
jax.jit(lambda x: x * 2 + 1)(jnp.ones((128, 128))).block_until_ready()
import os
entries = [f for f in os.listdir(p) if not f.startswith('.')]
assert entries, 'no cache entries written'
print('CACHE_OK', len(entries))
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "CACHE_OK" in r.stdout
    n_entries = int(r.stdout.split("CACHE_OK")[1].split()[0])

    # a second interpreter compiling the same program must REUSE the entries:
    # same count afterwards, not new ones (cross-process warm start, the
    # guarantee docs/warm_builds.md documents)
    r2 = subprocess.run([sys.executable, "-c", code], capture_output=True,
                        text=True, timeout=240)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    n_entries2 = int(r2.stdout.split("CACHE_OK")[1].split()[0])
    assert n_entries2 == n_entries, (n_entries, n_entries2)


def _record_cache_config(monkeypatch):
    """Route enable_compilation_cache's jax.config writes into a list
    instead of this process's config."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setattr(compilation_cache, "reset_cache", lambda: None)
    return calls


def test_cache_env_var_sets_no_directory(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR belongs to whoever runs the process: with it
    set, no cache directory is set in code, and the one in effect is its."""
    import raft_tpu.config

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outer"))
    calls = _record_cache_config(monkeypatch)
    got = raft_tpu.config.enable_compilation_cache(str(tmp_path / "inner"))
    assert got == str(tmp_path / "outer")
    assert "jax_compilation_cache_dir" not in [name for name, _ in calls]
    assert not (tmp_path / "inner").exists()


def test_cache_default_is_one_fixed_checkout_path(monkeypatch):
    """Without the variable the cache goes to <checkout>/.jax_cache — the
    same path on every call (the path is part of what finds entries)."""
    from pathlib import Path

    import raft_tpu.config

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_cache_config(monkeypatch)
    first = raft_tpu.config.enable_compilation_cache()
    second = raft_tpu.config.enable_compilation_cache()
    want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    assert first == second == want
    assert [v for n, v in calls if n == "jax_compilation_cache_dir"] == [
        want, want]


def test_cache_lands_in_env_dir_only(tmp_path):
    """End to end in a fresh interpreter: with the variable set, compiled
    entries land there and the checkout default stays untouched."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    outer = tmp_path / "outer"
    code = f"""
import sys
sys.path.insert(0, {str(repo)!r})
from raft_tpu.core.platform import force_virtual_cpu
force_virtual_cpu(1)
import raft_tpu.config
p = raft_tpu.config.enable_compilation_cache({str(tmp_path / 'inner')!r})
import jax, jax.numpy as jnp
jax.jit(lambda x: x * 5 - 3)(jnp.ones((77, 31))).block_until_ready()
print('DIR', p)
"""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(outer))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=240, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert f"DIR {outer}" in r.stdout
    assert [f for f in os.listdir(outer) if not f.startswith(".")]
    assert not (tmp_path / "inner").exists()


def test_warmup_warms_the_callers_k(tmp_path):
    """Regression (ADVICE r5 medium): the ivf_pq warmup used to search at
    ``max(k, 40)`` instead of the caller's k — the production k=10 program
    still compiled cold. The warmed search must be the SAME jitted program
    as the production search at those shapes: a production search after
    warmup adds ZERO new trace-cache entries to the k-carrying search jits.
    Runs in a subprocess because warmup permanently redirects the process's
    jax compilation-cache config."""
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    cache = tmp_path / "warmkcache"
    code = f"""
import sys
sys.path.insert(0, {str(repo)!r})
from raft_tpu.core.platform import force_virtual_cpu
force_virtual_cpu(1)
import jax, jax.numpy as jnp
import raft_tpu
from raft_tpu.neighbors import ivf_pq

ip = ivf_pq.IndexParams(n_lists=16, seed=0)
sp = ivf_pq.SearchParams(n_probes=4)
out = raft_tpu.warmup("ivf_pq", n=2000, d=16, k=7, queries=64,
                      index_params=ip, search_params=sp,
                      cache_dir={str(cache)!r})
assert out["search_s"] > 0, out

# production pipeline at the same shapes: identical data generation
# (warmup's own protocol, seed=0) so the built index has identical avals
kd, kq = jax.random.split(jax.random.key(0))
x = jax.random.uniform(kd, (2000, 16), jnp.float32)
q = jax.random.uniform(kq, (64, 16), jnp.float32)
idx = ivf_pq.build(ip, x)
before = ivf_pq._pq_search._cache_size()
ivf_pq.search(sp, idx, q, 7)
after = ivf_pq._pq_search._cache_size()
assert after == before, ("production k=7 search re-traced after a k=7 "
                         "warmup", before, after)
print("WARMK_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=360)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WARMK_OK" in r.stdout


def test_warmup_byte_dtype(tmp_path):
    """``warmup(..., dtype="uint8")`` must run the byte-dataset pipeline:
    random bytes in the target dtype so the s8 kernels and int8 list
    layouts compile exactly as production will run them."""
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    cache = tmp_path / "warmu8cache"
    code = f"""
import sys
sys.path.insert(0, {str(repo)!r})
from raft_tpu.core.platform import force_virtual_cpu
force_virtual_cpu(1)
import raft_tpu
from raft_tpu.neighbors import ivf_flat
out = raft_tpu.warmup("ivf_flat", n=2000, d=16, queries=64, dtype="uint8",
                      index_params=ivf_flat.IndexParams(n_lists=16, seed=0),
                      cache_dir={str(cache)!r})
assert out["build_s"] > 0 and out["search_s"] > 0, out
print("WARMU8_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=360)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WARMU8_OK" in r.stdout

    # the dtype guard needs no jax work and is safe in-process
    import raft_tpu
    from raft_tpu.core import RaftError

    with pytest.raises(RaftError, match="dtype must be"):
        raft_tpu.warmup("ivf_flat", n=100, d=8, dtype="float16")


def test_warmup_accepts_user_data_sample(tmp_path):
    """warmup(data=...) builds/searches on rows resampled from the user's
    sample (VERDICT r5 #5: uniform random data is the data-adaptive builds'
    measured worst case — 483 s vs ~130 s for cagra at 1M), keeping shapes
    (and therefore the warmed program set) identical. Subprocess for the
    same cache-redirect reason as test_warmup_entry_point."""
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    cache = tmp_path / "warmcache_sample"
    code = f"""
import sys
sys.path.insert(0, {str(repo)!r})
from raft_tpu.core.platform import force_virtual_cpu
force_virtual_cpu(1)
import numpy as np
import raft_tpu
from raft_tpu.neighbors import ivf_flat
rng = np.random.default_rng(0)
centers = rng.random((8, 16)).astype(np.float32) * 10
sample = (centers[rng.integers(0, 8, 300)]
          + rng.normal(0, 0.3, (300, 16)).astype(np.float32))
out = raft_tpu.warmup("ivf_flat", n=2000, d=16, queries=64, data=sample,
                      index_params=ivf_flat.IndexParams(n_lists=16, seed=0),
                      cache_dir={str(cache)!r})
assert out["build_s"] > 0 and out["search_s"] > 0, out
# int8 sample: dtype inferred from the sample bytes
i8 = rng.integers(-128, 128, (300, 16)).astype(np.int8)
out = raft_tpu.warmup("brute_force", n=500, d=16, queries=32, data=i8,
                      cache_dir={str(cache)!r})
print("WARMUP_SAMPLE_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=360)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WARMUP_SAMPLE_OK" in r.stdout

    # shape/dtype validation needs no cache and is safe in-process
    import numpy as np

    import raft_tpu
    from raft_tpu.core import RaftError

    with pytest.raises(RaftError, match="data sample must be"):
        raft_tpu.warmup("ivf_flat", n=100, d=8,
                        data=np.zeros((10, 9), np.float32))
    with pytest.raises(RaftError, match="dtype"):
        raft_tpu.warmup("ivf_flat", n=100, d=8, dtype="int8",
                        data=np.zeros((10, 8), np.float32))

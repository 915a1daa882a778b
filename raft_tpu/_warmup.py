"""Ahead-of-time warmup: pre-populate the persistent compilation cache.

The reference ships ahead-of-time compiled kernels in ``libraft.so`` (the
explicit-instantiation machinery, SURVEY.md R1/R2; compile-mode matrix
``cpp/test/CMakeLists.txt:183-190``), so a user's first 1M build never pays
device-code compilation. The TPU analogue is the persistent XLA compilation
cache (``config.enable_compilation_cache``) — but the cache only helps a
*second* process; a fresh host still pays minutes of cold jit on the flagship
path (1M ivf_pq: 103.6 s cold vs 7.3 s warm, r04 harness).

``warmup`` closes that first-touch gap: run it once per host — at deploy
time, in a provisioning step, off the serving path — with the shapes you will
build and search at, and every subsequent process (including the first
user-facing one) compiles from the cache. It executes the real build+search
pipeline on device-generated random data of the target shapes, because the
cache is keyed by HLO: only the genuinely identical programs (same shapes,
same static config) hit.

    import raft_tpu
    raft_tpu.warmup("ivf_pq", n=1_000_000, d=128)        # once, at deploy
    # ... later, any process on this host ...
    idx = ivf_pq.build(params, dataset)                   # warm: seconds

Random data is generated ON DEVICE (a 512 MB host->device transfer would
dominate), and the warmup returns its own build/search wall times so a
provisioning script can log them.
"""

from __future__ import annotations

import time
from typing import Any

__all__ = ["warmup", "warm_buckets"]

_KINDS = ("brute_force", "ivf_flat", "ivf_pq", "cagra")


def _random_queries(key, rows: int, d: int, dtype: str, sample=None):
    import jax
    import jax.numpy as jnp

    if sample is not None:
        # rows resampled (with replacement) from the user's sample: the
        # warmed programs see the REAL data distribution, not the uniform
        # worst case
        return _resample(key, sample, rows)
    if dtype == "float32":
        return jax.random.uniform(key, (rows, d), jnp.float32)
    lo, hi = (-128, 128) if dtype == "int8" else (0, 256)
    return jax.random.randint(key, (rows, d), lo, hi, jnp.int32).astype(dtype)


def _resample(key, sample, rows: int):
    """(rows, d) drawn with replacement from the user's sample rows — the
    warmup dataset keeps the production data's cluster/clump structure, it
    just repeats points when the sample is smaller than the target n."""
    import jax
    import jax.numpy as jnp

    sample = jnp.asarray(sample)
    idx = jax.random.randint(key, (rows,), 0, sample.shape[0])
    return jnp.take(sample, idx, axis=0)


def warm_buckets(searcher, *, dim: int, buckets, k: int = 10,
                 dtype: str = "float32", seed: int = 0,
                 sample=None) -> dict:
    """Compile-warm one serving searcher at every batch-shape bucket.

    The serving-layer half of :func:`warmup` (raft_tpu.serve): a micro-
    batched service flushes only the padded power-of-two shapes in
    ``buckets``, so running ``searcher(queries, k)`` once per bucket — with
    queries drawn in the index's own query ``dtype`` — compiles the exact
    program set the hot path will dispatch. The serve registry calls this
    from ``publish`` BEFORE flipping the active pointer (warm hot-swap);
    provisioning scripts can call it directly to populate the persistent
    cache off the serving path (enable the cache first, see
    :func:`raft_tpu.config.enable_compilation_cache`).

    ``sample`` (optional, (r, dim) in the serving query dtype) draws the
    bucket queries from real data instead of uniform noise. Compilation
    does not depend on VALUES, so any sample warms the same programs — but
    data-dependent execution time does (CAGRA's hop loop runs ~3.7x longer
    on uniform data than clustered, r06 warmup data-sample study), so a
    sample makes publish-time warms cheaper and their reported walls
    representative.

    Returns ``{bucket: {wall_s, compile_s, trace_s, programs, cache_hits,
    cache_misses}}`` via the obs compile-attribution subscription — all-warm
    buckets report ``compile_s == 0``, which is the zero-hiccup-swap proof
    ``bench.py --serve`` asserts.
    """
    import jax
    import jax.numpy as jnp

    from .core.errors import expects
    from .obs import compile as obs_compile

    expects(dtype in ("float32", "int8", "uint8"),
            "dtype must be 'float32', 'int8' or 'uint8', got %r", dtype)
    if sample is not None:
        sample = jnp.asarray(sample)
        expects(sample.ndim == 2 and sample.shape[1] == dim,
                "warm sample must be (rows, %d), got %s", dim,
                tuple(sample.shape))
        expects(str(sample.dtype) == dtype,
                "warm sample dtype %s must match the serving dtype %s",
                sample.dtype, dtype)
    out = {}
    key = jax.random.key(seed)
    for b in sorted(set(int(b) for b in buckets)):
        expects(b >= 1, "bucket sizes must be >= 1, got %d", b)
        key, kq = jax.random.split(key)
        q = _random_queries(kq, b, dim, dtype, sample=sample)
        jax.block_until_ready(q)
        t0 = time.perf_counter()
        with obs_compile.attribution() as rec:
            jax.block_until_ready(
                jax.tree_util.tree_leaves(searcher(q, k))[0])
        out[b] = {"wall_s": round(time.perf_counter() - t0, 3),
                  **rec.summary()}
    return out


def warmup(kind: str, n: int, d: int, *, k: int = 10, queries: int = 10_000,
           dtype: str = "float32", data: Any | None = None,
           index_params: Any | None = None,
           search_params: Any | None = None, cache_dir: str | None = None,
           seed: int = 0) -> dict:
    """Compile-warm one index kind at (n, d) build / (queries, d) search.

    Enables the persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``
    when set, else ``cache_dir`` or ``<checkout>/.jax_cache`` — see
    :func:`raft_tpu.config.enable_compilation_cache`), builds the index on
    uniform random data of the target shape, runs one search of the target
    batch shape, and returns headline walls
    (``build_s``/``search_s``/``cache_dir``) plus per-phase compile
    attribution (see below). Pass the same
    ``index_params``/``search_params`` you will use in production — the
    cache keys on static config (n_lists, pq_dim, itopk, ...), so a warmup
    with different params warms different programs. The same holds for
    ``k``: the search is warmed at EXACTLY the ``k`` passed here (k is a
    static argument of every search program), so a production pipeline that
    searches at several widths — e.g. IVF-PQ candidates at k=40 feeding a
    refine at k=10 — must call warmup once per width.

    ``dtype`` ("float32" | "int8" | "uint8") warms the byte-dataset search
    paths: random data is drawn in the target dtype, so the s8 kernels and
    byte list layouts compile exactly as production will run them.

    ``data`` (optional (r, d) array, any r) warms on a SAMPLE OF THE REAL
    DATA, resampled with replacement to the target (n, d) / (queries, d)
    shapes. Compiled programs are shape-keyed, so the cache outcome is
    identical either way — but the warmup's own wall time is not: uniform
    random data is the measured worst case of the data-adaptive builds
    (CAGRA's build_n_probes autotune keeps p=32 on uniform data, 483 s vs
    ~130 s at 1M on clustered — VERDICT r5 #5), so a few thousand rows of
    production data make a cagra warmup ~3.7x cheaper while warming the
    exact same programs. When ``data`` is int8/uint8, ``dtype`` must agree
    (or be left at its default, which is then inferred from the sample).

    The returned dict attributes each wall time instead of leaving it opaque
    (obs/compile.py, the jax.monitoring subscription): ``build``/``search``
    each carry ``{wall_s, compile_s, trace_s, programs, cache_hits,
    cache_misses, program_compile_s}`` — ``program_compile_s`` is the
    per-program backend-compile seconds, and the cache counters say whether
    this warmup paid cold compiles or found the cache already hot. A
    summary INFO line goes through the raft_tpu logger
    (``core.logger.basic_config`` formats it to stderr in one call).
    """
    import jax
    import jax.numpy as jnp

    from .config import enable_compilation_cache
    from .core.errors import expects
    from .core.logger import logger
    from .obs import compile as obs_compile

    expects(kind in _KINDS, "unknown index kind %r (one of %s)", kind,
            ", ".join(_KINDS))
    expects(dtype in ("float32", "int8", "uint8"),
            "dtype must be 'float32', 'int8' or 'uint8', got %r", dtype)
    sample = None
    if data is not None:
        # validate BEFORE the cache redirect below — a bad sample must fail
        # without permanently re-pointing this process's jax cache config
        sample = jnp.asarray(data)
        expects(sample.ndim == 2 and sample.shape[1] == d,
                "data sample must be (rows, %d), got %s", d,
                tuple(sample.shape))
        if str(sample.dtype) in ("int8", "uint8") and dtype == "float32":
            dtype = str(sample.dtype)  # infer byte kinds from the sample
        expects(str(sample.dtype) == dtype,
                "data sample dtype %s must match dtype=%r", sample.dtype,
                dtype)
    cache = enable_compilation_cache(cache_dir)
    kd, kq = jax.random.split(jax.random.key(seed))
    if sample is not None:
        x = _resample(kd, sample, n)
        q = _resample(kq, sample, queries)
    elif dtype == "float32":
        x = jax.random.uniform(kd, (n, d), jnp.float32)
        q = jax.random.uniform(kq, (queries, d), jnp.float32)
    else:
        lo, hi = (-128, 128) if dtype == "int8" else (0, 256)
        x = jax.random.randint(kd, (n, d), lo, hi, jnp.int32).astype(dtype)
        q = jax.random.randint(kq, (queries, d), lo, hi, jnp.int32).astype(dtype)
    jax.block_until_ready((x, q))

    t0 = time.perf_counter()
    with obs_compile.attribution() as build_attr:
        if kind == "brute_force":
            from .neighbors import brute_force

            idx = brute_force.BruteForce().build(x)
            searcher = lambda: idx.search(q, k)
        elif kind == "ivf_flat":
            from .neighbors import ivf_flat

            idx = ivf_flat.build(
                index_params or ivf_flat.IndexParams(n_lists=1024, seed=seed), x)
            jax.block_until_ready(idx.list_data)
            searcher = lambda: ivf_flat.search(
                search_params or ivf_flat.SearchParams(n_probes=8), idx, q, k)
        elif kind == "ivf_pq":
            from .neighbors import ivf_pq

            idx = ivf_pq.build(
                index_params or ivf_pq.IndexParams(
                    n_lists=1024, pq_bits=4, pq_dim=min(64, d), seed=seed), x)
            jax.block_until_ready(idx.list_codes)
            # the caller's k, EXACTLY: the compilation cache is keyed by HLO
            # and k is a static arg of _pq_search, so the old max(k, 40)
            # override left the production k=10 program cold (ADVICE r5
            # medium). Pipelines that also search a refine-candidate width
            # (e.g. k=40 feeding refine to 10) warm that width with a second
            # warmup call.
            searcher = lambda: ivf_pq.search(
                search_params or ivf_pq.SearchParams(
                    n_probes=8, lut_dtype="bfloat16"), idx, q, k)
        else:  # cagra
            from .neighbors import cagra

            idx = cagra.build(index_params or cagra.IndexParams(seed=seed), x)
            jax.block_until_ready(idx.graph)
            searcher = lambda: cagra.search(
                search_params or cagra.SearchParams(itopk_size=32), idx, q, k)
    build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    with obs_compile.attribution() as search_attr:
        jax.block_until_ready(jax.tree_util.tree_leaves(searcher())[0])
    search_s = time.perf_counter() - t0

    def _phase(wall_s, rec) -> dict:
        return {
            "wall_s": round(wall_s, 2),
            **rec.summary(),
            "program_compile_s": [round(s, 3) for s in rec.program_compile_s],
        }

    out = {
        # headline walls keep their historical keys (provisioning scripts)
        "build_s": round(build_s, 2), "search_s": round(search_s, 2),
        "cache_dir": cache, "attribution": "jax.monitoring",
        "build": _phase(build_s, build_attr),
        "search": _phase(search_s, search_attr),
    }
    logger.info(
        "warmup(%s, n=%d, d=%d, k=%d): build %.1fs (%.1fs compile over %d "
        "programs), search %.1fs (%.1fs compile), cache %d hits / %d misses "
        "at %s", kind, n, d, k, build_s, build_attr.compile_s,
        build_attr.programs, search_s, search_attr.compile_s,
        build_attr.cache_hits + search_attr.cache_hits,
        build_attr.cache_misses + search_attr.cache_misses, cache)
    return out

"""Batched top-k selection with index payloads.

Re-design of the reference's select_k (cpp/include/raft/matrix/select_k.cuh;
two CUDA algorithms — 11-bit radix filter detail/select_radix.cuh and warp
bitonic queues detail/select_warpsort.cuh — picked by a learned heuristic,
detail/select_k-inl.cuh:46). The TPU mirror of that two-algorithm split:
XLA's native TopK (`lax.top_k`, a tuned sort) below ~64k columns, and the
threshold-gated streaming Pallas selector (raft_tpu.ops.topk_pallas, one HBM
pass) for wide rows with k <= 256 (r06 lift). The payload
(caller-provided source indices, used when merging per-shard candidate lists)
is carried by gathering with the top-k permutation.
"""

from __future__ import annotations

from ..config import auto_convert_output

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core import tracing
from ..core.errors import expects
from ..obs.instrument import instrument, nrows

__all__ = ["select_k", "set_wide_cols_threshold", "wide_cols_threshold"]

# Widest k the TPU streaming selector is dispatched for — MUST equal
# ops.topk.TOPK_MAX_K (pinned by tests/test_matrix.py::test_select_k_dispatch
# _cap_matches_kernel_limit so neither can silently drift). History: r05
# capped dispatch at 128 because two kh=256 kernel instances in one XLA
# program hit a TPU-internal Mosaic error; r06's half-width merge
# (ops/topk.py wide_merge="half") keeps every merge intermediate <= kh lanes
# and lifts the cap to the kernel's full 256. RAFT_TPU_WIDE_SELECT_CAP can
# re-impose a lower cap at runtime (e.g. =128) if a future toolchain
# regresses — the escape hatch the repro harness (bench/topk_chain_repro.py)
# documents.
SELECT_K_DISPATCH_MAX_K = 256


# Column width above which the streaming selector wins over lax.top_k
# (measured at r05: parity below ~64k cols, 1.3x at 100k). A parked
# conservative guess until a TPU run moves it — which is why it is now a
# TUNABLE: raft_tpu.tune.sweep_select_k measures the crossover and
# tune.apply_global pins it here (or set RAFT_TPU_WIDE_SELECT_COLS).
WIDE_SELECT_COLS_DEFAULT = 65536

_wide_cols_override: int | None = None


def set_wide_cols_threshold(n: int | None) -> None:
    """Pin (or with None, reset) the wide-select column threshold — the
    application point of a ``select_k`` tune decision
    (:func:`raft_tpu.tune.apply_global`). Read at TRACE time: programs
    already compiled for a shape keep the dispatch they traced with."""
    global _wide_cols_override
    expects(n is None or int(n) >= 1,
            "wide-select threshold must be >= 1 columns, got %r", n)
    _wide_cols_override = None if n is None else int(n)


def wide_cols_threshold() -> int:
    """The live wide-select column threshold: a :func:`set_wide_cols_
    threshold` pin, else RAFT_TPU_WIDE_SELECT_COLS, else the measured
    65536-column default."""
    import os

    if _wide_cols_override is not None:
        return _wide_cols_override
    env = os.environ.get("RAFT_TPU_WIDE_SELECT_COLS")
    if not env:
        return WIDE_SELECT_COLS_DEFAULT
    try:
        return int(env)
    except ValueError:
        raise ValueError(
            f"RAFT_TPU_WIDE_SELECT_COLS must be an integer, got {env!r}")


def _dispatch_cap() -> int:
    # Read at TRACE time: programs already compiled for a shape keep the
    # dispatch they traced with — apply the escape hatch in a fresh process
    # (or before the first search of a shape), not mid-flight.
    import os

    cap = os.environ.get("RAFT_TPU_WIDE_SELECT_CAP")
    if not cap:
        return SELECT_K_DISPATCH_MAX_K
    try:
        return min(int(cap), SELECT_K_DISPATCH_MAX_K)
    except ValueError:
        raise ValueError(
            f"RAFT_TPU_WIDE_SELECT_CAP must be an integer, got {cap!r}")


def wide_dispatch_ok(n: int, k: int, dtype, backend: str | None = None) -> bool:
    """True when (n, k, dtype) is in the streaming Pallas selector's measured
    win regime on the given backend (default: the ambient one). The single
    definition of the dispatch rule — used by :func:`select_k` and by the
    in-jit routed selects inside ivf_pq's scan (the CAGRA build chunk's
    k=gpu_top_k+1 select reaches the kernel through this same predicate).
    The column threshold is tunable (see :func:`wide_cols_threshold`)."""
    if backend is None:
        backend = jax.default_backend()
    return (backend == "tpu" and n >= wide_cols_threshold()
            and 0 < k <= _dispatch_cap()
            and dtype in (jnp.float32, jnp.bfloat16, jnp.float16))


def select_k_impl(values, in_idx, k: int, select_min: bool,
                  impl: str = "auto"):
    """In-jit routed top-k: the trace-time dispatch between lax.top_k and the
    streaming Pallas selector, callable from inside jitted pipelines (no
    nested-jit re-dispatch; shapes are static at trace time).

    ``impl``: "auto" applies :func:`wide_dispatch_ok`; "xla" forces
    lax.top_k; "pallas" forces the Pallas kernel (float inputs only — the
    kernel ranks after an f32 cast) and is the A/B lever
    ``bench/cagra_build_select_ab.py`` uses at the CAGRA build-chunk shapes.
    Device stage ``select_k``.
    """
    expects(impl in ("auto", "xla", "pallas"),
            "select impl must be 'auto', 'xla' or 'pallas', got %r", impl)
    n = values.shape[1]
    use_pallas = (impl == "pallas" or
                  (impl == "auto" and wide_dispatch_ok(n, k, values.dtype)))
    if use_pallas:
        expects(values.dtype in (jnp.float32, jnp.bfloat16, jnp.float16),
                "the Pallas selector ranks after an f32 cast; integer or "
                "f64 values (%s) need the exact lax.top_k path "
                "(same restriction as the public select_k dispatch)",
                values.dtype)
        from ..ops.topk import topk_pallas

        with tracing.scope("select_k"):
            out_v, pos = topk_pallas(values, int(k),
                                     select_min=bool(select_min))
            out_i = (pos if in_idx is None
                     else jnp.take_along_axis(in_idx, pos, axis=1))
            return out_v, out_i.astype(jnp.int32)
    return _select_k(values, in_idx, int(k), bool(select_min))


@functools.partial(jax.jit, static_argnames=("k", "select_min"))
def _select_k(values, in_idx, k: int, select_min: bool):
    with tracing.scope("select_k"):
        if jnp.issubdtype(values.dtype, jnp.integer):
            # integer scores (e.g. exact int32 distances from the s8 MXU search
            # paths): ~v is the wrap-free order flip in the SAME dtype for both
            # families (unsigned: max - v; signed: -v - 1 — unlike negation,
            # which wraps at INT_MIN, and unlike a python-int `max - v`, which
            # overflows the weak-typed i32 scalar path for uint32). Output
            # values are gathered from the input, so they keep the caller's
            # dtype and exact magnitudes.
            key = ~values if select_min else values
            _, top_i = lax.top_k(key, k)
            top_v = jnp.take_along_axis(values, top_i, axis=1)
            if in_idx is not None:
                top_i = jnp.take_along_axis(in_idx, top_i, axis=1)
            return top_v, top_i.astype(jnp.int32)
        v = -values if select_min else values
        top_v, top_i = lax.top_k(v, k)  # ties resolved by lowest index, like the ref
        if select_min:
            top_v = -top_v
        if in_idx is not None:
            top_i = jnp.take_along_axis(in_idx, top_i, axis=1)
        return top_v, top_i.astype(jnp.int32)


@instrument("matrix.select_k",
            items=lambda a, kw: nrows(a[0] if a else kw["values"]),
            labels=lambda a, kw: {"k": a[1] if len(a) > 1 else kw["k"]})
@auto_convert_output
def select_k(values, k: int, select_min: bool = True, indices=None):
    """Select the k smallest (or largest) entries per row, with their indices.

    Reference: raft::matrix::select_k (matrix/select_k.cuh) and the pylibraft
    binding (matrix/select_k.pyx). ``indices`` optionally supplies the payload
    ids of each column (shape == values.shape); by default the column offsets
    0..n-1 are returned — exactly the reference's in_idx=nullopt behavior.

    Returns ``(out_values (m, k), out_indices (m, k) int32)``.
    """
    values = jnp.asarray(values)
    expects(values.ndim == 2, "select_k expects a 2-D (batch, n) matrix")
    n = values.shape[1]
    expects(0 < k <= n, "k=%d must be in (0, n=%d]", k, n)
    if indices is not None:
        indices = jnp.asarray(indices)
        expects(indices.shape == values.shape, "indices payload must match values shape")
    # Wide rows on TPU: the streaming Pallas selector (ops/topk.py) reads the
    # matrix once vs the TopK custom call's ~3 sort passes — measured 1.3x at
    # (1000, 100k) k=10 (18.3 vs 23.8 ms/iter chained); parity below ~64k
    # columns, so the dispatch stays conservative. Restricted to <=32-bit
    # floats: the kernel ranks after an f32 cast, so under jax_enable_x64 a
    # float64 row whose entries differ only beyond f32 precision would be
    # silently misranked vs the exact lax.top_k path.
    # k <= 64 is the r05-measured narrow path; 64 < k <= 256 is the
    # bitonic-merge wide path (ops/topk.py) — 3.06x lax.top_k at (10k, 65k)
    # k=128, 1.5-1.7x at k=193/256 in-process (r05 wide-k selector
    # study). r05 capped dispatch at 128 (two kh=256 instances per
    # program hit a Mosaic error); r06's half-width merge lifts the cap to
    # the kernel's full 256 (SELECT_K_DISPATCH_MAX_K above has the history
    # and the RAFT_TPU_WIDE_SELECT_CAP escape hatch).
    # Integer values (exact int32 scores from the s8 search paths, uint8
    # payload matrices, ...) stay on the lax.top_k path: the Pallas
    # selector ranks after an f32 cast, which would misrank int32 values
    # differing only beyond 2^24; _select_k handles them exactly.
    if (not jnp.issubdtype(values.dtype, jnp.integer)
            and wide_dispatch_ok(n, int(k), values.dtype)):
        from ..ops.topk import topk_pallas

        out_v, pos = topk_pallas(values, int(k), select_min=bool(select_min))
        out_i = (pos if indices is None
                 else jnp.take_along_axis(indices, pos, axis=1))
        return out_v, out_i.astype(jnp.int32)
    return _select_k(values, indices, int(k), bool(select_min))

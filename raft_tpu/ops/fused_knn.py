"""Fused distance + top-k selection Pallas kernel for brute-force kNN.

This is the TPU resolution of SURVEY.md hard part #2 ("competitive batched
select_k").  The reference GPU stack computes a tiled distance GEMM, writes the
score tile to global memory, and runs a separate selection kernel over it
(cpp/include/raft/neighbors/detail/knn_brute_force.cuh:232-273 tile+select
loop; cpp/include/raft/matrix/detail/select_radix.cuh and
detail/select_warpsort.cuh selection kernels).  On TPU the measured bottleneck
of that structure is HBM traffic: the (m, n) score matrix costs one write plus
~3 sort passes of reads, and XLA's TopK custom call cannot fuse its producer.

This kernel never materializes scores to HBM.  Grid = (query_tiles,
dataset_blocks), dataset-block minor.  Each step computes a (QT, NBLK) score
block in VMEM with one MXU contraction (scores are oriented so *larger is
better*: ``2 q·y - |y|^2`` for L2, ``q·y`` for inner product), then runs a
threshold-gated iterative extraction: the block is scanned for candidates
only while its row-maximum still beats the running k-th best (``tau``),
which skips most extraction work once the running top-k tightens after the
first few blocks.  Running top-k state lives in VMEM scratch that persists
across the dataset-block walk; only the final (QT, k) values and indices
leave the chip.  Sample-filter masks and the padded tail of the norms operand
are folded into that operand (one fused subtract) instead of
iota/compare/select passes, and bf16-mode operands are cast OUTSIDE the
kernel (half the DMA bytes, no per-block VPU cast).  A corpus whose feature
dim is a whole number of 128-lane rows is read unpadded: the grid walks
``cdiv(n, NBLK)`` blocks and the final block, which overhangs the corpus,
forces its out-of-range columns out inside the kernel, whatever its rows
hold. Other feature dims are zero-padded, with the row count, per call.
The wrapper is one program: the keep-mask fold, the kernel, the query norms
and the distances. The corpus norms come from the caller (an index holds
them: neighbors/brute_force.BruteForce), or the program computes them.

Profiling notes (v5e, 100k x 128, k=10, 10k-query batches; details and
QPS-with-controls in r03 fused-kernel engineering notes):
- the kernel is VPU-extraction-bound, not MXU-bound: k=1 runs 3.3x faster
  than k=10, while a 6x MXU-cost swing (f32 HIGHEST vs bf16) moves QPS ~20%;
- three redesigns measured and REJECTED, kept here as negative results:
  (a) two-pass with XLA top_k tau between (2nd contraction sweep costs more
  than the extraction it skips), (b) segmented extraction over per-128-lane
  maxima (every (QT, NSEG) narrow-lane intermediate costs a vreg relayout;
  5x slower — keep Pallas hot-loop ops full-lane-width), (c) slice-maxima
  tau pass seeding the running k-th slot (flat: the running tau is already
  tight after ~2 blocks; the per-TILE any-row gate, not tau quality, sets
  the iteration count);
- the same per-tile-gate insight made qt=128 the default: fewer rows share
  one extraction loop, so it gates off sooner (+32% f32 / +11% bf16 over
  qt=256 in the same session).

Modes:
  "f32"   — f32 inputs, Precision.HIGHEST contraction. Exact: neighbor sets
            match the XLA f32 pipeline; within-1-ULP distance ties may order
            differently (score accumulation order differs between kernels).
  "f32x3" — compensated bf16x3 contraction (hi/lo split, three MXU passes),
            f32-class accuracy at roughly half the MXU cost. Neighbor
            sets match f32 except where two distances differ by < ~1e-6 rel.
  "bf16"  — single-pass bf16 contraction. Fastest; set recall ~0.98 on
            worst-case (uniform) data, higher on clustered data.
  "s8"    — int8 operands, s8 x s8 -> s32 MXU contraction (~2x bf16 peak,
            1-byte operand DMAs). For int8/uint8 datasets (the reference's
            ivf_flat/brute-force int8_t/uint8_t instantiations,
            cpp/src/neighbors/*_int8_t_*.cu): callers pass SHIFTED signed
            values (uint8 - 128 — L2 is shift-invariant; inner-product
            callers fold the 128-sum correction into the yn operand).
            EXACT distances when 3*128^2*d < 2^24 (d <= ~340): every
            intermediate is an integer below f32's exact range.

Ties: equal scores resolve to the lowest dataset index, matching lax.top_k.

Magnitude limit: scores are ranked against a -3e38 sentinel and masked
entries ride at ~-3e38, so inputs whose scores approach float32 max (|q·y|
beyond ~1e37 — feature scales ~1e17+) are out of contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import tracing


__all__ = ["fused_knn", "FUSED_KNN_MAX_K"]

FUSED_KNN_MAX_K = 64          # merge buffer is one 128-lane register: 2k <= 128


def fused_backend_ok():
    """True when the fused kernel may run: Mosaic on TPU, or interpret mode
    explicitly opted into for tests (RAFT_TPU_FUSED_KNN_INTERPRET=1)."""
    import os

    on_tpu = jax.default_backend() == "tpu"
    interpret_ok = os.environ.get(
        "RAFT_TPU_FUSED_KNN_INTERPRET", "").lower() in ("1", "true", "yes")
    return on_tpu or interpret_ok, not on_tpu


def shapes_eligible(n: int, d: int, k: int) -> bool:
    """Shared shape gate for fused-kernel dispatch: big-enough candidate set
    (below ~4096 rows XLA is fine and kernel padding overhead dominates),
    feature dim within the VMEM budget, and d not dominated by lane padding
    (inputs are zero-padded to 128 lanes; d << 64 would mostly multiply
    zeros and pay a padded dataset copy per call)."""
    return 0 < k <= FUSED_KNN_MAX_K and n >= 4096 and 64 <= d <= 4096
_NEG = -3.0e38                # finite sentinel: 0 * _NEG must stay finite
_BIG = 2**30                  # "no index" sentinel
_MASK_PENALTY = 3.0e38        # added to |y|^2 for padded / filtered-out rows


def _extract_topk_ids(v, ids, k):
    """k iterations of (max, argmin-id, mask-by-id) over a small (QT, W) array.

    Ties resolve to the smallest payload id; masking is by id, so a value
    merged twice under the same id (e.g. a running entry re-offered by a
    later candidate set) is consumed in one step, never duplicated.
    """
    vals, idxs = [], []
    for _ in range(k):
        m = jnp.max(v, axis=1, keepdims=True)
        am = jnp.min(jnp.where(v >= m, ids, _BIG), axis=1, keepdims=True)
        vals.append(m)
        idxs.append(am)
        v = jnp.where(ids == am, _NEG, v)
    return jnp.concatenate(vals, axis=1), jnp.concatenate(idxs, axis=1)


def _scores(q, y, mode):
    """MXU contraction q @ y.T in the requested precision mode."""
    dn = (((1,), (1,)), ((), ()))
    if mode == "s8":
        # int8 MXU path: s8 x s8 -> s32 (double bf16 peak), f32 at the end
        # for the extraction machinery's sentinel arithmetic
        return jax.lax.dot_general(
            q, y, dn, preferred_element_type=jnp.int32).astype(jnp.float32)
    if mode == "bf16":
        return jax.lax.dot_general(
            q.astype(jnp.bfloat16), y.astype(jnp.bfloat16), dn,
            preferred_element_type=jnp.float32)
    if mode == "f32x3":
        # compensated bf16x3: x·y ~ hi·hi + hi·lo + lo·hi (Mosaic has no
        # Precision.HIGH lowering, so the split is spelled out)
        qh = q.astype(jnp.bfloat16)
        ql = (q - qh.astype(jnp.float32)).astype(jnp.bfloat16)
        yh = y.astype(jnp.bfloat16)
        yl = (y - yh.astype(jnp.float32)).astype(jnp.bfloat16)
        return (jax.lax.dot_general(qh, yh, dn, preferred_element_type=jnp.float32)
                + jax.lax.dot_general(qh, yl, dn, preferred_element_type=jnp.float32)
                + jax.lax.dot_general(ql, yh, dn, preferred_element_type=jnp.float32))
    return jax.lax.dot_general(q, y, dn, precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _make_kernel(k, nblk, qt, mode, l2, n_valid):
    """``n_valid``: the corpus rows when the final dataset block overhangs
    them (an unpadded corpus with ``n % nblk != 0``), else None."""
    def kernel(q_ref, y_ref, yn_ref, out_v_ref, out_i_ref,
               run_v, run_i, s_ref, cand_v, cand_i, go_ref):
        j = pl.program_id(1)
        nb = pl.num_programs(1)

        @pl.when(j == 0)
        def _init():
            run_v[:] = jnp.full((qt, 128), _NEG, jnp.float32)
            run_i[:] = jnp.full((qt, 128), _BIG, jnp.int32)

        dots = _scores(q_ref[:], y_ref[:], mode)
        # yn carries |y|^2 (L2), the bounds padding penalty AND the sample
        # filter penalty — one fused subtract instead of iota/compare/select
        # masking passes. (A segmented-extraction variant that reduced the
        # block to per-128-lane maxima measured 5x SLOWER: every (qt, nseg)
        # narrow-lane intermediate costs a vreg relayout on TPU; all hot ops
        # here deliberately stay (qt, nblk)-wide.)
        s_ref[:] = (2.0 * dots if l2 else dots) - yn_ref[:]
        cols = jax.lax.broadcasted_iota(jnp.int32, (qt, nblk), 1) + j * nblk

        if n_valid is not None:
            @pl.when(j == nb - 1)
            def _tail():
                # the rows past the corpus hold whatever the block buffer
                # held (NaN included): their scores, not the penalty, are
                # forced out, on this block only
                s_ref[:] = jnp.where(cols < n_valid, s_ref[:], _NEG)

        tau = run_v[:, k - 1:k]
        go_ref[0] = 1
        cand_v[:] = jnp.full((qt, 128), _NEG, jnp.float32)
        cand_i[:] = jnp.full((qt, 128), _BIG, jnp.int32)

        for t in range(k):                      # static unroll, flag-gated
            @pl.when(go_ref[0] == 1)
            def _step(t=t):
                sv = s_ref[:]
                m = jnp.max(sv, axis=1, keepdims=True)
                any_improve = jnp.any(m > tau)
                go_ref[0] = any_improve.astype(jnp.int32)

                @pl.when(any_improve)
                def _extract():
                    am = jnp.min(jnp.where(sv >= m, cols, _BIG), axis=1,
                                 keepdims=True)
                    cand_v[:, t] = m[:, 0]
                    cand_i[:, t] = am[:, 0]
                    s_ref[:] = jnp.where(cols == am, _NEG, sv)

        mv = jnp.concatenate([run_v[:, :k], cand_v[:, :k]], axis=1)
        mi = jnp.concatenate([run_i[:, :k], cand_i[:, :k]], axis=1)
        nv, ni = _extract_topk_ids(mv, mi, k)
        run_v[:, :k] = nv
        run_i[:, :k] = ni

        @pl.when(j == nb - 1)
        def _emit():
            out_v_ref[:] = run_v[:, :k]
            out_i_ref[:] = run_i[:, :k]

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("k", "l2", "mode", "qt", "nblk", "interpret", "sqrt"))
def _fused_knn_impl(dataset, queries, yn, keep, k, l2, mode, qt, nblk,
                    interpret, sqrt=False):
    """The whole search as one program: ``yn`` (n,) f32 is the row operand
    the scores subtract (``|y|^2`` for L2, plus any row bias; None: the L2
    norms are computed here, nothing for ip); ``keep`` (n,) or None the
    sample filter. Returns the distances and ids of :func:`fused_knn`."""
    n, d = dataset.shape
    if yn is None and l2:
        with tracing.scope("brute_force.norms"):
            yn = jnp.sum(dataset.astype(jnp.float32) ** 2, axis=1)
    m = queries.shape[0]
    n_pad = -(-n // nblk) * nblk
    m_pad = -(-m // qt) * qt
    d_pad = -(-d // 128) * 128
    # a lane-aligned corpus is read as it is: the kernel's final block
    # overhangs it and masks the overhang; any other corpus is padded here
    n_rows = n if d_pad == d else n_pad
    # bf16 mode: cast once here, outside the kernel — the per-block VPU cast
    # inside the kernel was costing more than the narrower MXU pass saved
    # (measured bf16 SLOWER than f32 with in-kernel casts), and bf16 operands
    # also halve the per-step DMA bytes
    io_t = {"bf16": jnp.bfloat16, "s8": jnp.int8}.get(mode, jnp.float32)
    with tracing.scope("fused_knn.pad"):
        ds = dataset.astype(io_t)
        if (n_rows, d_pad) != (n, d):
            ds = jnp.pad(ds, ((0, n_rows - n), (0, d_pad - d)))
        qs = jnp.pad(queries.astype(io_t), ((0, m_pad - m), (0, d_pad - d)))
        base = yn if yn is not None else jnp.zeros((n,), jnp.float32)
        if keep is not None:
            # clamp: |y|^2 + penalty would overflow f32 to +inf for rows
            # with |y|^2 beyond ~4e37, and an inf norm turns the kernel's
            # masked arithmetic into NaN — pin filtered rows at the finite
            # sentinel so masking stays magnitude-independent
            base = jnp.minimum(
                base + jnp.where(keep.astype(bool), 0.0, _MASK_PENALTY),
                _MASK_PENALTY)
        ynp = jnp.pad(base, (0, n_pad - n),
                      constant_values=_MASK_PENALTY).reshape(1, n_pad)
    grid = (m_pad // qt, n_pad // nblk)

    kern = _make_kernel(k, nblk, qt, mode, l2,
                        n if n_rows % nblk else None)
    out_v, out_i = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((qt, d_pad), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((nblk, d_pad), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nblk), lambda i, j: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((qt, k), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((qt, k), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((m_pad, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qt, 128), jnp.float32),     # running top-k values
            pltpu.VMEM((qt, 128), jnp.int32),       # running top-k ids
            pltpu.VMEM((qt, nblk), jnp.float32),    # staged score block
            pltpu.VMEM((qt, 128), jnp.float32),     # block candidates (values)
            pltpu.VMEM((qt, 128), jnp.int32),       # block candidates (ids)
            pltpu.SMEM((1,), jnp.int32),            # extraction gate
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="fused_knn",
    )(qs, ds, ynp)
    out_v, out_i = out_v[:m], out_i[:m]
    empty = out_v <= _NEG / 2
    out_i = jnp.where(empty, -1, out_i)
    if not l2:
        return jnp.where(empty, -jnp.inf, out_v), out_i  # larger = closer
    qn = jnp.sum(queries.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    dist = jnp.maximum(qn - out_v, 0.0)
    if sqrt:
        dist = jnp.sqrt(dist)
    return jnp.where(empty, jnp.inf, dist), out_i


def fused_knn(dataset, queries, k, *, metric="l2", mode="f32", keep_mask=None,
              sqrt=False, row_operand=None, qt=128, nblk=4096,
              interpret=False):
    """Exact brute-force kNN via the fused Pallas kernel.

    ``metric``: "l2" (squared euclidean; ``sqrt=True`` for euclidean) or
    "ip" (inner product; larger = closer, like the reference's
    DistanceType::InnerProduct contract).  Cosine is "ip" over pre-normalized
    inputs (the caller normalizes, as distance/pairwise._cosine does).

    ``mode="s8"`` requires int8 inputs (uint8 callers shift by -128 first —
    L2 is shift-invariant; see brute_force._as_signed). ``row_operand`` (n,)
    f32 is what every row's score subtracts: ``|y|^2`` for l2 (an index
    passes the norms it holds; None computes them in the search program)
    and nothing for ip (None), plus any row bias — the hook ip callers use
    to restore uint8 inner products from shifted operands
    (q·v = q'·v' + 128·Σv' + const(q), where the Σv' term is the row bias
    with sign flipped).

    Returns (distances (m, k) f32, indices (m, k) int32).  Rows with fewer
    than k admissible dataset points (under ``keep_mask``) get -1 indices and
    +inf distances in the unfilled slots, matching brute_force.knn.
    """
    from ..core.errors import expects

    n, d = dataset.shape
    expects(0 < k <= FUSED_KNN_MAX_K,
            "fused_knn supports k in (0, %d], got %d — use brute_force.knn "
            "for larger k", FUSED_KNN_MAX_K, k)
    # Mosaic block shapes need 128-lane alignment, and the (qt, nblk) f32
    # score scratch must fit VMEM alongside the operand blocks
    expects(nblk % 128 == 0 and 128 <= nblk <= 16384,
            "nblk must be a multiple of 128 lanes in [128, 16384]")
    if mode == "s8":
        expects(dataset.dtype == jnp.int8 and queries.dtype == jnp.int8,
                "mode='s8' requires int8 operands (shift uint8 by -128 "
                "first), got %s/%s", dataset.dtype, queries.dtype)
    l2 = metric == "l2"
    expects(row_operand is None or row_operand.shape == (n,),
            "row_operand must be (n,)")
    # shrink the dataset block if the feature dim would blow the VMEM budget
    # (in whole 128-lane segments so the invariant above survives the shrink)
    while nblk > 512 and (qt + nblk) * max(d, 128) * 4 + qt * nblk * 4 > 24 * 2**20:
        nblk = (nblk // 2 // 128) * 128
    keep = None if keep_mask is None else jnp.asarray(keep_mask)
    return _fused_knn_impl(dataset, queries, row_operand, keep, int(k),
                           l2, mode, qt, nblk, interpret, sqrt=bool(sqrt))

"""Pallas streaming top-k with index payloads.

The selection kernel called out in SURVEY.md §2.3 (P8): the reference
implements two CUDA selectors (11-bit radix filter,
matrix/detail/select_radix.cuh, and warp bitonic queues,
detail/select_warpsort.cuh) because a full sort is wasteful for k ≪ n; XLA's
TopK custom call on TPU is sort-based and costs ~3 HBM passes over the
matrix.

This kernel streams the matrix once: column blocks arrive in VMEM (Pallas
pipelines the HBM reads) and a running top-k per row lives in VMEM scratch.
Selection is *threshold-gated* iterative extraction — a block is scanned only
while its row-maximum still beats the running k-th best (``tau``), so most
blocks beyond the first few cost one max-pass over VMEM. The same structure
fused with the distance GEMM is ops/fused_knn.py; this variant is the
standalone selector for matrices that already exist in HBM, dispatched from
matrix/select_k.py for wide rows on TPU.

An earlier ungated VPU design (k-iteration argmax/mask run unconditionally
per block) measured 66-138 ms vs 56 ms for lax.top_k on (10k, 100k); the
gated form beats lax.top_k at wide shapes (see matrix/select_k.py dispatch
notes for measurements).

Exact values; ties resolve to the lowest column index, matching lax.top_k.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["topk_pallas", "TOPK_MAX_K"]

# k <= 64: merge buffer is one 128-lane register (measured path).
# 64 < k <= 256: the running buffer is kept SORTED and merged with the
# sorted block candidates by a bitonic merge network (VERDICT r4 #5) —
# since r06 at HALF the lane width: the first stage of the 2k-wide network
# is an elementwise compare of the two k-wide halves (the discarded loser
# half is never formed), so every merge intermediate is <= kh lanes wide.
# log2(k) kh-lane compare-exchange stages instead of k extraction
# iterations (8 stages at kh lanes vs 256 iterations at k=256).
TOPK_MAX_K = 256
_NEG = -3.0e38
_BIG = 2**30


def _extract_topk_ids(v, ids, k):
    """k iterations of (max, argmin-id, mask-by-id) over a small array."""
    vals, idxs = [], []
    for _ in range(k):
        m = jnp.max(v, axis=1, keepdims=True)
        am = jnp.min(jnp.where(v >= m, ids, _BIG), axis=1, keepdims=True)
        vals.append(m)
        idxs.append(am)
        v = jnp.where(ids == am, _NEG, v)
    return jnp.concatenate(vals, axis=1), jnp.concatenate(idxs, axis=1)


def _bitonic_merge_desc(v, ids, s0):
    """Sort a (qt, w) bitonic sequence into descending order with stages
    s0, s0/2, ..., 1, ids riding along; ties resolve to the lower id,
    matching lax.top_k. All ops stay full (qt, w)-lane-width — rolls
    instead of narrow reshapes (the r03 lesson: narrow-lane intermediates
    cost a vreg relayout each). With s0 == w/2 this is the full bitonic
    merge network for a w-length bitonic sequence."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    s = s0
    while s >= 1:
        vf, idf = jnp.roll(v, -s, axis=1), jnp.roll(ids, -s, axis=1)
        vb, idb = jnp.roll(v, s, axis=1), jnp.roll(ids, s, axis=1)
        up = (lane % (2 * s)) < s
        # descending compare-exchange: winner (greater value, lower id on
        # ties) moves to the window's first half
        fwd_win = (v > vf) | ((v == vf) & (ids < idf))
        bwd_win = (vb > v) | ((vb == v) & (idb < ids))
        v_new = jnp.where(up, jnp.where(fwd_win, v, vf),
                          jnp.where(bwd_win, v, vb))
        i_new = jnp.where(up, jnp.where(fwd_win, ids, idf),
                          jnp.where(bwd_win, ids, idb))
        v, ids = v_new, i_new
        s //= 2
    return v, ids


def _select_kernel(x_ref, out_i_ref, run_v, run_i, s_ref,
                   cand_v, cand_i, go_ref, *, k, kh, blk, n, qt, select_min,
                   wide_merge):
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    wide = kh > 64
    w = kh if wide else 128

    @pl.when(j == 0)
    def _init():
        run_v[:] = jnp.full((qt, w), _NEG, jnp.float32)
        run_i[:] = jnp.full((qt, w), _BIG, jnp.int32)

    s = x_ref[:].astype(jnp.float32)
    if select_min:
        s = -s
    # clamp into the sentinel-safe range so +/-inf inputs still rank above the
    # padding sentinel (exact values are restored by a final gather from x)
    s = jnp.clip(s, -2.9e38, 2.9e38)
    cols = jax.lax.broadcasted_iota(jnp.int32, (qt, blk), 1) + j * blk
    s = jnp.where(cols < n, s, _NEG)
    s_ref[:] = s

    tau = run_v[:, k - 1:k]
    go_ref[0] = 1
    go_ref[1] = 0
    cand_v[:] = jnp.full((qt, w), _NEG, jnp.float32)
    cand_i[:] = jnp.full((qt, w), _BIG, jnp.int32)

    for t in range(k):                           # static unroll, flag-gated
        # wide path: write best-first extractions into REVERSED lanes so the
        # candidate buffer is born ascending — Mosaic has no `rev` lowering,
        # so the bitonic concat below must not need a flip
        tpos = (kh - 1 - t) if wide else t

        @pl.when(go_ref[0] == 1)
        def _step(t=t, tpos=tpos):
            sv = s_ref[:]
            m = jnp.max(sv, axis=1, keepdims=True)
            any_improve = jnp.any(m > tau)
            go_ref[0] = any_improve.astype(jnp.int32)

            @pl.when(any_improve)
            def _extract():
                am = jnp.min(jnp.where(sv >= m, cols, _BIG), axis=1,
                             keepdims=True)
                cand_v[:, tpos] = m[:, 0]
                cand_i[:, tpos] = am[:, 0]
                s_ref[:] = jnp.where(cols == am, _NEG, sv)
                go_ref[1] = 1

    if not wide:
        # measured k<=64 path, unchanged: 2k-wide buffer, k-step extraction
        mv = jnp.concatenate([run_v[:, :k], cand_v[:, :k]], axis=1)
        mi = jnp.concatenate([run_i[:, :k], cand_i[:, :k]], axis=1)
        nv, ni = _extract_topk_ids(mv, mi, k)
        run_v[:, :k] = nv
        run_i[:, :k] = ni
    else:
        # wide path: merge only when this block extracted anything (most
        # blocks beyond the first few are gated off entirely once tau
        # tightens — an unconditional full-width merge would dominate)
        @pl.when(go_ref[1] == 1)
        def _merge():
            # run is sorted desc; candidates were written reversed (see
            # tpos above) so cand is already ascending — run ++ cand is
            # bitonic with no flip
            rv, riv = run_v[:, :kh], run_i[:, :kh]
            cv, civ = cand_v[:, :kh], cand_i[:, :kh]
            if wide_merge == "half":
                # half-width form (r06): the 2kh-wide network's first
                # stage (stride kh) only ever routes the winner of
                # (run[i], cand[i]) into the kept half — computed as one
                # elementwise compare-exchange of the two kh-wide halves,
                # whose output is itself bitonic. The remaining stages
                # (kh/2 .. 1) never cross the half boundary, so NOTHING
                # in the merge exceeds kh lanes: the kh=256 instance uses
                # exactly the lane widths of the chaining-proven kh=128
                # path (the workaround for the two-instance Mosaic
                # failure, see topk_pallas docstring), and one full-width
                # stage is saved outright.
                win = (rv > cv) | ((rv == cv) & (riv < civ))
                nv = jnp.where(win, rv, cv)
                ni = jnp.where(win, riv, civ)
                nv, ni = _bitonic_merge_desc(nv, ni, kh // 2)
                run_v[:, :kh] = nv
                run_i[:, :kh] = ni
            else:  # "concat": the r05 formulation (2kh-lane concat +
                # full network) — kept verbatim for the on-hardware
                # chaining repro/bisect (bench/topk_chain_repro.py)
                mv = jnp.concatenate([rv, cv], axis=1)
                mi = jnp.concatenate([riv, civ], axis=1)
                nv, ni = _bitonic_merge_desc(mv, mi, kh)
                run_v[:, :kh] = nv[:, :kh]
                run_i[:, :kh] = ni[:, :kh]

    @pl.when(j == nb - 1)
    def _emit():
        out_i_ref[:] = run_i[:, :k]


@functools.partial(jax.jit,
                   static_argnames=("k", "select_min", "blk", "qt", "interpret",
                                    "wide_merge"))
def topk_pallas(x, k: int, select_min: bool = True, blk: int = 4096,
                qt: int = 256, interpret: bool | None = None,
                wide_merge: str = "half"):
    """Top-k of each row of ``x`` (2-D) with source-column payloads.

    Returns (values (m, k), indices (m, k) int32), values sorted best-first.
    Exact; ``select_min=True`` mirrors lax.top_k on -x. ``interpret``
    defaults to True off-TPU (Pallas interpreter) so the kernel is testable
    on the CPU mesh. k <= TOPK_MAX_K; larger k belongs to lax.top_k (the
    matrix/select_k.py dispatch handles that split).

    Magnitude limit: ranking happens after a clamp to +/-2.9e38 (so +/-inf
    inputs still beat the padding sentinel), which collapses finite f32
    magnitudes in (2.9e38, 3.4e38] with each other and with +/-inf — among
    such values the selected *index* can differ from lax.top_k (returned
    values are exact either way, restored by the final gather). Pre-scale
    inputs if distinctions above 2.9e38 matter; distance pipelines never get
    near this range.

    kh=256 chaining history (r05 -> r06): embedding two kh=256 kernel
    instances (two k > 128 calls) inside one XLA program used to hit a
    TPU-internal Mosaic error, which capped the matrix/select_k.py dispatch
    at k <= 128. The r05 merge built 2*kh-lane intermediates — 512 lanes at
    kh=256, the ONLY lane width the chaining-proven kh=128 path never uses —
    so ``wide_merge="half"`` (default) now computes the first network stage
    as an elementwise compare of the two kh-wide halves (the discarded loser
    half is never formed) and keeps every merge intermediate <= kh lanes;
    the dispatch cap is lifted to k <= 256. ``wide_merge="concat"`` keeps
    the r05 formulation verbatim so ``bench/topk_chain_repro.py`` can
    reproduce and bisect the original failure on hardware; if a future
    toolchain still rejects chained kh=256 "half" instances, re-cap the
    dispatch with ``RAFT_TPU_WIDE_SELECT_CAP=128`` (see select_k) and run
    the repro. The two-instance composition at the CAGRA build-chunk shapes
    is pinned by ``tests/test_ops.py::test_topk_pallas_two_wide_instances``.
    """
    m, n = x.shape
    if k > min(TOPK_MAX_K, n):
        raise ValueError(f"k={k} must be <= min({TOPK_MAX_K}, n={n})")
    if wide_merge not in ("half", "concat"):
        raise ValueError(f"wide_merge must be 'half' or 'concat', got {wide_merge!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    blk = max(128, min(blk, -(-n // 128) * 128))
    # kh: running-buffer width — 64 keeps the measured narrow path; wider k
    # rounds to a power of two for the bitonic merge network
    kh = 64 if k <= 64 else 1 << (k - 1).bit_length()
    w = 128 if kh == 64 else kh
    # no host-side jnp.pad (it would copy the whole matrix through HBM):
    # Pallas pads boundary blocks itself and the kernel masks cols >= n;
    # boundary-row garbage is sliced away below
    n_blocks = -(-n // blk)
    m_blocks = -(-m // qt)
    grid = (m_blocks, n_blocks)
    kern = functools.partial(_select_kernel, k=k, kh=kh, blk=blk, n=n, qt=qt,
                             select_min=bool(select_min),
                             wide_merge=wide_merge)
    out_i = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((qt, blk), lambda i, j: (i, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((qt, k), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m_blocks * qt, k), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((qt, w), jnp.float32),       # running top-k values
            pltpu.VMEM((qt, w), jnp.int32),         # running top-k ids
            pltpu.VMEM((qt, blk), jnp.float32),     # block scratch
            pltpu.VMEM((qt, w), jnp.float32),       # block candidates
            pltpu.VMEM((qt, w), jnp.int32),
            pltpu.SMEM((2,), jnp.int32),            # extraction + merge gates
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="topk",
    )(x)
    pos = jnp.minimum(out_i[:m], n - 1)        # _BIG only when a row is degenerate
    vals = jnp.take_along_axis(x, pos, axis=1)  # exact values, infs included
    return vals, pos

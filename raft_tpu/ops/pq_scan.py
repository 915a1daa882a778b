"""Fused Pallas LUT-scan kernel for IVF-PQ (the reference's hottest kernel).

The reference's `compute_similarity` keeps the per-(query, probe) LUT in smem
and each thread gathers LUT[s, code_s] at full shared-memory throughput
(cpp/include/raft/neighbors/detail/ivf_pq_compute_similarity-inl.cuh, launched
from ivf_pq_search.cuh:419-557). A TPU has no smem gather, so rounds 1-3
re-expressed the gather as a one-hot MXU contraction — correct, but it
synthesizes a (T, pc, cap, pq_dim*K) one-hot operand through HBM. An
XLA-level compare+select chain (`ivf_pq._select_scores`) measured 2x SLOWER
still (24.6k vs 46.4k QPS at 1M): XLA materializes each pass of the 16-step
chain instead of keeping it register-resident.

This kernel hand-schedules that sweep as the TPU analogue of ScaNN's SIMD
LUT16 shuffle:

- codes stream as int8 planes (32-64 bytes per candidate instead of the
  one-hot's 1-2 KB) and are PACKED so the lane dimension is full 128-wide:
  for pq_dim=64, two candidates share one lane row ((cap, 64) viewed as
  (cap/2, 128) — a free reshape in HBM; a 64-lane array would waste half of
  every VPU op in 128-lane vregs);
- the LUT block (lane-tiled to the packed width) stays resident in VMEM;
- the gather itself is ONE hardware op per (16, lanes) tile:
  ``tpu.dynamic_gather`` (Mosaic's lowering of a same-shape 2D
  take_along_axis) — the literal TPU LUT16 shuffle. Two earlier variants
  measured and rejected: a 16-pass compare+select chain (~48 whole-array VPU
  passes — Mosaic executes op-at-a-time, so the chain streams the
  accumulator through VMEM) and the XLA one-hot contraction (HBM-streamed
  operand);
- per-candidate-half partial sums come from masked lane reductions, emitted
  as a (pack, bt, capb) output the XLA caller de-interleaves (cheap).

Scores are raw Σ_s LUT[s, code_s]; bias/consts/±inf masking stay in the XLA
epilogue (cheap: (T, pc, cap) elementwise, ~40 KB/query).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["pq_lut_scan", "pq_scan_backend_ok"]


def pq_scan_backend_ok():
    """(may_run, interpret): Mosaic on TPU, or interpret mode opted into for
    tests via RAFT_TPU_PQ_SCAN_INTERPRET=1 (same contract as fused_knn)."""
    import os

    on_tpu = jax.default_backend() == "tpu"
    interpret_ok = os.environ.get(
        "RAFT_TPU_PQ_SCAN_INTERPRET", "").lower() in ("1", "true", "yes")
    return on_tpu or interpret_ok, not on_tpu


def _make_kernel(split: bool, bt: int, capb: int, lanes: int, s: int,
                 pack: int):
    """capb = packed candidate rows per block; lanes = s*pack."""

    def kernel(*refs):
        if split:
            hi_ref, lo_ref, lut_ref, out_ref, g_ref = refs
        else:
            code_ref, lut_ref, out_ref, g_ref = refs
        # selector for the per-half lane sums: sel[h, l] = 1 iff lane l
        # belongs to candidate-half h; M padded to >= 8 sublanes for the MXU
        mrows = max(8, pack)
        lane = jax.lax.broadcasted_iota(jnp.int32, (mrows, lanes), 1)
        half = jax.lax.broadcasted_iota(jnp.int32, (mrows, lanes), 0)
        sel = ((lane // s) == half).astype(jnp.float32)

        def lut16(idx_ref, b, rows, t_lo, t_hi):
            # the hardware LUT16 in two halves: tpu.dynamic_gather (Mosaic's
            # lowering of a same-shape 2D take_along_axis) shuffles one
            # source vreg, i.e. 8 f32 sublanes — so the 16-entry table is
            # split into two (8, lanes) halves, gathered with the masked
            # index, and recombined on bit 3. ~7 ops per (8, lanes) tile vs
            # ~48 whole-array passes for a compare+select chain (measured
            # slower than even the one-hot MXU path).
            idx = idx_ref[b, rows, :].astype(jnp.int32)
            lo_bits = idx & 7
            g_lo = jnp.take_along_axis(t_lo, lo_bits, axis=0,
                                       mode="promise_in_bounds")
            g_hi = jnp.take_along_axis(t_hi, lo_bits, axis=0,
                                       mode="promise_in_bounds")
            return jnp.where(idx < 8, g_lo, g_hi)

        for b in range(bt):
            lut = lut_ref[b].astype(jnp.float32)  # (K, lanes), VMEM-resident
            tables = [(lut[0:8], lut[8:16])]
            if split:
                tables.append((lut[16:24], lut[24:32]))
            for j in range(capb // 8):
                rows = slice(j * 8, (j + 1) * 8)
                g = lut16(hi_ref if split else code_ref, b, rows, *tables[0])
                if split:
                    g = g + lut16(lo_ref, b, rows, *tables[1])
                g_ref[rows, :] = g
            # both half-sums in ONE MXU contraction over the lane dim —
            # masked lane reductions per tile measured ~2x the gather cost
            mm = jax.lax.dot_general(
                sel, g_ref[...], (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,  # bf16 would round g
                preferred_element_type=jnp.float32)  # (8, capb)
            out_ref[:, b, :] = mm[:pack]

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("bt", "capb", "pack", "interpret"))
def _pq_scan_impl(codes_hi, codes_lo, lut, bt: int, capb: int,
                  pack: int, interpret: bool):
    """codes_*: (B, capP, lanes) int8 packed planes; lut: (B, K, lanes)
    lane-tiled. Returns (pack, B, capP) f32 partial scores (pack = lanes//S
    candidate interleave)."""
    B, capP, lanes = codes_hi.shape
    K = lut.shape[1]
    split = codes_lo is not None
    Bp = -(-B // bt) * bt
    capp = -(-capP // capb) * capb
    pad3 = ((0, Bp - B), (0, capp - capP), (0, 0))
    ch = jnp.pad(codes_hi, pad3)
    cl = jnp.pad(codes_lo, pad3) if split else None
    lp = jnp.pad(lut, ((0, Bp - B), (0, 0), (0, 0)))
    grid = (Bp // bt, capp // capb)
    code_spec = pl.BlockSpec((bt, capb, lanes), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _make_kernel(split, bt, capb, lanes, lanes // pack, pack),
        grid=grid,
        in_specs=[code_spec] + ([code_spec] if split else []) + [
            pl.BlockSpec((bt, K, lanes), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((pack, bt, capb), lambda i, j: (0, i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((pack, Bp, capp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((capb, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="pq_scan",
    )(*([ch, cl, lp] if split else [ch, lp]))
    return out[:, :B, :capP]


def pq_lut_scan(codes, lut, codes_lo=None, *, bt: int = 32,
                capb: int | None = None, interpret: bool = False):
    """Σ_s LUT[s, code_s] for every (batch row, candidate).

    ``codes``: (B, cap, S) int8 values in [0, 16) — the stage-1 (or only)
    code plane. ``codes_lo``: optional stage-2 plane (nibble-split pq8).
    ``lut``: (B, K, S) float (K = 16 single-stage, 32 split; any float dtype
    — cast to f32 in-kernel). Returns (B, cap) f32.
    """
    from ..core.errors import expects

    B, cap, S = codes.shape
    expects(lut.shape[0] == B and lut.shape[2] == S,
            "lut must be (B, K, S) matching codes (B, cap, S)")
    expects(lut.shape[1] == (32 if codes_lo is not None else 16),
            "lut K must be 16 (single-stage) or 32 (split with codes_lo)")
    # Mosaic requires the lane (last) dim be 128-aligned: pad S up to the
    # next divisor of 128 (S < 128) or multiple of 128 (S > 128) with
    # zero-valued LUT columns — pad lanes gather lut[0, pad] == 0 and add
    # nothing to the sum, so scores are exact. (A raw S like 96 or 24,
    # reachable via pq_bits=4 builds, would otherwise hit an opaque Mosaic
    # lowering failure that interpret-mode tests cannot catch.)
    if 128 % S != 0:
        Sp = 1 << (S - 1).bit_length() if S < 128 else -(-S // 128) * 128
        zpad = ((0, 0), (0, 0), (0, Sp - S))
        codes = jnp.pad(codes, zpad)
        if codes_lo is not None:
            codes_lo = jnp.pad(codes_lo, zpad)
        lut = jnp.pad(lut, zpad)
        S = Sp
    pack = 128 // S if 128 % S == 0 else 1
    capP = -(-cap // pack)
    lanes = S * pack

    def packit(c):
        if pack == 1:
            return c
        padded = jnp.pad(c, ((0, 0), (0, capP * pack - cap), (0, 0)))
        return padded.reshape(B, capP, lanes)  # free: contiguous in HBM

    ch = packit(codes)
    cl = packit(codes_lo) if codes_lo is not None else None
    lt = jnp.tile(lut, (1, 1, pack))  # lane-tiled LUT
    if capb is None:
        capb = -(-capP // 16) * 16 if capP <= 1024 else 512
    capb = max(16, min(capb, -(-capP // 16) * 16))
    capb = -(-capb // 8) * 8  # whole (8, lanes) gather tiles
    out = _pq_scan_impl(ch, cl, lt, bt, int(capb), pack, interpret)
    # de-interleave: candidate pack*row + h lives at out[h, :, row]
    scores = jnp.moveaxis(out, 0, 2).reshape(B, capP * pack)
    return scores[:, :cap]

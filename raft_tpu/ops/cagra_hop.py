"""Fused CAGRA hop kernel: the whole per-hop beam update in ONE Pallas pass.

The r04 hop study (r04 CAGRA hop study + addendum)
decomposed the 1M batch-synchronous search into ~0.27 us/query/hop of
expansion scoring (the vector gather — which XLA's gather engine serves at
~60 GB/s effective on overlapping beam frontiers, 15x the isolated per-row
DMA rate, so an in-kernel `make_async_copy` gather CANNOT win) and
~0.46 us/query of "everything else": ~20 op-at-a-time XLA passes over the
(m, itopk+deg) beam-state arrays per hop, none individually hot — dispatch
and small-op latency, not bandwidth. This kernel attacks exactly that term,
the way the reference's persistent SINGLE_CTA kernel keeps its itopk queue
in registers/smem (detail/cagra/search_single_cta.cuh): the two gathers
(graph row, vectors) stay in XLA where they are fastest, and EVERYTHING
between them — candidate scoring, dedup against the beam, the
beam-merge selection, visited bookkeeping, and the next hop's pick —
runs in one kernel launch with all beam state resident in VMEM.

Per hop the XLA level does exactly three ops: graph-row gather, vector
gather, this kernel. Beam state crosses HBM once per hop instead of ~20
times, and 20 op dispatches collapse into 1.

Layout: beam arrays are (m, 128)-padded (lanes >= itopk carry the empty
sentinel) so every in-kernel op is full-lane-width. The beam is an unsorted
arena that candidates enter by a threshold-gated insertion, best first with
lowest-id tie-breaks; the caller sorts it once after the hop loop. A
candidate id already in the arena is consumed, not inserted (the arena's
copy of a node carries the identical exact distance). This arena merge is
the only one. A sorted-extraction merge measured neutral against the XLA
loop, and the first arena form (gate and candidate pool in SMEM/VMEM
scratch) was kept only as the control of an A/B no cell ran; both were
deleted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["cagra_hop", "hop_backend_ok", "hop_shapes_eligible"]

_POOL = 128               # merge pool lanes: itopk + deg must fit
_BIG = 2 ** 30
_INF = jnp.inf
_NEG = -3.0e38            # finite sentinel for masked maxima


def hop_backend_ok():
    """(may_run, interpret): Mosaic on TPU, or interpret mode opted into for
    tests via RAFT_TPU_CAGRA_HOP_INTERPRET=1 (same contract as fused_knn)."""
    import os

    on_tpu = jax.default_backend() == "tpu"
    interpret_ok = os.environ.get(
        "RAFT_TPU_CAGRA_HOP_INTERPRET", "").lower() in ("1", "true", "yes")
    return on_tpu or interpret_ok, not on_tpu


# VMEM budget for the staged candidate-vector block (of the kernel's 100MB
# vmem_limit_bytes, leaving headroom for the beam-state blocks and scratch)
_HOP_VMEM_BUDGET = 80 * 1024 * 1024


def hop_shapes_eligible(itopk: int, deg: int, width: int, d: int,
                        itemsize: int = 4) -> bool:
    """The fused hop supports any search_width whose merge pool
    (itopk + width*degree candidates) fits one 128-lane register row AND
    whose staged d-scaled blocks fit the VMEM budget: the kernel stages a
    (qt=128, width*deg, d_pad) candidate block of the dataset's dtype
    (``itemsize`` bytes/element — 1 for byte datasets, which are upcast
    in-kernel) plus a (qt, d_pad) f32 query tile, both double-buffered by
    the Pallas pipeline. Bounding by
    estimated bytes instead of a flat ``d <= 4096`` cap means
    ``hop_impl='auto'`` falls back to the XLA loop for large-d configs
    (e.g. itopk=32, deg=32, d=4096 f32: ~67MB/block, >100MB double-buffered)
    instead of failing at compile (ADVICE r5)."""
    if not (width >= 1 and itopk + width * deg <= _POOL and itopk >= 1
            and d >= 1):
        return False
    d_pad = -(-d // 128) * 128
    vec_bytes = 128 * width * deg * d_pad * itemsize
    q_bytes = 128 * d_pad * 4  # f32 query tile, also double-buffered
    return 2 * (vec_bytes + q_bytes) <= _HOP_VMEM_BUDGET


def _make_hop_kernel(itopk: int, cw: int, width: int, qt: int):
    def kernel(q_ref, bd_ref, bi_ref, bv_ref, nbr_ref, vec_ref, valid_ref,
               nbd_ref, nbi_ref, nbv_ref, pick_ref, nocand_ref):
        lane = jax.lax.broadcasted_iota(jnp.int32, (qt, _POOL), 1)

        # ---- candidate scoring: direct ||v - q||^2, (qt, cw). The
        # expanded ||v||^2 - 2 q.v form was tried and measured WORSE (r05):
        # gathering ||v||^2 per candidate doubles the hop's random-gather
        # count (4 B norm gathers are as latency-bound as the 512 B rows)
        # and costs far more than the one VPU pass it saves (arena
        # 38k -> 28.5k QPS at 1M).
        nbr = nbr_ref[...]                   # (qt, cw) int32
        q = q_ref[...]                       # (qt, dp)
        # byte datasets arrive as int8 (a quarter of the f32 DMA bytes
        # — the hop's vector traffic) and upcast HERE, at the tile
        # level; 8-bit integers are exact in f32, so the s8 path's
        # distances match the f32 path's bitwise
        vecs = vec_ref[...].astype(jnp.float32)  # (qt, cw, dp)
        diff = vecs - q[:, None, :]
        nd = jnp.sum(diff * diff, axis=-1)   # (qt, cw)
        # valid is per-candidate (the XLA side expands the per-pick flags
        # over each pick's deg candidates)
        ok = (nbr >= 0) & (valid_ref[...] > 0)          # (qt, cw)
        nd = jnp.where(ok, nd, _INF)

        # ---- threshold-gated arena merge: the beam is an UNSORTED arena
        # of itopk slots (sorted once in XLA after the loop); a candidate is
        # inserted — replacing the arena's current worst — only while the
        # best remaining candidate beats that worst. Late hops insert ~0-2
        # candidates, so the whole merge gates off after a couple of
        # iterations (the fused_knn per-tile-gate insight applied to the
        # beam), vs itopk unconditional extraction passes. Candidate count
        # bounds the iterations. A candidate already in the arena carries
        # the same exact score, so it is consumed at insertion, not
        # inserted (dedup-on-insert instead of an itopk-pass pre-mask).
        #
        # The gate lives in a REGISTER (lax.cond carries it across
        # iterations as a loop value — no SMEM write+read handshake
        # serializing the VPU per candidate), candidate scores ride the
        # fori_loop carry (vregs, no pool-scratch round trips), candidate
        # ids are the already-loaded nbr (never mutated), and the one
        # any(improve) reduction both closes the gate and masks the writes.
        bi = bi_ref[...]                     # (qt, _POOL)
        nbd_ref[...] = bd_ref[...]
        nbi_ref[...] = bi
        nbv_ref[...] = bv_ref[...]
        itmask = lane < itopk

        def _insert_step(_, carry):
            go, cd = carry

            def _live():
                ad = nbd_ref[...]
                admask = jnp.where(itmask, ad, _NEG)
                worst = jnp.max(admask, axis=1, keepdims=True)
                best = jnp.min(cd, axis=1, keepdims=True)
                improve = best < worst              # (qt, 1)
                bid = jnp.min(jnp.where(cd <= best, nbr, _BIG),
                              axis=1, keepdims=True)
                ai = nbi_ref[...]
                dup = jnp.any((ai == bid) & itmask, axis=1, keepdims=True)
                ins = improve & jnp.logical_not(dup)
                # arena slot to evict: the worst entry, highest lane on
                # ties (any one copy)
                wsel = (admask >= worst)
                wlane = jnp.max(jnp.where(wsel, lane, -1), axis=1,
                                keepdims=True)
                at = ins & (lane == wlane)
                # masked writes: rows whose improve is false keep their
                # arena untouched, so no inner when-branch
                nbd_ref[...] = jnp.where(at, best, ad)
                nbi_ref[...] = jnp.where(at, bid, ai)
                nbv_ref[...] = jnp.where(at, 0, nbv_ref[...])
                # consume the candidate (all copies of its id)
                cd2 = jnp.where(improve & (nbr == bid), _INF, cd)
                return jnp.any(improve).astype(jnp.int32), cd2

            return jax.lax.cond(go == 1, _live, lambda: (jnp.int32(0), cd))

        jax.lax.fori_loop(0, cw, _insert_step, (jnp.int32(1), nd))

        _emit_pick(itopk, width, qt, lane, nbd_ref, nbi_ref, nbv_ref,
                   pick_ref, nocand_ref)

    return kernel


def _emit_pick(itopk, width, qt, lane, nbd_ref, nbi_ref, nbv_ref, pick_ref,
               nocand_ref):
    """Next picks: the ``width`` best unvisited entries in the itopk window,
    each marked visited as it is taken (matching the XLA loop's argsort
    top-width pick)."""
    nbd = nbd_ref[...]
    for w in range(width):
        nbv = nbv_ref[...]
        cd = jnp.where((nbv > 0) | (lane >= itopk), _INF, nbd)
        mn = jnp.min(cd, axis=1, keepdims=True)
        nocand = (mn >= _INF).astype(jnp.int32)
        sel = cd <= mn
        pick_id = jnp.min(jnp.where(sel, nbi_ref[...], _BIG), axis=1,
                          keepdims=True)
        nbv_ref[...] = jnp.where(
            (nbi_ref[...] == pick_id) & (nocand == 0), 1, nbv)
        pick_ref[:, w] = jnp.clip(pick_id[:, 0], 0, _BIG)
        nocand_ref[:, w] = nocand[:, 0]


@functools.partial(jax.jit, static_argnames=("itopk", "width", "qt",
                                             "interpret"))
def cagra_hop(queries, beam_d, beam_i, beam_v, nbrs, vecs, valid,
              itopk: int, width: int = 1, qt: int = 128,
              interpret: bool = False):
    """One fused CAGRA hop over the whole query batch.

    ``queries`` (m, d) f32; ``beam_d/beam_i/beam_v`` (m, 128) padded beam
    state (the unsorted arena: distances f32, ids i32, visited i32; lanes
    >= itopk are +inf/-1/1); ``nbrs`` (m, cw) i32 candidate ids for cw = width*degree
    (-1 = none); ``vecs`` (m, cw, d) their vectors — f32, or int8 for byte
    datasets (upcast in-kernel at the tile level: quarter the DMA bytes,
    bitwise-identical distances); ``valid`` (m, cw) i32 —
    0 masks a candidate (the caller expands each pick's validity over its
    deg candidates; all-zero primes the loop).

    Returns (beam_d, beam_i, beam_v, pick (m, width) i32 clipped >= 0,
    no_cand (m, width) i32). Beam distances are full ||v - q||^2.
    """
    m, d = queries.shape
    cw = nbrs.shape[1]
    dp = -(-d // 128) * 128
    mp = -(-m // qt) * qt
    pad_rows = mp - m

    def prow(x, fill=0):
        return jnp.pad(x, ((0, pad_rows),) + ((0, 0),) * (x.ndim - 1),
                       constant_values=fill) if pad_rows else x

    qp = prow(jnp.pad(queries, ((0, 0), (0, dp - d))) if dp > d else queries)
    vp = prow(jnp.pad(vecs, ((0, 0), (0, 0), (0, dp - d)))
              if dp > d else vecs)
    args = (qp, prow(beam_d, _INF), prow(beam_i, -1), prow(beam_v, 1),
            prow(nbrs, -1), vp, prow(valid))
    grid = (mp // qt,)
    spec2 = lambda w: pl.BlockSpec((qt, w), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)
    outs = pl.pallas_call(
        _make_hop_kernel(itopk, cw, width, qt),
        grid=grid,
        in_specs=[spec2(dp), spec2(_POOL), spec2(_POOL), spec2(_POOL),
                  spec2(cw),
                  pl.BlockSpec((qt, cw, dp), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
                  spec2(cw)],
        out_specs=[spec2(_POOL), spec2(_POOL), spec2(_POOL), spec2(width),
                   spec2(width)],
        out_shape=[
            jax.ShapeDtypeStruct((mp, _POOL), jnp.float32),
            jax.ShapeDtypeStruct((mp, _POOL), jnp.int32),
            jax.ShapeDtypeStruct((mp, _POOL), jnp.int32),
            jax.ShapeDtypeStruct((mp, width), jnp.int32),
            jax.ShapeDtypeStruct((mp, width), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="cagra_hop",
    )(*args)
    return tuple(o[:m] for o in outs)

"""IVF-PQ: inverted-file index with product-quantized residuals.

Re-design of the reference's IVF-PQ (cpp/include/raft/neighbors/ivf_pq-inl.cuh:
build :270 / search :723; build detail/ivf_pq_build.cuh — rotation matrix
make_rotation_matrix :121, residuals select_residuals :165, codebook training
train_per_subset :343 / train_per_cluster :424; search detail/ivf_pq_search.cuh
— select_clusters :68, LUT scan ivfpq_search_worker :419, fused top-k; params
ivf_pq_types.hpp:48-140). The TPU re-think:

- **Rotation**: a (d_rot, d) orthonormal matrix (QR of Gaussian noise, exactly
  the reference's construction) — one GEMM at build and per query batch.
- **Codebooks**: PER_SUBSPACE trains one codebook per pq_dim subspace over
  all residual sub-vectors (vmapped balanced-EM — all subspaces train
  simultaneously as one batched kmeans, a TPU win over the reference's
  sequential stream loop); PER_CLUSTER trains per coarse cluster.
- **Codes**: stored unpacked, one uint8 per (vector, subspace) in padded
  lists (n_lists, capacity, pq_dim) — trading the reference's bit-packed
  layout (ivf_pq_codepacking.cuh) for direct gather/byte loads; pq_bits
  still bounds the codebook size.
- **Search**: coarse GEMM + select_k, then per (query-tile, probe-chunk):
  LUT = ‖residual_sub - codebook‖² for every subspace (one batched GEMM
  against the codebooks), scores = LUT-gather summed over subspaces, fused
  select_k. The reference's fp8-LUT trick maps to bf16 LUTs (lut_dtype).
"""

from __future__ import annotations

from ..config import auto_convert_output

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..cluster import kmeans_balanced
from ..cluster.kmeans_balanced import KMeansBalancedParams
from ..core import chunked, tracing
from ..core.errors import expects
from ..core.logger import logger
from ..obs import mem as obs_mem
from ..obs import metrics
from ..obs.instrument import dtype_of, instrument, nrows
from ..core.resources import Resources, default_resources
from ..core import serialize as core_serialize
from ..core.serialize import (check_header, deserialize_mdspan, deserialize_scalar,
                              deserialize_tuned, serialize_header,
                              serialize_mdspan, serialize_scalar,
                              serialize_tuned, version_number)
from ..distance.pairwise import _choose_tile
from ..distance.types import DistanceType, resolve_metric
from ..matrix.select_k import _select_k, select_k_impl
from ..random.rng import as_key
from ._list_utils import (assign_to_lists, bound_capacity,
                          funnel_scan_bytes_per_probe_row, list_positions,
                          plan_search_tiles, pq_scan_bytes_per_probe_row,
                          round_up)

__all__ = ["IndexParams", "SearchParams", "IvfPqIndex", "build", "extend", "search", "save", "load"]


@functools.lru_cache(maxsize=None)
def _quant_opq_seconds():
    return metrics.histogram(
        "raft_tpu_quant_opq_train_seconds",
        "OPQ rotation training wall time per build", unit="s")


@functools.lru_cache(maxsize=None)
def _quant_funnel_total():
    return metrics.counter(
        "raft_tpu_quant_funnel_searches_total",
        "searches routed through the fast-scan funnel (funnel_widen > 1)")


@functools.lru_cache(maxsize=None)
def _scan_plan_total():
    return metrics.counter(
        "raft_tpu_ivf_pq_scan_plan_total",
        "ivf_pq.search dispatches by scan plan: n_probes / probe_chunk "
        "sequential scan steps per query tile (labels: query_tile, "
        "probe_chunk)")


@functools.lru_cache(maxsize=None)
def _quant_bytes_per_row():
    return metrics.gauge(
        "raft_tpu_quant_scan_bytes_per_row",
        "hot-scan HBM bytes per stored row, by tier (labels: tier)",
        unit="bytes")


@dataclasses.dataclass(frozen=True)
class IndexParams:
    """Reference: ivf_pq::index_params (ivf_pq_types.hpp:48-105)."""

    n_lists: int = 1024
    metric: str | DistanceType = "sqeuclidean"
    # codebook size = 2**pq_bits; 4..8 supported. DEFAULT DIFFERS FROM THE
    # REFERENCE (ivf_pq_types.hpp:68 defaults 8): the TPU LUT scan is a
    # one-hot MXU contraction whose axis is pq_dim * 2**pq_bits, so pq8
    # costs ~16x pq4 at equal code bytes (measured at 1M x 128: pq4x64
    # 41.4k QPS vs pq8x32 2.6k at the same recall point; int8/bf16 LUTs do
    # not close it). The reference's smem-gather LUT is bits-insensitive,
    # which does NOT hold here — prefer pq_bits=4 with doubled pq_dim. See
    # docs/migrating_from_raft.md.
    pq_bits: int = 4
    pq_dim: int = 0  # 0 → auto: same code bytes as the reference default (d/2 at 8 bits, d at 4)
    codebook_kind: str = "per_subspace"  # ref :43 codebook_gen
    force_random_rotation: bool = False  # ref :98
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    # coarse-trainer EM cost policy (KMeansBalancedParams.train_mode /
    # batch_rows; see ivf_flat.IndexParams — same contract): "auto" =
    # mini-batch EM above 2 x kmeans_batch_rows trainset rows, cutting the
    # ~22 full-trainset assignment passes to the two closing passes. The
    # PQ codebook trainers are untouched (they already train on a pooled
    # subsample).
    kmeans_train_mode: str = "auto"
    kmeans_batch_rows: int = 65536
    add_data_on_build: bool = True
    seed: int = 0
    # capacity bound for sub-list splitting (multiple of mean list size, see
    # _list_utils.bound_capacity). The LUT scan's one-hot contraction work
    # scales with capacity, so tighter capacity pays even more than for
    # ivf_flat: 1.3 measured +68% QPS (20.5k -> 34.4k at 1M, p=8) at
    # identical recall
    split_factor: float = 1.3
    # pq_bits=8 layout. True: two-stage 4+4-bit residual quantizer per
    # subspace — the codeword is cb1[hi_nibble] + cb2[lo_nibble], so the
    # scan's one-hot contraction axis is pq_dim*32 instead of pq_dim*256 (8x
    # less MXU work; for L2 the query-independent cross term 2*cb1·cb2 is
    # precomputed per vector at encode time into list_consts). Same 8 code
    # bits per subspace; the representable set is the Minkowski sum of two
    # 16-entry codebooks. False: the reference's joint 256-entry codebook
    # (ivf_pq_compute_similarity's LUT), ~8x slower to scan on TPU but a
    # finer quantizer. None (default) = metric-aware auto: split for L2
    # (measured ~12% relative bare-recall cost for a 8x QPS gain),
    # joint for inner_product (the Minkowski coarseness costs
    # IP ranking far more — measured recall@5 0.375 joint vs 0.075 split on
    # tight clusters at 4x compression).
    pq8_split: bool | None = None
    # Per-list residual scale normalization (VERDICT r5 #2, the heavytail
    # remedy; reference counterpart: PER_CLUSTER codebook_gen is the
    # reference's only scale-adaptation lever, ivf_pq_types.hpp:43 — this is
    # the cheaper half of it). Population-skewed data (the repo's heavytail
    # family: lognormal per-cluster residual scales) makes ONE codebook span
    # orders of magnitude of residual norm, so the codewords concentrate on
    # the large-scale clusters and small-scale lists quantize to mush
    # (measured collapse: 0.28 recall @ 1M, r05 heavytail family). True:
    # store one f32 scale per list (RMS residual norm of the training
    # members; global RMS for lists the trainset missed), train the
    # codebooks on UNIT-scale residuals, encode r/s_list, and fold s back in
    # at search inside the LUT (s^2 for L2, s for IP) — exact scoring of
    # ||r - s*decode||^2, ~zero scan-time cost (the fold is one multiply on
    # the per-probe LUT). Composes with either codebook_kind.
    residual_scale_norm: bool = False
    # Learned rotation (quantization funnel stage a). "opq": alternate
    # codebook-fit / orthogonal-Procrustes updates on rotating mini-batches
    # (Ge et al., CVPR'13 — the same jitted mini-batch EM discipline as the
    # coarse trainer's minibatch mode) and fold the learned R into the
    # index rotation, so search pays nothing beyond the one rotation matmul
    # it already does. "none": the reference behavior (identity/QR per
    # force_random_rotation).
    rotation: str = "none"
    opq_rounds: int = 8  # alternations of fit-codebooks / Procrustes-update
    opq_batch_rows: int = 16384  # rows per rotating OPQ mini-batch
    # Codebook training loss (funnel stage b). "anisotropic": ScaNN-style
    # score-aware weighting (Guo et al., ICML'20) — residual error PARALLEL
    # to the datapoint direction costs eta x the orthogonal error, because
    # parallel error is what perturbs inner-product scores near the top of
    # a ranking. Codebook fit and encode assignment both use the weighted
    # distance; search LUTs are untouched (scores stay exact for whatever
    # code was assigned). Pays on IP workloads; needs a joint codebook
    # (incompatible with the nibble-split pq8 trainer).
    codebook_loss: str = "l2"
    # anisotropic parallel/orthogonal weight; 0.0 = auto from the ScaNN
    # threshold rule eta = (d_rot - 1) T^2 / (1 - T^2) at T = 0.2
    anisotropic_eta: float = 0.0
    # Fast-scan pre-filter tier (funnel stage c): per-row bit-packed
    # signatures of the rotated residual scanned AHEAD of the PQ scan, so
    # widen-then-refine becomes binary widen -> PQ rerank -> exact refine.
    #   "1bit" — RaBitQ-style sign bits, ceil(d_rot/8) bytes/row: the hot
    #            scan streams ~4x fewer HBM bytes than pq4 x (pq_dim=d)
    #            unpacked codes.
    #   "4bit" — per-dim 4-bit levels, ceil(d_rot/2) bytes/row: a finer
    #            estimator at pq4-class bytes.
    #   "none" — no tier; SearchParams.funnel_widen must stay 1.
    # Estimated scores pre-filter only — survivors are re-scored exactly
    # (PQ decode), so funnel results at sufficient widen match classic PQ.
    fast_scan: str = "none"


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Reference: ivf_pq::search_params (ivf_pq_types.hpp:108-140).

    The scan computes Σ_s LUT[s, code_s] (ref compute_similarity's smem
    gather, ivf_pq_compute_similarity-inl.cuh) as a one-hot MXU contraction,
    query-major over (query_tile, probe_chunk) tiles: a (T, pc, cap,
    pq_dim*K) operand XLA fuses the codes gather + compare + cast into,
    streamed at HBM rate. It is the only formulation because every
    alternative measured slower or no faster at 1M (r04 PQ scan study) and
    was deleted: an XLA compare+select chain 0.55x, a Pallas
    tpu.dynamic_gather (LUT16) kernel 0.73x, and a probe-major order that
    shared one one-hot per list group neutral (40.7k vs 41.2k QPS).
    """

    n_probes: int = 20
    # "float32" | "bfloat16" | "int8" (ref lut_dtype, ivf_pq_types.hpp:122 —
    # the fp8-class smem LUT maps to bf16/int8 here; int8 quantizes per
    # (query, probe) with a symmetric scale and accumulates in int32 on the
    # MXU's int8 path, halving LUT operand bytes again vs bf16)
    lut_dtype: str = "float32"
    # candidate top-k implementation for the scan's per-chunk + final-merge
    # selects (matrix/select_k.py select_k_impl):
    #   "auto"   — the measured dispatch rule (streaming Pallas selector on
    #              TPU for f32 rows >= 65536 cols, k <= 256 since r06;
    #              lax.top_k otherwise). The k <= 256 reach is what routes
    #              CAGRA's build-chunk k=gpu_top_k+1 select through the
    #              wide selector when its shapes qualify.
    #   "xla"    — force lax.top_k (the r01-r05 behavior).
    #   "pallas" — force the Pallas selector (f32 scores only); the A/B
    #              lever bench/cagra_build_select_ab.py sweeps at the CAGRA
    #              build-chunk shapes, whose per-chunk widths sit BELOW the
    #              65536-column auto threshold — the driver measurement
    #              decides whether auto's wide-k threshold should drop.
    # The coarse cluster select (k = n_probes, n_lists cols) always stays
    # on lax.top_k — never in the wide regime.
    select_impl: str = "auto"
    # Quantization-funnel width (first-class tuned knob, like refine_ratio):
    # > 1 routes search through the fast-scan tier — per probe chunk the
    # bit-packed signatures are scanned first and only the best
    # funnel_widen * k candidates reach the exact PQ decode-and-rerank; the
    # candidate merges stay on the one select_k dispatch with the shared
    # -1/±inf sentinel. Requires an index built with IndexParams.fast_scan.
    # 1 (default) = the classic full PQ scan, bit-for-bit unchanged.
    funnel_widen: int = 1


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IvfPqIndex:
    """Reference: ivf_pq::index (ivf_pq_types.hpp:172-300)."""

    centers: jax.Array  # (n_lists, d) f32 coarse centers
    centers_rot: jax.Array  # (n_lists, d_rot) f32 — rotated centers
    rotation: jax.Array  # (d_rot, d) f32 orthonormal
    codebooks: jax.Array  # per_subspace: (pq_dim, K, pq_len); per_cluster: (n_lists, K, pq_len); K = 2**bits, or 2*16 when pq_split
    list_codes: jax.Array  # (n_lists, capacity, pq_dim) uint8
    list_ids: jax.Array  # (n_lists, capacity) int32, -1 padding
    list_sizes: jax.Array  # (n_lists,) int32
    # (n_lists, capacity) f32 per-vector scan constant for pq_split L2
    # (sum_s 2*cb1[s,hi_s]·cb2[s,lo_s]); (n_lists, 0) otherwise
    list_consts: jax.Array = None
    # (n_lists,) f32 per-list residual scales (IndexParams.residual_scale
    # _norm); (0,) = normalization disabled. Codes encode r/s_list; search
    # folds s_list back into the LUT, so scores stay exact ||r - s*decode||^2
    list_scales: jax.Array = None
    # (n_lists, capacity, sig_words) uint8 bit-packed fast-scan signatures
    # (IndexParams.fast_scan); (n_lists, 0, 0) = no tier. 1bit packs sign
    # bits of the raw rotated residual 8/byte; 4bit packs per-dim levels
    # 2/byte (lo nibble = even dim)
    list_sig: jax.Array = None
    # (n_lists,) f32 per-list signature decode scales (mean |r_j| for 1bit,
    # per-dim RMS for 4bit); (0,) = no tier
    sig_scales: jax.Array = None
    metric: DistanceType = DistanceType.L2Expanded
    codebook_kind: str = "per_subspace"
    pq_bits: int = 8
    # build-time capacity policy, inherited by extend()
    split_factor: float = 1.3
    # True: codes are hi/lo nibble pairs into two 16-entry stage codebooks
    # (codebooks[..., :16, :] and [..., 16:, :]); see IndexParams.pq8_split
    pq_split: bool = False
    # what the ingested dataset WAS (reference: the ivf_pq int8_t/uint8_t
    # instantiations, cpp/src/neighbors/ivf_pq_build_*.cu): "float32"
    # (float data), "int8" (signed bytes), "uint8" (bytes ingested shifted
    # by -128 into the s8 domain — queries shift the same way at search;
    # L2 is shift-invariant). The stored representation is PQ codes either
    # way; data_kind governs what extend() accepts and how search()
    # coerces queries, so a byte index never silently mixes domains.
    data_kind: str = "float32"
    # quantization-funnel codec provenance (raft_tpu/13 codec record):
    # rotation_kind "none"|"opq"; codebook_loss "l2"|"anisotropic" (encode
    # assignment re-derives the auto eta from d_rot); fast_scan
    # "none"|"1bit"|"4bit" (must agree with the list_sig shape)
    rotation_kind: str = "none"
    codebook_loss: str = "l2"
    fast_scan: str = "none"
    # pinned operating point (raft_tpu.tune decision dict; None = untuned):
    # consulted by batched_searcher when no explicit params are given,
    # persisted by save/load (raft_tpu/9). NOT part of the pytree (same
    # contract as cagra's seed_pool_hint): tree round trips drop it back
    # to None — defaults, never an error.
    tuned: dict | None = None

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def rot_dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def pq_dim(self) -> int:
        return self.list_codes.shape[2]

    @property
    def pq_len(self) -> int:
        return self.rot_dim // self.pq_dim

    @property
    def capacity(self) -> int:
        return self.list_codes.shape[1]

    @property
    def size(self) -> int:
        """Total stored vectors. Computed on host so it stays concrete even
        when an enclosing jit trace is active (e.g. a user wrapping search()
        in jax.jit captures the index as a closure constant — staging the sum
        would make int() fail on a tracer). Unavailable when the index itself
        is a traced jit argument."""
        import numpy as np

        return int(np.asarray(jax.device_get(self.list_sizes)).sum())

    def __post_init__(self):
        if self.list_consts is None:
            self.list_consts = jnp.zeros((self.list_codes.shape[0], 0), jnp.float32)
        if self.list_scales is None:
            self.list_scales = jnp.zeros((0,), jnp.float32)
        if self.list_sig is None:
            self.list_sig = jnp.zeros((self.list_codes.shape[0], 0, 0),
                                      jnp.uint8)
        if self.sig_scales is None:
            self.sig_scales = jnp.zeros((0,), jnp.float32)

    @property
    def scale_normed(self) -> bool:
        """True when codes encode per-list-normalized residuals (shape-level
        flag, so it stays concrete inside jit traces)."""
        return self.list_scales.shape[0] > 0

    @property
    def has_fast_scan(self) -> bool:
        """True when the index carries a bit-packed fast-scan tier (shape-
        level flag — the sig word width is static inside jit traces)."""
        return self.list_sig.shape[-1] > 0

    def tree_flatten(self):
        children = (self.centers, self.centers_rot, self.rotation, self.codebooks,
                    self.list_codes, self.list_ids, self.list_sizes,
                    self.list_consts, self.list_scales, self.list_sig,
                    self.sig_scales)
        return children, (self.metric, self.codebook_kind, self.pq_bits,
                          self.split_factor, self.pq_split, self.data_kind,
                          self.rotation_kind, self.codebook_loss,
                          self.fast_scan)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kind = aux[5] if len(aux) > 5 else "float32"
        # pre-funnel pytrees (9 children, 6 aux) unflatten to a codec-free
        # index — the same back-compat contract as data_kind above
        extra = aux[6:9] if len(aux) > 8 else ("none", "l2", "none")
        return cls(*children, metric=aux[0], codebook_kind=aux[1], pq_bits=aux[2],
                   split_factor=aux[3], pq_split=aux[4], data_kind=kind,
                   rotation_kind=extra[0], codebook_loss=extra[1],
                   fast_scan=extra[2])


def _resolve_pq_ingest(x, mt: DistanceType):
    """int8/uint8 dataset ingestion (reference: the ivf_pq int8_t/uint8_t
    instantiations, cpp/src/neighbors/ivf_pq_build_*.cu — BigANN-class byte
    data is PQ's home regime). Returns (data_kind, f32 working view): uint8
    shifts by -128 into the s8 domain first (L2 is shift-invariant; queries
    shift the same way at search), and all PQ math — coarse k-means,
    residuals, codebook training, encoding — runs in f32, where every 8-bit
    integer is exactly representable. Shared by the single-chip and
    distributed (parallel/ivf.build_pq) builds so both ingest identically."""
    int_dtypes = (jnp.dtype(jnp.int8), jnp.dtype(jnp.uint8))
    if x.dtype not in int_dtypes:
        return "float32", x
    # uint8 under IP is NOT shift-invariant and the per-vector sum
    # correction is not stored (same contract as ivf_flat int8 storage)
    expects(mt != DistanceType.InnerProduct or x.dtype == jnp.int8,
            "uint8 + inner_product is unsupported for ivf_pq byte ingestion "
            "(the -128 shift changes inner products); cast to float32")
    from .brute_force import _as_signed

    return str(x.dtype), _as_signed(x).astype(jnp.float32)


def _stream_ingest(data_kind: str):
    """Device-side conversion raw chunk -> the build's f32 working domain —
    the streamed twin of :func:`_resolve_pq_ingest`'s second return. Float
    data passes through untouched (exactly as in-core, where the working
    view IS the ingested array); bytes shift + upcast. Elementwise, so it
    commutes with the trainset row gather (bit-equality contract)."""
    if data_kind in ("int8", "uint8"):
        from .brute_force import _as_signed

        return lambda v: _as_signed(v).astype(jnp.float32)
    return lambda v: v


def _default_pq_dim(d: int, pq_bits: int = 4) -> int:
    """Bits-aware variant of the reference heuristic (ivf_pq_types.hpp:81,
    ~d/2 at its default 8 bits): the auto pq_dim keeps CODE BYTES equal to
    the reference default — d/2 dims at 8 bits and d dims at 4 bits are both
    d/2 bytes per vector, so switching the TPU-preferred pq_bits=4 default
    does not silently halve quantization budget."""
    pq = max((d * 8) // (2 * pq_bits), 1)
    if pq >= 8:
        pq = (pq // 8) * 8
    return min(pq, d)


def _make_rotation(key, d_rot: int, d: int, force_random: bool):
    """Reference: make_rotation_matrix (ivf_pq_build.cuh:121) — random
    orthonormal via QR when forced or when d_rot != d; else identity(-pad)."""
    if not force_random and d_rot == d:
        return jnp.eye(d, dtype=jnp.float32)
    if not force_random:
        eye = jnp.zeros((d_rot, d), jnp.float32)
        return eye.at[jnp.arange(min(d_rot, d)), jnp.arange(min(d_rot, d))].set(1.0)
    g = jax.random.normal(key, (max(d_rot, d), max(d_rot, d)), jnp.float32)
    q, _ = jnp.linalg.qr(g)
    return q[:d_rot, :d]


@functools.partial(jax.jit, static_argnames=("n_codes", "n_iters"))
def _train_codebooks_batched(subvecs, key, n_codes: int, n_iters: int):
    """Train all codebooks simultaneously: subvecs (B, n, pq_len) → codebooks
    (B, n_codes, pq_len). One vmapped mini-batch EM — every subspace (or
    cluster) trains in parallel on the MXU (ref: train_per_subset :343 runs a
    stream loop; TPU batches it instead)."""

    def one(sv, k):
        n = sv.shape[0]
        # small pools (n < n_codes) seed with replacement — duplicates split
        # during EM; matches the reference's tolerance for tiny trainsets
        init_idx = jax.random.choice(k, n, (n_codes,), replace=n < n_codes)
        centers = jnp.take(sv, init_idx, axis=0)

        def body(i, c):
            d2 = (
                jnp.sum(c * c, axis=1)[None, :]
                - 2.0 * sv @ c.T
            )  # (n, n_codes)
            labels = jnp.argmin(d2, axis=1)
            onehot = jax.nn.one_hot(labels, n_codes, dtype=jnp.float32, axis=0)
            sums = onehot @ sv
            counts = jnp.sum(onehot, axis=1)
            return jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], c)

        return lax.fori_loop(0, n_iters, body, centers)

    keys = jax.random.split(key, subvecs.shape[0])
    return jax.vmap(one)(subvecs.astype(jnp.float32), keys)


@functools.partial(jax.jit, static_argnames=("n_iters", "refine_rounds"))
def _train_split_codebooks(subvecs, key, n_iters: int, refine_rounds: int = 3):
    """Two-stage 4+4-bit residual codebooks (pq8_split): stage 1 is 16-means
    over the subvectors, stage 2 is 16-means over the stage-1 residuals
    (classic residual VQ), then ``refine_rounds`` of alternating
    re-fitting under the JOINT encoding (additive-quantization style: encode
    against the composed 256-codeword sum, re-fit each stage to the residual
    of the other) — recovers part of the expressiveness gap vs a free
    256-entry codebook. Returns (B, 32, pq_len): stage-1 entries in
    [..., :16, :], stage-2 in [..., 16:, :]."""
    k1, k2 = jax.random.split(key)
    sv = subvecs.astype(jnp.float32)
    cb1 = _train_codebooks_batched(sv, k1, 16, n_iters)

    def stage1_residual(s, c):
        d2 = jnp.sum(c * c, axis=1)[None, :] - 2.0 * s @ c.T
        return s - jnp.take(c, jnp.argmin(d2, axis=1), axis=0)

    resid2 = jax.vmap(stage1_residual)(sv, cb1)
    cb2 = _train_codebooks_batched(resid2, k2, 16, n_iters)

    def refine_one(s, c1, c2):
        def round_(carry, _):
            c1, c2 = carry
            comp = (c1[:, None, :] + c2[None, :, :]).reshape(256, c1.shape[-1])
            d2 = jnp.sum(comp * comp, axis=1)[None, :] - 2.0 * s @ comp.T
            code = jnp.argmin(d2, axis=1)
            hi, lo = code // 16, code % 16
            oh_hi = jax.nn.one_hot(hi, 16, dtype=jnp.float32, axis=0)  # (16, n)
            oh_lo = jax.nn.one_hot(lo, 16, dtype=jnp.float32, axis=0)
            r1 = s - jnp.take(c2, lo, axis=0)
            c1n = jnp.where(
                (oh_hi.sum(1) > 0)[:, None],
                (oh_hi @ r1) / jnp.maximum(oh_hi.sum(1), 1.0)[:, None], c1)
            r2 = s - jnp.take(c1n, hi, axis=0)
            c2n = jnp.where(
                (oh_lo.sum(1) > 0)[:, None],
                (oh_lo @ r2) / jnp.maximum(oh_lo.sum(1), 1.0)[:, None], c2)
            return (c1n, c2n), None

        (c1, c2), _ = lax.scan(round_, (c1, c2), None, length=refine_rounds)
        return jnp.concatenate([c1, c2], axis=0)

    return jax.vmap(refine_one)(sv, cb1, cb2)


def _composed_codebooks(codebooks):
    """Expand split codebooks (B, 32, L) to the effective 256-entry codebook
    (B, 256, L); entry hi*16+lo = cb1[hi] + cb2[lo] (row-major flatten keeps
    the hi/lo nibble order consistent with the scan)."""
    cb = codebooks.astype(jnp.float32)
    cb1, cb2 = cb[:, :16, :], cb[:, 16:, :]
    comp = cb1[:, :, None, :] + cb2[:, None, :, :]
    return comp.reshape(cb.shape[0], 256, cb.shape[-1])


def _per_cluster_gain(resid, labels, codebooks, split: bool, key, n_iters: int,
                      n_trial: int = 8, member_cap: int = 2048):
    """Trial-train per-cluster codebooks on the ``n_trial`` largest clusters
    and return err_per_cluster / err_per_subspace (< 1 = per-cluster
    quantizes better). The empirical basis of the codebook-kind auto
    heuristic (reference counterpart: the PER_CLUSTER codebook_gen mode,
    ivf_pq_build.cuh:424 train_per_cluster — the reference leaves the choice
    entirely to the caller)."""
    import numpy as np

    n, pq_dim, pq_len = resid.shape
    # split codebooks are compared COMPOSED (the effective 256-entry Minkowski
    # sum), not by their 16-entry stage-1 proxy — the auto decision must weigh
    # what search actually scores against (ADVICE r3)
    cb_ps = _composed_codebooks(codebooks) if split else codebooks  # (pq_dim, K, L)
    k_codes = cb_ps.shape[1]
    # ONE host round-trip for the labels: counts and the per-cluster row
    # pools both derive from the same materialized array (two separate
    # np.asarray(labels) syncs here used to stall the dispatch queue twice
    # per auto build)
    lab_h = np.asarray(labels)
    counts = np.bincount(lab_h, minlength=1)
    trial = np.argsort(counts)[::-1][:n_trial]
    trial = trial[counts[trial] > 0]
    pools = []
    cap = min(member_cap, int(counts[trial].max()))
    for c in trial:
        rows = np.nonzero(lab_h == c)[0]
        rows = rows[np.arange(cap) % len(rows)]  # wraparound to fixed size
        pools.append(rows)
    pools = jnp.asarray(np.stack(pools))  # (C, cap)
    rv = jnp.take(resid, pools, axis=0)  # (C, cap, pq_dim, L)

    # per-subspace error: each subvector against its own subspace codebook
    def ps_err(r):  # (cap, pq_dim, L)
        d = (jnp.sum(cb_ps * cb_ps, axis=-1)[None]
             - 2.0 * jnp.einsum("nsl,skl->nsk", r, cb_ps))
        return jnp.sum(jnp.min(d, axis=-1) + jnp.sum(r * r, axis=-1))

    err_ps = jnp.sum(jax.vmap(ps_err)(rv))

    # trial per-cluster codebooks: pool subvectors across subspaces per
    # cluster; split trials train the same two-stage quantizer and compose,
    # so both error terms measure 256-entry effective codebooks
    flat = rv.reshape(len(trial), cap * pq_dim, pq_len)
    if split:
        cb_pc = _composed_codebooks(_train_split_codebooks(flat, key, n_iters))
    else:
        cb_pc = _train_codebooks_batched(flat, key, k_codes, n_iters)

    def pc_err(v, c):  # (cap*pq_dim, L), (K, L)
        d = (jnp.sum(c * c, axis=-1)[None]
             - 2.0 * v @ c.T)
        return jnp.sum(jnp.min(d, axis=-1) + jnp.sum(v * v, axis=-1))

    err_pc = jnp.sum(jax.vmap(pc_err)(flat, cb_pc))
    return float(err_pc) / max(float(err_ps), 1e-30)


@functools.partial(jax.jit, static_argnames=("n_lists",))
def _per_list_residual_scales(resid, labels, n_lists: int):
    """(n_lists,) RMS residual scale per list from the training residuals:
    s_l = sqrt(mean ||r||^2 / d_rot) over l's members; lists the trainset
    missed fall back to the global RMS (a fresh list has no scale evidence,
    and 1.0 would be arbitrary on data whose scales are nowhere near 1).
    Accumulation is a chunked one-hot matmul, not a scatter-add — XLA
    serializes scatters on TPU (the _reverse_merge lesson)."""
    n, pq_dim, pq_len = resid.shape
    rn2 = jnp.sum(resid.reshape(n, -1) ** 2, axis=1)
    blk = min(16384, max(round_up(n, 8), 8))
    num = -(-n // blk)
    rp = jnp.pad(rn2, (0, num * blk - n))
    # padding rows carry label n_lists — summed into a discard bucket
    lp = jnp.pad(labels.astype(jnp.int32), (0, num * blk - n),
                 constant_values=n_lists)

    def body(args):
        r, l = args
        oh = jax.nn.one_hot(l, n_lists + 1, dtype=jnp.float32, axis=0)
        return oh @ r, jnp.sum(oh, axis=1)

    sums, counts = lax.map(body, (rp.reshape(num, blk), lp.reshape(num, blk)))
    s = jnp.sum(sums, axis=0)[:n_lists]
    c = jnp.sum(counts, axis=0)[:n_lists]
    gmean = jnp.sum(rn2) / jnp.maximum(n, 1)
    msq = jnp.where(c > 0, s / jnp.maximum(c, 1.0), gmean)
    d_rot = pq_dim * pq_len
    return jnp.sqrt(jnp.maximum(msq / d_rot, 1e-24))


def _default_aniso_eta(d_rot: int, t: float = 0.2) -> float:
    """ScaNN's threshold rule (Guo et al., ICML'20 §3.2): weight parallel
    residual error eta = (d - 1) T^2 / (1 - T^2) at relative score
    threshold T — errors along the datapoint direction perturb the
    inner-product ranking ~eta times as much as orthogonal ones."""
    return max((d_rot - 1) * t * t / (1.0 - t * t), 1.0)


@functools.partial(jax.jit, static_argnames=("n_codes", "n_iters", "eta"))
def _train_codebooks_aniso(subvecs, key, n_codes: int, n_iters: int,
                           eta: float):
    """Anisotropic weighted EM (IndexParams.codebook_loss="anisotropic"):
    subvecs (B, n, pq_len) → codebooks (B, n_codes, pq_len), same batched
    layout as :func:`_train_codebooks_batched`. Assignment minimizes
    ||x - c||^2 + (eta - 1) <u, x - c>^2 with u = x/||x|| (parallel error
    weighted eta x); the centroid update solves the per-codeword normal
    equations (count I + (eta-1) Σ u u^T) c = eta Σ x — a (n_codes,
    pq_len, pq_len) batched solve, tiny at PQ subvector widths."""

    em1 = eta - 1.0

    def one(sv, k):
        n, L = sv.shape
        norm = jnp.sqrt(jnp.maximum(jnp.sum(sv * sv, axis=1), 1e-30))
        u = sv / norm[:, None]
        init_idx = jax.random.choice(k, n, (n_codes,), replace=n < n_codes)
        centers = jnp.take(sv, init_idx, axis=0)
        eye = jnp.eye(L, dtype=jnp.float32)

        def body(i, c):
            d2 = jnp.sum(c * c, axis=1)[None, :] - 2.0 * sv @ c.T
            # <u, x - c> = ||x|| - <u, c> (u is x's own direction)
            upar = norm[:, None] - u @ c.T
            labels = jnp.argmin(d2 + em1 * upar * upar, axis=1)
            oh = jax.nn.one_hot(labels, n_codes, dtype=jnp.float32, axis=0)
            counts = jnp.sum(oh, axis=1)
            suu = jnp.einsum("kn,nl,nm->klm", oh, u, u)
            a = counts[:, None, None] * eye[None] + em1 * suu
            a = a + 1e-6 * eye[None]
            b = eta * (oh @ sv)
            sol = jnp.linalg.solve(a, b[..., None])[..., 0]
            return jnp.where(counts[:, None] > 0, sol, c)

        return lax.fori_loop(0, n_iters, body, centers)

    keys = jax.random.split(key, subvecs.shape[0])
    return jax.vmap(one)(subvecs.astype(jnp.float32), keys)


@functools.partial(jax.jit, static_argnames=("pq_dim", "n_codes", "n_iters",
                                             "rounds", "batch"))
def _train_opq_rotation(resid_flat, key, pq_dim: int, n_codes: int,
                        n_iters: int, rounds: int, batch: int):
    """OPQ rotation (Ge et al., CVPR'13 Alg. 1), mini-batched: alternate
    (1) fit per-subspace codebooks on a rotating batch of rotated residuals
    (the same jitted mini-batch EM as the coarse trainer's minibatch mode),
    (2) solve the orthogonal Procrustes problem min_R ||X R^T - Y||_F over
    the batch's reconstructions Y (R = U V^T from SVD(Y^T X)). Returns the
    (d_rot, d_rot) learned rotation to fold into the index rotation —
    search pays nothing beyond the rotation matmul it already does."""
    n, d_rot = resid_flat.shape
    pq_len = d_rot // pq_dim
    kp, kr = jax.random.split(key)
    # one shuffle, then rounds walk it in rotating windows — every round
    # sees fresh rows until the trainset wraps (the coarse minibatch-EM
    # batching discipline)
    perm = jax.random.permutation(kp, resid_flat.astype(jnp.float32))
    rot = jnp.eye(d_rot, dtype=jnp.float32)
    keys = jax.random.split(kr, rounds)
    for i in range(rounds):
        start = (i * batch) % max(n - batch + 1, 1)
        xb = lax.dynamic_slice_in_dim(perm, start, batch, axis=0)
        xr = xb @ rot.T
        sub = jnp.moveaxis(xr.reshape(batch, pq_dim, pq_len), 1, 0)
        cb = _train_codebooks_batched(sub, keys[i], n_codes, n_iters)
        cb_n2 = jnp.sum(cb * cb, axis=-1)
        dots = jnp.einsum("snl,skl->snk", sub, cb,
                          precision=lax.Precision.HIGHEST)
        code = jnp.argmin(cb_n2[:, None, :] - 2.0 * dots, axis=-1)
        recon = jnp.take_along_axis(cb, code[..., None], axis=1)
        y = jnp.moveaxis(recon, 0, 1).reshape(batch, d_rot)
        u, _, vt = jnp.linalg.svd(y.T @ xb, full_matrices=True)
        rot = u @ vt
    return rot


def _sig_words(d_rot: int, fast_scan: str) -> int:
    """Packed signature bytes per row for a fast-scan mode."""
    if fast_scan == "1bit":
        return -(-d_rot // 8)
    if fast_scan == "4bit":
        return -(-d_rot // 2)
    return 0


@functools.partial(jax.jit, static_argnames=("n_lists", "fast_scan"))
def _per_list_sig_scales(resid_flat, labels, n_lists: int, fast_scan: str):
    """(n_lists,) decode scale per list for the signature estimator, from
    the RAW rotated training residuals: the least-squares fit of r ≈ s·σ
    is s = mean|r_j| for ±1 signs (1bit); 4bit levels span ±2 per-dim RMS,
    so s = sqrt(mean r_j^2). Lists the trainset missed fall back to the
    global mean (same contract as _per_list_residual_scales); accumulation
    is the same chunked one-hot matmul (no scatter-adds on TPU)."""
    n, d_rot = resid_flat.shape
    red = (jnp.sum(jnp.abs(resid_flat), axis=1) if fast_scan == "1bit"
           else jnp.sum(resid_flat * resid_flat, axis=1))
    blk = min(16384, max(round_up(n, 8), 8))
    num = -(-n // blk)
    rp = jnp.pad(red, (0, num * blk - n))
    lp = jnp.pad(labels.astype(jnp.int32), (0, num * blk - n),
                 constant_values=n_lists)

    def body(args):
        r, l = args
        oh = jax.nn.one_hot(l, n_lists + 1, dtype=jnp.float32, axis=0)
        return oh @ r, jnp.sum(oh, axis=1)

    sums, counts = lax.map(body, (rp.reshape(num, blk), lp.reshape(num, blk)))
    s = jnp.sum(sums, axis=0)[:n_lists]
    c = jnp.sum(counts, axis=0)[:n_lists]
    gmean = jnp.sum(red) / jnp.maximum(n, 1)
    per_dim = jnp.where(c > 0, s / jnp.maximum(c, 1.0), gmean) / d_rot
    per_dim = jnp.maximum(per_dim, 1e-24)
    return per_dim if fast_scan == "1bit" else jnp.sqrt(per_dim)


@functools.partial(jax.jit, static_argnames=("fast_scan",))
def _encode_sig(resid_flat, scales, fast_scan: str):
    """Bit-pack fast-scan signatures: resid_flat (n, d_rot) RAW rotated
    residuals + per-row decode scales (n,) → (n, sig_words) uint8.
    1bit: sign bits, dim 8w+b in bit b of byte w (scale-free). 4bit:
    levels round((r/s)/step) clipped to [0, 15] around mid-level 7.5
    (span ±2 RMS), even dim in the lo nibble. Padding dims pack as zero
    bits — the query-side LUT zeroes their contribution."""
    n, d_rot = resid_flat.shape
    r = resid_flat.astype(jnp.float32)
    if fast_scan == "1bit":
        w = -(-d_rot // 8)
        bits = (r > 0).astype(jnp.uint8)
        bits = jnp.pad(bits, ((0, 0), (0, w * 8 - d_rot)))
        weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
        return jnp.sum(bits.reshape(n, w, 8) * weights[None, None, :],
                       axis=-1, dtype=jnp.uint8)
    w = -(-d_rot // 2)
    step = 4.0 / 15.0
    lev = jnp.clip(jnp.round(r / (scales[:, None] * step) + 7.5), 0, 15)
    lev = jnp.pad(lev, ((0, 0), (0, w * 2 - d_rot))).astype(jnp.uint8)
    lo, hi = lev[:, 0::2], lev[:, 1::2]
    return lo | (hi << 4)


def _sig_nibble_lut(r, fast_scan: str, sig_words: int):
    """Per-(query, probe) nibble LUT for the signature scan: r (..., d_rot)
    raw rotated residuals → (..., sig_words, 32) where [..., :16] scores
    the HI nibble of each packed byte and [..., 16:] the LO nibble — the
    exact layout of the nibble-split one-hot contraction, so the fast-scan
    tier reuses the pq8_split scan machinery unchanged. Entry value =
    Σ_bits r_dim · level(bit), i.e. the contraction computes <r, σ> (1bit,
    σ ∈ {-1,1}) or <r, lev> (4bit) exactly; padding dims contribute 0."""
    d_rot = r.shape[-1]
    if fast_scan == "1bit":
        pad = sig_words * 8 - d_rot
        # padded query dims are ZERO, so their ±1 level contributes 0 —
        # padding needs no masking on either side
        rp = jnp.pad(r, [(0, 0)] * (r.ndim - 1) + [(0, pad)])
        r8 = rp.reshape(*r.shape[:-1], sig_words, 8)
        v = jnp.arange(16, dtype=jnp.int32)
        b = jnp.arange(4, dtype=jnp.int32)
        pm = (2 * ((v[:, None] >> b[None, :]) & 1) - 1).astype(jnp.float32)
        lut_lo = jnp.einsum("...wb,vb->...wv", r8[..., 0:4], pm)
        lut_hi = jnp.einsum("...wb,vb->...wv", r8[..., 4:8], pm)
        return jnp.concatenate([lut_hi, lut_lo], axis=-1)
    # 4bit: byte w covers dims 2w (lo nibble) and 2w+1 (hi nibble)
    pad = sig_words * 2 - d_rot
    rp = jnp.pad(r, [(0, 0)] * (r.ndim - 1) + [(0, pad)])
    r2 = rp.reshape(*r.shape[:-1], sig_words, 2)
    step = 4.0 / 15.0
    levels = (jnp.arange(16, dtype=jnp.float32) - 7.5) * step  # (16,)
    lut_lo = r2[..., 0:1] * levels
    lut_hi = r2[..., 1:2] * levels
    return jnp.concatenate([lut_hi, lut_lo], axis=-1)


@functools.partial(jax.jit, static_argnames=("per_cluster",))
def _pq_cross_consts(codes, codebooks, labels, per_cluster: bool):
    """Per-vector scan constant for split L2 scoring: sum_s 2*cb1[s,hi_s]·
    cb2[s,lo_s] — the cross term of ||cb1+cb2||^2 that the separated hi/lo
    LUTs cannot carry. Query-independent, so it is paid once here (encode
    time) instead of per (query, probe) at search."""
    cb = codebooks.astype(jnp.float32)
    X = 2.0 * jnp.einsum("bhl,bgl->bhg", cb[:, :16, :], cb[:, 16:, :])
    Xf = X.reshape(-1)  # flat index b*256 + hi*16 + lo = b*256 + code
    n, pq_dim = codes.shape
    blk = min(65536, max(round_up(n, 8), 8))
    num = -(-n // blk)
    cp = jnp.pad(codes, ((0, num * blk - n), (0, 0))).astype(jnp.int32)
    ct = cp.reshape(num, blk, pq_dim)
    if per_cluster:
        lp = jnp.pad(labels, (0, num * blk - n)).astype(jnp.int32)
        lt = lp.reshape(num, blk)

        def body(args):
            cb_, lb_ = args
            return jnp.sum(jnp.take(Xf, lb_[:, None] * 256 + cb_, axis=0), axis=1)

        out = lax.map(body, (ct, lt))
    else:
        offs = jnp.arange(pq_dim, dtype=jnp.int32) * 256

        def body(cb_):
            return jnp.sum(jnp.take(Xf, cb_ + offs[None, :], axis=0), axis=1)

        out = lax.map(body, ct)
    return out.reshape(num * blk)[:n]


@functools.partial(jax.jit, static_argnames=("per_cluster", "tile",
                                             "aniso_eta"))
def _encode(residuals_rot, codebooks, labels, per_cluster: bool, tile: int,
            aniso_eta: float = 0.0):
    """Nearest codebook entry per subspace, as tiled GEMMs.

    residuals_rot: (n, pq_dim, pq_len). codebooks: (pq_dim, K, L) for
    per_subspace, (n_lists, K, L) for per_cluster (selected via labels).
    Computes argmin over ‖r‖²-free scores ‖c‖² - 2·r·c (the search-LUT
    expansion) in row tiles so the (tile, pq_dim, K) block bounds memory.
    ``aniso_eta > 0`` switches to the score-aware anisotropic assignment
    (codebook_loss="anisotropic"): + (eta-1)·<u, r-c>² with u = r/‖r‖,
    matching the training loss. Returns (n, pq_dim) uint8.
    """
    n = residuals_rot.shape[0]
    cb = codebooks.astype(jnp.float32)
    cb_n2 = jnp.sum(cb * cb, axis=-1)  # (B, K)
    num = -(-n // tile)
    pad = num * tile - n
    r = jnp.pad(residuals_rot, ((0, pad), (0, 0), (0, 0))) if pad else residuals_rot
    lb = jnp.pad(labels, (0, pad)) if pad else labels
    rt = r.reshape(num, tile, *residuals_rot.shape[1:])
    lt = lb.reshape(num, tile)

    def body(args):
        rb, lbl = args  # (t, pq_dim, L), (t,)
        if per_cluster:
            cbl = cb[lbl]  # (t, K, L)
            dots = jnp.einsum("tsl,tkl->tsk", rb, cbl, precision=lax.Precision.HIGHEST)
            d2 = cb_n2[lbl][:, None, :] - 2.0 * dots
        else:
            dots = jnp.einsum("tsl,skl->tsk", rb, cb, precision=lax.Precision.HIGHEST)
            d2 = cb_n2[None] - 2.0 * dots
        if aniso_eta > 0.0:
            nrm = jnp.sqrt(jnp.maximum(jnp.sum(rb * rb, axis=-1), 1e-30))
            u = rb / nrm[..., None]  # (t, pq_dim, L)
            if per_cluster:
                ucb = jnp.einsum("tsl,tkl->tsk", u, cbl,
                                 precision=lax.Precision.HIGHEST)
            else:
                ucb = jnp.einsum("tsl,skl->tsk", u, cb,
                                 precision=lax.Precision.HIGHEST)
            # <u, r - c> = ‖r‖ - <u, c>; the ‖r‖²-free d2 gains the full
            # parallel-error surcharge (the dropped ‖r‖² is code-constant)
            d2 = d2 + (aniso_eta - 1.0) * (nrm[..., None] - ucb) ** 2
        return jnp.argmin(d2, axis=-1).astype(jnp.uint8)

    codes = lax.map(body, (rt, lt))
    return codes.reshape(num * tile, -1)[:n]


def _fill_code_lists(codes, ids, labels, n_lists: int, capacity: int,
                     consts=None, sig=None):
    """Scatter codes into padded lists (shared ivf::list scheme). ``sig``
    (n, sig_words) scatters the fast-scan tier alongside the codes so the
    two layouts can never disagree on slot positions."""
    n, pq_dim = codes.shape
    pos, counts = list_positions(labels, n_lists)
    buf = jnp.zeros((n_lists, capacity, pq_dim), jnp.uint8)
    idbuf = jnp.full((n_lists, capacity), -1, jnp.int32)
    buf = buf.at[labels, pos].set(codes)
    idbuf = idbuf.at[labels, pos].set(ids.astype(jnp.int32))
    if consts is None:
        cbuf = jnp.zeros((n_lists, 0), jnp.float32)
    else:
        cbuf = jnp.zeros((n_lists, capacity), jnp.float32).at[labels, pos].set(consts)
    if sig is None:
        sbuf = jnp.zeros((n_lists, 0, 0), jnp.uint8)
    else:
        sbuf = jnp.zeros((n_lists, capacity, sig.shape[1]), jnp.uint8
                         ).at[labels, pos].set(sig)
    return buf, idbuf, counts.astype(jnp.int32), cbuf, sbuf


@instrument("ivf_pq.build",
            items=lambda a, kw: nrows(a[1] if len(a) > 1 else kw["dataset"]),
            labels=lambda a, kw: {
                "dtype": dtype_of(a[1] if len(a) > 1 else kw["dataset"]),
                "n_lists": (a[0] if a else kw["params"]).n_lists,
            })
def build(params: IndexParams, dataset, res: Resources | None = None) -> IvfPqIndex:
    """Build the index (reference: ivf_pq::build, ivf_pq-inl.cuh:270; call
    stack SURVEY.md §3.B)."""
    res = res or default_resources()
    stream = chunked.is_reader(dataset)
    x = None if stream else jnp.asarray(dataset)
    src = dataset if stream else x
    expects(src.ndim == 2, "dataset must be (n, d)")
    n, d = (int(s) for s in src.shape)
    expects(params.n_lists <= n, "n_lists > n_samples")
    expects(4 <= params.pq_bits <= 8, "pq_bits must be in [4, 8] (ref ivf_pq_types.hpp:68)")
    mt = resolve_metric(params.metric)
    expects(
        mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
               DistanceType.L2Unexpanded, DistanceType.L2SqrtUnexpanded,
               DistanceType.InnerProduct),
        "ivf_pq supports L2 / inner_product metrics, got %s", mt.name,
    )
    expects(params.codebook_kind in ("per_subspace", "per_cluster", "auto"),
            "codebook_kind must be per_subspace|per_cluster|auto")
    expects(params.rotation in ("none", "opq"),
            "rotation must be 'none' or 'opq', got %r", params.rotation)
    expects(params.codebook_loss in ("l2", "anisotropic"),
            "codebook_loss must be 'l2' or 'anisotropic', got %r",
            params.codebook_loss)
    expects(params.fast_scan in ("none", "1bit", "4bit"),
            "fast_scan must be 'none', '1bit' or '4bit', got %r",
            params.fast_scan)

    if stream:
        # dtype-only ingest resolution (same validation, on an empty
        # probe — the corpus never materializes here), then the STREAMED
        # admission: price the chunked build peak against BOTH budgets
        # before the coarse trainer spends anything
        from .ivf_flat import _stream_probe

        data_kind, _ = _resolve_pq_ingest(_stream_probe(dataset.dtype, d),
                                          mt)
        plan_kw = dict(
            dtype=data_kind if data_kind in ("int8", "uint8") else "float32",
            streamed=True, chunk_rows=dataset.chunk_rows)
        obs_mem.gate(
            res,
            lambda: obs_mem.plan("ivf_pq", params, n, d,
                                 **plan_kw)["build_peak_bytes"],
            site="build_stream", detail=f"ivf_pq {n}x{d} ooc",
            host_bytes=lambda: obs_mem.plan("ivf_pq", params, n, d,
                                            **plan_kw)["host_peak_bytes"])
        # the coarse trainer and the trainset gather below see the reader
        # through the build's exact working-domain conversion
        x = chunked.converted(dataset, _stream_ingest(data_kind))
    else:
        data_kind, x = _resolve_pq_ingest(x, mt)
        # memory-budget admission (no-op unless res.memory_budget_bytes is
        # set): refuse BEFORE the coarse trainer spends anything
        obs_mem.gate(res, lambda: obs_mem.plan(
            "ivf_pq", params, n, d)["index_bytes"],
            site="build", detail=f"ivf_pq {n}x{d}")
    pq_dim = params.pq_dim or _default_pq_dim(d, params.pq_bits)
    pq_len = -(-d // pq_dim)
    d_rot = pq_dim * pq_len
    n_codes = 1 << params.pq_bits
    key = as_key(params.seed)

    # 1. coarse quantizer (ref §3.B step 2)
    max_train = max(int(n * params.kmeans_trainset_fraction), params.n_lists)
    train_metric = "inner_product" if mt == DistanceType.InnerProduct else "sqeuclidean"
    kb = KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=train_metric, seed=params.seed,
        max_train_points=min(max_train, n),
        train_mode=params.kmeans_train_mode,
        batch_rows=params.kmeans_batch_rows,
    )
    with tracing.range("ivf_pq.build.coarse_kmeans"):
        centers = kmeans_balanced.fit(kb, x, params.n_lists, res=res)
    if params.add_data_on_build:
        from .ivf_flat import _count_fill_pass

        _count_fill_pass(kb, n)

    # 2. rotation (ref step 3)
    key, kr = jax.random.split(key)
    rotation = _make_rotation(kr, d_rot, d, params.force_random_rotation)
    centers_rot = centers @ rotation.T  # (n_lists, d_rot)

    # 3. residuals of a training subsample (ref steps 4-5 — the reference
    # trains codebooks on the same subsampled trainset as the coarse
    # quantizer, train_per_subset operates on the trainset, not the dataset)
    n_train = min(max_train, n)
    key, ks = jax.random.split(key)
    if n_train < n:
        with tracing.range("ivf_pq.build.trainset"):
            train_idx = jax.random.choice(ks, n, (n_train,), replace=False)
            # take_rows: jnp.take in-core, a host page-gather off the
            # reader streamed — SAME indices, bit-equal rows
            # (core/chunked docstring)
            xt = chunked.take_rows(x, train_idx)
    else:
        xt = chunked.materialize(x) if stream else x
    tile = _choose_tile(n_train, params.n_lists, 1, res.workspace_bytes)
    with tracing.range("ivf_pq.build.residuals"):
        labels = assign_to_lists(xt, centers, mt, tile)
        resid = (xt.astype(jnp.float32) - jnp.take(centers, labels, axis=0)) @ rotation.T
        resid = resid.reshape(n_train, pq_dim, pq_len)
    list_scales = jnp.zeros((0,), jnp.float32)
    if params.residual_scale_norm:
        # per-list scale normalization (see IndexParams docstring): train
        # the codebooks — and the auto per-cluster trial below — on
        # unit-scale residuals; encode/search re-apply s_list exactly
        with tracing.range("ivf_pq.build.residual_scales"):
            list_scales = _per_list_residual_scales(resid, labels,
                                                    params.n_lists)
        resid = resid / jnp.take(list_scales, labels)[:, None, None]

    # 3b. learned rotation (funnel stage a): alternate codebook-fit /
    # Procrustes on rotating mini-batches of the (unit-scale) residual
    # trainset, then FOLD the learned R into the index rotation — the
    # query transform is still one matmul, and the per-list scales stay
    # valid (orthogonal R preserves residual norms)
    if params.rotation == "opq":
        import time as _time

        key, ko = jax.random.split(key)
        split_pref_ = (params.pq8_split if params.pq8_split is not None
                       else mt != DistanceType.InnerProduct)
        opq_codes = 16 if (params.pq_bits == 8 and split_pref_) else n_codes
        batch = min(int(params.opq_batch_rows), n_train)
        t0 = _time.perf_counter()
        with tracing.range("ivf_pq.build.opq"):
            r_opq = _train_opq_rotation(
                resid.reshape(n_train, d_rot), ko, pq_dim, opq_codes,
                min(params.kmeans_n_iters, 10), int(params.opq_rounds),
                batch)
            r_opq = jax.block_until_ready(r_opq)
        if metrics.enabled():
            _quant_opq_seconds().observe(_time.perf_counter() - t0)
        rotation = r_opq @ rotation
        centers_rot = centers @ rotation.T
        resid = (resid.reshape(n_train, d_rot) @ r_opq.T
                 ).reshape(n_train, pq_dim, pq_len)

    # 4. codebooks (ref train_per_subset :343 / train_per_cluster :424)
    key, kc = jax.random.split(key)
    split_pref = (params.pq8_split if params.pq8_split is not None
                  else mt != DistanceType.InnerProduct)
    split = params.pq_bits == 8 and split_pref
    aniso_eta = 0.0
    if params.codebook_loss == "anisotropic":
        expects(not split, "codebook_loss='anisotropic' needs a joint "
                "codebook — nibble-split pq8 trains a two-stage residual "
                "quantizer (set pq8_split=False or pq_bits < 8)")
        aniso_eta = float(params.anisotropic_eta
                          or _default_aniso_eta(d_rot))

    def train(pools):
        if split:
            return _train_split_codebooks(pools, kc, params.kmeans_n_iters)
        if aniso_eta > 0.0:
            return _train_codebooks_aniso(pools, kc, n_codes,
                                          params.kmeans_n_iters, aniso_eta)
        return _train_codebooks_batched(pools, kc, n_codes, params.kmeans_n_iters)

    kind = params.codebook_kind
    if kind != "per_cluster":
        # (pq_dim, n_train, pq_len) — every subspace trains on all residuals
        sub = jnp.moveaxis(resid, 1, 0)
        with tracing.range("ivf_pq.build.train_codebooks"):
            codebooks = train(sub)
        # codebook-kind heuristic: for "auto" ONLY, trial-train per-cluster
        # codebooks on the largest clusters and adopt them when they quantize
        # markedly better (the caller opted into the trial + possible ~3x
        # build cost by choosing auto). Plain per_subspace builds — including
        # internal ones like CAGRA's knn-graph IVF-PQ, which expose no
        # codebook knob — pay nothing.
        if kind == "auto":
            if params.n_lists >= 16 and n_train >= 4 * params.n_lists:
                key, kt = jax.random.split(key)
                ratio = _per_cluster_gain(resid, labels, codebooks, split, kt,
                                          min(params.kmeans_n_iters, 10))
                if ratio < 0.9:
                    logger.info(
                        "ivf_pq auto codebooks: per-cluster trial error is "
                        "%.2fx per-subspace — training per-cluster codebooks "
                        "(reference PER_CLUSTER mode, ivf_pq_build.cuh:424)",
                        ratio)
                    kind = "per_cluster"
                else:
                    logger.info(
                        "ivf_pq auto codebooks: per-cluster trial gains "
                        "little (%.2fx) — keeping per-subspace codebooks",
                        ratio)
            if kind == "auto":
                kind = "per_subspace"
    if kind == "per_cluster":
        # per-cluster: pool subspace-vectors of each cluster's members.
        # Pad each cluster's pool to a fixed size for batching.
        pool_cap = round_up(max(int(jnp.max(jnp.bincount(labels, length=params.n_lists))), n_codes), 8)
        order = jnp.argsort(labels, stable=True)
        counts = jnp.bincount(labels, length=params.n_lists)
        starts = jnp.cumsum(counts) - counts
        # gather rows per cluster with wraparound padding (repeat members)
        offs = jnp.arange(pool_cap)[None, :] % jnp.maximum(counts, 1)[:, None]
        rows = jnp.take(order, starts[:, None] + offs)  # (n_lists, pool_cap)
        pools = jnp.take(resid.reshape(n_train, d_rot), rows, axis=0)  # (L, pool_cap, d_rot)
        pools = pools.reshape(params.n_lists, pool_cap * pq_dim, pq_len)
        with tracing.range("ivf_pq.build.train_codebooks"):
            codebooks = train(pools)

    # 5. fast-scan tier decode scales (funnel stage c): fit per-list from
    # the RAW rotated residuals (signatures are scale-norm-independent —
    # restoring s_list here keeps the estimator exact either way)
    sig_scales = jnp.zeros((0,), jnp.float32)
    sig_w = _sig_words(d_rot, params.fast_scan)
    if params.fast_scan != "none":
        raw = resid.reshape(n_train, d_rot)
        if params.residual_scale_norm:
            raw = raw * jnp.take(list_scales, labels)[:, None]
        with tracing.range("ivf_pq.build.sig_scales"):
            sig_scales = _per_list_sig_scales(raw, labels, params.n_lists,
                                              params.fast_scan)

    index = IvfPqIndex(
        centers=centers,
        centers_rot=centers_rot,
        rotation=rotation,
        codebooks=codebooks,
        list_codes=jnp.zeros((params.n_lists, 0, pq_dim), jnp.uint8),
        list_ids=jnp.zeros((params.n_lists, 0), jnp.int32),
        list_sizes=jnp.zeros((params.n_lists,), jnp.int32),
        list_scales=list_scales,
        list_sig=jnp.zeros((params.n_lists, 0, sig_w), jnp.uint8),
        sig_scales=sig_scales,
        metric=mt,
        codebook_kind=kind,
        pq_bits=params.pq_bits,
        split_factor=params.split_factor,
        pq_split=split,
        data_kind=data_kind,
        rotation_kind=params.rotation,
        codebook_loss=params.codebook_loss,
        fast_scan=params.fast_scan,
    )
    if not params.add_data_on_build:
        obs_mem.account_index(index)
        return index
    if stream:
        return _extend_stream_f32(index, dataset, None, res=res)
    # x is already the f32 working view (byte data was shifted+upcast above)
    return _extend_f32(index, x, jnp.arange(n, dtype=jnp.int32), res=res)


def _check_split_consts(index: IvfPqIndex) -> None:
    """A pq_split L2 index must carry per-vector cross-term constants; a
    hand-constructed index without them would otherwise fail deep inside the
    jitted scan with an opaque broadcast error (ADVICE r3)."""
    if (index.pq_split and index.metric != DistanceType.InnerProduct
            and index.capacity > 0):
        expects(index.list_consts.shape == index.list_ids.shape,
                "pq_split L2 index needs list_consts of shape %s (per-vector "
                "cross terms), got %s — build via build()/extend(), which "
                "populate them", index.list_ids.shape, index.list_consts.shape)


@instrument("ivf_pq.extend",
            items=lambda a, kw: nrows(a[1] if len(a) > 1 else kw["new_vectors"]))
def extend(index: IvfPqIndex, new_vectors, new_ids=None, res: Resources | None = None,
           split_factor: float | None = None) -> IvfPqIndex:
    """Encode + append vectors (reference: ivf_pq::extend; encode path
    process_and_fill_codes, detail/ivf_pq_build.cuh). Byte indexes
    (data_kind int8/uint8) take vectors in the index's ORIGINAL dtype —
    a plain astype would wrap uint8 values mod 256 instead of shifting.

    A :class:`~raft_tpu.core.chunked.ChunkedReader` batch (or any host
    ndarray past the streaming threshold) takes the out-of-core path:
    per-chunk assign + encode + scatter, never materializing the batch on
    device."""
    from .ivf_flat import _STREAM_EXTEND_BYTES

    if (not chunked.is_reader(new_vectors)
            and isinstance(new_vectors, np.ndarray)
            and new_vectors.ndim == 2
            and new_vectors.nbytes > _STREAM_EXTEND_BYTES):
        new_vectors = chunked.ChunkedReader(new_vectors)
    if chunked.is_reader(new_vectors):
        return _extend_stream_f32(index, new_vectors, new_ids, res=res,
                                  split_factor=split_factor)
    x = jnp.asarray(new_vectors)
    if index.data_kind in ("int8", "uint8"):
        expects(str(x.dtype) == index.data_kind,
                "this index stores %s vectors; got %s", index.data_kind,
                x.dtype)
        from .brute_force import _as_signed

        x = _as_signed(x).astype(jnp.float32)
    return _extend_f32(index, x, new_ids, res=res, split_factor=split_factor)


def _extend_f32(index: IvfPqIndex, new_vectors, new_ids=None,
                res: Resources | None = None,
                split_factor: float | None = None) -> IvfPqIndex:
    """extend() after domain conversion: vectors already live in the index's
    f32 working domain (s8-shifted for uint8 kinds)."""
    res = res or default_resources()
    _check_split_consts(index)
    x = jnp.asarray(new_vectors)
    expects(x.ndim == 2 and x.shape[1] == index.dim, "vector dim mismatch")
    n_new = x.shape[0]
    if new_ids is None:
        new_ids = index.size + jnp.arange(n_new, dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)

    tile = _choose_tile(n_new, index.n_lists, 1, res.workspace_bytes)
    with tracing.range("ivf_pq.extend.assign"):
        labels = assign_to_lists(x, index.centers, index.metric, tile)
    resid = (x.astype(jnp.float32) - jnp.take(index.centers, labels, axis=0)) @ index.rotation.T
    sig = None
    if index.has_fast_scan:
        # signatures pack the RAW rotated residual (scale-norm independent)
        with tracing.range("ivf_pq.extend.encode_sig"):
            sig = _encode_sig(resid, jnp.take(index.sig_scales, labels),
                              index.fast_scan)
    resid = resid.reshape(n_new, index.pq_dim, index.pq_len)
    if index.scale_normed:
        # codes encode UNIT-scale residuals; search re-applies s_list in the
        # LUT (IndexParams.residual_scale_norm)
        resid = resid / jnp.take(index.list_scales, labels)[:, None, None]
    per_cluster = index.codebook_kind == "per_cluster"
    # split indexes encode against the effective composed 256-entry codebook
    # (joint argmin over the Minkowski sum — optimal for this codebook, and
    # the flat composed index IS hi*16+lo)
    enc_cb = _composed_codebooks(index.codebooks) if index.pq_split else index.codebooks
    n_codes = enc_cb.shape[-2]
    enc_tile = max(min(n_new, res.workspace_bytes // max(index.pq_dim * n_codes * 4, 1)), 8)
    with tracing.range("ivf_pq.extend.encode"):
        codes = _encode(
            resid, enc_cb, labels,
            per_cluster=per_cluster,
            tile=min(enc_tile, 8192),
            aniso_eta=(_default_aniso_eta(index.rot_dim)
                       if index.codebook_loss == "anisotropic" else 0.0),
        )
    consts = None
    if index.pq_split and index.metric != DistanceType.InnerProduct:
        # L2 scoring needs the per-vector cross term; IP scoring is exactly
        # separable, so split IP indexes keep the empty (n_lists, 0) buffer
        # (no dead capacity-sized zeros stored/serialized/sharded)
        consts = _pq_cross_consts(codes, index.codebooks, labels, per_cluster)
        if index.scale_normed:
            # the stored cross term enters scoring raw, so the s^2 of
            # ||r - s*(cb1+cb2)||^2 folds in HERE, at encode time
            consts = consts * jnp.take(index.list_scales, labels) ** 2

    if index.capacity > 0 and index.size > 0:
        old_mask = index.list_ids.reshape(-1) >= 0
        old_codes = index.list_codes.reshape(-1, index.pq_dim)[old_mask]
        old_ids = index.list_ids.reshape(-1)[old_mask]
        old_labels = jnp.repeat(jnp.arange(index.n_lists), index.capacity)[old_mask]
        codes = jnp.concatenate([old_codes, codes])
        new_ids = jnp.concatenate([old_ids, new_ids])
        labels = jnp.concatenate([old_labels.astype(jnp.int32), labels])
        if consts is not None:
            old_consts = index.list_consts.reshape(-1)[old_mask]
            consts = jnp.concatenate([old_consts, consts])
        if sig is not None:
            old_sig = index.list_sig.reshape(-1, index.list_sig.shape[2])[old_mask]
            sig = jnp.concatenate([old_sig, sig])

    import numpy as np

    # shared capacity policy: oversized lists split into sub-lists sharing
    # their parent's center (+rotated center, +per-cluster codebook).
    # Residuals/codes were computed against the parent center, which
    # sub-lists share, so codes stay valid.
    sf = index.split_factor if split_factor is None else split_factor
    labels, rep, n_lists, capacity, _ = bound_capacity(labels, index.n_lists, sf)
    centers, centers_rot, codebooks = index.centers, index.centers_rot, index.codebooks
    list_scales, sig_scales = index.list_scales, index.sig_scales
    if rep is not None:
        centers = jnp.asarray(np.repeat(np.asarray(centers), rep, axis=0))
        centers_rot = jnp.asarray(np.repeat(np.asarray(centers_rot), rep, axis=0))
        if index.codebook_kind == "per_cluster":
            codebooks = jnp.asarray(np.repeat(np.asarray(codebooks), rep, axis=0))
        if index.scale_normed:
            # sub-lists share their parent's center AND its residual scale
            # (codes were encoded against both)
            list_scales = jnp.asarray(
                np.repeat(np.asarray(list_scales), rep, axis=0))
        if index.has_fast_scan:
            # ... and its signature decode scale, for the same reason
            sig_scales = jnp.asarray(
                np.repeat(np.asarray(sig_scales), rep, axis=0))
    with tracing.range("ivf_pq.extend.fill_lists"):
        buf, idbuf, sizes, cbuf, sbuf = _fill_code_lists(
            codes, new_ids, labels, n_lists, capacity, consts, sig)
    out = dataclasses.replace(
        index, centers=centers, centers_rot=centers_rot, codebooks=codebooks,
        list_codes=buf, list_ids=idbuf, list_sizes=sizes, list_consts=cbuf,
        list_scales=list_scales, list_sig=sbuf, sig_scales=sig_scales,
        split_factor=sf,
    )
    # ledger hook (docs/observability.md): the re-packed lists are the
    # long-lived allocation; a superseded index's entry auto-releases
    # when its last reference drops
    obs_mem.account_index(out)
    if metrics.enabled():
        g = _quant_bytes_per_row()
        g.set(index.pq_dim + 4, tier="pq")
        if index.has_fast_scan:
            g.set(index.list_sig.shape[2] + 4, tier="sig")
    return out


@functools.partial(jax.jit, static_argnames=("n_lists",),
                   donate_argnums=(0, 1, 2, 3, 4))
def _fill_pq_chunk(buf, idbuf, cbuf, sbuf, offsets, codes, ids, labels,
                   consts, sig, n_lists: int):
    """One streamed scatter pass over the PQ list layout — the ivf_pq twin
    of ``ivf_flat._fill_chunk`` (same running-offset position math, same
    sentinel-label OOB drop for pad rows, same in-place donation; see that
    docstring). ``consts``/``sig`` ride along when the index carries
    cross-term constants / fast-scan signatures so the four layouts can
    never disagree on slot positions."""
    pos_local, counts = list_positions(labels, n_lists + 1)
    offs = jnp.concatenate([offsets, jnp.zeros((1,), jnp.int32)])
    pos = pos_local + jnp.take(offs, labels)
    buf = buf.at[labels, pos].set(codes, mode="drop")
    idbuf = idbuf.at[labels, pos].set(ids.astype(jnp.int32), mode="drop")
    if consts is not None:
        cbuf = cbuf.at[labels, pos].set(consts, mode="drop")
    if sig is not None:
        sbuf = sbuf.at[labels, pos].set(sig, mode="drop")
    return buf, idbuf, cbuf, sbuf, offsets + counts[:n_lists]


def _extend_stream_f32(index: IvfPqIndex, reader, new_ids=None,
                       res: Resources | None = None,
                       split_factor: float | None = None) -> IvfPqIndex:
    """The streamed twin of :func:`_extend_f32`: two passes over the
    reader's chunks — assign, then residual/encode/scatter — instead of
    one whole-corpus device array. Bit-equal to the in-core path: every
    per-row quantity (ingest conversion, label, residual, signature, code,
    cross-term constant) comes from the SAME helpers, none couples rows
    across a batch, and the post-split gathers against ``np.repeat``ed
    per-list arrays return exactly the parent values the in-core path
    reads pre-split (split children share their parent's center, scale and
    codebook). Device peak: index accumulators + two staged chunks + the
    label/id vectors — CONSTANT in corpus rows beyond the index itself."""
    from ..obs import build as build_metrics

    res = res or default_resources()
    _check_split_consts(index)
    n_new, d = (int(s) for s in reader.shape)
    expects(d == index.dim, "vector dim mismatch")
    if index.data_kind in ("int8", "uint8"):
        expects(str(reader.dtype) == index.data_kind,
                "this index stores %s vectors; got %s", index.data_kind,
                reader.dtype)
    ingest = _stream_ingest(index.data_kind)
    if new_ids is None:
        new_ids = index.size + jnp.arange(n_new, dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)
        expects(int(new_ids.shape[0]) == n_new, "ids/vectors length mismatch")

    pq_dim, pq_len = index.pq_dim, index.pq_len
    cr = int(reader.chunk_rows)
    emit = metrics.enabled()
    stager = chunked.ChunkStager(cr, d, reader.dtype, kind="ivf_pq")
    try:
        # ---- pass A: per-chunk nearest-center assignment (labels stay
        # device-resident; no per-chunk host syncs)
        tile = _choose_tile(cr, index.n_lists, 1, res.workspace_bytes)
        parts = []
        with tracing.range("ivf_pq.extend.assign_stream"):
            for start, block in reader.chunks():
                xs = ingest(stager.stage(block))
                parts.append(assign_to_lists(xs, index.centers,
                                             index.metric, tile))
                if emit:
                    build_metrics.ooc_chunks().inc(1, kind="ivf_pq",
                                                   stage="assign")
        labels = jnp.concatenate(parts)[:n_new]  # drop pad-row garbage
        del parts

        # merge with existing list contents (old rows FIRST — stable
        # ranks, and therefore the final layout, match the in-core twin)
        n_old = 0
        old_codes = old_ids = old_consts = old_sig = None
        want_consts = (index.pq_split
                       and index.metric != DistanceType.InnerProduct)
        if index.capacity > 0 and index.size > 0:
            old_mask = index.list_ids.reshape(-1) >= 0
            old_codes = index.list_codes.reshape(-1, pq_dim)[old_mask]
            old_ids = index.list_ids.reshape(-1)[old_mask]
            old_labels = jnp.repeat(jnp.arange(index.n_lists),
                                    index.capacity)[old_mask]
            n_old = int(old_codes.shape[0])
            labels = jnp.concatenate([old_labels.astype(jnp.int32), labels])
            if want_consts:
                old_consts = index.list_consts.reshape(-1)[old_mask]
            if index.has_fast_scan:
                old_sig = index.list_sig.reshape(
                    -1, index.list_sig.shape[2])[old_mask]

        # capacity policy over the FULL label vector — identical to the
        # in-core call (ivf_pq never spatial-splits: sub-lists must share
        # their parent's center for the codes to stay valid)
        with tracing.range("ivf_pq.extend.capacity_stream"):
            sf = index.split_factor if split_factor is None else split_factor
            labels, rep, n_lists2, capacity, _ = bound_capacity(
                labels, index.n_lists, sf)
            centers, centers_rot = index.centers, index.centers_rot
            codebooks = index.codebooks
            list_scales, sig_scales = index.list_scales, index.sig_scales
            if rep is not None:
                centers = jnp.asarray(np.repeat(np.asarray(centers), rep,
                                                axis=0))
                centers_rot = jnp.asarray(np.repeat(np.asarray(centers_rot),
                                                    rep, axis=0))
                if index.codebook_kind == "per_cluster":
                    codebooks = jnp.asarray(np.repeat(np.asarray(codebooks),
                                                      rep, axis=0))
                if index.scale_normed:
                    list_scales = jnp.asarray(
                        np.repeat(np.asarray(list_scales), rep, axis=0))
                if index.has_fast_scan:
                    sig_scales = jnp.asarray(
                        np.repeat(np.asarray(sig_scales), rep, axis=0))

        # ---- pass B: per-chunk residual -> encode -> scatter ----------
        # Gathers run against the REPEATED arrays with POST-split labels:
        # bitwise the parent values the in-core path uses pre-split (and
        # repeat/compose commute for the split-codebook expansion).
        per_cluster = index.codebook_kind == "per_cluster"
        enc_cb = (_composed_codebooks(codebooks) if index.pq_split
                  else codebooks)
        n_codes = enc_cb.shape[-2]
        enc_tile = max(min(cr, res.workspace_bytes
                           // max(pq_dim * n_codes * 4, 1)), 8)
        aniso_eta = (_default_aniso_eta(index.rot_dim)
                     if index.codebook_loss == "anisotropic" else 0.0)
        sig_w = index.list_sig.shape[2] if index.has_fast_scan else 0
        buf = jnp.zeros((n_lists2, capacity, pq_dim), jnp.uint8)
        idbuf = jnp.full((n_lists2, capacity), -1, jnp.int32)
        cbuf = (jnp.zeros((n_lists2, capacity), jnp.float32) if want_consts
                else jnp.zeros((n_lists2, 0), jnp.float32))
        sbuf = (jnp.zeros((n_lists2, capacity, sig_w), jnp.uint8)
                if index.has_fast_scan
                else jnp.zeros((n_lists2, 0, 0), jnp.uint8))
        offsets = jnp.zeros((n_lists2,), jnp.int32)
        # transient ledger entry — the streamed build's device working set
        # (released before the sealed index is accounted)
        ooc_tok = obs_mem.account(
            "build/ooc", name="ivf_pq",
            device_bytes=int(buf.nbytes + idbuf.nbytes + cbuf.nbytes
                             + sbuf.nbytes + offsets.nbytes + labels.nbytes
                             + new_ids.nbytes),
            owner=stager)
        with tracing.range("ivf_pq.extend.fill_stream"):
            if n_old > 0:
                buf, idbuf, cbuf, sbuf, offsets = _fill_pq_chunk(
                    buf, idbuf, cbuf, sbuf, offsets, old_codes, old_ids,
                    labels[:n_old],
                    old_consts if want_consts else None,
                    old_sig if index.has_fast_scan else None,
                    n_lists=n_lists2)
                labels = labels[n_old:]
            pad = -(-n_new // cr) * cr - n_new
            lab_p = (jnp.concatenate(
                [labels, jnp.full((pad,), n_lists2, jnp.int32)])
                if pad else labels)
            ids_p = (jnp.concatenate(
                [new_ids, jnp.full((pad,), -1, jnp.int32)])
                if pad else new_ids)
            for start, block in reader.chunks():
                xs = ingest(stager.stage(block))
                st = jnp.int32(start)  # operand, not executable key
                lab_c = lax.dynamic_slice_in_dim(lab_p, st, cr)
                ids_c = lax.dynamic_slice_in_dim(ids_p, st, cr)
                resid = (xs.astype(jnp.float32)
                         - jnp.take(centers, lab_c, axis=0)
                         ) @ index.rotation.T
                sig_c = None
                if index.has_fast_scan:
                    sig_c = _encode_sig(resid,
                                        jnp.take(sig_scales, lab_c),
                                        index.fast_scan)
                resid = resid.reshape(cr, pq_dim, pq_len)
                if index.scale_normed:
                    resid = resid / jnp.take(list_scales,
                                             lab_c)[:, None, None]
                codes_c = _encode(resid, enc_cb, lab_c,
                                  per_cluster=per_cluster,
                                  tile=min(enc_tile, 8192),
                                  aniso_eta=aniso_eta)
                consts_c = None
                if want_consts:
                    consts_c = _pq_cross_consts(codes_c, codebooks, lab_c,
                                                per_cluster)
                    if index.scale_normed:
                        consts_c = (consts_c
                                    * jnp.take(list_scales, lab_c) ** 2)
                buf, idbuf, cbuf, sbuf, offsets = _fill_pq_chunk(
                    buf, idbuf, cbuf, sbuf, offsets, codes_c, ids_c,
                    lab_c, consts_c, sig_c, n_lists=n_lists2)
                if emit:
                    build_metrics.ooc_chunks().inc(1, kind="ivf_pq",
                                                   stage="fill")
        sizes = offsets
        obs_mem.release(ooc_tok)
    finally:
        stager.release()
    out = dataclasses.replace(
        index, centers=centers, centers_rot=centers_rot, codebooks=codebooks,
        list_codes=buf, list_ids=idbuf, list_sizes=sizes, list_consts=cbuf,
        list_scales=list_scales, list_sig=sbuf, sig_scales=sig_scales,
        split_factor=sf,
    )
    obs_mem.account_index(out)
    if emit:
        g = _quant_bytes_per_row()
        g.set(pq_dim + 4, tier="pq")
        if index.has_fast_scan:
            g.set(index.list_sig.shape[2] + 4, tier="sig")
    return out


@functools.partial(
    jax.jit,
    static_argnames=("n_probes", "k", "query_tile", "probe_chunk", "metric",
                     "codebook_kind", "lut_dtype", "select_impl"),
)
def _pq_search(index: IvfPqIndex, queries, n_probes: int, k: int, query_tile: int,
               probe_chunk: int, metric: DistanceType, codebook_kind: str, lut_dtype: str,
               keep_mask=None, select_impl: str = "auto"):
    m, d = queries.shape
    qf = queries.astype(jnp.float32)
    inner = metric == DistanceType.InnerProduct
    pq_dim, pq_len = index.pq_dim, index.pq_len
    n_codes = index.codebooks.shape[-2]

    # ---- stage 1: coarse clusters (ref select_clusters :68) ----
    with tracing.scope("ivf_pq.search.coarse"):
        cscore = qf @ index.centers.T
        if not inner:
            cn = jnp.sum(index.centers * index.centers, axis=1)
            cscore = cn[None, :] - 2.0 * cscore
        _, probes = _select_k(cscore, None, n_probes, not inner)  # (m, p)

    num = -(-m // query_tile)
    pad = num * query_tile - m
    with tracing.scope("ivf_pq.search.lut"):
        # rotated queries
        qrot = qf @ index.rotation.T  # (m, d_rot)
        qp = jnp.pad(qrot, ((0, pad), (0, 0))) if pad else qrot
        pp = jnp.pad(probes, ((0, pad), (0, 0))) if pad else probes
        qt = qp.reshape(num, query_tile, index.rot_dim)
        pt = pp.reshape(num, query_tile, n_probes)
        # codebook norms (for LUT via ‖c‖² - 2·r·c)
        cb = index.codebooks.astype(jnp.float32)
        cb_n2 = jnp.sum(cb * cb, axis=-1)  # (B?, n_codes) matching codebook layout

    n_chunks = n_probes // probe_chunk
    cap = index.capacity

    def chunk_lut(q, pc):
        with tracing.scope("ivf_pq.search.lut"):
            crot = index.centers_rot[pc]  # (T, pc, d_rot)

            # ---- LUT (ref ivfpq_search_worker :419 lut computation) ----
            # per-list residual scales (IndexParams.residual_scale_norm):
            # codes decode to s_list * codeword, so the fold is one multiply
            # on the per-probe LUT — s^2 for L2 (with the residual
            # pre-divided so dots see the unit-scale domain the codebooks
            # were trained in), s for IP. Bias terms stay in the RAW
            # residual domain (they carry ||r||^2 / q·c exactly).
            sc = (jnp.take(index.list_scales, pc, axis=0)
                  if index.scale_normed else None)       # (T, pc) | None
            if inner:
                # IP(q, v) = q·c + q_rot·decoded_residual: LUT over the rotated
                # query's subvectors; the q·c bias is added to scores below.
                qs = jnp.broadcast_to(
                    q[:, None, :], (query_tile, probe_chunk, index.rot_dim)
                ).reshape(query_tile, probe_chunk, pq_dim, pq_len)
                if codebook_kind == "per_subspace":
                    lut = jnp.einsum("tpsl,skl->tpsk", qs, cb, precision=lax.Precision.HIGHEST)
                else:
                    lut = jnp.einsum("tpsl,tpkl->tpsk", qs, cb[pc], precision=lax.Precision.HIGHEST)
                if sc is not None:
                    lut = lut * sc[:, :, None, None]
                bias = jnp.einsum("td,tpd->tp", q, crot, precision=lax.Precision.HIGHEST)
            else:
                # L2: ‖q - c - decoded‖² = Σ_s ‖r_s - codeword_s‖², r = q_rot - c_rot
                r = (q[:, None, :] - crot).reshape(query_tile, probe_chunk, pq_dim, pq_len)
                # Σ_s ‖r_s‖² per probe: constant within a list, needed so
                # scores are comparable across probed lists
                bias = jnp.sum(r * r, axis=(2, 3))  # (T, pc)
                if sc is not None:
                    r = r / sc[:, :, None, None]
                if codebook_kind == "per_subspace":
                    # cb: (pq_dim, n_codes, pq_len)
                    dots = jnp.einsum("tpsl,skl->tpsk", r, cb, precision=lax.Precision.HIGHEST)
                    lut = cb_n2[None, None] - 2.0 * dots  # (T, pc, pq_dim, n_codes)
                else:
                    cbl = cb[pc]  # (T, pc, n_codes, pq_len)
                    dots = jnp.einsum("tpsl,tpkl->tpsk", r, cbl, precision=lax.Precision.HIGHEST)
                    lut = cb_n2[pc][:, :, None, :] - 2.0 * dots
                if sc is not None:
                    lut = lut * (sc * sc)[:, :, None, None]
            return lut, bias

    def chunk_scan(lut, bias, pc):
        with tracing.scope("ivf_pq.search.scan"):
            # ---- scan: score = Σ_s LUT[s, code_s] (ref compute_similarity) ----
            # One-hot MXU formulation: Σ_s LUT[s, c_s] = onehot(codes)·LUTflat.
            # An elementwise take_along_axis gather is ~4x slower on TPU
            # (measured 1.95s vs 0.52s per 1M-scale chunk) — single-element
            # HBM gathers don't vectorize; the MXU one-hot contraction is the
            # TPU analogue of ScaNN's SIMD LUT16 shuffle, and pq_bits=4
            # shrinks the contracted axis 16x for exactly that reason.
            codes = index.list_codes[pc]  # (T, pc, cap, pq_dim) gather
            ids = index.list_ids[pc]  # (T, pc, cap)
            if index.pq_split:
                # nibble-split one-hot: stage-1 hit in lanes [0,16), stage-2
                # in [16,32) — one contraction against the 32-entry LUT sums
                # LUT1[hi] + LUT2[lo]; the missing cross term rides in
                # list_consts (added below). Axis pq_dim*32 vs the joint
                # pq_dim*256: 8x less MXU work for the same 8 code bits.
                ar16 = jnp.arange(16, dtype=codes.dtype)
                oh = jnp.concatenate(
                    [(codes >> 4)[..., None] == ar16,
                     (codes & 0xF)[..., None] == ar16],
                    axis=-1)  # (T, pc, cap, pq_dim, 32)
            else:
                oh = (
                    codes[..., None] == jnp.arange(n_codes, dtype=codes.dtype)
                )  # (T, pc, cap, pq_dim, n_codes)
            # the contraction dtype follows lut_dtype (0/1 one-hot entries
            # are exact in any of them):
            #   float32  — exact LUT values
            #   bfloat16 — LUT rounded to ~2^-8 relative, half the bytes
            #   int8     — LUT quantized per (query, probe) with a
            #              symmetric scale (the reference's fp8 smem LUT
            #              analogue, detail/fp_8bit.cuh); int32
            #              accumulation on the int8 MXU path, quarter the
            #              operand bytes
            ohf = oh.reshape(query_tile, probe_chunk, cap, pq_dim * n_codes)
            lutf = lut.reshape(query_tile, probe_chunk, pq_dim * n_codes)
            if lut_dtype not in ("float32", "bfloat16", "int8"):
                raise ValueError(f"unknown lut_dtype {lut_dtype!r}")
            if lut_dtype == "int8":
                amax = jnp.max(jnp.abs(lutf), axis=2, keepdims=True)  # (T,pc,1)
                scale = jnp.maximum(amax, 1e-30) / 127.0
                lut_q = jnp.clip(jnp.round(lutf / scale), -127, 127).astype(jnp.int8)
                acc = lax.dot_general(
                    ohf.astype(jnp.int8), lut_q,
                    (((3,), (2,)), ((0, 1), (0, 1))),
                    preferred_element_type=jnp.int32,
                )  # (T, pc, cap) int32
                scores = acc.astype(jnp.float32) * scale
            else:
                ct = jnp.bfloat16 if lut_dtype == "bfloat16" else jnp.float32
                scores = lax.dot_general(
                    ohf.astype(ct), lutf.astype(ct),
                    (((3,), (2,)), ((0, 1), (0, 1))),
                    preferred_element_type=jnp.float32,
                )  # (T, pc, cap)
            scores = scores + bias[:, :, None]
            if index.pq_split and not inner:
                scores = scores + index.list_consts[pc]  # (T, pc, cap)
            scores = jnp.where(ids >= 0, scores, -jnp.inf if inner else jnp.inf)
            return scores, ids

    def per_tile(args):
        q, pr = args  # (T, d_rot), (T, p)

        def per_chunk(c, _):
            pc = lax.dynamic_slice_in_dim(pr, c * probe_chunk, probe_chunk, axis=1)  # (T, pc)
            lut, bias = chunk_lut(q, pc)
            scores, ids = chunk_scan(lut, bias, pc)
            if keep_mask is not None:
                from .sample_filter import apply_id_filter

                scores = apply_id_filter(scores, ids, keep_mask, not inner)
            flat_s = scores.reshape(query_tile, probe_chunk * cap)
            flat_i = ids.reshape(query_tile, probe_chunk * cap)
            # candidate selects route through the dispatching selector
            # (r06): at wide k this is the call site the Pallas wide-k
            # kernel was commissioned for — CAGRA's build chunk reaches
            # here with k = gpu_top_k + 1 (193 at defaults)
            return c + 1, select_k_impl(flat_s, flat_i, k, not inner,
                                        impl=select_impl)

        _, (cv, ci) = lax.scan(per_chunk, 0, None, length=n_chunks)
        cv = jnp.moveaxis(cv, 0, 1).reshape(query_tile, n_chunks * k)
        ci = jnp.moveaxis(ci, 0, 1).reshape(query_tile, n_chunks * k)
        return select_k_impl(cv, ci, k, not inner, impl=select_impl)

    dists, idx = lax.map(per_tile, (qt, pt))
    dists = dists.reshape(num * query_tile, k)[:m]
    idx = idx.reshape(num * query_tile, k)[:m]
    if not inner and metric in (DistanceType.L2SqrtExpanded, DistanceType.L2SqrtUnexpanded):
        dists = jnp.where(jnp.isfinite(dists), jnp.sqrt(jnp.maximum(dists, 0.0)), dists)
    if keep_mask is not None:
        # filtered-out candidates carry ±inf scores — report id -1
        idx = jnp.where(jnp.isinf(dists), -1, idx)
    return dists, idx


@functools.partial(
    jax.jit,
    static_argnames=("n_probes", "k", "k_widen", "query_tile", "probe_chunk",
                     "metric", "codebook_kind", "lut_dtype", "select_impl"),
)
def _pq_search_funnel(index: IvfPqIndex, queries, n_probes: int, k: int,
                      k_widen: int, query_tile: int, probe_chunk: int,
                      metric: DistanceType, codebook_kind: str, lut_dtype: str,
                      keep_mask=None, select_impl: str = "auto"):
    """Three-stage quantization funnel (docs/tuning.md "Quantization
    funnel"): binary widen → PQ rerank → the caller's exact refine.

    Stage A scores EVERY probed slot against the packed fast-scan tier
    (``list_sig``) with the same nibble-split one-hot contraction as the
    pq8_split scan — the 32-entry signature LUT makes the contracted axis
    ``sig_words * 32`` (for 1bit at d=128: half of classic pq4's), and the
    operand bytes are the packed signatures, not the PQ codes. Stage B
    re-scores only the per-chunk top ``k_widen`` survivors against the
    full PQ codes by direct decode (exact PQ scores; the split cross term
    rides in the decoded sum, no list_consts needed). Both selects and the
    chunk merge route through the one ``select_k`` dispatch with the
    shared ``-1/±inf`` sentinel, so no new merge shapes are minted, and
    candidates the estimator filtered (dead slots, sample-filter hits)
    keep their ±inf score through the rerank — they cannot resurrect.
    """
    m, d = queries.shape
    qf = queries.astype(jnp.float32)
    inner = metric == DistanceType.InnerProduct
    pq_dim, pq_len = index.pq_dim, index.pq_len
    d_rot = index.rot_dim
    sig_w = index.list_sig.shape[2]
    n_codes = index.codebooks.shape[-2]

    # ---- stage 1: coarse clusters (shared with the classic scan) ----
    with tracing.scope("ivf_pq.search.coarse"):
        cscore = qf @ index.centers.T
        if not inner:
            cn = jnp.sum(index.centers * index.centers, axis=1)
            cscore = cn[None, :] - 2.0 * cscore
        _, probes = _select_k(cscore, None, n_probes, not inner)  # (m, p)

    qrot = qf @ index.rotation.T  # (m, d_rot)

    num = -(-m // query_tile)
    pad = num * query_tile - m
    qp = jnp.pad(qrot, ((0, pad), (0, 0))) if pad else qrot
    pp = jnp.pad(probes, ((0, pad), (0, 0))) if pad else probes
    qt = qp.reshape(num, query_tile, d_rot)
    pt = pp.reshape(num, query_tile, n_probes)

    n_chunks = n_probes // probe_chunk
    cap = index.capacity
    fast_scan = index.fast_scan
    cb = index.codebooks.astype(jnp.float32)
    # binary contraction dtype follows lut_dtype (int8 is rejected at the
    # dispatcher — the symmetric-scale quantization is a PQ-LUT-range
    # optimization and the estimator tier is already 1-4 bits)
    ct = jnp.bfloat16 if lut_dtype == "bfloat16" else jnp.float32

    def per_tile(args):
        q, pr = args  # (T, d_rot), (T, p)

        def per_chunk(c, _):
            pc = lax.dynamic_slice_in_dim(pr, c * probe_chunk, probe_chunk, axis=1)  # (T, pc)
            crot = index.centers_rot[pc]        # (T, pc, d_rot)
            ids = index.list_ids[pc]            # (T, pc, cap)
            ss = jnp.take(index.sig_scales, pc, axis=0)  # (T, pc)

            # ---- stage A: signature estimator over every probed slot.
            # The nibble LUT carries raw = <r, σ> (1bit) / <r, lev> (4bit)
            # in the RAW residual domain (sig_scales were fit there), so
            # residual_scale_norm never enters the estimator:
            #   L2  est = ‖r‖² + s²·d_rot − 2·s·raw   (‖σ‖² = d_rot for ±1;
            #             the 4bit level-norm uses the same s²·d_rot model —
            #             levels span ±2 per-dim RMS, so E‖lev‖² ≈ d_rot)
            #   IP  est = q·c + s·raw
            if inner:
                r = jnp.broadcast_to(q[:, None, :],
                                     (query_tile, probe_chunk, d_rot))
            else:
                r = q[:, None, :] - crot
            slut = _sig_nibble_lut(r, fast_scan, sig_w)  # (T, pc, W, 32)
            sig = index.list_sig[pc]                     # (T, pc, cap, W)
            ar16 = jnp.arange(16, dtype=sig.dtype)
            oh = jnp.concatenate(
                [(sig >> 4)[..., None] == ar16,
                 (sig & 0xF)[..., None] == ar16],
                axis=-1)  # (T, pc, cap, W, 32)
            ohf = oh.reshape(query_tile, probe_chunk, cap, sig_w * 32)
            lutf = slut.reshape(query_tile, probe_chunk, sig_w * 32)
            raw = lax.dot_general(
                ohf.astype(ct), lutf.astype(ct),
                (((3,), (2,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32)  # (T, pc, cap)
            if inner:
                bias = jnp.einsum("td,tpd->tp", q, crot,
                                  precision=lax.Precision.HIGHEST)
                est = bias[:, :, None] + ss[:, :, None] * raw
            else:
                bias = jnp.sum(r * r, axis=-1)  # (T, pc) = ‖r‖² per probe
                est = ((bias + ss * ss * d_rot)[:, :, None]
                       - 2.0 * ss[:, :, None] * raw)
            est = jnp.where(ids >= 0, est, -jnp.inf if inner else jnp.inf)
            if keep_mask is not None:
                from .sample_filter import apply_id_filter

                est = apply_id_filter(est, ids, keep_mask, not inner)

            # ---- widen: top-k_widen flat positions through select_k ----
            flat_est = est.reshape(query_tile, probe_chunk * cap)
            flat_pos = jnp.broadcast_to(
                jnp.arange(probe_chunk * cap, dtype=jnp.int32)[None, :],
                (query_tile, probe_chunk * cap))
            est_sel, pos_sel = select_k_impl(flat_est, flat_pos, k_widen,
                                             not inner, impl=select_impl)
            probe_sel = pos_sel // cap           # (T, kw) chunk-local probe
            slot_sel = pos_sel % cap
            list_sel = jnp.take_along_axis(pc, probe_sel, axis=1)  # (T, kw)

            # ---- stage B: PQ rerank of the survivors by direct decode ----
            codes_sel = index.list_codes[list_sel, slot_sel]  # (T, kw, pq_dim)
            ids_sel = index.list_ids[list_sel, slot_sel]      # (T, kw)
            if index.pq_split:
                # nibble one-hot over the 32-entry split codebook decodes
                # cb1[hi] + cb2[lo] in one contraction (cross term included)
                ohc = jnp.concatenate(
                    [(codes_sel >> 4)[..., None] == ar16,
                     (codes_sel & 0xF)[..., None] == ar16],
                    axis=-1)  # (T, kw, pq_dim, 32)
            else:
                ohc = (codes_sel[..., None]
                       == jnp.arange(n_codes, dtype=codes_sel.dtype))
            if codebook_kind == "per_cluster":
                dec = jnp.einsum("twsk,twkl->twsl", ohc.astype(jnp.float32),
                                 cb[list_sel],
                                 precision=lax.Precision.HIGHEST)
            else:
                dec = jnp.einsum("twsk,skl->twsl", ohc.astype(jnp.float32),
                                 cb, precision=lax.Precision.HIGHEST)
            dec = dec.reshape(query_tile, k_widen, d_rot)
            if index.scale_normed:
                # codes decode to s_list · codeword (residual_scale_norm)
                dec = dec * jnp.take(index.list_scales, list_sel)[..., None]
            crot_sel = index.centers_rot[list_sel]  # (T, kw, d_rot)
            if inner:
                score = jnp.einsum("td,twd->tw", q, crot_sel + dec,
                                   precision=lax.Precision.HIGHEST)
            else:
                rr = q[:, None, :] - crot_sel - dec
                score = jnp.sum(rr * rr, axis=-1)
            # estimator-filtered survivors keep their ±inf score (their
            # slots/ids may be real rows the sample filter dropped)
            score = jnp.where(jnp.isfinite(est_sel), score, est_sel)
            return c + 1, select_k_impl(score, ids_sel, k, not inner,
                                        impl=select_impl)

        _, (cv, ci) = lax.scan(per_chunk, 0, None, length=n_chunks)
        cv = jnp.moveaxis(cv, 0, 1).reshape(query_tile, n_chunks * k)
        ci = jnp.moveaxis(ci, 0, 1).reshape(query_tile, n_chunks * k)
        return select_k_impl(cv, ci, k, not inner, impl=select_impl)

    with tracing.scope("ivf_pq.search.funnel"):
        dists, idx = lax.map(per_tile, (qt, pt))
    dists = dists.reshape(num * query_tile, k)[:m]
    idx = idx.reshape(num * query_tile, k)[:m]
    if not inner and metric in (DistanceType.L2SqrtExpanded, DistanceType.L2SqrtUnexpanded):
        dists = jnp.where(jnp.isfinite(dists), jnp.sqrt(jnp.maximum(dists, 0.0)), dists)
    if keep_mask is not None:
        # filtered-out candidates carry ±inf scores — report id -1
        idx = jnp.where(jnp.isinf(dists), -1, idx)
    return dists, idx


@instrument(
    "ivf_pq.search",
    items=lambda a, kw: nrows(a[2] if len(a) > 2 else kw["queries"]),
    labels=lambda a, kw: {"k": a[3] if len(a) > 3 else kw["k"],
                          "n_probes": (a[0] if a else kw["params"]).n_probes},
)
@auto_convert_output
def search(params: SearchParams, index: IvfPqIndex, queries, k: int,
           sample_filter=None, res: Resources | None = None):
    """Search (reference: ivf_pq::search :723; pylibraft neighbors/ivf_pq;
    filtered overload neighbors/ivf_pq.cuh search_with_filtering).

    Returns (distances (m, k), ids (m, k)); distances are approximate
    (PQ-quantized), id -1 marks empty candidate slots.

    Tracer caveat: when ``index`` is passed as a jit argument its
    ``list_sizes`` is a tracer, so the "index is empty" guard (like the
    ``index.size`` property) cannot run — searching an empty index inside a
    user jit returns all-sentinel results (-1 ids, +inf distances) instead
    of raising."""
    from .sample_filter import resolve_filter
    from .brute_force import _coerce_queries

    res = res or default_resources()
    queries = jnp.asarray(queries)
    expects(queries.ndim == 2 and queries.shape[1] == index.dim, "query dim mismatch")
    queries = _coerce_queries(index.data_kind, queries)
    expects(index.capacity > 0, "index is empty")
    _check_split_consts(index)
    if not isinstance(index.list_sizes, jax.core.Tracer):
        expects(index.size > 0, "index is empty")
    n_probes = min(params.n_probes, index.n_lists)
    expects(k <= n_probes * index.capacity, "k exceeds probed candidate pool")
    m = queries.shape[0]

    expects(params.lut_dtype in ("float32", "bfloat16", "int8"),
            "lut_dtype must be 'float32', 'bfloat16' or 'int8', got %r",
            params.lut_dtype)
    n_codes = index.codebooks.shape[-2]
    expects(params.select_impl in ("auto", "xla", "pallas"),
            "select_impl must be 'auto', 'xla' or 'pallas', got %r",
            params.select_impl)
    if params.select_impl == "pallas":
        from ..ops.topk import TOPK_MAX_K

        expects(k <= TOPK_MAX_K,
                "select_impl='pallas' selects with the streaming kernel: "
                "k=%d must be <= %d", k, TOPK_MAX_K)
    widen = int(params.funnel_widen)
    expects(widen >= 1, "funnel_widen must be >= 1, got %d", widen)
    if widen > 1:
        expects(index.has_fast_scan,
                "funnel_widen=%d widens through the fast-scan tier, but "
                "this index carries none — build with "
                "IndexParams.fast_scan='1bit'|'4bit'", widen)
    # funnel_widen == 1 is the classic scan BY CONSTRUCTION (ids bit-equal):
    # the funnel dispatch below is taken only for a real widen factor
    use_funnel = widen > 1
    if use_funnel:
        bytes_per_probe_row = funnel_scan_bytes_per_probe_row(
            index.capacity, index.list_sig.shape[2])
    else:
        bytes_per_probe_row = pq_scan_bytes_per_probe_row(
            index.capacity, index.pq_dim, n_codes)
    query_tile, probe_chunk = plan_search_tiles(
        m, n_probes, int(k), index.capacity,
        bytes_per_probe_row=bytes_per_probe_row,
        budget_bytes=res.workspace_bytes,
        max_query_tile=128,
    )
    if metrics.enabled():
        _scan_plan_total().inc(1, query_tile=query_tile,
                               probe_chunk=probe_chunk)

    keep_mask = resolve_filter(sample_filter)
    if keep_mask is not None:
        from .sample_filter import validate_filter_covers

        validate_filter_covers(index, keep_mask)
    if use_funnel:
        expects(params.lut_dtype != "int8",
                "lut_dtype='int8' quantizes the PQ LUT; the funnel's "
                "signature tier is already 1-4 bit — use float32/bfloat16")
        # per-chunk widen pool: at least k (the rerank must fill the final
        # select), at most every slot the chunk scans
        k_widen = max(int(k), min(widen * int(k),
                                  probe_chunk * index.capacity))
        if metrics.enabled():
            _quant_funnel_total().inc()
        return _pq_search_funnel(
            index, queries, n_probes, int(k), k_widen, query_tile,
            probe_chunk, index.metric, index.codebook_kind, params.lut_dtype,
            keep_mask, select_impl=params.select_impl)
    return _pq_search(
        index, queries, n_probes, int(k), query_tile, probe_chunk, index.metric,
        index.codebook_kind, params.lut_dtype,
        keep_mask, select_impl=params.select_impl,
    )


def write_index(f, index: IvfPqIndex) -> None:
    """Serialize to an open binary stream (the composable half of
    :func:`save` — :mod:`raft_tpu.stream` embeds sealed indexes this way)."""
    serialize_header(f, "ivf_pq")
    serialize_scalar(f, int(index.metric))
    serialize_scalar(f, index.codebook_kind)
    serialize_scalar(f, index.pq_bits)
    serialize_scalar(f, float(index.split_factor))
    serialize_scalar(f, bool(index.pq_split))
    serialize_scalar(f, index.data_kind)
    for arr in (index.centers, index.centers_rot, index.rotation, index.codebooks,
                index.list_codes, index.list_ids, index.list_sizes,
                index.list_consts, index.list_scales):
        serialize_mdspan(f, arr)
    serialize_tuned(f, index.tuned)
    # raft_tpu/13 quantization-codec record (trailing, after tuned — the
    # serialize_tuned shared-layout discipline). Gated on the CURRENT
    # format version through the module attribute, so a writer pinned to
    # an older version (back-compat tests monkeypatch it) emits true
    # old-layout bytes.
    if version_number(core_serialize.SERIALIZATION_VERSION) >= 13:
        serialize_scalar(f, index.rotation_kind)
        serialize_scalar(f, index.codebook_loss)
        serialize_scalar(f, index.fast_scan)
        serialize_mdspan(f, index.list_sig)
        serialize_mdspan(f, index.sig_scales)


def read_index(f) -> IvfPqIndex:
    """Deserialize from an open binary stream (pairs with
    :func:`write_index`)."""
    ver = check_header(f, "ivf_pq")
    metric = DistanceType(deserialize_scalar(f))
    codebook_kind = deserialize_scalar(f)
    pq_bits = deserialize_scalar(f)
    split_factor = float(deserialize_scalar(f))
    pq_split = bool(deserialize_scalar(f))
    # raft_tpu/6 added data_kind (int8/uint8 byte ingestion); older
    # files could only hold float data
    kind = (deserialize_scalar(f)
            if ver not in ("raft_tpu/3", "raft_tpu/4", "raft_tpu/5")
            else "float32")
    arrs = [jnp.asarray(deserialize_mdspan(f)) for _ in range(8)]
    # raft_tpu/7 added list_scales (residual_scale_norm); older files
    # never normalized, so the disabled (0,) sentinel is exact
    if ver not in ("raft_tpu/3", "raft_tpu/4", "raft_tpu/5",
                   "raft_tpu/6"):
        arrs.append(jnp.asarray(deserialize_mdspan(f)))
    else:
        arrs.append(jnp.zeros((0,), jnp.float32))
    # raft_tpu/9 appended the optional tuned record (pinned operating
    # point); older files are untuned
    tuned = deserialize_tuned(f, ver)
    # raft_tpu/13 appended the quantization-codec record; /12-and-older
    # files carry the codec defaults exactly (no learned rotation — any
    # rotation they DO have is already folded into the serialized matrix —
    # l2 loss, no fast-scan tier)
    if version_number(ver) >= 13:
        rotation_kind = deserialize_scalar(f)
        codebook_loss = deserialize_scalar(f)
        fast_scan = deserialize_scalar(f)
        arrs.append(jnp.asarray(deserialize_mdspan(f)))  # list_sig
        arrs.append(jnp.asarray(deserialize_mdspan(f)))  # sig_scales
    else:
        rotation_kind, codebook_loss, fast_scan = "none", "l2", "none"
        n_lists = arrs[0].shape[0]
        arrs.append(jnp.zeros((n_lists, 0, 0), jnp.uint8))
        arrs.append(jnp.zeros((0,), jnp.float32))
    return IvfPqIndex(*arrs, metric=metric, codebook_kind=codebook_kind, pq_bits=pq_bits,
                      split_factor=split_factor, pq_split=pq_split,
                      data_kind=kind, rotation_kind=rotation_kind,
                      codebook_loss=codebook_loss, fast_scan=fast_scan,
                      tuned=tuned)


def save(index: IvfPqIndex, path: str) -> None:
    """Serialize (reference: ivf_pq_serialize.cuh:52-110).
    Atomic: temp file + rename, a crashed save keeps the previous file."""
    from ..core.serialize import atomic_write

    with atomic_write(path) as f:
        write_index(f, index)


def load(path: str, res: Resources | None = None) -> IvfPqIndex:
    """Deserialize (reference: ivf_pq_serialize.cuh deserialize)."""
    with open(path, "rb") as f:
        return read_index(f)


def batched_searcher(index: IvfPqIndex, params: SearchParams | None = None):
    """Stable serving hook (raft_tpu.serve; contract in :mod:`._hooks`) —
    the surface the serve registry warms and hot-swaps through. For the
    candidates+refine serving pattern, publish a hook built by the caller
    (serve accepts any callable with the hook attributes — or
    ``raft_tpu.tune.make_searcher``, which wires the refine epilogue from
    a pinned ``refine_ratio`` decision). With no explicit ``params``, an
    attached refine-free tune decision (``index.tuned``) supplies the
    operating point — docs/tuning.md."""
    from ._hooks import make_hook

    if params is None and index.tuned is not None:
        from ..tune.apply import make_searcher as tuned_searcher

        return tuned_searcher(index, True, degrade_without_rows=True)
    sp = params or SearchParams()
    return make_hook(lambda queries, k: search(sp, index, queries, k),
                     "ivf_pq", index.dim, index.data_kind)

"""Shared inverted-list machinery for IVF indexes.

The reference factors this as ivf::list (neighbors/ivf_list.hpp) shared by
IVF-Flat and IVF-PQ; same idea here: within-list position assignment for the
padded scatter, and the search-time (query_tile, probe_chunk) sizing plan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..distance.fused_nn import _fused_l2_nn
from ..distance.types import DistanceType

__all__ = ["round_up", "list_positions", "plan_search_tiles", "assign_to_lists",
           "split_oversized", "spatial_split_key", "bound_capacity",
           "pq_scan_bytes_per_probe_row", "funnel_scan_bytes_per_probe_row"]


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def list_cap_target(rows: int, n_lists: int, factor: float) -> int:
    """The shared list-capacity policy bound (:func:`bound_capacity`):
    lists larger than ``factor`` x the mean split, so allocated capacity
    is at most this. ``obs.mem.plan`` sizes its IVF estimates from the
    SAME expression — a policy change here moves both, which is what
    keeps the estimator's ±20% contract from silently drifting."""
    mean = max(rows / max(n_lists, 1), 1.0)
    return round_up(max(int(mean * factor), 8), 8)


def assign_to_lists(x, centers, metric: DistanceType, tile: int):
    """List assignment consistent with the index metric (the reference uses
    kmeans_balanced::predict with the index metric so storage placement and
    search probing agree)."""
    if metric == DistanceType.InnerProduct:
        scores = jnp.asarray(x).astype(jnp.float32) @ jnp.asarray(centers).T
        return jnp.argmax(scores, axis=1).astype(jnp.int32)
    return _fused_l2_nn(x, centers, False, tile)[1]


def list_positions(labels, n_lists: int):
    """Within-list position of each row = its rank among same-label rows,
    via one stable argsort (no (n, n_lists) intermediate).

    Returns (pos (n,) int32, counts (n_lists,) int32).
    """
    n = labels.shape[0]
    order = jnp.argsort(labels, stable=True)
    sorted_labels = jnp.take(labels, order)
    counts = jnp.bincount(labels, length=n_lists)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - jnp.take(starts, sorted_labels).astype(jnp.int32)
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    return pos, counts.astype(jnp.int32)


def split_oversized(labels, n_lists: int, cap_target: int, order_key=None):
    """Split lists larger than ``cap_target`` into sub-lists.

    The padded layout prices every list at the MAX size, so one hot cluster
    inflates all scans; bounding capacity by sub-list splitting is the
    coarse-grained analogue of the reference's fixed 32-vector interleaved
    groups (ivf_flat_build.cuh:135-153).

    ``order_key`` (optional, (n,) float) controls HOW members divide among a
    list's sub-lists: with None, by input order (arbitrary — fine when
    sub-lists share the parent's center and are probed together); with a
    per-row spatial key (e.g. projection on the list's principal axis,
    :func:`spatial_split_key`), each sub-list is a spatially coherent SLAB,
    so a caller that re-centers sub-lists on their member means gets
    differentiated coarse scores and queries probe only nearby slabs — the
    fix for Zipf-population data, where an order-split mega-cluster
    scattered every query's neighbors uniformly over ~population/cap
    identical-score sub-lists (r05 heavytail family).

    Returns ``(new_labels (n,), rep (n_lists,) host int array)`` where
    ``rep[l]`` is how many sub-lists list ``l`` became (all 1 = no change);
    the new list count is ``rep.sum()``. Callers repeat center-indexed arrays
    with ``np.repeat(arr, rep, axis=0)``.
    """
    import numpy as np

    if order_key is None:
        pos, counts = list_positions(labels, n_lists)
        counts_h = np.asarray(counts)
    else:
        # within-list rank by the spatial key: one lexicographic sort by
        # (label, key) — the proj-ordered twin of list_positions
        n = labels.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        _, _, s_idx = jax.lax.sort(
            (labels.astype(jnp.int32), order_key.astype(jnp.float32), idx),
            num_keys=2)
        counts = jnp.bincount(labels, length=n_lists)
        starts = jnp.cumsum(counts) - counts
        pos_sorted = (jnp.arange(n, dtype=jnp.int32)
                      - jnp.take(starts, jnp.take(labels, s_idx)).astype(jnp.int32))
        pos = jnp.zeros((n,), jnp.int32).at[s_idx].set(pos_sorted)
        counts_h = np.asarray(counts)
    rep = np.maximum(1, -(-counts_h // cap_target)).astype(np.int64)
    base = np.concatenate([[0], np.cumsum(rep)[:-1]]).astype(np.int32)
    new_labels = jnp.asarray(base)[labels] + (pos // cap_target).astype(jnp.int32)
    return new_labels, rep


def spatial_split_key(x, labels, n_lists: int, n_iters: int = 3):
    """Per-row projection onto its list's principal axis — the spatial
    order key for :func:`split_oversized`. Fully vectorized across lists:
    per-list means by segment sum, then ``n_iters`` power iterations of the
    per-list covariance action (each iteration is two passes over (n, d)),
    then the scalar projection. The reference reaches the same goal through
    hierarchical balanced k-means (detail/kmeans_balanced.cuh
    build_hierarchical); a principal-axis slab split is the one-shot TPU
    form (slabs are contiguous ranks, so the split stays exactly
    capacity-balanced)."""
    return _spatial_key_impl(x, labels, n_lists, n_iters)


@functools.partial(jax.jit, static_argnames=("n_lists", "n_iters"))
def _spatial_key_impl(x, labels, n_lists: int, n_iters: int):
    xf = x.astype(jnp.float32)
    n, d = xf.shape
    lab = labels.astype(jnp.int32)
    onehot_sum = jnp.zeros((n_lists, d), jnp.float32).at[lab].add(xf)
    counts = jnp.zeros((n_lists,), jnp.float32).at[lab].add(1.0)
    means = onehot_sum / jnp.maximum(counts, 1.0)[:, None]
    xc = xf - means[lab]
    key = jax.random.key(0)
    v = jax.random.normal(key, (n_lists, d), jnp.float32)

    def body(i, v):
        w = jnp.sum(xc * v[lab], axis=1)                     # (n,)
        v2 = jnp.zeros((n_lists, d), jnp.float32).at[lab].add(
            w[:, None] * xc)
        return v2 / jnp.maximum(
            jnp.linalg.norm(v2, axis=1, keepdims=True), 1e-20)

    v = jax.lax.fori_loop(0, n_iters, body, v)
    return jnp.sum(xc * v[lab], axis=1)


def bound_capacity(labels, n_lists: int, factor: float = 1.3, x=None):
    """Shared capacity policy for IVF fills: lists larger than ``factor`` x
    the mean split into sub-lists (see :func:`split_oversized`); otherwise
    capacity is the max size rounded to the sublane tile. Lower factors cut
    the padded-gather bytes every scan pays (the 1M-scale search bottleneck)
    at the cost of more sub-lists competing for probe slots.

    ``x`` (optional, (n, d)): when given, oversized lists split SPATIALLY
    along their principal axis (see :func:`split_oversized`); the caller
    should then re-center split sub-lists on their member means.

    Returns ``(labels, rep, n_lists, capacity, spatial)`` where ``rep`` is
    None when no splitting happened, else the host repeat-count array for
    center-indexed arrays (``np.repeat(arr, rep, axis=0)``), and ``spatial``
    is None or a host bool array over ORIGINAL lists marking which were
    slab-ordered (the caller should recenter exactly those lists' children).
    """
    import numpy as np

    sizes = jnp.bincount(labels, length=n_lists)
    max_size = max(int(jnp.max(sizes)), 1)
    cap_target = list_cap_target(labels.shape[0], n_lists, factor)
    if max_size <= cap_target:
        return labels, None, n_lists, round_up(max_size, 8), None
    # spatial splitting only for lists that shatter SEVERELY (>= 8
    # sub-lists — a mega-cluster the coarse trainer could not divide, e.g.
    # n_lists below the natural cluster count on population-skewed data).
    # The 8x-size threshold is a measured compromise: it sits just above
    # the hot-list tail balanced k-means leaves on ordinary clustered data
    # (isotropic-1M: max list 7.4x cap, 3 lists past 4x; recentring those
    # measured -0.0014 recall on the flagship row), at the cost of leaving
    # lists in the (4x, 8x) band on the order split, where the
    # ~n_probes/rep recall cap is partial (rep up to 8 at the default
    # p=8) rather than the catastrophic many-fold cap this path exists
    # to fix.
    # Mild splits keep the order split + duplicated centers bit-for-bit:
    # siblings tie in coarse score and are probed together, and an r05 A/B
    # measured the spatial form ~0.001-0.003 recall WORSE there
    # (recentring perturbs probe ranking for no coverage gain), while on a
    # shattered mega-cluster the order split caps recall at ~n_probes/rep
    # (tests/test_ivf_flat.py::test_spatial_split_recall_on_skewed_population).
    # Selectivity is PER LIST: the spatial key applies only to severe
    # lists' rows (everyone else keys to 0, and the stable sort preserves
    # their input order exactly), and `spatial` reports which original
    # lists were slab-ordered so the caller recenters exactly those.
    order_key = None
    spatial = None
    severe_h = np.asarray(sizes) >= 8 * cap_target
    if x is not None and severe_h.any():
        proj = spatial_split_key(x, labels, n_lists)
        severe = jnp.asarray(severe_h)
        order_key = jnp.where(severe[labels], proj, 0.0)
        spatial = severe_h
    new_labels, rep = split_oversized(labels, n_lists, cap_target, order_key)
    return new_labels, rep, int(rep.sum()), cap_target, spatial


def pq_scan_bytes_per_probe_row(capacity: int, pq_dim: int, n_codes: int) -> int:
    """Memory model for one (query, probe) pair of the PQ LUT scan, shared by
    the single-chip and distributed searches: codes gather (uint8) + gathered
    LUT values (f32) + scores (f32) per capacity slot, plus the LUT itself;
    x2 for XLA temporaries (the gather and its consumer co-exist) —
    undercounting here OOMed the device at 1M scale."""
    return 2 * (capacity * pq_dim * 9 + pq_dim * n_codes * 8)


def funnel_scan_bytes_per_probe_row(capacity: int, sig_words: int) -> int:
    """Memory model for one (query, probe) pair of the fast-scan funnel's
    binary tier (ivf_pq fast_scan): packed signature gather (uint8) +
    estimator scores (f32) per capacity slot, plus the 32-entry nibble LUT;
    same x2 temporaries convention as :func:`pq_scan_bytes_per_probe_row`.
    The PQ rerank that follows touches only the k_widen survivors, so the
    binary tier dominates the per-probe footprint."""
    return 2 * (capacity * (sig_words * 9 + 4) + sig_words * 32 * 8)


def plan_search_tiles(m: int, n_probes: int, k: int, capacity: int,
                      bytes_per_probe_row: int, budget_bytes: int,
                      max_query_tile: int = 256):
    """Pick (query_tile, probe_chunk) so the per-step gather block fits the
    workspace budget while every chunk still holds >= k candidates — the
    shared analogue of the reference's memory-aware tile sizing
    (knn_brute_force.cuh:78 applied to list scans).

    ``bytes_per_probe_row``: bytes a single (query, probe) pair contributes
    (list payload + LUT etc.).
    """
    min_chunk = -(-k // capacity)
    if min_chunk > n_probes:
        raise ValueError(
            f"k={k} exceeds the probed candidate pool "
            f"(n_probes={n_probes} x capacity={capacity})"
        )
    probe_chunk = n_probes
    query_tile = min(m, max_query_tile)

    def cost(qt, pc):
        return qt * pc * bytes_per_probe_row

    while probe_chunk // 2 >= min_chunk and probe_chunk % 2 == 0 and cost(query_tile, probe_chunk) > budget_bytes:
        probe_chunk //= 2
    while query_tile > 8 and cost(query_tile, probe_chunk) > budget_bytes:
        query_tile //= 2
    while n_probes % probe_chunk:
        probe_chunk -= 1
    probe_chunk = max(probe_chunk, min_chunk)
    while n_probes % probe_chunk:
        probe_chunk += 1
    return query_tile, probe_chunk

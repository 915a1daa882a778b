"""Exact re-ranking of ANN candidate lists.

Re-design of the reference's refine (cpp/include/raft/neighbors/refine.cuh;
detail/refine.cuh refine_device :80 / refine_host :169). Gather each query's
candidate vectors, compute exact distances, keep the best k — one batched
gather + one batched distance contraction on TPU, no per-query kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core import tracing
from ..core.errors import expects
from ..core.resources import Resources, default_resources
from ..distance.types import DistanceType, resolve_metric

__all__ = ["refine", "refine_gathered"]


def _score_candidates(cand_vecs, queries, candidates, k: int,
                      metric: DistanceType):
    """Exact re-rank of PRE-GATHERED candidate rows — the scoring body
    shared (traced, not called) by both jitted entry points, so the
    all-HBM gather-inside-jit path and the tiered host-gather path run
    the IDENTICAL scoring program (the tiered-vs-HBM bit-parity contract
    rides on it)."""
    valid = candidates >= 0  # negative ids = padding slots
    q = queries[:, None, :].astype(jnp.float32)
    c = cand_vecs.astype(jnp.float32)
    if metric == DistanceType.InnerProduct:
        scores = jnp.einsum("mkd,mod->mk", c, q)
        scores = jnp.where(valid, scores, -jnp.inf)
        top_v, top_pos = lax.top_k(scores, k)
    else:
        d2 = jnp.sum(jnp.square(c - q), axis=-1)  # (m, k0)
        if metric in (DistanceType.L2SqrtExpanded, DistanceType.L2SqrtUnexpanded):
            d2 = jnp.sqrt(jnp.maximum(d2, 0.0))
        d2 = jnp.where(valid, d2, jnp.inf)
        top_v, top_pos = lax.top_k(-d2, k)
        top_v = -top_v
    ids = jnp.where(
        jnp.take_along_axis(valid, top_pos, axis=1),
        jnp.take_along_axis(candidates, top_pos, axis=1),
        -1,
    )
    return top_v, ids.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _refine(dataset, queries, candidates, k: int, metric: DistanceType):
    with tracing.scope("refine"):
        safe = jnp.maximum(candidates, 0)
        cand_vecs = jnp.take(dataset, safe, axis=0)  # (m, k0, d)
        return _score_candidates(cand_vecs, queries, candidates, k, metric)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _refine_gathered(cand_vecs, queries, candidates, k: int,
                     metric: DistanceType):
    with tracing.scope("refine"):
        return _score_candidates(cand_vecs, queries, candidates, k, metric)


def refine(dataset, queries, candidates, k: int, metric="sqeuclidean", res: Resources | None = None):
    """Re-rank ``candidates`` (m, k0) by exact distance; return the top
    ``k <= k0`` (reference: neighbors/refine.cuh, pylibraft
    neighbors/refine.pyx). Negative candidate ids are treated as padding:
    they sort last (distance ±inf) and surface as id -1."""
    res = res or default_resources()
    dataset = jnp.asarray(dataset)
    queries = jnp.asarray(queries)
    candidates = jnp.asarray(candidates).astype(jnp.int32)
    expects(candidates.ndim == 2 and candidates.shape[0] == queries.shape[0],
            "candidates must be (n_queries, k0)")
    expects(k <= candidates.shape[1], "k must be <= candidate width")
    mt = resolve_metric(metric)
    return _refine(dataset, queries, candidates, int(k), mt)


def refine_gathered(cand_vecs, queries, candidates, k: int,
                    metric="sqeuclidean"):
    """:func:`refine` over candidate rows ALREADY gathered to the device
    — the tiered-storage refine epilogue: the (m, k0, d) ``cand_vecs``
    arrive through :meth:`raft_tpu.stream.tiered.TieredStore.fetch`'s
    double-buffered host→device hop (or a device-mirror gather), and this
    runs exactly the scoring program :func:`refine` traces after its own
    in-jit gather — same k0 candidates, bit-identical distances. Negative
    ``candidates`` are padding: their (arbitrary) gathered row is masked,
    sorts last, and surfaces as id ``-1``."""
    queries = jnp.asarray(queries)
    cand_vecs = jnp.asarray(cand_vecs)
    candidates = jnp.asarray(candidates).astype(jnp.int32)
    expects(candidates.ndim == 2 and candidates.shape[0] == queries.shape[0],
            "candidates must be (n_queries, k0)")
    expects(cand_vecs.shape[:2] == candidates.shape,
            "cand_vecs must be (n_queries, k0, d) matching candidates")
    expects(k <= candidates.shape[1], "k must be <= candidate width")
    return _refine_gathered(cand_vecs, queries, candidates, int(k),
                            resolve_metric(metric))

"""The plain reference for squared-L2 k-NN, and the numbers that judge an
answer against it. Imports nothing of the system under test.

``exact_topk`` is ``chip_smoke.py``'s ``exact_reference`` (PR 21): top
``pool`` candidates from the expanded-form distance at HIGHEST matmul
precision, re-ranked exactly in float64 on the host (f32 cancellation can
swap near neighbours, so ``pool`` >> k).

``judge`` compares served answers row by row:

- ``dist_err``: the widest gap between a served distance and the float64
  distance of the id it was served with, as a share of the row's scale
  ``|q|^2 + max |x|^2`` (the magnitude the expanded form cancels). An id
  outside the corpus, a repeated id in one row, or a distance that is not
  finite reads ``inf``. This catches an id, a distance or a row that was
  altered, swapped or left out anywhere on the path.
- ``rank_gap``: the widest gap by which a served neighbour's exact
  distance lies beyond the reference's k-th, on the same scale — the
  exact-search number (chip_smoke's ``recall_up_to_ties``, read as a gap
  instead of a tolerance test).
- ``recall``: mean recall@k of the served ids against the reference ids.
"""

from __future__ import annotations

import functools

import numpy as np


def exact_topk(corpus, queries, k: int, pool: int = 64, block: int = 100):
    """Exact top-k ids (int64) and float64 squared distances of
    ``queries`` (host or device, (m, d)) in the device ``corpus``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def candidates(x, qb):
        xn = jnp.sum(x * x, axis=1)
        dots = jnp.matmul(qb, x.T, precision=lax.Precision.HIGHEST)
        _, idx = lax.top_k(2.0 * dots - xn[None, :], pool)
        return idx, x[idx]

    queries = np.asarray(queries, np.float32)
    ids, dists = [], []
    for s in range(0, queries.shape[0], block):
        qb = queries[s:s + block]
        if qb.shape[0] < block:  # one program shape for every block
            qb = np.concatenate([qb, np.zeros((block - qb.shape[0],
                                               qb.shape[1]), np.float32)])
        idx, vecs = candidates(corpus, jnp.asarray(qb))
        n = min(block, queries.shape[0] - s)
        idx, vecs = np.asarray(idx)[:n], np.asarray(vecs, np.float64)[:n]
        d = ((vecs - qb[:n, None, :].astype(np.float64)) ** 2).sum(-1)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        ids.append(np.take_along_axis(idx, order, axis=1).astype(np.int64))
        dists.append(np.take_along_axis(d, order, axis=1))
    return np.concatenate(ids), np.concatenate(dists)


def bf16_search(corpus):
    """The reference computed in bfloat16 (inputs rounded to bf16, f32
    accumulation): ``fn(queries, k) -> (distances, ids)`` — the control
    that a lower-precision path would give."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    xb = jnp.asarray(corpus).astype(jnp.bfloat16)
    xn = jnp.sum(jnp.square(xb.astype(jnp.float32)), axis=1)

    @functools.partial(jax.jit, static_argnames=("k",))
    def search(xb, xn, q, k):
        qb = q.astype(jnp.bfloat16)
        dots = jnp.matmul(qb, xb.T, preferred_element_type=jnp.float32)
        _, idx = lax.top_k(2.0 * dots - xn[None, :], k)
        diff = xb[idx] - qb[:, None, :]
        d = jnp.sum(jnp.square(diff).astype(jnp.float32), axis=-1)
        return d.astype(jnp.bfloat16).astype(jnp.float32), idx

    return lambda queries, k: search(xb, xn, jnp.asarray(queries), int(k))


def served(corpus, queries, qidx, ids, dists, chunk: int = 4096):
    """What every served entry is, row by row: ``(d64, scale, valid,
    bad)``. ``d64`` (m, k) is the float64 distance of each served id (of
    row 0 where the id lies outside the corpus), ``scale`` (m,) the row's
    ``|q|^2 + max |x|^2``, ``valid`` (m, k) whether the id lies inside the
    corpus, and ``bad`` (m,) a row with an id outside it, a repeated id or
    a distance that is not finite."""
    ids = np.asarray(ids, np.int64)
    m, k = ids.shape
    valid = (ids >= 0) & (ids < corpus.shape[0])
    srt = np.sort(ids, axis=1)
    bad = (~valid.all(1) | (srt[:, 1:] == srt[:, :-1]).any(1)
           | ~np.isfinite(dists).all(1))
    safe = np.where(valid, ids, 0)
    d64 = np.empty((m, k))
    scale = np.empty(m)
    for s in range(0, m, chunk):
        e = slice(s, s + chunk)
        q = queries[qidx[e]].astype(np.float64)
        v = corpus[safe[e]].astype(np.float64)
        d64[e] = ((v - q[:, None, :]) ** 2).sum(-1)
        scale[e] = (q * q).sum(1) + (v * v).sum(-1).max(1)
    return d64, scale, valid, bad


def judge(corpus, queries, judged, ref_ids, ref_d, qidx, ids, dists,
          chunk: int = 4096) -> dict:
    """Numbers for the answers ``(ids, dists)`` (m, k) served to query
    rows ``qidx`` (m,). ``corpus`` (N, d) and ``queries`` are host f32;
    ``judged`` is the sorted array of query indices the reference
    ``(ref_ids, ref_d)`` covers, and every ``qidx`` is among them."""
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float64)
    m, k = ids.shape
    pos = np.searchsorted(judged, qidx)
    if m == 0 or not np.array_equal(judged[np.minimum(pos, len(judged) - 1)],
                                    qidx):
        raise ValueError("every judged answer needs a reference row")
    d64, scale, _, bad = served(corpus, queries, qidx, ids, dists, chunk)
    err = np.abs(dists - d64).max(1) / scale
    gap = np.maximum(d64 - ref_d[pos, k - 1:k], 0.0).max(1) / scale
    err[bad] = np.inf
    gap[bad] = np.inf
    hits = (ids[:, :, None] == ref_ids[pos][:, None, :k]).any(2).sum(1)
    return {"dist_err": float(err.max()), "rank_gap": float(gap.max()),
            "recall": float(hits.mean() / k), "rows": int(m)}

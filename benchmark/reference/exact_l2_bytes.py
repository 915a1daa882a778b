"""The plain reference for squared-L2 k-NN over a uint8 corpus, and the
numbers that judge an answer against it. Imports nothing of the system
under test.

``exact_topk`` blocks over corpus rows (``block`` at a time), casts each
block to float32 and takes its top ``pool`` candidates of the
expanded-form score ``2 q.x - |x|^2`` at HIGHEST matmul precision, merges
the blocks' candidates, then re-ranks them exactly in float64 on the
host. For bytes every term is an integer below 2**24 (``|x|^2``, ``q.x``
<= 255^2 * 128 = 8,323,200 and the score <= 2 * that), so the float32
scores are exact and the blocks never hold a float copy of the whole
corpus: one block's float32 rows (512 MB at 1M rows) is the largest
temporary. Ties keep the lower row id first, block by block, as one
``lax.top_k`` over the whole corpus does, so on the same rows cast to
float32 the answer equals ``exact_l2.exact_topk``'s.

``judge`` gives ``exact_l2``'s ``dist_err`` and ``rank_gap`` (the served
entries through ``exact_l2.served``, which casts the bytes to float64
before any arithmetic), and a recall up to ties: a served id counts as a
hit where it is among the reference's ids or its exact distance equals
the reference's k-th. Byte distances are integers, so at 10M rows the
k-th distance is often shared and an id-only recall would punish a
correct answer.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.reference import exact_l2

BLOCK = 1 << 20


@functools.cache
def _candidates_fn():
    import jax
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(jax.jit, static_argnames=("pool", "block"))
    def candidates(corpus, xn, q, pool: int, block: int):
        """Top ``pool`` row ids of ``q`` (b, d) by the expanded-form
        score, block by block over the corpus rows, merged."""
        qf = q.astype(jnp.float32)
        scores, ids = [], []
        for s in range(0, corpus.shape[0], block):
            x = corpus[s:s + block].astype(jnp.float32)
            dots = jnp.matmul(qf, x.T, precision=lax.Precision.HIGHEST)
            sc, ix = lax.top_k(2.0 * dots - xn[None, s:s + block],
                               min(pool, x.shape[0]))
            scores.append(sc)
            ids.append(ix + s)
        _, pos = lax.top_k(jnp.concatenate(scores, 1), pool)
        return jnp.take_along_axis(jnp.concatenate(ids, 1), pos, axis=1)

    @jax.jit
    def norms(corpus):
        return jnp.sum(jnp.square(corpus.astype(jnp.float32)), axis=1)

    return candidates, norms


def exact_topk(corpus, queries, k: int, pool: int = 64, block: int = 100,
               rows: int = BLOCK):
    """Exact top-k ids (int64) and float64 squared distances of
    ``queries`` (host or device, (m, d)) in the device uint8 ``corpus``,
    ``rows`` corpus rows and ``block`` queries at a time."""
    import jax.numpy as jnp

    candidates, norms = _candidates_fn()
    xn = norms(corpus)
    pool = min(pool, corpus.shape[0])
    queries = np.asarray(queries)
    ids, dists = [], []
    for s in range(0, queries.shape[0], block):
        qb = queries[s:s + block]
        n = qb.shape[0]
        if n < block:  # one program shape for every block
            qb = np.concatenate([qb, np.zeros((block - n, qb.shape[1]),
                                              qb.dtype)])
        idx = candidates(corpus, xn, jnp.asarray(qb), pool,
                         min(rows, corpus.shape[0]))
        vecs = np.asarray(jnp.take(corpus, idx, axis=0), np.float64)[:n]
        idx = np.asarray(idx)[:n]
        d = ((vecs - qb[:n, None, :].astype(np.float64)) ** 2).sum(-1)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        ids.append(np.take_along_axis(idx, order, axis=1).astype(np.int64))
        dists.append(np.take_along_axis(d, order, axis=1))
    return np.concatenate(ids), np.concatenate(dists)


def bf16_rounded_search(corpus, rows: int = BLOCK):
    """The control: this reference's exact answer with its distances
    rounded to bfloat16, ``fn(queries, k) -> (distances, ids)`` — what a
    path that scored the final distances in bfloat16 would serve."""
    import jax
    import jax.numpy as jnp

    candidates, norms = _candidates_fn()
    xn = norms(corpus)
    block = min(rows, corpus.shape[0])

    @functools.partial(jax.jit, static_argnames=("k",))
    def search(corpus, xn, q, k):
        idx = candidates(corpus, xn, q, k, block)
        diff = (corpus[idx].astype(jnp.float32)
                - q.astype(jnp.float32)[:, None, :])
        d = jnp.sum(jnp.square(diff), axis=-1)
        return d.astype(jnp.bfloat16).astype(jnp.float32), idx

    return lambda queries, k: search(corpus, xn, jnp.asarray(queries),
                                     int(k))


def judge(corpus, queries, judged, ref_ids, ref_d, qidx, ids, dists,
          chunk: int = 4096) -> dict:
    """Numbers for the answers ``(ids, dists)`` (m, k) served to query
    rows ``qidx`` (m,), as ``exact_l2.judge`` gives them, with the recall
    up to ties. ``corpus`` (N, d) and ``queries`` are host uint8."""
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float64)
    m, k = ids.shape
    pos = np.searchsorted(judged, qidx)
    if m == 0 or not np.array_equal(judged[np.minimum(pos, len(judged) - 1)],
                                    qidx):
        raise ValueError("every judged answer needs a reference row")
    d64, scale, valid, bad = exact_l2.served(corpus, queries, qidx, ids,
                                             dists, chunk)
    kth = ref_d[pos, k - 1:k]
    err = np.abs(dists - d64).max(1) / scale
    gap = np.maximum(d64 - kth, 0.0).max(1) / scale
    err[bad] = np.inf
    gap[bad] = np.inf
    hits = ((ids[:, :, None] == ref_ids[pos][:, None, :k]).any(2)
            | (valid & (d64 == kth))).sum(1)
    return {"dist_err": float(err.max()), "rank_gap": float(gap.max()),
            "recall": float(hits.mean() / k), "rows": int(m)}

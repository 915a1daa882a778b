"""The plain reference for squared-L2 k-NN over a corpus that changes while
it is served, and the numbers that judge a window of answers against it.
Imports nothing of the system under test.

The configuration's ``stream`` block holds the corpus's last ``held_out``
rows out of the build; the traffic's writer inserts them under their row
index and deletes earlier rows (``loadgen.WriteLog``). A judged row,
submitted at ``t_s`` and answered at ``t_d``, is judged against

- the live set ``L(t_s)``: the base rows, plus the rows whose insert was
  acknowledged before ``t_s``, less those whose delete was;
- a write whose call overlaps ``[t_s, t_d]`` may or may not be seen.

``judge_record`` gives:

- ``dist_err``: as ``exact_l2.judge`` (float64 distance of each served id,
  on the scale ``|q|^2 + max |x|^2``; a bad, repeated or non-finite entry
  reads inf), over the served ids of base rows;
- ``dist_err_fresh``: the same over served ids of inserted rows, which
  the delta's exact scan produced;
- ``stale_served``: served ids that could not be live anywhere in
  ``[t_s, t_d]``: deleted by a call acknowledged before ``t_s``, or
  inserted by a call that started after ``t_d`` (or never);
- ``missed_fresh``: inserted rows acknowledged before ``t_s`` and not
  deleted by a call started before ``t_d``, whose float64 distance lies
  below the served k-th's by more than ``miss_margin`` times the scale,
  yet not served;
- ``recall``: mean recall@k against the top k of ``L(t_s)``.

The top k of ``L(t_s)``: the base rows that are live among the candidate
pool ``exact_l2.exact_topk`` gives over every row (HIGHEST precision,
float64 re-rank), and the inserted rows live at ``t_s`` among each judged
query's nearest, ranked exactly in float64. Where the k-th lies beyond
what the pool or those nearest rows cover, the row is computed over
``L(t_s)`` in full (``full_rows`` counts them).
"""

from __future__ import annotations

import functools
import threading

import numpy as np

from benchmark.reference.exact_l2 import exact_topk, served

POOL = 64  # pool positions kept (exact_topk ranks twice as many in f32)
FRESH = 64  # nearest inserted rows kept per judged query


def write_times(n: int, base_n: int, writes):
    """``(ins_call, ins_ack, del_call, del_ack)`` of every id, (n,) each:
    -inf for the inserts of base rows, +inf for what never happened, and
    +inf for the ack of a call that raised (it may have landed or not)."""
    ins_call, ins_ack, del_call, del_ack = (np.full(n, np.inf)
                                            for _ in range(4))
    ins_call[:base_n] = ins_ack[:base_n] = -np.inf
    if writes is not None:
        for ids, call, ack, c_out, a_out in (
                (writes.ins_ids, writes.ins_call, writes.ins_ack,
                 ins_call, ins_ack),
                (writes.del_ids, writes.del_call, writes.del_ack,
                 del_call, del_ack)):
            for t, out in ((call, c_out), (ack, a_out)):
                out[ids] = np.where(np.isnan(t), np.inf, t)[:, None]
    return ins_call, ins_ack, del_call, del_ack


def row_times(rec):
    """``(t_s, t_d)`` of every answered row of ``rec``, in ``rec.qidx``'s
    order: its request's submit (closed loop) or send (open loop) time,
    and its answer time."""
    ok = ~np.isnan(rec.done)
    sent = rec.start if rec.send is None else rec.send
    return (np.repeat(sent[ok], rec.rows[ok]),
            np.repeat(rec.done[ok], rec.rows[ok]))


def _full_row(corpus_host, q, alive, k: int, chunk: int = 65536):
    """Exact top-k ids and float64 distances of ``q`` over the rows
    ``alive``, on the host."""
    q = q.astype(np.float64)
    best_i, best_d = np.zeros(0, np.int64), np.zeros(0)
    for s in range(0, corpus_host.shape[0], chunk):
        rows = np.flatnonzero(alive[s:s + chunk]) + s
        d = ((corpus_host[rows].astype(np.float64) - q) ** 2).sum(1)
        best_i = np.concatenate([best_i, rows])
        best_d = np.concatenate([best_d, d])
        top = np.argsort(best_d, kind="stable")[:k]
        best_i, best_d = best_i[top], best_d[top]
    return best_i, best_d


def judge_record(cfg: dict, corpus, corpus_host, queries, judged, rec,
                 chunk: int = 4096) -> dict:
    """Numbers for every answer in ``rec`` (a ``loadgen.Record``) to a
    query in ``judged`` (sorted query indices), against the live set.
    ``corpus`` is the device corpus, ``corpus_host`` and ``queries`` host
    float32."""
    k = int(cfg["k"])
    n = corpus_host.shape[0]
    base_n = n - int(cfg["stream"]["held_out"])
    margin = float(cfg["stream"]["miss_margin"])
    keep = np.isin(rec.qidx, judged)
    if not keep.any():
        return {"dist_err": np.inf, "dist_err_fresh": np.inf,
                "stale_served": 0, "missed_fresh": 0, "recall": 0.0,
                "rows": 0, "full_rows": 0}
    qidx = rec.qidx[keep]
    ids = np.asarray(rec.ids[keep], np.int64)
    dists = np.asarray(rec.dists[keep], np.float64)
    t_s, t_d = (t[keep][:, None] for t in row_times(rec))
    m = len(qidx)
    pos = np.searchsorted(judged, qidx)
    ins_call, ins_ack, del_call, del_ack = write_times(n, base_n, rec.writes)

    # every served entry: its exact distance, and whether it is valid
    d64, scale, valid, bad = served(corpus_host, queries, qidx, ids, dists,
                                    chunk)
    safe = np.where(valid, ids, 0)
    err = np.abs(dists - d64) / scale[:, None]
    fresh = valid & (ids >= base_n)
    dist_err = (np.inf if bad.any() else
                float(np.max(err, initial=0.0, where=~fresh)))
    dist_err_fresh = float(np.max(err, initial=0.0,
                                  where=fresh & ~bad[:, None]))
    stale = valid & ((del_ack[safe] < t_s) | (ins_call[safe] > t_d))

    # every judged query against every held-out row, exactly
    qj = queries[judged].astype(np.float64)
    held = corpus_host[base_n:].astype(np.float64)
    dh = ((qj * qj).sum(1)[:, None] + (held * held).sum(1)[None, :]
          - 2.0 * qj @ held.T)
    order = np.argsort(dh, axis=1, kind="stable")
    dh_sorted = np.take_along_axis(dh, order, axis=1)

    # missed_fresh: live held-out rows nearer than the served k-th
    kth = np.where(valid, d64, np.inf).max(1)
    thr = kth - margin * scale
    below = np.zeros(m, np.int64)
    by_query = np.argsort(pos, kind="stable")
    edges = np.searchsorted(pos[by_query], np.arange(len(judged) + 1))
    for j in range(len(judged)):
        rows = by_query[edges[j]:edges[j + 1]]
        below[rows] = np.searchsorted(dh_sorted[j], thr[rows])
    missed = 0
    for r in np.flatnonzero(below):
        cand = base_n + order[pos[r], :below[r]]
        sure = (ins_ack[cand] < t_s[r, 0]) & (del_call[cand] > t_d[r, 0])
        missed += int((sure & ~np.isin(cand, ids[r])).sum())

    # recall: the top k of L(t_s)
    pool_ids, pool_d = exact_topk(corpus, queries[judged], POOL,
                                  pool=2 * POOL)
    p_ids, p_d = pool_ids[pos], pool_d[pos]
    live_p = (p_ids < base_n) & (del_ack[p_ids] >= t_s)
    nf = min(FRESH, n - base_n)
    f_ids = base_n + order[pos, :nf]
    live_f = (ins_ack[f_ids] < t_s) & (del_ack[f_ids] >= t_s)
    cand_d = np.concatenate([np.where(live_p, p_d, np.inf),
                             np.where(live_f, dh_sorted[pos, :nf], np.inf)], 1)
    cand_i = np.concatenate([p_ids, f_ids], 1)
    top = np.argsort(cand_d, axis=1, kind="stable")[:, :k]
    ref_ids = np.take_along_axis(cand_i, top, axis=1)
    ref_kth = np.take_along_axis(cand_d, top, axis=1)[:, -1]
    covered = ref_kth < pool_d[pos, -1]
    if nf < n - base_n:
        covered &= ref_kth < dh_sorted[pos, nf]
    full = np.flatnonzero(~covered)
    for r in full:
        alive = (ins_ack < t_s[r, 0]) & (del_ack >= t_s[r, 0])
        ref_ids[r] = _full_row(corpus_host, queries[qidx[r]], alive, k)[0]
    hits = (ids[:, :, None] == ref_ids[:, None, :]).any(2).sum(1)
    return {"dist_err": dist_err, "dist_err_fresh": dist_err_fresh,
            "stale_served": int(stale.sum()), "missed_fresh": missed,
            "recall": float(hits.mean() / k), "rows": int(m),
            "full_rows": int(len(full))}


@functools.lru_cache(maxsize=None)
def _bf16_knn():
    import jax
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(jax.jit, static_argnames=("k",))
    def search(xb, xn, alive, q, k):
        qb = q.astype(jnp.bfloat16)
        dots = jnp.matmul(qb, xb.T, preferred_element_type=jnp.float32)
        score = jnp.where(alive[None, :], 2.0 * dots - xn[None, :], -jnp.inf)
        _, idx = lax.top_k(score, k)
        diff = xb[idx] - qb[:, None, :]
        d = jnp.sum(jnp.square(diff).astype(jnp.float32), axis=-1)
        return d.astype(jnp.bfloat16).astype(jnp.float32), idx

    return search


class Bf16Live:
    """The reference over the live set, computed in bfloat16 (inputs
    rounded to bf16, f32 accumulation), to stand in the program's place:
    the control. ``upsert`` and ``delete`` flip an alive mask over the
    corpus's rows, and ``search`` scans the rows alive. An upserted row is
    taken to be the corpus's own row under its id, which is what the
    stream's writer inserts."""

    def __init__(self, corpus, base_n: int):
        import jax.numpy as jnp

        self._jnp = jnp
        self._xb = jnp.asarray(corpus).astype(jnp.bfloat16)
        self._xn = jnp.sum(jnp.square(self._xb.astype(jnp.float32)), axis=1)
        self._alive = np.arange(corpus.shape[0]) < base_n
        self._alive_dev = jnp.asarray(self._alive)
        self._lock = threading.Lock()

    def _set(self, ids, value: bool) -> None:
        with self._lock:
            alive = self._alive.copy()
            alive[np.asarray(ids, np.int64).reshape(-1)] = value
            self._alive, self._alive_dev = alive, self._jnp.asarray(alive)

    def upsert(self, rows, ids, res=None):
        self._set(ids, True)
        return np.asarray(ids, np.int64)

    def delete(self, ids) -> int:
        self._set(ids, False)
        return int(np.asarray(ids).size)

    def search(self, queries, k: int):
        return _bf16_knn()(self._xb, self._xn, self._alive_dev,
                           self._jnp.asarray(queries), int(k))

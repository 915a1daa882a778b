"""The least work an IVF-PQ flush over a uint8 corpus needs, counted from
the algorithm as ``work/ivf_pq.py`` counts it:

- bytes: the coarse centres once (float32); the codes of each distinct
  probed list once, at ``pq_dim * pq_bits / 8`` bytes per row; the refine
  rows, ``B * k * refine_ratio * d`` at 1 byte an element;
- flops: the coarse product ``2 * B * n_lists * d`` and the LUT build
  ``2 * B * n_probes * 2**pq_bits * d``.

The index ingests bytes shifted by -128 (the s8 domain) and its centres
live there, so the flush's queries shift the same way before the probed
lists are ranked (nearest ``n_probes`` by squared L2, as the coarse stage
ranks them)."""

from __future__ import annotations

import numpy as np


def flush_work(cfg: dict, info: dict, queries) -> tuple[float, float]:
    """``(flops, bytes)`` of one flush answering uint8 ``queries`` (B, d)."""
    ix = cfg["index"]
    centers, sizes = info["centers"], info["list_sizes"]
    q = np.asarray(queries, np.float64) - 128.0
    b, d = q.shape
    n_lists = centers.shape[0]
    p = min(ix["n_probes"], n_lists)
    c = centers.astype(np.float64)
    score = (c * c).sum(1)[None, :] - 2.0 * q @ c.T
    probed = np.unique(np.argpartition(score, p - 1, axis=1)[:, :p])
    code_bytes = float(sizes[probed].sum()) * ix["pq_dim"] * ix["pq_bits"] / 8
    nbytes = (n_lists * d * 4 + code_bytes
              + b * cfg["k"] * ix["refine_ratio"] * d)
    flops = 2.0 * b * n_lists * d + 2.0 * b * p * (2 ** ix["pq_bits"]) * d
    return flops, nbytes

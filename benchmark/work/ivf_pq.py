"""The least work an IVF-PQ flush needs, counted from the algorithm and not
from the implementation, so no implementation can read above 100%:

- bytes: the coarse centres once; the codes of each distinct probed list
  once, at ``pq_dim * pq_bits / 8`` bytes per row; the refine rows,
  ``B * k * refine_ratio * d * 4``;
- flops: the coarse product ``2 * B * n_lists * d`` and the LUT build
  ``2 * B * n_probes * 2**pq_bits * d``.

The probed lists are found here, from the flush's queries and the built
index's centres (nearest ``n_probes`` by squared L2, as the coarse stage
ranks them)."""

from __future__ import annotations

import numpy as np


def flush_work(cfg: dict, info: dict, queries) -> tuple[float, float]:
    """``(flops, bytes)`` of one flush answering ``queries`` (B, d)."""
    ix = cfg["index"]
    centers, sizes = info["centers"], info["list_sizes"]
    q = np.asarray(queries, np.float64)
    b, d = q.shape
    n_lists = centers.shape[0]
    p = min(ix["n_probes"], n_lists)
    c = centers.astype(np.float64)
    score = (c * c).sum(1)[None, :] - 2.0 * q @ c.T
    probed = np.unique(np.argpartition(score, p - 1, axis=1)[:, :p])
    code_bytes = float(sizes[probed].sum()) * ix["pq_dim"] * ix["pq_bits"] / 8
    nbytes = (n_lists * d * 4 + code_bytes
              + b * cfg["k"] * ix["refine_ratio"] * d * 4)
    flops = 2.0 * b * n_lists * d + 2.0 * b * p * (2 ** ix["pq_bits"]) * d
    return flops, nbytes

"""The least work a CAGRA flush needs, counted from the algorithm at a fixed
hop count and a fixed seed pool, not from the implementation or its tuning,
so no implementation can read above 100% and no retuning moves the
yardstick:

- seed: a pool of ``SEED_POOL`` rows (or every row of a smaller corpus)
  read once, ``pool * d * 4`` bytes, and scored against every query,
  ``2 * B * pool * d`` flops. 65,536 is the pool the library's auto setting
  resolved to for the SIFT-1M graph on a TPU v5e;
- hops: ``itopk_size / search_width`` hops, one expansion for each beam
  slot. A hop covers ``B * search_width * graph_degree`` candidates, each
  ``d * itemsize + 4`` bytes (its vector and its graph entry) and ``2 * d``
  flops.

At the cell's shape (``B`` 64, ``d`` 128, f32, pool 65,536, degree 32,
itopk 32, width 1): seed 33,554,432 bytes and 1,073,741,824 flops; 32 hops
of 2,048 candidates, 33,816,576 bytes and 16,777,216 flops; 67,371,008
bytes and 1,090,519,040 flops a flush, bound by the bytes (82 us at 819
GB/s).

The floor is a lower bound on the hops: a beam of ``itopk`` is done only
when each of its slots has been expanded once, ``search_width`` slots a
hop. The served loop runs further while a query's beam keeps changing, up
to ``itopk / width + 10`` hops: a traced TPU v5e window of the cell made
37 to 43 hop-kernel launches a flush, 42.7 on average, the first of them
the loop's priming launch."""

from __future__ import annotations

import numpy as np

SEED_POOL = 65_536


def flush_work(cfg: dict, info: dict, queries) -> tuple[float, float]:
    """``(flops, bytes)`` of one flush answering ``queries`` (B, d)."""
    ix, data = cfg["index"], cfg["data"]
    b, d = queries.shape[0], data["dim"]
    pool = min(SEED_POOL, data["rows"])
    itemsize = np.dtype(data["dtype"]).itemsize
    hops = ix["itopk_size"] // ix["search_width"]
    cands = hops * b * ix["search_width"] * ix["graph_degree"]
    nbytes = pool * d * 4 + cands * (d * itemsize + 4)
    flops = 2.0 * b * pool * d + cands * 2.0 * d
    return float(flops), float(nbytes)

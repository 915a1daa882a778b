"""The least work an exact flush needs: the corpus and its norms read once,
and ``2 * B * N * d`` flops."""

from __future__ import annotations


def flush_work(cfg: dict, info: dict, queries) -> tuple[float, float]:
    b = queries.shape[0]
    n, d = cfg["data"]["rows"], cfg["data"]["dim"]
    return 2.0 * b * n * d, float(n * d * 4 + n * 4)

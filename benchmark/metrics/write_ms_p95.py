"""Nearest-rank 95th percentile, in ms, of the write steps' latency: from
each step's due time to its last acknowledgement (a step whose call failed
counts as missing every limit). The writer and the flush worker share the
host, so it moves p99_ms."""

from benchmark.harness import percentile


def read(ctx):
    w = ctx.rec.writes
    return None if w is None else 1e3 * percentile(w.latency(), 95.0)

"""99th percentile, in ms, of the client-side time of every request
answered in the window (from submit in a closed loop, from the due time in
an open loop); a failed request counts as missing every limit."""

from benchmark.harness import percentile


def read(ctx):
    return 1e3 * percentile(ctx.latencies(), 99.0)

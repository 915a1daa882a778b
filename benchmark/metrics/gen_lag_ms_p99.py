"""99th percentile, in ms, of how late the open-loop generator sent each
request after its due time."""

import numpy as np

from benchmark.harness import percentile


def read(ctx):
    rec = ctx.rec
    if rec.send is None:
        return None
    sent = ~np.isnan(rec.send)
    return 1e3 * percentile(rec.send[sent] - rec.start[sent], 99.0)

"""The wire's share of a request, in ms: the mean client-observed time from
send to answer, less the mean queue wait and mean flush time of the serve
histograms over the window (raft_tpu_net_wire_seconds alone includes
both, net/server.py)."""

import numpy as np


def read(ctx):
    rec = ctx.rec
    q, f = ctx.hist_mean("queue_wait"), ctx.hist_mean("flush")
    ok = ctx.in_window()
    if rec.send is None or q is None or f is None or not ok.any():
        return None
    return 1e3 * (float(np.mean(rec.done[ok] - rec.send[ok])) - q - f)

"""Mean of raft_tpu_serve_batch_occupancy (valid rows / bucket rows per
flush) over the window, in %."""


def read(ctx):
    m = ctx.hist_mean("occupancy")
    return None if m is None else 100.0 * m

"""Mean of raft_tpu_serve_queue_wait_seconds over the window, in ms."""


def read(ctx):
    m = ctx.hist_mean("queue_wait")
    return None if m is None else 1e3 * m

"""The exact scan's share of its roofline (``ops/fused_knn.py`` does most
of the flush): the least time one flush needs (``work/brute_force.py``)
over the device operation time per flush in the trace."""


def read(ctx):
    if ctx.cfg["index"]["kind"] != "brute_force":
        return None
    return ctx.roofline_pct()

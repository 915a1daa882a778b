"""IVF-PQ search's share of its roofline: the least time one flush needs
(``work/ivf_pq.py``) over the device operation time per flush in the
trace."""


def read(ctx):
    if ctx.cfg["index"]["kind"] != "ivf_pq":
        return None
    return ctx.roofline_pct()

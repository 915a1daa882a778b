"""CAGRA search's share of its roofline: the least time one flush needs
(``work/cagra.py``) over the device's busy time per flush in the trace.
Busy time is the union of the operation intervals, so the hop loop's
``while`` op is not counted again beside the operations it runs."""


def read(ctx):
    if (ctx.cfg["index"]["kind"] != "cagra" or not ctx.trace
            or not ctx.trace_flushes or not ctx.least_flush_s):
        return None
    return 100.0 * ctx.least_flush_s / (ctx.trace["busy_s"]
                                        / ctx.trace_flushes)

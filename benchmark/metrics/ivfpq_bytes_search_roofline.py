"""IVF-PQ search over a uint8 corpus, its share of its roofline: the least
time one flush needs (``work/ivf_pq_bytes.py``) over the device's busy time
per flush in the trace. Busy time is the union of the operation intervals:
at 10M rows the scan runs as a loop of sequential steps, and the loop's
``while`` op spans the operations it runs, so summed operation time would
count them twice."""


def read(ctx):
    if (ctx.cfg["index"]["kind"] != "ivf_pq_bytes" or not ctx.trace
            or not ctx.trace_flushes or not ctx.least_flush_s):
        return None
    return 100.0 * ctx.least_flush_s / (ctx.trace["busy_s"]
                                        / ctx.trace_flushes)

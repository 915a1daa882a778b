"""Share of the traced interval in which no operation ran on the device
(full-batch cells: moves qps)."""


def read(ctx):
    return ctx.trace["idle_pct"] if ctx.trace else None

"""Share of the traced interval in which no operation ran on the device
(open-loop cells: moves p99_ms)."""


def read(ctx):
    return ctx.trace["idle_pct"] if ctx.trace else None

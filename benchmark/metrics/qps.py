"""Query rows answered inside the window, per second of the window."""


def read(ctx):
    rec = ctx.rec
    return float(rec.rows[ctx.in_window()].sum()) / (rec.t1 - rec.t0)

"""Seconds from process start to the first timed request: device start,
data, index build and publish (compiles included), warm-up."""


def read(ctx):
    return ctx.setup_s

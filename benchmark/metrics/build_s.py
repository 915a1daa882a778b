"""Index build wall time to block_until_ready, in set-up."""


def read(ctx):
    return ctx.build_s

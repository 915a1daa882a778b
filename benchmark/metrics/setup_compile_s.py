"""Backend compile seconds during set-up (jax.monitoring events)."""


def read(ctx):
    return ctx.setup_compile_s

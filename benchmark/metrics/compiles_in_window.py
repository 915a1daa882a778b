"""Programs compiled, or fetched from the persistent cache, inside the
window (jax.monitoring events); 0 when the warm-up covered every shape."""


def read(ctx):
    return ctx.window_programs

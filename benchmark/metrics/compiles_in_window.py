"""Programs compiled (or loaded from the cache) between the window's
start and its end, from `jax.monitoring`. Should be nought."""


def read(ctx):
    return ctx.compiles_in_window

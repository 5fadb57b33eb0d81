"""Persistent-cache misses during set-up, from `jax.monitoring`: nought
in every run of a checkout but the first."""


def read(ctx):
    return ctx.compile_cache_misses

"""Blocks of the summary pool (one row a chunk of every finished or
running window) in use over blocks reserved, mean over the window's
ticks, in percent; from the engine's `summary()`."""


def read(ctx):
    v = ctx.counters["engine"].get("summary_block_utilization")
    return None if v is None else 100.0 * v

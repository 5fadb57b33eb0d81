"""Blocks of the full layers' latent pool in use over blocks reserved,
mean over the window's ticks, in percent; from the engine's `summary()`
of a model with two cache kinds (one that keeps a single pool reports
`kv_pool_in_use_share`)."""


def read(ctx):
    eng = ctx.counters["engine"]
    v = eng.get("block_utilization")
    if v is None or "window_block_utilization" not in eng:
        return None
    return 100.0 * v

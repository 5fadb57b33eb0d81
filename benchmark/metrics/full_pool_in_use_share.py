"""Blocks of the full layers' K/V pool (the one that grows with the
stream) in use over blocks reserved, mean over the window's ticks, in
percent; from the engine's `summary()` of a model that keeps a `full` pool
beside a window pool (one that keeps a single pool reports
`kv_pool_in_use_share`)."""


def read(ctx):
    eng = ctx.counters["engine"]
    v = eng.get("full_block_utilization")
    return 100.0 * v if v else None

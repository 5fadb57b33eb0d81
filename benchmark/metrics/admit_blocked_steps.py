"""Engine steps of the window in which the queue's head could not start
its prefill because the KV pool could not back it (the pool, not the
prefill lane, held it), from the engine's `summary()`."""


def read(ctx):
    return ctx.counters["engine"].get("admit_blocked")

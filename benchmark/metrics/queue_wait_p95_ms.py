"""95th percentile of the time a request waited in the engine's queue
(submit to admission) over the requests whose first token came in the
window, from the engine's `summary()`."""


def read(ctx):
    return ctx.counters["engine"].get("queue_wait_ms_p95")

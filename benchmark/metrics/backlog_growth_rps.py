"""Requests waiting (router queue, engine queue, prefill in flight) at
the window's end minus at its start, over its length."""


def read(ctx):
    c = ctx.counters
    return (c["queued_t1"] - c["queued_t0"]) / ctx.seconds

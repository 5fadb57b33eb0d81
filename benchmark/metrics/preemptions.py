"""Requests the engine preempted in the window, from its `summary()`."""


def read(ctx):
    return ctx.counters["engine"].get("preemptions")

"""How late the load generator sent: sent minus due, 95th percentile
over every request of the run."""

from benchmark import window


def read(ctx):
    return window.lateness_p95_ms(ctx.records)

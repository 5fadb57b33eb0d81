"""Median time from a request's admission to its first token (its
prefill chunks and the ticks between them) over the requests whose first
token came in the window, from the engine's `summary()`."""


def read(ctx):
    return ctx.counters["engine"].get("prefill_span_ms_p50")

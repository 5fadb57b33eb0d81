"""95th percentile of first-token time minus due time over every request
due in the window. Recorded; judges nothing."""


def read(ctx):
    return ctx.e2e.get("ttft_p95_ms")

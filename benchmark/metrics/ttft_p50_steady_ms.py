"""Median of first-token time minus due time over every request due in
the window, kept as a per-layer reading where it is not steady enough to
judge a PR by."""


def read(ctx):
    return ctx.e2e.get("ttft_p50_ms")

"""Share of the intervals between tokens at or over the 95th percentile
(`stepread.tail` over `stepread.TOP`) whose step ran the params-finite
probe (a `serve/probe` span begun in the interval), by streams: the
probe's cadence (25% at one step in four) where it does not matter to the
tail, 100% where the probing steps are the longest twentieth. A cheaper
probe can only lower it: a step's rank falls with its length, and the
band has no upper edge for a long step to pass. A program without
`serve/probe` reads nothing."""

from benchmark import stepread


def read(ctx):
    return stepread.share_with(
        stepread.tail(stepread.gaps(ctx), stepread.TOP), "probed")

"""Share of the intervals between tokens at or over the 95th percentile
(`stepread.tail` over `stepread.TOP`) whose step ran a prefill chunk (a
`serve/prefill` span begun in the interval), by streams: the chunks'
share of all steps where a chunk does not matter to the tail, 100% where
every gap of the longest twentieth waited for one. A cheaper chunk can
only lower it."""

from benchmark import stepread


def read(ctx):
    return stepread.share_with(
        stepread.tail(stepread.gaps(ctx), stepread.TOP), "chunks")

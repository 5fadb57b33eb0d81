"""The program's own account of `itl_p95_ms`: the 95th percentile of the
intervals between the ends of consecutive tick deliveries over the whole
window, each counted once a stream it delivered to
(`benchmark/stepread.py`, which says what the ring leaves out)."""

from benchmark import stepread


def read(ctx):
    return stepread.percentile_ms(stepread.gaps(ctx), 95)

"""Of the intervals between tokens that set the 95th percentile
(`stepread.tail`), the interval less the time the host was blocked
waiting for the device, mean by streams: the most that any overlap of
host and device can take off the tail. A program without
`serve/probe_sync` has the probe's wait for the device in here."""

from benchmark import stepread


def read(ctx):
    return stepread.mean(stepread.tail(stepread.gaps(ctx)),
                         lambda g: (g.t1 - g.t0 - g.wait) / 1e6)

"""Of the intervals between tokens that set the 95th percentile
(`stepread.tail`), the time the host was blocked waiting for the device
(`serve/tick_sync`, `serve/prefill_sync`, `serve/probe_sync`), mean by
streams. A program without `serve/probe_sync` has the probe's wait under
`itl_tail_host_ms` instead."""

from benchmark import stepread


def read(ctx):
    return stepread.mean(stepread.tail(stepread.gaps(ctx)),
                         lambda g: g.wait / 1e6)

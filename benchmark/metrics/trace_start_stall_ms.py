"""What starting the profiler's capture cost the window it measures: the
interval between two tick deliveries in which `benchmark/run.py` called
`jax.profiler.start_trace` (the one that holds `ctx.trace_span[0]`) less
the median interval of the window."""

import statistics

from benchmark import stepread


def read(ctx):
    across, rest = stepread.across_capture(stepread.gaps(ctx), ctx)
    if across is None or not rest:
        return None
    return (stepread.length_ms(across)
            - statistics.median(map(stepread.length_ms, rest)))

"""The longest interval between two tick deliveries of the window
(`stepread.gaps`). The one interval in which the benchmark started the
profiler is left out: that stall is the benchmark's, and
`trace_start_stall_ms` reads it. An interval over three times the median
is explained on the standard error: when, and the ring's spans that
overlap it."""

import statistics
import sys

from benchmark import ringread, stepread


def read(ctx):
    _, gaps = stepread.across_capture(stepread.gaps(ctx), ctx)
    if not gaps:
        return None
    worst = max(gaps, key=stepread.length_ms)
    ms = stepread.length_ms(worst)
    if ms > 3 * statistics.median(map(stepread.length_ms, gaps)):
        inside = ringread.overlapping(ringread.window_spans(ctx),
                                      worst.t0, worst.t1)
        print(f"[benchmark] serve_step_gap_max_ms {ms:.1f}, "
              f"{(worst.t0 / 1e9 - ctx.t0):.2f} s into the window, "
              f"chunks {worst.chunks}, probed {worst.probed}; spans "
              f"inside it: "
              + ", ".join(f"{n} {t:.1f}" for n, t in inside),
              file=sys.stderr, flush=True)
    return ms

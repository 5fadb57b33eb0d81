"""The longest distance between the starts of two consecutive
`train/step` spans of the window (one step's device time when nothing
stalls). The one gap in which the benchmark started the profiler is left
out: that stall is the benchmark's. A gap over one and a half times the
median is explained on the standard error: the ring's spans that overlap
it."""

import statistics
import sys

from benchmark import ringread


def read(ctx):
    spans = ringread.window_spans(ctx)
    gaps = ringread.start_gaps_ms(spans, "train/step")
    traced_from = (ctx.trace_span[0] * 1e9 if ctx.trace_span
                   else float("inf"))
    gaps = [g for g in gaps
            if not g[1].t0_ns < traced_from <= g[2].t0_ns]
    if not gaps:
        return None
    worst, a, b = max(gaps, key=lambda g: g[0])
    if worst > 1.5 * statistics.median(g[0] for g in gaps):
        inside = ringread.overlapping(spans, a.t0_ns, b.t0_ns)
        print(f"[benchmark] train_step_gap_max_ms {worst:.1f} after step "
              f"{a.ids.get('step')}, {(a.t0_ns / 1e9 - ctx.t0):.2f} s "
              f"into the window; spans inside it: "
              + ", ".join(f"{n} {ms:.1f}" for n, ms in inside),
              file=sys.stderr, flush=True)
    return worst

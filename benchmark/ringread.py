"""The arithmetic the readers of the program's span ring share.

The program keeps one ring of host spans (`pytorchdistributed_tpu/
telemetry/spans.py`): `(id, parent, name, t0_ns, t1_ns, ids)`, on
`time.perf_counter`'s clock, which is the clock of `ctx.t0` / `ctx.t1`.
The ring covers the whole window; the profiler's capture covers its last
`trace_s`. A program without that ring (an older commit) gives no spans,
and every reader built on this returns `None`.
"""

from __future__ import annotations


def window_spans(ctx) -> list:
    """The ring's spans that lie wholly inside the window (a span cut by
    an edge would be read without the children that ended outside)."""
    try:
        from pytorchdistributed_tpu.telemetry import spans

        snap = spans.snapshot(ctx.t0, ctx.t1)
    except (ImportError, AttributeError):
        return []
    lo = ctx.t0 * 1e9
    return [s for s in snap if s.t0_ns >= lo]


def named(spans, name: str) -> list:
    return [s for s in spans if s.name == name]


def mean_ms(spans, name: str):
    """Mean length of the spans called `name`, in ms."""
    got = named(spans, name)
    if not got:
        return None
    return sum(s.t1_ns - s.t0_ns for s in got) / len(got) / 1e6


def mean_less_ms(spans, outer: str, inner: tuple):
    """Mean, over the spans called `outer`, of the span's length less
    what its descendants called one of `inner` cover (a layer's time
    less the layers below it, or less its waits), in ms."""
    by_id = {s.id: s for s in spans}
    total = {s.id: s.t1_ns - s.t0_ns for s in spans if s.name == outer}
    if not total:
        return None
    for s in spans:
        if s.name not in inner:
            continue
        up = by_id.get(s.parent)
        while up is not None and up.id not in total:
            up = by_id.get(up.parent)
        if up is not None:
            total[up.id] -= s.t1_ns - s.t0_ns
    return sum(total.values()) / len(total) / 1e6


def start_gaps_ms(spans, name: str) -> list:
    """[(gap in ms, the earlier span, the later span)] between the starts
    of consecutive spans called `name`."""
    got = sorted(named(spans, name), key=lambda s: s.t0_ns)
    return [((b.t0_ns - a.t0_ns) / 1e6, a, b)
            for a, b in zip(got, got[1:])]


def overlapping(spans, lo_ns: float, hi_ns: float, top: int = 6) -> list:
    """[(name, ms inside [lo, hi])] of the spans that overlap the
    interval, longest overlap first: what the host was in during a gap."""
    out = []
    for s in spans:
        cover = min(s.t1_ns, hi_ns) - max(s.t0_ns, lo_ns)
        if cover > 0:
            out.append((s.name, cover / 1e6))
    return sorted(out, key=lambda kv: -kv[1])[:top]

"""What a gap between two tokens is made of, from the program's span ring.

A tick's `serve/deliver` span is where every live stream's `on_token`
fires, so the time from the end of one to the end of the next is one gap
between tokens for every stream both delivered to: as many as the later
one's `tokens`, less the streams admitted in between (one
`serve/prefill_sync` each: the wait for a stream's first token, on its
last chunk). An interval that is nobody's gap (the engine ran empty, and
the later tick's streams are all new) has the weight 0: no percentile, no
tail and no longest gap counts it. `gaps(ctx)` gives every interval with
the host's time inside it sorted by what the host was in and with what its
step carried, counted from the step's own child spans; `tail(gaps)` keeps
the intervals in a band of ranks: `TAIL`, round the 95th percentile, for
what a p95 gap is made of, and `TOP`, at or over it, for which steps set
it. The readers of `benchmark/metrics/` that start with `itl_tail_`,
`ring_itl_p95_ms`, `serve_step_gap_max_ms` and `trace_start_stall_ms` are
a few lines over these two.

What the ring's account leaves out of the clients' own `itl_p95_ms`: a
stream's first gap (from its first token, handed over at the end of its
last chunk under `serve/admit`, to its first tick: shorter than an
interval, so never in the tail); a callback's own position inside
`serve/deliver`; and the intervals are one engine's (every cell runs one
replica).

A ring without the spans of PR 38 (`serve/tick_operands`,
`serve/tick_call`, `serve/probe` > `serve/probe_sync`) still gives the
intervals and their chunks: those parts read 0, `probed` reads `None`, and
the probe's wait for the device counts as host time, since nothing marks
it. A ring that no longer reaches back to the window's start (it evicts
its oldest span once it is full) gives no interval at all, and says so on
the standard error: a percentile over the window's later part would look
like one over the whole.
"""

from __future__ import annotations

import bisect
import collections
import math
import sys

from benchmark import ringread

# One interval between the ends of two consecutive tick deliveries, in
# ns. `wait` is the host blocked on the device (`serve/tick_sync`,
# `serve/prefill_sync`, `serve/probe_sync`); `chunk` is `serve/prefill`
# less its sync and `probe` is `serve/probe` less its sync; `outside` lies
# under no `serve/router_step` (the harness and the load generator);
# `other` is the rest of the host's time (admission's bookkeeping, the
# blocks, delivery, the router's own). The seven sum to `t1 - t0`.
# `chunks` and `probed` count the `serve/prefill` and the `serve/probe`
# spans begun inside it: what the step that ended it carried.
Gap = collections.namedtuple(
    "Gap", "t0 t1 weight wait operands call chunk probe outside other "
           "chunks probed")

WAITS = ("serve/tick_sync", "serve/prefill_sync", "serve/probe_sync")
TAIL = (0.925, 0.975)
TOP = (0.95, 1.0)


class _Cover:
    """Spans of one thread under a few names: disjoint, so sorted by
    start they are sorted by end, and the time they cover of an interval
    is one bisection and a short walk."""

    def __init__(self, spans, names):
        got = sorted((s for s in spans if s.name in names),
                     key=lambda s: s.t0_ns)
        self.spans = got
        self.starts = [s.t0_ns for s in got]
        self.ends = [s.t1_ns for s in got]

    def of(self, lo, hi) -> int:
        total = 0
        for i in range(bisect.bisect_right(self.ends, lo), len(self.spans)):
            if self.starts[i] >= hi:
                break
            total += min(self.ends[i], hi) - max(self.starts[i], lo)
        return total

    def begun_in(self, lo, hi) -> list:
        return self.spans[bisect.bisect_left(self.starts, lo):
                          bisect.bisect_left(self.starts, hi)]


def window_is_whole(ctx) -> bool:
    """Whether the ring still holds a span that ended before the window
    began (warm-up's, if no other): if it does not, it has evicted the
    window's start."""
    try:
        from pytorchdistributed_tpu.telemetry import spans

        return bool(spans.snapshot(None, ctx.t0))
    except (ImportError, AttributeError):
        return False


def gaps(ctx) -> list:
    """The window's intervals between tick deliveries, oldest first."""
    spans = ringread.window_spans(ctx)
    ticks = sorted((s for s in spans
                    if s.name == "serve/deliver" and "tokens" in s.ids),
                   key=lambda s: s.t1_ns)
    if len(ticks) < 2:
        return []
    if not window_is_whole(ctx):
        print(f"[benchmark] the span ring no longer reaches the window's "
              f"start: its oldest span begins "
              f"{min(s.t0_ns for s in spans) / 1e9 - ctx.t0:.2f} s into "
              f"it, so no interval between tokens is read",
              file=sys.stderr, flush=True)
        return []
    cover = {key: _Cover(spans, names) for key, names in (
        ("wait", WAITS), ("operands", ("serve/tick_operands",)),
        ("call", ("serve/tick_call",)), ("prefill", ("serve/prefill",)),
        ("prefill_sync", ("serve/prefill_sync",)),
        ("probe", ("serve/probe",)), ("probe_sync", ("serve/probe_sync",)),
        ("router", ("serve/router_step",)))}
    probes = bool(cover["probe"].spans)
    out = []
    for a, b in zip(ticks, ticks[1:]):
        lo, hi = a.t1_ns, b.t1_ns
        part = {k: c.of(lo, hi) for k, c in cover.items()}
        wait = part["wait"]
        chunk = part["prefill"] - part["prefill_sync"]
        probe = part["probe"] - part["probe_sync"]
        outside = hi - lo - part["router"]
        named = (wait + part["operands"] + part["call"] + chunk + probe
                 + outside)
        streams = int(b.ids["tokens"]) - len(
            cover["prefill_sync"].begun_in(lo, hi))
        out.append(Gap(
            lo, hi, max(0, streams), wait, part["operands"],
            part["call"], chunk, probe, outside, hi - lo - named,
            len(cover["prefill"].begun_in(lo, hi)),
            len(cover["probe"].begun_in(lo, hi)) if probes else None))
    return out


def length_ms(gap) -> float:
    return (gap.t1 - gap.t0) / 1e6


def percentile_ms(gaps, q: float):
    """The q-th percentile of the intervals' lengths, each counted
    `weight` times, interpolated between order statistics as
    `benchmark/window.py` does for the clients' own gaps."""
    order = sorted((length_ms(g), g.weight) for g in gaps if g.weight > 0)
    total = sum(w for _, w in order)
    if not total:
        return None

    def at(k):
        for value, w in order:
            k -= w
            if k < 0:
                return value

    pos = (total - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return at(lo) + (at(hi) - at(lo)) * (pos - lo)


def tail(gaps, band=TAIL) -> list:
    """The intervals whose weighted rank by length lies in `band`, each
    with the part of its weight that lies inside: the steps that set the
    95th percentile."""
    total = sum(g.weight for g in gaps)
    out, seen = [], 0
    for g in sorted(gaps, key=length_ms):
        lo = max(seen, band[0] * total)
        seen += g.weight
        inside = min(seen, band[1] * total) - lo
        if inside > 0:
            out.append(g._replace(weight=inside))
    return out


def mean(gaps, value):
    """Mean of `value(gap)` by weight, over the gaps where it is not
    None; None where there is none."""
    got = [(value(g), g.weight) for g in gaps]
    got = [(v, w) for v, w in got if v is not None]
    total = sum(w for _, w in got)
    return sum(v * w for v, w in got) / total if total else None


def share_with(gaps, key: str):
    """Percent of `gaps`, by weight, whose step ran one of `key`
    (`chunks`, `probed`) or more; None where the ring cannot say."""
    share = mean(gaps, lambda g: None if getattr(g, key) is None
                 else float(getattr(g, key) > 0))
    return None if share is None else 100.0 * share


def across_capture(gaps, ctx):
    """(the interval in which the benchmark started the profiler's
    capture, or None; every other interval that is some stream's gap)."""
    at = ctx.trace_span[0] * 1e9 if ctx.trace_span else float("inf")
    inside = [g for g in gaps if g.t0 < at <= g.t1]
    return (inside[0] if inside else None,
            [g for g in gaps if g.weight > 0 and not g.t0 < at <= g.t1])

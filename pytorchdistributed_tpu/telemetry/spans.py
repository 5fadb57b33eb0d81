"""Host-span tracing — the host half of "where did the step go?".

ONE instrument for the process: a ring of host spans (`ring()`) and a
module-level `span(name, **ids)` that the router, the engine, the
Trainer and the loader all enter. Design constraints, in order:

  * **Always on, bounded**: entering+exiting a span is two
    `perf_counter_ns` calls, one profiler annotation and one deque append
    (~2-3 µs measured — tests/test_telemetry.py pins the budget at 10).
    The ring (`capacity` spans, oldest evicted) bounds memory for
    arbitrarily long jobs. "Off" means no capture and no files.
  * **Causality**: a span records `(id, parent, name, t0_ns, t1_ns,
    ids)`; `parent` is the span open on this thread when it was entered
    (None at the top), `ids` are small integers (`request=`, `replica=`,
    `step=`). A layer's self time is its span less what its children
    cover; `snapshot(t0, t1)` hands a window's spans to whoever does
    that arithmetic.
  * **The profiler's clock**: entering a span also enters
    `jax.profiler.TraceAnnotation(name)` with the BARE name, so while a
    capture runs the span sits in the `.xplane.pb` beside the device's
    operations. Ids stay in the ring: a reduction that keys idle gaps by
    event name must not get one row a request.
  * **Chrome-trace output**: `dump()` writes the Trace Event JSON format,
    one file per rank, `pid` = rank — openable directly in
    ui.perfetto.dev / chrome://tracing, and mergeable across ranks
    (`merge_chrome_traces`). Timestamps are unix-epoch microseconds
    (wall-clock anchored once at tracer construction, monotonic within
    the trace), so independently-dumped ranks land on one timeline.
  * **No jax at import**: the tracer must be constructible before any
    backend init and usable from launcher-side code; `jax.profiler` is
    imported when the first span is entered.
  * **`host/gc`**: a generation-2 garbage collection is a span of the
    process ring (`gc.callbacks`, installed with it) — the cheapest
    suspect for a second lost on the host.
"""

from __future__ import annotations

import collections
import gc
import glob
import itertools
import json
import os
import threading
import time

Span = collections.namedtuple("Span", "id parent name t0_ns t1_ns ids")

GC_SPAN = "host/gc"

_annotation = None  # jax.profiler.TraceAnnotation, once a span was entered


def _load_annotation():
    global _annotation
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    return TraceAnnotation


class _OpenSpan:
    """One `with span(name):` region. Allocation-light on purpose: the
    hot loops enter a dozen of these per step."""

    __slots__ = ("_tr", "_name", "_ids", "_id", "_parent", "_t0", "_ann")

    def __init__(self, tracer, name, ids):
        self._tr = tracer
        self._name = name
        self._ids = ids

    def note(self, **ids) -> None:
        """Add ids known only once the region has run (a count)."""
        self._ids.update(ids)

    def __enter__(self):
        tr = self._tr
        self._ann = (_annotation or _load_annotation())(self._name)
        stack = tr._stack()
        self._parent = stack[-1] if stack else None
        self._id = next(tr._next_id)
        stack.append(self._id)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        self._tr._stack().pop()
        self._tr._buf.append(Span(self._id, self._parent, self._name,
                                  self._t0, t1, self._ids))
        return False


class SpanTracer:
    """Ring-buffer host-span recorder. The process has one (`ring()`);
    tests build their own.

    ``rank`` stamps the Chrome-trace pid (defaults to the launcher env
    contract's RANK, 0 outside one); ``capacity`` bounds memory — at 15
    spans/step the default holds ~4k steps of history.
    """

    def __init__(self, capacity: int = 65536, rank: int | None = None):
        self.rank = (rank if rank is not None
                     else int(os.environ.get("RANK", "0")))
        self._buf: collections.deque = collections.deque(maxlen=capacity)
        self._next_id = itertools.count()
        self._local = threading.local()
        self._gc_span = None
        # One-time wall-clock anchor: spans record monotonic perf_counter
        # times; the anchor maps them onto unix-epoch µs so traces dumped
        # by different ranks (different processes, same or different
        # hosts) merge onto a shared timeline.
        self._epoch_us = time.time() * 1e6 - time.perf_counter_ns() / 1e3

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def span(self, name: str, **ids) -> _OpenSpan:
        return _OpenSpan(self, name, ids)

    def __len__(self) -> int:
        return len(self._buf)

    def clear(self) -> None:
        self._buf.clear()

    def snapshot(self, t0: float | None = None,
                 t1: float | None = None) -> list[Span]:
        """The buffered spans that ENDED inside ``[t0, t1]`` — seconds on
        `time.perf_counter`'s clock; an open end is unbounded."""
        lo = -1 if t0 is None else t0 * 1e9
        hi = float("inf") if t1 is None else t1 * 1e9
        return [s for s in list(self._buf) if lo <= s.t1_ns <= hi]

    def totals(self) -> dict[str, tuple[float, int]]:
        """{span name: (total ms, count)} over the buffered spans."""
        out: dict[str, list] = {}
        for s in list(self._buf):
            r = out.setdefault(s.name, [0.0, 0])
            r[0] += (s.t1_ns - s.t0_ns) / 1e6
            r[1] += 1
        return {k: (v[0], v[1]) for k, v in out.items()}

    def to_chrome_trace(self, rank: int | None = None,
                        replica: int | None = None,
                        since: float | None = None) -> dict:
        """Trace Event JSON dict: complete ("X") events, ts/dur in µs,
        id / parent / ids under ``args``. ``rank`` overrides the pid;
        ``replica`` keeps the spans that carry that ``replica`` id
        themselves or through an ancestor, and those that carry none —
        how in-process replicas sharing the ring each dump their own;
        ``since`` (a `time.perf_counter` reading) leaves out what ended
        before the dumping object existed."""
        pid = self.rank if rank is None else rank
        spans = self.snapshot(since)
        events: list[dict] = [
            {"ph": "M", "name": "process_name", "pid": pid,
             "args": {"name": f"host rank {pid}"}},
            {"ph": "M", "name": "thread_name", "pid": pid, "tid": 0,
             "args": {"name": "host spans"}},
        ]
        owner: dict = {}
        if replica is not None:
            # parents END after their children, so walk newest first:
            # a span's parent is resolved before the span is
            for s in reversed(spans):
                owner[s.id] = s.ids.get("replica", owner.get(s.parent))
        for s in spans:
            if replica is not None and owner[s.id] not in (None, replica):
                continue
            events.append({
                "ph": "X", "name": s.name, "pid": pid, "tid": 0,
                "ts": round(self._epoch_us + s.t0_ns / 1e3, 3),
                "dur": round((s.t1_ns - s.t0_ns) / 1e3, 3),
                "cat": "host",
                "args": {"id": s.id, "parent": s.parent, **s.ids},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def dump(self, path: str | os.PathLike, rank: int | None = None,
             replica: int | None = None,
             since: float | None = None) -> None:
        """Write the Chrome-trace JSON (atomic rename: a reader — the
        report CLI, a mid-run Perfetto open — never sees a torn file)."""
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(rank, replica, since), f)
        os.replace(tmp, path)

    def _on_gc(self, phase: str, info: dict) -> None:
        """`gc.callbacks` hook: a generation-2 collection is a span, under
        whatever is open on the thread that triggered it."""
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_span = self.span(GC_SPAN).__enter__()
        elif self._gc_span is not None:
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None


_ring: SpanTracer | None = None


def ring() -> SpanTracer:
    """The process's one ring, built (and its `host/gc` hook installed)
    on first use."""
    global _ring
    if _ring is None:
        _ring = SpanTracer()
        gc.callbacks.append(_ring._on_gc)
    return _ring


def span(name: str, **ids) -> _OpenSpan:
    """`with span("serve/admit", request=7):` on the process ring."""
    return _OpenSpan(_ring or ring(), name, ids)


def snapshot(t0: float | None = None, t1: float | None = None) -> list[Span]:
    """`ring().snapshot(t0, t1)`: what a reader of a measured window
    calls."""
    return ring().snapshot(t0, t1)


# writer filename / reader glob pair — rename together (report.py and
# the Trainer both import these; see the matching contract in events.py)
SPAN_TRACE_FILE = "spans_rank{rank}.trace.json"
SPAN_TRACE_GLOB = "spans_rank*.trace.json"


def merge_chrome_traces(run_dir: str | os.PathLike,
                        extra_events: list[dict] | None = None) -> dict:
    """Merge every rank's span trace under ``run_dir`` into one
    Chrome-trace dict (each file already carries a distinct pid = rank).
    ``extra_events`` lets a caller overlay another trace's events — e.g.
    the device events of a `jax.profiler` capture — on the same timeline."""
    events: list[dict] = []
    for path in sorted(glob.glob(os.path.join(str(run_dir),
                                              SPAN_TRACE_GLOB))):
        with open(path) as f:
            events.extend(json.load(f).get("traceEvents", []))
    if extra_events:
        events.extend(extra_events)
    return {"traceEvents": events, "displayTimeUnit": "ms"}

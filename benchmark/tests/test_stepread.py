"""`benchmark/stepread.py` and the twelve readers of PR 38 on hand-made
rings, each value worked out by hand: what an interval between two tick
deliveries is made of, which intervals set the 95th percentile, the one
the profiler's start falls into, and `None` where a ring (the parent
commit's, an empty one, one that has evicted the window's start) lacks
what a reader needs."""

import types

import numpy as np
import pytest

from benchmark import manifest, ringread, stepread
from pytorchdistributed_tpu.telemetry import spans as program_spans
from pytorchdistributed_tpu.telemetry.spans import Span

MS = 1_000_000  # ns
NEW_SPANS = ("serve/tick_operands", "serve/tick_call",
             "serve/chunk_operands", "serve/chunk_call", "serve/probe",
             "serve/probe_sync")
READERS = ("tick_operands_host_ms", "tick_call_host_ms", "chunk_host_ms",
           "probe_ms", "ring_itl_p95_ms", "itl_tail_device_wait_ms",
           "itl_tail_host_ms", "itl_tail_chunk_share",
           "itl_tail_probe_share", "serve_step_gap_max_ms",
           "trace_start_stall_ms", "chunk_operands_host_ms")
NEED_PR38 = ("tick_operands_host_ms", "tick_call_host_ms", "probe_ms",
             "itl_tail_probe_share", "chunk_operands_host_ms")


def sp(i, parent, name, t0_ms, t1_ms, **ids):
    return Span(i, parent, name, int(t0_ms * MS), int(t1_ms * MS), ids)


def tick(i, parent, t0, operands, call, sync, deliver_to, tokens):
    """A tick from `t0`: its operands, its call and its sync, each so
    many ms long, then (half a ms later) a delivery up to `deliver_to`."""
    a, b, c = t0 + operands, t0 + operands + call, \
        t0 + operands + call + sync
    return [sp(i + 2, i + 1, "serve/tick_operands", t0, a),
            sp(i + 3, i + 1, "serve/tick_call", a, b),
            sp(i + 1, i, "serve/tick_dispatch", t0, b),
            sp(i + 4, i, "serve/tick_sync", b, c),
            sp(i, parent, "serve/decode_tick", t0, c),
            sp(i + 5, parent, "serve/deliver", c + 0.5, deliver_to,
               tokens=tokens)]


# Six router steps, in ms; deliveries end at 10, 30, 60, 100, 200, 220.
# Step 2 is a bare tick; step 3 probes (33-37, the device's answer awaited
# 34-37) and runs an admission's last chunk (40-47, the first token
# awaited 43-47); step 4 runs a chunk that is not the last (65-70). Between
# steps 4 and 5 the harness is out of the router for 59 ms: the capture
# starts 150 ms in. A ms lies between two router steps (half a ms before
# the last).
RING = [
    sp(12, 11, "serve/deliver", 9, 10, tokens=3),
    sp(11, 10, "serve/engine_step", 1, 10.8, step=1),
    sp(10, None, "serve/router_step", 0, 11, step=1),
    # step 2: 12-31
    sp(21, 20, "serve/router_health", 12, 12.5),
    *tick(30, 23, 14, 1, 2, 11, 30, tokens=3),
    sp(23, 22, "serve/engine_step", 13.2, 30.4, step=2),
    sp(22, 20, "serve/replica_step", 13, 30.5, replica=0),
    sp(20, None, "serve/router_step", 12, 31, step=2),
    # step 3: 32-62
    sp(43, 42, "serve/probe_sync", 34, 37),
    sp(42, 41, "serve/probe", 33, 37),
    sp(41, 40, "serve/router_health", 32, 38),
    sp(47, 46, "serve/chunk_operands", 40, 41),
    sp(48, 46, "serve/chunk_call", 41, 43),
    sp(49, 46, "serve/prefill_sync", 43, 47, request=5),
    sp(46, 45, "serve/prefill", 40, 47, request=5, pos=16),
    sp(45, 44, "serve/admit", 39, 48),
    *tick(50, 44, 49, 1.5, 1.5, 6, 60, tokens=4),
    sp(44, 40, "serve/engine_step", 39, 61, step=3),
    sp(40, None, "serve/router_step", 32, 62, step=3),
    # step 4: 63-101
    sp(63, 62, "serve/chunk_operands", 65, 66),
    sp(64, 62, "serve/chunk_call", 66, 70),
    sp(62, 61, "serve/prefill", 65, 70, request=6, pos=0),
    *tick(70, 61, 71, 1, 2, 23, 100, tokens=4),
    sp(61, 60, "serve/engine_step", 64, 100.5, step=4),
    sp(60, None, "serve/router_step", 63, 101, step=4),
    # step 5: 160-201, after the harness started the capture
    *tick(90, 81, 162, 1, 2, 32, 200, tokens=2),
    sp(81, 80, "serve/engine_step", 161, 200.5, step=5),
    sp(80, None, "serve/router_step", 160, 201, step=5),
    # step 6: 201.5-221
    *tick(110, 101, 203, 1, 2, 11, 220, tokens=2),
    sp(101, 100, "serve/engine_step", 202, 220.5, step=6),
    sp(100, None, "serve/router_step", 201.5, 221, step=6),
]


def upto(step):
    """The ring's steps 1 to `step`."""
    hi = [s for s in RING if s.name == "serve/router_step"
          and s.ids["step"] == step][0].t1_ns
    return [s for s in RING if s.t1_ns <= hi]


def parent_shape(ring):
    """The same steps as the parent commit's ring holds them: none of
    PR 38's spans."""
    return [s for s in ring if s.name not in NEW_SPANS]


def ctx_of(monkeypatch, spans, trace_span=(0.150, 0.230), whole=True):
    monkeypatch.setattr(ringread, "window_spans", lambda ctx: list(spans))
    monkeypatch.setattr(stepread, "window_is_whole", lambda ctx: whole)
    return types.SimpleNamespace(t0=0.0, t1=10.0, trace_span=trace_span)


def reader(name):
    return manifest.Cell(manifest.load(),
                         "gpt2m-docqa-steady").reader(name)


def in_ms(gap):
    return {k: getattr(gap, k) / MS for k in (
        "wait", "operands", "call", "chunk", "probe", "outside", "other")}


def test_an_interval_is_sorted_by_what_the_host_was_in(monkeypatch):
    got = stepread.gaps(ctx_of(monkeypatch, RING))
    # an interval counts once a stream that both ticks delivered to: of
    # step 3's four, one was admitted inside it
    assert [(stepread.length_ms(g), g.weight) for g in got] == [
        (20, 3), (30, 3), (40, 4), (100, 2), (20, 2)]
    bare, probe_and_chunk, chunk, across, _ = got
    assert in_ms(bare) == dict(wait=11, operands=1, call=2, chunk=0,
                               probe=0, outside=1, other=5)
    # the three waits are 3 (probe) + 4 (first token) + 6 (tick); the
    # chunk is its span of 7 less the 4, the probe its 4 less the 3
    assert in_ms(probe_and_chunk) == dict(wait=13, operands=1.5, call=1.5,
                                          chunk=3, probe=1, outside=1,
                                          other=9)
    assert in_ms(chunk) == dict(wait=23, operands=1, call=2, chunk=5,
                                probe=0, outside=1, other=8)
    assert in_ms(across) == dict(wait=32, operands=1, call=2, chunk=0,
                                 probe=0, outside=59, other=6)
    for g in got:
        assert sum(in_ms(g).values()) == stepread.length_ms(g)
    # a step with a chunk, with a probe, with neither: by the
    # `serve/prefill` and `serve/probe` spans begun in the interval
    assert [(g.chunks, g.probed) for g in got] == [
        (0, 0), (1, 1), (1, 0), (0, 0), (0, 0)]


def test_an_interval_the_engine_ran_empty_in_is_nobodys_gap(monkeypatch):
    """Two ticks 90 ms apart with one stream each, the second admitted in
    between: no stream waited that long for a token."""
    ring = [
        sp(2, 1, "serve/deliver", 9, 10, tokens=1),
        sp(1, None, "serve/engine_step", 1, 10.5, step=1),
        sp(13, 12, "serve/prefill_sync", 84, 86, request=2),
        sp(12, 11, "serve/prefill", 81, 86, request=2, pos=0),
        *tick(20, 11, 87, 1, 2, 8, 100, tokens=1),
        sp(11, None, "serve/engine_step", 80, 100.5, step=2),
        *tick(30, 14, 102, 1, 2, 8, 115, tokens=1),
        sp(14, None, "serve/engine_step", 101, 115.5, step=3),
    ]
    ctx = ctx_of(monkeypatch, ring, trace_span=(0.050, 0.110))
    assert [(stepread.length_ms(g), g.weight)
            for g in stepread.gaps(ctx)] == [(90, 0), (15, 1)]
    assert reader("ring_itl_p95_ms")(ctx) == pytest.approx(15)
    assert reader("itl_tail_host_ms")(ctx) == pytest.approx(15 - 8)
    # the capture started in it all the same
    assert reader("trace_start_stall_ms")(ctx) == pytest.approx(90 - 15)
    ctx.trace_span = None
    assert reader("serve_step_gap_max_ms")(ctx) == pytest.approx(15)


def test_a_ring_of_the_parents_shape_has_the_intervals_and_their_chunks(
        monkeypatch):
    got = stepread.gaps(ctx_of(monkeypatch, parent_shape(RING)))
    assert [stepread.length_ms(g) for g in got] == [20, 30, 40, 100, 20]
    # `serve/prefill` is the parent's too; nothing there marks a probe
    assert [g.chunks for g in got] == [0, 1, 1, 0, 0]
    assert all(g.probed is None for g in got)
    # nothing marks the probe: its 3 ms of waiting count as host time
    assert in_ms(got[1]) == dict(wait=10, operands=0, call=0, chunk=3,
                                 probe=0, outside=1, other=16)


@pytest.mark.parametrize("q", [0, 37.5, 50, 90, 95, 100])
def test_weighted_percentile_is_the_percentile_of_every_stream(
        monkeypatch, q):
    got = stepread.gaps(ctx_of(monkeypatch, RING))
    every = np.repeat([20, 30, 40, 100, 20], [3, 3, 4, 2, 2])
    assert stepread.percentile_ms(got, q) == pytest.approx(
        np.percentile(every, q))
    assert stepread.percentile_ms([], q) is None


def test_tail_is_the_band_round_the_95th_percentile():
    # twenty intervals of 1 .. 20 ms, a stream each: ranks 18.5 to 19.5
    # of 20 are the upper half of the 19 ms one and the lower half of
    # the 20 ms one
    every = [stepread.Gap(0, n * MS, 1, n * MS // 2, 0, 0, 0, 0, 0, 0,
                          n % 2, None) for n in range(20, 0, -1)]
    got = stepread.tail(every)
    assert [(stepread.length_ms(g), g.weight) for g in got] == [
        (19, 0.5), (20, 0.5)]
    assert stepread.mean(got, lambda g: g.wait / MS) == 9.75
    assert stepread.mean(got, lambda g: g.chunks) == 0.5
    assert stepread.mean(got, lambda g: g.probed) is None
    # one interval of 780 streams among them (ranks 7 to 787 of 800) is
    # all of the tail, 740 to 780
    big = every[0]._replace(t1=7 * MS, weight=780)
    assert [(stepread.length_ms(g), g.weight)
            for g in stepread.tail(every + [big])] == [(7, 40.0)]
    assert stepread.tail([]) == []
    # at or over the 95th percentile: ranks 19 to 20 are the 20 ms one
    assert [(stepread.length_ms(g), g.weight)
            for g in stepread.tail(every, stepread.TOP)] == [(20, 1.0)]


def test_a_longer_probe_never_lowers_its_share_of_the_top():
    """Forty intervals of 1 .. 40 ms, a stream each, every fourth a
    probing step's: as the probe grows the probing steps climb into the
    longest twentieth (two intervals) and stay there, where a band with
    an upper edge would lose them again."""
    def share(probe_ms, band):
        every = [stepread.Gap(0, int((n + probe_ms * (n % 4 == 0)) * MS),
                              1, 0, 0, 0, 0, 0, 0, 0, 0, int(n % 4 == 0))
                 for n in range(1, 41)]
        return stepread.share_with(stepread.tail(every, band), "probed")

    grown = [share(ms, stepread.TOP) for ms in (0, 1.5, 5, 50, 500)]
    assert grown == [50, 50, 100, 100, 100]
    # ten probing steps past thirty others: ranks 37 to 39 are theirs
    # too, but of a band that ends at rank 30 they are none
    assert share(500, stepread.TAIL) == 100
    assert share(500, (0.7, 0.75)) == 0


@pytest.mark.parametrize("steps, want", [
    # 20 ms x 3 streams, 30 x 3: the tail is the 30 ms interval, the
    # step that probed and ran a chunk
    (3, dict(ring_itl_p95_ms=30, itl_tail_device_wait_ms=13,
             itl_tail_host_ms=17, itl_tail_chunk_share=100,
             itl_tail_probe_share=100)),
    # and 40 x 4: the tail is the 40 ms interval, a chunk and no probe
    (4, dict(ring_itl_p95_ms=40, itl_tail_device_wait_ms=23,
             itl_tail_host_ms=17, itl_tail_chunk_share=100,
             itl_tail_probe_share=0)),
    # all six: the tail is the interval the capture started in
    (6, dict(ring_itl_p95_ms=100, itl_tail_device_wait_ms=32,
             itl_tail_host_ms=68, itl_tail_chunk_share=0,
             itl_tail_probe_share=0)),
])
def test_tail_readers(monkeypatch, steps, want):
    ctx = ctx_of(monkeypatch, upto(steps))
    assert {k: reader(k)(ctx) for k in want} == pytest.approx(want)


def test_span_readers(monkeypatch):
    ctx = ctx_of(monkeypatch, RING)
    assert reader("tick_operands_host_ms")(ctx) == pytest.approx(5.5 / 5)
    assert reader("tick_call_host_ms")(ctx) == pytest.approx(9.5 / 5)
    # chunks of 7 less a wait of 4, and of 5
    assert reader("chunk_host_ms")(ctx) == pytest.approx((3 + 5) / 2)
    assert reader("chunk_operands_host_ms")(ctx) == pytest.approx(1)
    assert reader("probe_ms")(ctx) == pytest.approx(4)


def test_the_interval_the_capture_started_in_is_read_apart(monkeypatch,
                                                           capsys):
    ctx = ctx_of(monkeypatch, RING)
    # 100 ms less the median of 20, 30, 40, 20
    assert reader("trace_start_stall_ms")(ctx) == pytest.approx(75)
    assert reader("serve_step_gap_max_ms")(ctx) == pytest.approx(40)
    assert capsys.readouterr().err == ""      # 40 is under 3 x 25
    # with no capture nothing is left out, and 100 ms over 3 x 30 is
    # explained: when, what the step carried, what the host was in
    ctx.trace_span = None
    assert reader("trace_start_stall_ms")(ctx) is None
    assert reader("serve_step_gap_max_ms")(ctx) == pytest.approx(100)
    err = capsys.readouterr().err
    assert "serve_step_gap_max_ms 100.0, 0.10 s into the window" in err
    assert "chunks 0, probed 0" in err
    assert "serve/router_step 40.0" in err and "serve/tick_sync 32.0" in err
    # a capture that started outside every interval leaves none out
    ctx.trace_span = (5.0, 9.0)
    assert reader("trace_start_stall_ms")(ctx) is None
    assert reader("serve_step_gap_max_ms")(ctx) == pytest.approx(100)


@pytest.mark.parametrize("name", READERS)
def test_on_the_parents_ring_and_on_an_empty_one(monkeypatch, name):
    """The parent commit has the spans of PR 27 alone: five readers find
    nothing there, seven read what they read of this PR's ring (the
    tail's wait apart: the capture's interval holds no probe). No reader
    raises on a ring with nothing, or with one delivery, in it."""
    ctx = ctx_of(monkeypatch, parent_shape(RING))
    got = reader(name)(ctx)
    if name in NEED_PR38:
        assert got is None
    else:
        assert got == pytest.approx(reader(name)(ctx_of(monkeypatch, RING)))
    assert reader(name)(ctx_of(monkeypatch, [])) is None
    assert reader(name)(ctx_of(monkeypatch, upto(1))) is None


def test_a_ring_that_evicted_the_windows_start_gives_no_interval(
        monkeypatch, capsys):
    """Past its capacity the ring drops its oldest span: the readers over
    the intervals then say nothing, and why, rather than a percentile of
    the window's later part; a mean over spans still reads."""
    ctx = ctx_of(monkeypatch, RING, whole=False)
    assert stepread.gaps(ctx) == []
    assert "no longer reaches the window's start" in capsys.readouterr().err
    for name in ("ring_itl_p95_ms", "itl_tail_device_wait_ms",
                 "itl_tail_host_ms", "itl_tail_chunk_share",
                 "itl_tail_probe_share", "serve_step_gap_max_ms",
                 "trace_start_stall_ms"):
        assert reader(name)(ctx) is None
    assert reader("tick_call_host_ms")(ctx) == pytest.approx(9.5 / 5)


def test_the_window_is_whole_while_the_ring_reaches_behind_it(monkeypatch):
    ring = program_spans.SpanTracer(capacity=4)
    monkeypatch.setattr(program_spans, "ring", lambda: ring)
    ctx = types.SimpleNamespace(t0=0.010, t1=1.0, trace_span=None)
    assert not stepread.window_is_whole(ctx)       # an empty ring
    ring._buf.append(sp(1, None, "serve/router_step", 2, 9))
    for i, t in enumerate((12, 14, 16), 2):
        ring._buf.append(sp(i, None, "serve/router_step", t, t + 1))
    assert stepread.window_is_whole(ctx)
    # one more span, and the one from before the window is gone
    ring._buf.append(sp(5, None, "serve/router_step", 18, 19))
    assert not stepread.window_is_whole(ctx)


def test_the_manifest_lists_the_twelve_for_the_five_serve_cells():
    bench = manifest.load()
    serve = [w["name"] for w in bench["workloads"]
             if "train" not in w["name"]]
    assert len(serve) == 5
    mine = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    assert bench["per_layer"][-12:] == mine
    for m in mine:
        assert m["workloads"] == serve and m["moves"] == "itl_p95_ms"
        assert m["source"] == "program_counter"
    assert manifest.problems(bench) == []

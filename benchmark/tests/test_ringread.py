"""The readers of the program's span ring and of the engine's queue
counters, each on a hand-made ring with the value worked out by hand,
and `None` where the ring (or the counter) is not there."""

import types

import pytest

from benchmark import manifest, ringread, xtrace
from pytorchdistributed_tpu.telemetry.spans import Span

MS = 1_000_000  # ns


def sp(i, parent, name, t0_ms, t1_ms, **ids):
    return Span(i, parent, name, int(t0_ms * MS), int(t1_ms * MS), ids)


# Two router steps. Step A, 0-100 ms: health 0-1, dispatch 1-2, one
# replica step 2-98 holding an engine step 3-97 (admit 3-13 with one chunk
# 4-12 whose sync is 10-12; grow 13-14; tick 14-90 = dispatch 14-16 +
# sync 16-90; deliver 90-96), reap 98-99.5. Step B, 100-170 ms: the same
# without admission work: engine step 102-168, tick 104-162 = dispatch
# 104-107 + sync 107-162, deliver 162-166, grow 103-104.
SERVE = [
    sp(1, 0, "serve/router_health", 0, 1),
    sp(2, 0, "serve/router_dispatch", 1, 2),
    sp(7, 6, "serve/prefill_sync", 10, 12, request=9),
    sp(6, 5, "serve/prefill", 4, 12, request=9, pos=0),
    sp(5, 4, "serve/admit", 3, 13),
    sp(8, 4, "serve/grow_slots", 13, 14),
    sp(10, 9, "serve/tick_dispatch", 14, 16),
    sp(11, 9, "serve/tick_sync", 16, 90),
    sp(9, 4, "serve/decode_tick", 14, 90),
    sp(12, 4, "serve/deliver", 90, 96, tokens=32),
    sp(4, 3, "serve/engine_step", 3, 97, step=1),
    sp(3, 0, "serve/replica_step", 2, 98, replica=0),
    sp(13, 0, "serve/router_reap", 98, 99.5),
    sp(0, None, "serve/router_step", 0, 100, step=1),
    sp(24, 23, "serve/admit", 102, 102.5),
    sp(25, 23, "serve/grow_slots", 103, 104),
    sp(27, 26, "serve/tick_dispatch", 104, 107),
    sp(28, 26, "serve/tick_sync", 107, 162),
    sp(26, 23, "serve/decode_tick", 104, 162),
    sp(29, 23, "serve/deliver", 162, 166, tokens=31),
    sp(23, 22, "serve/engine_step", 102, 168, step=2),
    sp(22, 20, "serve/replica_step", 101, 169, replica=0),
    sp(20, None, "serve/router_step", 100, 170, step=2),
]

# Five train steps that start 0, 172, 344, 1400 (a stall), 1572 ms in;
# each call returns after 3 ms on the host.
TRAIN = [sp(i, None, "train/step", t, t + 3, step=i + 1)
         for i, t in enumerate((0, 172, 344, 1400, 1572))]
TRAIN.insert(3, sp(9, None, "host/gc", 400, 1300))


def ctx_of(monkeypatch, spans, **kw):
    monkeypatch.setattr(ringread, "window_spans", lambda ctx: list(spans))
    return types.SimpleNamespace(t0=0.0, t1=10.0, trace_span=None,
                                 counters={"engine": {}}, **kw)


def reader(name):
    return manifest.Cell(manifest.load(), "gpt2m-docqa-steady").reader(
        name) if not name.startswith("train_") else manifest.Cell(
        manifest.load(), "gpt2m-train-1chip").reader(name)


@pytest.mark.parametrize("name, want", [
    # router steps of 100 and 70 ms less engine steps of 94 and 66
    ("router_own_host_ms", (6 + 4) / 2),
    # engine steps of 94 and 66 ms less syncs of 74 + 2 and 55
    ("engine_host_ms", (18 + 11) / 2),
    ("tick_dispatch_host_ms", (2 + 3) / 2),
    ("deliver_host_ms", (6 + 4) / 2),
    ("kv_grow_host_ms", (1 + 1) / 2),
])
def test_serve_ring_readers(monkeypatch, name, want):
    assert reader(name)(ctx_of(monkeypatch, SERVE)) == pytest.approx(want)
    assert reader(name)(ctx_of(monkeypatch, [])) is None


def test_train_ring_readers(monkeypatch, capsys):
    ctx = ctx_of(monkeypatch, TRAIN)
    assert reader("train_step_host_ms")(ctx) == pytest.approx(3.0)
    assert reader("train_step_gap_max_ms")(ctx) == pytest.approx(1056.0)
    # a gap over 1.5x the median is explained on the standard error
    err = capsys.readouterr().err
    assert "after step 3" in err and "host/gc 900.0" in err
    # the gap in which the benchmark started the profiler is not the
    # program's: with the capture starting 1.2 s in, the rest is clean
    ctx.trace_span = (1.2, 4.0)
    assert reader("train_step_gap_max_ms")(ctx) == pytest.approx(172.0)
    assert capsys.readouterr().err == ""
    for name in ("train_step_host_ms", "train_step_gap_max_ms"):
        assert reader(name)(ctx_of(monkeypatch, [])) is None
    # one step has no gap to read
    assert reader("train_step_gap_max_ms")(
        ctx_of(monkeypatch, TRAIN[:1])) is None


@pytest.mark.parametrize("name, key", [
    ("queue_wait_p50_ms", "queue_wait_ms_p50"),
    ("queue_wait_p95_ms", "queue_wait_ms_p95"),
    ("prefill_span_p50_ms", "prefill_span_ms_p50"),
    ("admit_blocked_steps", "admit_blocked"),
])
def test_summary_readers(monkeypatch, name, key):
    ctx = ctx_of(monkeypatch, [])
    assert reader(name)(ctx) is None     # a program without the counter
    ctx.counters["engine"][key] = 812.5
    assert reader(name)(ctx) == 812.5


def test_window_spans_keeps_what_lies_wholly_inside():
    from pytorchdistributed_tpu.telemetry import spans

    ring = spans.ring()
    ring.clear()
    ring._buf.extend([
        sp(1, None, "serve/router_step", 900, 1100),    # cut by t0
        sp(2, None, "serve/router_step", 1100, 1200),
        sp(3, None, "serve/router_step", 1950, 2050),   # cut by t1
    ])
    ctx = types.SimpleNamespace(t0=1.0, t1=2.0)
    assert [s.id for s in ringread.window_spans(ctx)] == [2]
    ring.clear()
    assert ringread.window_spans(ctx) == []


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch):
    """The parent of the PR that added the ring has no
    `spans.snapshot`: every ring reader returns `None`, none raises."""
    from pytorchdistributed_tpu.telemetry import spans

    monkeypatch.delattr(spans, "snapshot")
    ctx = types.SimpleNamespace(t0=0.0, t1=1.0, trace_span=None,
                                counters={"engine": {}})
    assert ringread.window_spans(ctx) == []
    for name in ("router_own_host_ms", "engine_host_ms",
                 "tick_dispatch_host_ms", "deliver_host_ms",
                 "kv_grow_host_ms", "train_step_host_ms",
                 "train_step_gap_max_ms"):
        assert reader(name)(ctx) is None


def test_the_program_spans_are_prefixes_the_breakdown_may_name():
    keep = xtrace.span_prefixes()
    assert {"serve/", "train/", "host/"} <= set(keep)
    # the benchmark's own are still there
    assert {"router.step", "train_step", "loadgen."} <= set(keep)
    assert manifest.problems(manifest.load()) == []


def test_idle_gaps_go_to_the_innermost_program_span():
    """A gap under the benchmark's `router.step` and the program's
    `serve/router_step` > `serve/deliver` is named after the innermost."""
    dev = xtrace.DevicePlane("/device:TPU:0", [], [
        xtrace.Event("%fusion.1 = f32[] fusion()", 0, 50),
        xtrace.Event("%fusion.1 = f32[] fusion()", 80, 20)], [])
    host = [xtrace.Event("bench.window", 0, 100),
            xtrace.Event("router.step", 0, 100),
            xtrace.Event("serve/router_step", 1, 98),
            xtrace.Event("serve/deliver", 55, 20)]
    assert xtrace.Trace([dev], host).idle_gaps() == [("serve/deliver",
                                                      30 / 1e9)]

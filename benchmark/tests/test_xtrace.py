"""The trace reduction: interval arithmetic on made-up events, and the
whole reduction on a small trace recorded on the chip."""

import pathlib

import pytest

from benchmark import xtrace
from benchmark.xtrace import DevicePlane, Event, Trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_and_subtract():
    assert xtrace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert xtrace.union_length([]) == 0
    assert xtrace.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == \
        [[0, 2], [3, 5], [7, 9]]
    assert xtrace.subtract([(0, 4), (6, 8)], []) == [[0, 4], [6, 8]]
    assert xtrace.clip([(0, 5), (7, 9)], 3, 8) == [(3, 5), (7, 8)]


def test_names():
    n = "%attn.12 = (bf16[8,64]{1,0}) custom-call(bf16[8,64] %x)"
    assert xtrace.op_name(n) == "attn.12"
    assert xtrace.family(n) == "attn"
    assert xtrace.family("%all-gather-start.3 = ...") == "all-gather-start"


def made_up():
    """10 us window; a `while` of two ops, a gap, an attention call; an
    all-gather in flight from 6 to 9 under which compute runs 7..8."""
    ops = [Event("%while.1 = w", 1000, 3000),
           Event("%fusion.1 = f", 1000, 1000),
           Event("%fusion.2 = f", 2500, 1500),
           Event("%attn.7 = custom-call", 5000, 1000),
           Event("%fusion.3 = f", 7000, 1000)]
    async_ops = [Event("%all-gather-start.1 = ag", 6000, 3000)]
    modules = [Event("jit_step(1)", 1000, 8000)]
    host = [Event("bench.window", 0, 10000),
            Event("train_step", 0, 4500), Event("input.next_batch", 4500,
                                                700)]
    return Trace([DevicePlane("/device:TPU:0", modules, ops, async_ops)],
                 host)


def test_reduction_on_made_up_events():
    t = made_up()
    assert t.window_s == pytest.approx(10e-6)
    # busy: [1,4] + [5,6] + [7,8]
    assert t.busy_s() == pytest.approx(5e-6)
    st = t.self_times()
    assert st["fusion"] == pytest.approx(3.5e-6)
    assert st["while"] == pytest.approx(0.5e-6)   # 3 less its children
    assert st["attn"] == pytest.approx(1e-6)
    assert t.scope_time("attn") == pytest.approx(1e-6)
    assert t.scope_time("attn", t.program_runs("jit_step")) == \
        pytest.approx(1e-6)
    assert t.scope_time("att") == 0
    # the gather is in flight 6..9, compute covers 7..8 of it
    assert t.collective_exposed_s() == pytest.approx(2e-6)
    gaps = dict(t.idle_gaps())
    assert gaps["train_step"] == pytest.approx(1e-6)        # 0..1
    assert gaps["input.next_batch"] == pytest.approx(1e-6)  # 4..5
    assert gaps["(no span)"] == pytest.approx(3e-6)   # 6..7, 8..10
    b = t.breakdown()
    assert b["device_ops"][0][0] == "fusion"
    assert len(t.program_runs("jit_step")) == 1


@pytest.mark.skipif(not (DATA / "tiny_train.xplane.pb").exists(),
                    reason="no recorded trace")
def test_reduction_on_a_recorded_trace():
    """Four steps of a two-layer GPT-2 on one v5e chip, recorded by
    `benchmark/tools/record_tiny_trace.py` (my chip run, PR 26)."""
    t = Trace.from_file(str(DATA / "tiny_train.xplane.pb"))
    runs = t.program_runs("jit_step")
    assert len(runs) == 4
    assert 0 < t.busy_s() <= t.window_s
    step_s = sum(r.dur for r in runs) / 1e9
    # a program's span holds small gaps between its operations
    assert t.busy_s() <= step_s <= t.busy_s() * 1.05
    attn = t.scope_time("attn", runs)
    # flash forward, dq and dkv for each of 2 layers and 4 steps
    assert 0 < attn < step_s
    assert "attn" in dict(t.self_times())
    gaps = dict(t.idle_gaps())
    assert abs(sum(gaps.values()) + t.busy_s() - t.window_s) < 1e-6
    assert "train_step" in gaps and "(no span)" in gaps
    assert t.collective_exposed_s() == 0.0

"""The window arithmetic: a stall injected into a fake clock moves every
end-to-end metric."""

import time
import types

import numpy as np

from benchmark import window


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt
        return self.t


def served(stall_at=None, stall=0.0, hiccup_every=0, hiccup=0.0):
    """Four streams served round-robin, a token every 10 ms each; a
    request arrives every 100 ms and waits for a free stream. One long
    stall delays most of the window's requests; a hiccup every few ticks
    lengthens more than a twentieth of the gaps."""
    clock = FakeClock()
    recs = [window.RequestRecord(due=0.1 * i, prompt_len=8,
                                 max_new_tokens=20) for i in range(40)]
    live, nxt = [], 0
    tick, stalled = 0, False
    while clock.t < 6.0:
        clock.advance(0.010)
        tick += 1
        if hiccup_every and tick % hiccup_every == 0:
            clock.advance(hiccup)
        if stall_at is not None and not stalled and clock.t >= stall_at:
            stalled = True
            clock.advance(stall)
        while nxt < len(recs) and recs[nxt].due <= clock.t and \
                len(live) < 4:
            recs[nxt].sent = clock.t
            live.append(recs[nxt])
            nxt += 1
        for r in list(live):
            r.token_times.append(clock.t)
            if len(r.token_times) == r.max_new_tokens:
                live.remove(r)
    return window.serve_metrics(recs, 0.0, 2.0, timeout_s=60.0)


def test_a_stall_moves_every_serve_metric():
    base = served()
    hit = served(stall_at=0.2, stall=1.0, hiccup_every=8, hiccup=0.03)
    assert hit["serve_tokens_per_s"] < 0.9 * base["serve_tokens_per_s"]
    assert hit["itl_p95_ms"] > 1.2 * base["itl_p95_ms"] or \
        hit["itl_p95_ms"] > base["itl_p95_ms"] + 1.0
    assert hit["ttft_p50_ms"] > base["ttft_p50_ms"] + 100
    assert hit["ttft_p95_ms"] > base["ttft_p95_ms"] + 100


def test_latency_counts_from_due_not_from_sent():
    r = window.RequestRecord(due=1.0, sent=1.4, token_times=[2.0, 2.1])
    m = window.serve_metrics([r], 0.0, 4.0, timeout_s=60.0)
    assert abs(m["ttft_p50_ms"] - 1000.0) < 1e-6
    assert abs(window.lateness_p95_ms([r]) - 400.0) < 1e-6


def test_a_request_without_a_first_token_counts_the_timeout():
    rs = [window.RequestRecord(due=0.5),
          window.RequestRecord(due=0.6, token_times=[0.7])]
    m = window.serve_metrics(rs, 0.0, 1.0, timeout_s=60.0)
    assert m["ttft_p95_ms"] > 50_000


def test_tokens_after_the_window_count_for_no_rate():
    r = window.RequestRecord(due=0.0, token_times=[0.5, 1.5, 2.5])
    m = window.serve_metrics([r], 0.0, 2.0, timeout_s=60.0)
    assert m["serve_tokens_per_s"] == 1.0


def test_a_stall_moves_the_train_rate():
    # 100 steps in 10 s, then the same steps with a 2 s stall before the
    # fence: the rate is over all the time of the window
    base = window.train_metrics(100, 8192, 0.0, 10.0)
    hit = window.train_metrics(100, 8192, 0.0, 12.0)
    assert hit["train_tokens_per_s"] < 0.85 * base["train_tokens_per_s"]
    assert base["train_tokens_per_s"] == 81920.0


def test_percentile_interpolates():
    assert window.percentile([1, 2, 3, 4], 50) == 2.5
    assert window.percentile([5], 95) == 5


class _FakeRouter:
    """Steps of a millisecond, until `stall_from`: the step that starts
    after it runs past the window's end."""

    def __init__(self):
        self.handles = []
        self.stall_from = float("inf")

    def submit(self, prompt, max_new_tokens, on_token):
        self.handles.append(types.SimpleNamespace(done=False))
        return self.handles[-1]

    def step(self):
        time.sleep(0.040 if time.perf_counter() >= self.stall_from
                   else 0.001)


class _FakeSystem:
    def __init__(self, mix):
        self.mix, self.router = mix, _FakeRouter()
        self.engine = types.SimpleNamespace(
            reset_stats=lambda: None, active_count=0, summary=dict)

    def queued(self):
        return len(self.router.handles)

    def busy(self):
        return True


def test_a_request_due_in_the_last_step_is_sent():
    """The loop leaves at the first iteration after the window's end; a
    request that fell due while the last step ran is sent then, late, and
    is no failure of the system's."""
    from benchmark import loadgen
    from benchmark.drivers import serve_open_loop as drv

    mix = {"ramp_s": 0.05, "first_token_timeout_s": 1.0}
    seconds = 0.25
    trace = [loadgen.Arrival(due, np.zeros(4, np.int32), 4, True)
             for due in (0.05, seconds - 0.010)]
    system = _FakeSystem(mix)
    # the last step starts at least 20 ms before the second request is
    # due and ends at least 10 ms after the window has closed
    system.router.stall_from = (time.perf_counter() + mix["ramp_s"]
                                + seconds - 0.030)
    out = drv.offer(system, trace, seconds)
    late = out["records"][1]
    assert late.sent is not None and late.sent >= out["t1"]
    assert len(system.router.handles) == 2
    assert out["counters"]["queued_t1"] == 2

"""The window arithmetic: a stall injected into a fake clock moves every
end-to-end metric."""

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

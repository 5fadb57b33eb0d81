"""Traffic-replay harness (ISSUE 15): seeded generators for realistic
million-user arrival shapes, and a fake-clock replay driver.

The generators are PURE HOST + numpy — no jax, no wall clock, no global
state — so the same seed always produces the identical trace
(tests/test_autoscale.py pins that tripwire). Three shapes cover the
capacity-planning stories the autoscaler must survive:

  * ``steady``  — homogeneous Poisson at ``base_qps``;
  * ``diurnal`` — a sinusoidal ramp peaking at ``base_qps * peak_mult``
    mid-trace (the day/night cycle, compressed to ``duration_s``);
  * ``flash``   — ``base_qps`` background with a ``peak_mult`` flash
    crowd inside ``[flash_at_s, flash_at_s + flash_len_s)`` — the
    scale-up reaction-time story.

Non-homogeneous arrivals use Poisson thinning at the peak rate, so
every shape is exact (not binned). Request lengths are heavy-tailed
(lognormal, clipped to the pool), and each tenant can open with a
shared prefix — the radix/fleet prefix cache's hot-prompt shape.

``replay()`` drives a ReplicaRouter (or anything with submit/step)
through a trace against a FakeClock: arrivals are released when the
fake clock passes them, one router step per tick, optionally stepping
an Autoscaler — zero wall-clock sleeps, which is what lets the quick
test tier drive it.
"""

from __future__ import annotations

import dataclasses
import math
import time
import zlib

import numpy as np

__all__ = [
    "Conversation",
    "ConversationTurn",
    "FakeClock",
    "TenantTraffic",
    "TrafficRequest",
    "WallClock",
    "make_conversations",
    "make_trace",
    "replay",
    "replay_conversations",
]


class FakeClock:
    """A monotonic clock you advance by hand — inject it wherever a
    component takes ``clock=`` (AdmissionController rate buckets,
    Autoscaler cooldowns, replay pacing) to make time a test input."""

    def __init__(self, start: float = 0.0):
        self._now = float(start)

    def now(self) -> float:
        return self._now

    __call__ = now

    def advance(self, dt: float) -> None:
        if dt < 0:
            raise ValueError(f"clocks only run forward, got dt={dt}")
        self._now += float(dt)


class WallClock:
    """FakeClock's real-time twin for subprocess soaks: ``now()`` is
    seconds since construction, ``advance(dt)`` sleeps just enough to
    hold the replay cadence (no sleep at all when the fleet is already
    behind schedule — a slow tick eats its own budget)."""

    def __init__(self):
        self._t0 = time.perf_counter()
        self._target = 0.0

    def now(self) -> float:
        return time.perf_counter() - self._t0

    __call__ = now

    def advance(self, dt: float) -> None:
        if dt < 0:
            raise ValueError(f"clocks only run forward, got dt={dt}")
        self._target += float(dt)
        lag = self._target - self.now()
        if lag > 0:
            time.sleep(lag)


@dataclasses.dataclass(frozen=True)
class TenantTraffic:
    """One tenant's slice of a generated trace: ``share`` of arrivals
    (normalized over the mix), the priority class its requests carry
    (0 = highest), and the shared-prefix shape — with probability
    ``prefix_frac`` a request opens with the tenant's own
    ``prefix_len`` fixed tokens (deterministic per (seed, name)), the
    hot-prompt pattern prefix caching feeds on."""

    name: str
    share: float = 1.0
    priority: int = 0
    prefix_len: int = 0
    prefix_frac: float = 0.0


@dataclasses.dataclass(frozen=True, eq=False)
class TrafficRequest:
    """One generated arrival (host-side only)."""

    at_s: float
    tenant: str
    priority: int
    prompt: np.ndarray        # int32 [prompt_len]
    max_new_tokens: int


def _lognormal_len(rng, mean: float, sigma: float, lo: int, hi: int) -> int:
    """Heavy-tail length draw with the given (linear-space) mean."""
    mu = math.log(max(mean, 1.0)) - sigma * sigma / 2.0
    return int(np.clip(round(rng.lognormal(mu, sigma)), lo, hi))


def make_trace(*, seed: int, duration_s: float, base_qps: float,
               shape: str = "steady", peak_mult: float = 4.0,
               flash_at_s: float | None = None,
               flash_len_s: float | None = None,
               tenants: tuple[TenantTraffic, ...] | None = None,
               vocab_size: int = 64, prompt_mean: float = 8.0,
               prompt_sigma: float = 0.6, prompt_cap: int = 32,
               new_mean: float = 8.0, new_sigma: float = 0.5,
               new_cap: int = 16) -> list[TrafficRequest]:
    """Generate one deterministic arrival trace, sorted by ``at_s``.

    Same arguments -> byte-identical trace (prompts included): the only
    entropy source is ``np.random.default_rng(seed)`` plus a per-tenant
    crc32-derived stream for shared prefixes.
    """
    if shape not in ("steady", "diurnal", "flash"):
        raise ValueError(f"unknown traffic shape {shape!r}; one of "
                         f"('steady', 'diurnal', 'flash')")
    if base_qps <= 0 or duration_s <= 0:
        raise ValueError("base_qps and duration_s must be > 0")
    tenants = tenants or (TenantTraffic("default"),)
    total_share = sum(t.share for t in tenants)
    if total_share <= 0:
        raise ValueError("tenant shares must sum > 0")
    cum = np.cumsum([t.share / total_share for t in tenants])
    # deterministic per-tenant shared prefixes: keyed on (seed, name)
    # so two tenants never collide and a re-run reproduces them
    prefixes = {
        t.name: np.random.default_rng(
            (seed, zlib.crc32(t.name.encode()))
        ).integers(1, vocab_size, (t.prefix_len,)).astype(np.int32)
        for t in tenants if t.prefix_len > 0
    }

    if shape == "flash":
        flash_at_s = duration_s / 3.0 if flash_at_s is None else flash_at_s
        flash_len_s = (duration_s / 6.0 if flash_len_s is None
                       else flash_len_s)

    def rate(t: float) -> float:
        if shape == "steady":
            return base_qps
        if shape == "diurnal":
            return base_qps * (1.0 + (peak_mult - 1.0) * 0.5
                               * (1.0 - math.cos(2 * math.pi
                                                 * t / duration_s)))
        return base_qps * (peak_mult
                           if flash_at_s <= t < flash_at_s + flash_len_s
                           else 1.0)

    lam_max = base_qps if shape == "steady" else base_qps * peak_mult
    rng = np.random.default_rng(seed)
    out: list[TrafficRequest] = []
    t = 0.0
    while True:
        t += float(rng.exponential(1.0 / lam_max))
        if t >= duration_s:
            break
        if rng.random() >= rate(t) / lam_max:  # thinning rejection
            continue
        ti = int(np.searchsorted(cum, rng.random(), side="right"))
        ten = tenants[min(ti, len(tenants) - 1)]
        plen = _lognormal_len(rng, prompt_mean, prompt_sigma, 1, prompt_cap)
        prompt = rng.integers(1, vocab_size, (plen,)).astype(np.int32)
        if ten.prefix_len and rng.random() < ten.prefix_frac:
            pre = prefixes[ten.name]
            keep = max(1, plen - pre.size)
            prompt = np.concatenate([pre, prompt[:keep]])[:prompt_cap]
        out.append(TrafficRequest(
            at_s=round(t, 6), tenant=ten.name, priority=ten.priority,
            prompt=prompt,
            max_new_tokens=_lognormal_len(rng, new_mean, new_sigma, 1,
                                          new_cap)))
    return out


@dataclasses.dataclass(frozen=True, eq=False)
class ConversationTurn:
    """One user turn of a multi-turn conversation: only the NEW user
    tokens — the replay driver concatenates the session's full history
    (earlier prompts + model replies) in front, which is exactly what
    a stateful chat client resubmits. ``think_gap_s`` is the seeded
    think time between the previous turn's last token and this turn's
    arrival (0.0 on the opening turn — the open time lives on the
    Conversation)."""

    user_tokens: np.ndarray   # int32 [len] — this turn's NEW tokens
    max_new_tokens: int
    think_gap_s: float


@dataclasses.dataclass(frozen=True, eq=False)
class Conversation:
    """One generated multi-turn session: opens at ``open_at_s``, then
    each turn follows the previous turn's completion by its think gap.
    ``session_id`` is stable across turns — the persistent-session
    reattach key."""

    session_id: str
    tenant: str
    priority: int
    open_at_s: float
    turns: tuple[ConversationTurn, ...]


def make_conversations(*, seed: int, duration_s: float,
                       session_rate: float,
                       tenants: tuple[TenantTraffic, ...] | None = None,
                       turns_mean: float = 3.0, turns_sigma: float = 0.5,
                       turns_cap: int = 8,
                       think_mean_s: float = 1.0,
                       vocab_size: int = 64,
                       turn_mean: float = 6.0, turn_sigma: float = 0.5,
                       turn_cap: int = 16,
                       new_mean: float = 6.0, new_sigma: float = 0.5,
                       new_cap: int = 12) -> list[Conversation]:
    """Generate a deterministic multi-turn conversation mix, sorted by
    ``open_at_s`` (ISSUE 18's traffic shape).

    Session OPENS are Poisson at ``session_rate``; each session draws
    a lognormal turn count (clipped to [1, turns_cap]), exponential
    think-time gaps with mean ``think_mean_s`` between turns, and
    heavy-tailed per-turn user/new token lengths. Tenants come from the
    same ``TenantTraffic`` mix as :func:`make_trace` — a tenant with
    ``prefix_len``/``prefix_frac`` opens its sessions with the shared
    tenant prompt (the system-prompt shape prefix caching feeds on).
    ``session_id`` is ``f"{tenant}-s{k}"`` with k the global open order
    — same seed, same ids, same tokens."""
    if session_rate <= 0 or duration_s <= 0:
        raise ValueError("session_rate and duration_s must be > 0")
    tenants = tenants or (TenantTraffic("default"),)
    total_share = sum(t.share for t in tenants)
    if total_share <= 0:
        raise ValueError("tenant shares must sum > 0")
    cum = np.cumsum([t.share / total_share for t in tenants])
    prefixes = {
        t.name: np.random.default_rng(
            (seed, zlib.crc32(t.name.encode()))
        ).integers(1, vocab_size, (t.prefix_len,)).astype(np.int32)
        for t in tenants if t.prefix_len > 0
    }
    rng = np.random.default_rng((seed, 0x5e55))
    out: list[Conversation] = []
    t = 0.0
    k = 0
    while True:
        t += float(rng.exponential(1.0 / session_rate))
        if t >= duration_s:
            break
        ti = int(np.searchsorted(cum, rng.random(), side="right"))
        ten = tenants[min(ti, len(tenants) - 1)]
        n_turns = _lognormal_len(rng, turns_mean, turns_sigma, 1,
                                 turns_cap)
        turns = []
        for j in range(n_turns):
            ulen = _lognormal_len(rng, turn_mean, turn_sigma, 1, turn_cap)
            toks = rng.integers(1, vocab_size, (ulen,)).astype(np.int32)
            if j == 0 and ten.prefix_len \
                    and rng.random() < ten.prefix_frac:
                toks = np.concatenate(
                    [prefixes[ten.name], toks])[:ten.prefix_len + ulen]
            turns.append(ConversationTurn(
                user_tokens=toks,
                max_new_tokens=_lognormal_len(rng, new_mean, new_sigma,
                                              1, new_cap),
                think_gap_s=(0.0 if j == 0 else round(
                    float(rng.exponential(think_mean_s)), 6))))
        out.append(Conversation(
            session_id=f"{ten.name}-s{k}", tenant=ten.name,
            priority=ten.priority, open_at_s=round(t, 6),
            turns=tuple(turns)))
        k += 1
    return out


def replay_conversations(router, convs, *,
                         clock: FakeClock | None = None,
                         tick_s: float = 0.02, autoscaler=None,
                         on_turn=None, max_seq_len: int | None = None,
                         submit_kwargs: dict | None = None,
                         max_ticks: int = 500_000) -> dict[str, list]:
    """Drive ``router`` through a conversation mix against a fake
    clock. A session's turn t submits only after turn t-1 finished AND
    its think gap has elapsed — the stream-close/reattach rhythm the
    session tiers live on. Each submit carries ``session_id=`` and the
    FULL history (prior prompts + delivered replies) as its prompt,
    exactly like a stateful chat client; turns that would overflow
    ``max_seq_len`` end their conversation early. Returns
    {session_id: [turn handles...]} in submit order."""
    clock = clock or FakeClock()
    kwargs = submit_kwargs or {}
    # per-conversation cursor: next turn index, earliest release time,
    # accumulated token history, the in-flight handle (if any)
    state = [{"c": c, "turn": 0, "ready_at": c.open_at_s,
              "history": np.zeros(0, np.int32), "inflight": None}
             for c in sorted(convs, key=lambda c: c.open_at_s)]
    out: dict[str, list] = {c.session_id: [] for c in convs}
    for ticks in range(max_ticks):
        now = clock.now()
        live = False
        for s in state:
            c = s["c"]
            if s["inflight"] is not None:
                rr = s["inflight"]
                if not rr.done:
                    live = True
                    continue
                toks = np.asarray(rr.tokens, np.int32)
                s["history"] = np.concatenate(
                    [rr.prompt, toks]) if rr.finish_reason in (
                        "stop", "length") else s["history"]
                s["inflight"] = None
                s["turn"] += 1
                if (s["turn"] < len(c.turns)
                        and rr.finish_reason in ("stop", "length")):
                    s["ready_at"] = (now
                                     + c.turns[s["turn"]].think_gap_s)
                else:
                    s["turn"] = len(c.turns)  # shed/failed: close early
            if s["turn"] >= len(c.turns) or s["ready_at"] > now:
                live = live or s["turn"] < len(c.turns)
                continue
            turn = c.turns[s["turn"]]
            prompt = np.concatenate([s["history"], turn.user_tokens])
            if (max_seq_len is not None
                    and prompt.size + turn.max_new_tokens > max_seq_len):
                s["turn"] = len(c.turns)  # context exhausted
                continue
            rr = router.submit(prompt,
                               max_new_tokens=turn.max_new_tokens,
                               tenant=c.tenant, priority=c.priority,
                               session_id=c.session_id, **kwargs)
            out[c.session_id].append(rr)
            if on_turn is not None:
                on_turn(c, s["turn"], rr, clock)
            s["inflight"] = rr
            live = True
        router.step()
        if autoscaler is not None:
            autoscaler.step()
        if not live and all(s["turn"] >= len(s["c"].turns)
                            for s in state):
            return out
        clock.advance(tick_s)
    raise RuntimeError(
        f"conversation replay did not drain within {max_ticks} ticks")


def replay(router, trace, *, clock: FakeClock | None = None,
           tick_s: float = 0.02, autoscaler=None, on_tick=None,
           submit_kwargs: dict | None = None,
           max_ticks: int = 500_000) -> list:
    """Drive ``router`` through ``trace`` against a fake clock: release
    every arrival whose ``at_s`` the clock has passed, step the router
    (and the autoscaler, if given) once per tick, advance the clock by
    ``tick_s``, and keep ticking past the last arrival until the router
    drains. Returns the submitted request handles in arrival order —
    shed/failed ones included, exactly as ``router.submit`` returned
    them. No wall-clock sleeps anywhere: replay speed is whatever the
    engines can step."""
    clock = clock or FakeClock()
    kwargs = submit_kwargs or {}
    reqs: list = []
    i = 0
    for ticks in range(max_ticks):
        now = clock.now()
        while i < len(trace) and trace[i].at_s <= now:
            tr = trace[i]
            i += 1
            reqs.append(router.submit(
                tr.prompt, max_new_tokens=tr.max_new_tokens,
                tenant=tr.tenant, priority=tr.priority, **kwargs))
        router.step()
        if autoscaler is not None:
            autoscaler.step()
        if on_tick is not None:
            on_tick(ticks, clock)
        if (i >= len(trace) and not router.queue_depth
                and not router.in_flight):
            return reqs
        clock.advance(tick_s)
    raise RuntimeError(f"replay did not drain within {max_ticks} ticks "
                       f"({len(trace) - i} arrivals unreleased, "
                       f"queue={router.queue_depth}, "
                       f"in_flight={router.in_flight})")

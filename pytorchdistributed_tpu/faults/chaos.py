"""Rate-based fault schedules + wire-level mangling (ISSUE 19).

The base ``FaultInjector`` fires one-shot ``@tick=T`` specs — fine for
"prove the watchdog catches ONE hang", useless for a soak, where faults
must keep arriving for minutes with random overlap. ``ChaosSchedule``
extends it into a *process*: every serving/wire spec may carry

  * ``rate=R`` — a Poisson process at R events/sec over the schedule's
    clock (wall or fake): each consult fires with probability
    ``1 - exp(-R * dt)`` for the elapsed ``dt``;
  * ``period=P`` — deterministic firings every P seconds (elapsed time
    is accumulated, so a slow tick can fire multiple times);
  * ``burst=B`` — each firing claims B victims instead of one;
  * ``replica=I`` — target replica I; omitted → a seeded-RNG choice
    from the replicas the schedule has seen this tick.

One-shot ``@tick=T`` specs still work (``super().on_serving_tick``
handles them, markers and all), so a plan can mix
``replica_crash@tick=40; replica_hang@rate=0.05; wire_torn@rate=0.02``.
Determinism: all randomness flows from the constructor seed plus the
injected clock, so a soak with ``FakeClock`` replays bit-identically.

Wire faults never reach ``on_serving_tick`` — the router's
``SubprocessReplica`` consults ``mangle_recv`` on every response line
instead, and the schedule corrupts/tears/delays/drops it there. The
router's job (serving/router.py) is to survive whatever this returns:
a mangled line is a protocol fault → quarantine, a dropped line is
silence → the per-op timeout machinery.

``recovery_table`` is the read side: given the router's telemetry event
stream it matches each injection to its detection and recovery events
and reports per-fault-class MTTR percentiles — the number a soak's
report carries (serving/soak.py).
"""

from __future__ import annotations

import math
import random
import time

from pytorchdistributed_tpu.faults.inject import (
    _SERVING_KINDS,
    _WIRE_KINDS,
    FaultInjector,
    FaultPlan,
    FaultSpec,
)
from pytorchdistributed_tpu.telemetry.events import EventLog

__all__ = ["ChaosSchedule", "recovery_table"]


class ChaosSchedule(FaultInjector):
    """A FaultInjector whose serving/wire specs fire as rate-based
    processes over an injected clock.

    The router consults ``on_serving_tick(tick, replica)`` once per
    replica per tick (exactly the base-class contract) and
    ``mangle_recv(replica, line)`` once per received wire line. Rate
    decisions are made once per (spec, tick): the first consult of a
    tick draws how many victims each spec claims and which replicas
    they are; later consults of the same tick just collect their
    verdicts. Targeted specs (``replica=I``) only ever hit I; random
    ones draw from the replicas seen on the *previous* consult round,
    so the victim pool tracks the live fleet.
    """

    #: Routers check this to know the injector wants per-tick consults
    #: even for subprocess replicas (whose workers run their own base
    #: injector for one-shot specs) — rate decisions live router-side.
    rate_based = True

    def __init__(self, plan: FaultPlan | str, *, seed: int = 0,
                 rank: int = 0, state_dir: str | None = None,
                 events: EventLog | None = None,
                 clock=time.monotonic):
        if isinstance(plan, str):
            plan = FaultPlan.parse(plan)
        super().__init__(plan, rank=rank, state_dir=state_dir,
                         events=events, seed=seed)
        self._clock = clock
        self._chaos_rng = random.Random((seed, 0xC4A05, len(plan.specs))
                                        .__hash__())
        #: last decision time per spec index (None = epoch unset: the
        #: first consult only anchors the clock, nothing fires at t=0)
        self._spec_t: list[float | None] = [None] * len(plan.specs)
        self._acc = [0.0] * len(plan.specs)   # period accumulator
        #: wire rate/period state is PER (spec, replica): each pipe is
        #: its own Poisson process anchored at its own first line, so a
        #: replica whose first response lands late (sequential warmups
        #: take tens of seconds each) doesn't inherit a huge dt and a
        #: near-certain fault from a sibling's anchor
        self._wire_t: dict[tuple[int, int], float] = {}
        self._wire_acc: dict[tuple[int, int], float] = {}
        self._known: set[int] = set()         # replicas seen this tick
        self._prev_known: set[int] = set()
        self._decided_tick: int | None = None
        self._decisions: dict[int, FaultSpec] = {}  # replica -> spec
        #: append-only log of every firing (serving AND wire), for the
        #: soak report: {kind, replica, tick, time}
        self.injected: list[dict] = []

    # -- rate machinery ----------------------------------------------------

    def _draw_fires(self, i: int, spec: FaultSpec, now: float) -> int:
        """How many times spec i fires for the elapsed interval ending
        at ``now``. First consult anchors the epoch and returns 0."""
        last = self._spec_t[i]
        self._spec_t[i] = now
        if last is None:
            return 0
        dt = max(0.0, now - last)
        fires = 0
        if spec.rate is not None:
            # P(at least one Poisson arrival in dt); one firing per
            # consult interval is plenty at soak rates, and burst=
            # scales the blast radius when it isn't
            if self._chaos_rng.random() < -math.expm1(-spec.rate * dt):
                fires = 1
        elif spec.period is not None:
            self._acc[i] += dt
            while self._acc[i] >= spec.period:
                self._acc[i] -= spec.period
                fires += 1
        return fires * spec.burst

    def _serving_decisions(self, tick: int) -> None:
        """Draw this tick's rate/period victims (once per tick)."""
        if self._decided_tick == tick:
            return
        self._decided_tick = tick
        self._decisions = {}
        self._prev_known = self._known or self._prev_known
        self._known = set()
        now = float(self._clock())
        pool = sorted(self._prev_known)
        for i, spec in enumerate(self.plan.specs):
            if (spec.kind not in _SERVING_KINDS
                    or (spec.rate is None and spec.period is None)):
                continue
            fires = self._draw_fires(i, spec, now)
            if not fires:
                continue
            if spec.replica is not None:
                self._decisions.setdefault(spec.replica, spec)
                continue
            victims = (self._chaos_rng.sample(pool, min(fires, len(pool)))
                       if pool else [])
            for v in victims:
                self._decisions.setdefault(v, spec)

    # -- hooks -------------------------------------------------------------

    def on_serving_tick(self, tick: int, replica: int,
                        rate_only: bool = False) -> str | None:
        """Base one-shot specs first (unless ``rate_only`` — subprocess
        workers already run those in-process), then this tick's
        rate/period decision for ``replica``, if any."""
        self._serving_decisions(tick)
        self._known.add(replica)
        if not rate_only:
            kind = super().on_serving_tick(tick, replica)
            if kind is not None:
                self._record(kind, replica, tick)
                return kind
        spec = self._decisions.pop(replica, None)
        if spec is None:
            return None
        self._emit(spec, step=tick, replica=replica)
        self.last_fired = spec
        self._record(spec.kind, replica, tick)
        return spec.kind

    def _draw_wire_fires(self, i: int, spec: FaultSpec, replica: int,
                         now: float) -> int:
        """Per-(spec, replica) twin of ``_draw_fires`` for wire lines.
        The first line on a pipe anchors that pipe's epoch."""
        key = (i, replica)
        last = self._wire_t.get(key)
        self._wire_t[key] = now
        if last is None:
            return 0
        dt = max(0.0, now - last)
        if spec.rate is not None:
            return int(
                self._chaos_rng.random() < -math.expm1(-spec.rate * dt))
        acc = self._wire_acc.get(key, 0.0) + dt
        fires = 0
        while acc >= spec.period:
            acc -= spec.period
            fires += 1
        self._wire_acc[key] = acc
        return fires

    def on_wire(self, replica: int) -> FaultSpec | None:
        """The wire-fault draw for one received line on ``replica``.
        tick= wire specs are one-shot at/after that tick; rate/period
        specs use the same machinery as serving faults; bare p= specs
        draw per line."""
        tick = self._decided_tick or 0
        now = float(self._clock())
        for i, spec in enumerate(self.plan.specs):
            if (spec.kind not in _WIRE_KINDS
                    or (spec.replica is not None
                        and spec.replica != replica)):
                continue
            if spec.tick is not None:
                if (tick >= spec.tick
                        and self._once(f"{i}_{spec.kind}@{spec.tick}"
                                       + (f"_r{spec.replica}"
                                          if spec.replica is not None
                                          else ""))):
                    return spec
                continue
            if spec.rate is not None or spec.period is not None:
                if self._draw_wire_fires(i, spec, replica, now):
                    return spec
                continue
            if self._chaos_rng.random() < spec.p:
                return spec
        return None

    def mangle_recv(self, replica: int,
                    line: str) -> tuple[str | None, str | None]:
        """Apply at most one wire fault to a received line. Returns
        ``(line, kind)``: the (possibly mangled) line to deliver — None
        means the line was dropped — and the fault kind applied (None
        when the wire was clean)."""
        spec = self.on_wire(replica)
        if spec is None:
            return line, None
        tick = self._decided_tick or 0
        self._emit(spec, step=tick, replica=replica)
        self._record(spec.kind, replica, tick)
        if spec.kind == "wire_drop":
            return None, spec.kind
        if spec.kind == "wire_delay":
            time.sleep(spec.ms / 1e3)
            return line, spec.kind
        body = line.rstrip("\n")
        if spec.kind == "wire_torn":
            return body[: max(1, len(body) // 2)] + "\n", spec.kind
        # wire_corrupt: splice garbage mid-line — guaranteed non-JSON
        mid = max(1, len(body) // 2)
        return body[:mid] + '\x00{"~garbage' + body[mid:] + "\n", spec.kind

    def _record(self, kind: str, replica: int, tick: int) -> None:
        self.injected.append(dict(kind=kind, replica=replica, tick=tick,
                                  time=float(self._clock())))


# -- MTTR analysis ---------------------------------------------------------

#: Telemetry events that mean "the router noticed", per fault surface.
_DETECT_EVENTS = frozenset((
    "replica_dead", "quarantine", "wire_fault_detected", "wire_timeout",
    "wire_retry", "wire_slow", "handoff_aborted"))
#: Events that mean "the fleet healed": a quarantined/respawned replica
#: passing its canary back to HEALTHY.
_RECOVER_EVENTS = frozenset(("rejoin",))
#: Fault kinds that need no replica-level recovery — detection IS the
#: recovery (a delayed op completing, a slow step absorbed).
_SELF_HEALING = frozenset(("wire_delay", "replica_slow"))


def _percentile(values: list[float], q: float) -> float:
    xs = sorted(values)
    if not xs:
        return float("nan")
    idx = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return xs[idx]


def recovery_table(events: list[dict]) -> dict[str, dict]:
    """Join injection events with detection + recovery events into a
    per-fault-class table: ``{kind: {injected, detected, recovered,
    mttr_p50_s, mttr_p95_s, mttr_max_s}}``.

    ``events`` are router telemetry event rows ({"event", "time",
    "replica"?, "fault"?, ...}) in time order — the ring
    (``telemetry.recent_events``) for short runs, the
    ``router_metrics_rank*.jsonl`` "event" rows for soaks (the ring is
    bounded). Injections are ``fault_injected`` / ``wire_fault`` rows
    (the router emits one per applied fault, stamped with ``fault=``);
    a detection is the first detect-class event on the same replica at
    or after the injection; recovery is the first ``rejoin`` on that
    replica after detection. MTTR = recovery − injection. Self-healing
    kinds (wire_delay, replica_slow) count detection as recovery."""
    rows = sorted((e for e in events if "event" in e),
                  key=lambda e: float(e.get("time", 0.0)))
    table: dict[str, dict] = {}
    mttrs: dict[str, list[float]] = {}
    for i, e in enumerate(rows):
        if e["event"] not in ("fault_injected", "wire_fault"):
            continue
        kind = str(e.get("fault", "unknown"))
        rep = e.get("replica")
        t0 = float(e.get("time", 0.0))
        ent = table.setdefault(kind, dict(
            injected=0, detected=0, recovered=0))
        ent["injected"] += 1
        det_t = None
        for later in rows[i:]:
            if (later["event"] in _DETECT_EVENTS
                    and later.get("replica") == rep
                    and float(later.get("time", 0.0)) >= t0):
                det_t = float(later.get("time", 0.0))
                break
        if det_t is None:
            continue
        ent["detected"] += 1
        if kind in _SELF_HEALING:
            ent["recovered"] += 1
            mttrs.setdefault(kind, []).append(det_t - t0)
            continue
        for later in rows[i:]:
            if (later["event"] in _RECOVER_EVENTS
                    and later.get("replica") == rep
                    and float(later.get("time", 0.0)) >= det_t):
                ent["recovered"] += 1
                mttrs.setdefault(kind, []).append(
                    float(later.get("time", 0.0)) - t0)
                break
    for kind, ent in table.items():
        xs = mttrs.get(kind, [])
        ent["mttr_p50_s"] = round(_percentile(xs, 0.50), 4) if xs else None
        ent["mttr_p95_s"] = round(_percentile(xs, 0.95), 4) if xs else None
        ent["mttr_max_s"] = round(max(xs), 4) if xs else None
    return table

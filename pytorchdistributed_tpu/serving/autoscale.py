"""SLO-aware autoscaling control loop (ISSUE 15's tentpole).

The ``Autoscaler`` closes the loop the elastic pieces left open: PR 9's
SIGTERM drain and PR 10's respawn/warm-join machinery gave the fleet
lossless ways to SHRINK and GROW, but both waited for an operator (or a
crash). This control loop watches the router's own telemetry signal
rings — queue depth, TTFT EMA, shed rate, slot occupancy, prefill
backlog — against an ``SLOConfig``, and turns sustained breaches into
``router.add_replica()`` (the warm-join path: in-process joins share
the jit cache and compile NOTHING; subprocess joins restore from
checkpoint + JAX's persistent compilation cache) and sustained idleness into
``router.remove_replica()`` (graceful DRAINING -> tombstone — no
stream is ever dropped by a scale-down).

Control-theory guardrails, all injectable for fake-clock tests:

  * **hysteresis** — a breach must persist ``breach_ticks`` consecutive
    evaluations before scaling up, idleness ``clear_ticks`` before
    scaling down (clear_ticks > breach_ticks by default: growing is
    cheap and urgent, shrinking is neither);
  * **per-direction cooldowns** — after a scale-up the loop waits
    ``up_cooldown_s`` before growing again (the new replica needs time
    to absorb load, or one flash crowd buys the whole max_replicas
    range), and ``down_cooldown_s`` before shrinking;
  * **bounds** — ``min_replicas``/``max_replicas`` per pool; in a
    disaggregated fleet the prefill and decode pools scale
    INDEPENDENTLY on their own signals (queue/backlog pressure is a
    prefill problem; occupancy/TTFT pressure a decode problem).

Every decision is durable: appended to ``decisions`` with the metric
snapshot that justified it, and emitted as an ``autoscale_up`` /
``autoscale_down`` TelemetryEvent — the report CLI's scaling timeline.
``reaction_times()`` joins scale-up decisions against
``router.first_token_times`` to measure decision -> first-token wall
latency.

The router surface consumed here is deliberately narrow —
``telemetry.snapshot()``, ``pool_state()``, ``add_replica()`` /
``remove_replica()``, ``first_token_times`` — so the unit tests drive
the whole decision machine against a pure-host stub router, no jax.
"""

from __future__ import annotations

import dataclasses
import time

__all__ = ["SLOConfig", "Autoscaler"]

#: pool name -> the role a new replica of that pool is born with
_POOL_ROLE = {"fleet": "both", "prefill": "prefill", "decode": "decode"}


@dataclasses.dataclass(frozen=True)
class SLOConfig:
    """The serving objectives the autoscaler defends.

    ttft_target_ms: fleet TTFT EMA above this is a latency breach.
    shed_rate_max: windowed shed fraction (shed/submitted over the
      signal window) above this is a capacity breach.
    queue_high: router queue-depth EMA above this is a backlog breach.
    occupancy_high / occupancy_low: slot-occupancy band — above high
      breaches (decode/fleet pools); below low, with an empty queue and
      zero shed, counts toward scale-down.
    prefill_backlog_high: queue + prefilling + parked EMA above this
      breaches the PREFILL pool (disaggregated fleets only).
    """

    ttft_target_ms: float = 500.0
    shed_rate_max: float = 0.02
    queue_high: float = 8.0
    occupancy_high: float = 0.85
    occupancy_low: float = 0.25
    prefill_backlog_high: float = 8.0

    def __post_init__(self):
        if not 0.0 <= self.occupancy_low < self.occupancy_high:
            raise ValueError(
                f"need 0 <= occupancy_low < occupancy_high, got "
                f"{self.occupancy_low} / {self.occupancy_high}")
        if self.shed_rate_max < 0:
            raise ValueError("shed_rate_max must be >= 0")


class Autoscaler:
    """One evaluation per ``step()`` (call it right after
    ``router.step()`` — the replay harness does). Stateless between
    processes on purpose: everything it knows, it reads fresh from the
    router each tick."""

    def __init__(self, router, slo: SLOConfig | None = None, *,
                 min_replicas: int = 1, max_replicas: int = 4,
                 pool_bounds: dict[str, tuple[int, int]] | None = None,
                 breach_ticks: int = 3, clear_ticks: int = 8,
                 up_cooldown_s: float = 0.5, down_cooldown_s: float = 2.0,
                 window: int = 64, hold_on_degraded: bool = True,
                 clock=time.monotonic):
        if min_replicas < 1:
            raise ValueError(
                f"min_replicas must be >= 1, got {min_replicas}")
        if max_replicas < min_replicas:
            raise ValueError(
                f"max_replicas {max_replicas} < min_replicas "
                f"{min_replicas}")
        self.router = router
        self.slo = slo or SLOConfig()
        self.min_replicas = min_replicas
        self.max_replicas = max_replicas
        self.pool_bounds = dict(pool_bounds or {})
        self.breach_ticks = max(1, breach_ticks)
        self.clear_ticks = max(1, clear_ticks)
        self.up_cooldown_s = up_cooldown_s
        self.down_cooldown_s = down_cooldown_s
        self.window = window
        self.hold_on_degraded = bool(hold_on_degraded)
        self._clock = clock
        self._breach: dict[str, int] = {}
        self._clear: dict[str, int] = {}
        self._last_up: dict[str, float] = {}
        self._last_down: dict[str, float] = {}
        self.decisions: list[dict] = []

    # -- signal extraction ---------------------------------------------

    def _bounds(self, pool: str) -> tuple[int, int]:
        return self.pool_bounds.get(
            pool, (self.min_replicas, self.max_replicas))

    @staticmethod
    def _sig(snap: dict, name: str, field: str = "ema"):
        return (snap.get(name) or {}).get(field)

    def _read(self, pool: str, st: dict, snap: dict) -> dict:
        """The pool's decision inputs, as one flat dict — also exactly
        what a decision event gets stamped with."""
        sub = self._sig(snap, "submitted", "sum") or 0.0
        shed = self._sig(snap, "shed", "sum") or 0.0
        m = {
            "queue_depth": self._sig(snap, "queue_depth") or 0.0,
            "ttft_ema_s": self._sig(snap, "ttft_ema_s", "last"),
            "shed_rate": (shed / sub) if sub else 0.0,
            "prefill_backlog": self._sig(snap, "prefill_backlog") or 0.0,
            "occupancy": st.get("occupancy"),
            "healthy": st.get("healthy", 0),
            "draining": st.get("draining", 0),
            "quarantined": st.get("quarantined", 0),
        }
        # when the router carries a request tracer (ISSUE 17), its live
        # per-tenant SLO-debt ledger rides the same decision snapshot —
        # "slo_debt_s" (total TTFT seconds beyond budget) and
        # "slo_debt_tenant" (the worst offender) land in every stamped
        # decision event
        tracer = getattr(self.router, "trace", None)
        if tracer is not None:
            m.update(tracer.debt_totals())
        return m

    def _breaches(self, pool: str, m: dict) -> list[str]:
        """Which SLO signals this pool is currently violating. Role-
        aware: backlog/queue/shed pressure belongs to the pool that
        ADMITS (prefill, or the whole fleet colocated); occupancy and
        TTFT to the pool that DECODES."""
        slo, out = self.slo, []
        admits = pool in ("fleet", "prefill")
        decodes = pool in ("fleet", "decode")
        if admits and m["queue_depth"] > slo.queue_high:
            out.append("queue_depth")
        if admits and m["shed_rate"] > slo.shed_rate_max:
            out.append("shed_rate")
        if (pool == "prefill"
                and m["prefill_backlog"] > slo.prefill_backlog_high):
            out.append("prefill_backlog")
        if decodes and (m["occupancy"] or 0.0) > slo.occupancy_high:
            out.append("occupancy")
        if (decodes and m["ttft_ema_s"] is not None
                and m["ttft_ema_s"] * 1e3 > slo.ttft_target_ms):
            out.append("ttft")
        return out

    def _idle(self, pool: str, m: dict) -> bool:
        slo = self.slo
        occ_ok = (m["occupancy"] is None
                  or m["occupancy"] < slo.occupancy_low)
        if pool == "prefill":
            return (m["prefill_backlog"] <= 1.0
                    and m["queue_depth"] < 1.0 and m["shed_rate"] == 0.0)
        return (occ_ok and m["queue_depth"] < 1.0
                and m["shed_rate"] == 0.0)

    # -- the control loop ----------------------------------------------

    def step(self) -> list[dict]:
        """One evaluation over every pool; returns the decisions made
        this tick (usually empty)."""
        snap = self.router.telemetry.snapshot(self.window)
        made: list[dict] = []
        for pool, st in self.router.pool_state().items():
            d = self._eval(pool, st, snap)
            if d is not None:
                made.append(d)
        return made

    def _eval(self, pool: str, st: dict, snap: dict) -> dict | None:
        m = self._read(pool, st, snap)
        breaches = self._breaches(pool, m)
        if breaches:
            self._breach[pool] = self._breach.get(pool, 0) + 1
            self._clear[pool] = 0
        elif self._idle(pool, m):
            self._clear[pool] = self._clear.get(pool, 0) + 1
            self._breach[pool] = 0
        else:
            self._breach[pool] = 0
            self._clear[pool] = 0
        if (self.hold_on_degraded
                and (st.get("dead", 0) or st.get("quarantined", 0))):
            # a degraded fleet can READ as idle (dead replicas serve
            # nothing); never scale down while recovery is in flight —
            # chaos soaks hit this constantly
            self._clear[pool] = 0
        now = self._clock()
        lo, hi = self._bounds(pool)
        # joins in flight (QUARANTINED warming) count toward the max —
        # a slow-warming subprocess join must not trigger a second one
        size = st.get("healthy", 0) + st.get("quarantined", 0)
        if (self._breach.get(pool, 0) >= self.breach_ticks
                and size < hi
                and now - self._last_up.get(pool, -1e18)
                >= self.up_cooldown_s):
            idx = self.router.add_replica(role=_POOL_ROLE[pool])
            self._last_up[pool] = now
            self._breach[pool] = 0
            return self._decide("scale_up", pool, idx, breaches, m, now)
        if (self._clear.get(pool, 0) >= self.clear_ticks
                and st.get("healthy", 0) > lo
                and st.get("draining", 0) == 0   # one drain at a time
                and now - self._last_down.get(pool, -1e18)
                >= self.down_cooldown_s):
            idx = self.router.remove_replica(
                role=None if pool == "fleet" else _POOL_ROLE[pool])
            if idx is None:
                return None   # the router vetoed (last capable replica)
            self._last_down[pool] = now
            self._clear[pool] = 0
            return self._decide("scale_down", pool, idx, ["idle"], m, now)
        return None

    def _decide(self, action: str, pool: str, replica: int,
                why: list[str], m: dict, now: float) -> dict:
        d = {"action": action, "pool": pool, "replica": replica,
             "why": list(why), "t": now,
             "wall_t": time.perf_counter(),
             **{f"m_{k}": v for k, v in m.items()}}
        self.decisions.append(d)
        self.router.telemetry.event(
            f"auto{action}", pool=pool, replica=replica,
            why=",".join(why),
            **{k: (round(v, 4) if isinstance(v, float) else v)
               for k, v in m.items() if v is not None})
        return d

    # -- measurement ---------------------------------------------------

    def reaction_times(self) -> list[dict]:
        """Per scale-up decision: wall seconds from the decision to the
        new replica's FIRST delivered token (None while it hasn't
        served yet)."""
        ftt = self.router.first_token_times
        out = []
        for d in self.decisions:
            if d["action"] != "scale_up":
                continue
            t = ftt.get(d["replica"])
            out.append({"replica": d["replica"], "pool": d["pool"],
                        "reaction_s": (round(t - d["wall_t"], 4)
                                       if t is not None
                                       and t >= d["wall_t"] else None)})
        return out

    def summary(self) -> dict:
        ups = [d for d in self.decisions if d["action"] == "scale_up"]
        downs = [d for d in self.decisions
                 if d["action"] == "scale_down"]
        reacts = [r["reaction_s"] for r in self.reaction_times()
                  if r["reaction_s"] is not None]
        return {
            "scale_ups": len(ups),
            "scale_downs": len(downs),
            "reaction_s_max": max(reacts) if reacts else None,
            "reaction_s_mean": (round(sum(reacts) / len(reacts), 4)
                                if reacts else None),
        }

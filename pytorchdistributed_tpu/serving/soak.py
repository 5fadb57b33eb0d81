"""Chaos soak (ISSUE 19): continuously-checked invariants over a live,
fault-riddled fleet.

``InvariantChecker`` rides ``traffic.replay()``'s ``on_tick`` hook and
watches the router the whole run — not a post-mortem: a violation is
stamped the tick it happens, with the tick and clock time attached.
The invariants are the serving layer's whole contract, restated as
runtime assertions:

  * **no orphan processes** — every worker PID ever seen is gone after
    ``router.close()`` (the torchrun elastic-agent contract: an agent
    that loses a worker tears down the rest, never leaks one);
  * **no compliant-tenant sheds** — a tenant inside its admission caps
    never pays for overload or for other tenants' bursts, even while
    replicas are being crashed/hung/corrupted under it;
  * **bounded per-tenant SLO debt** — queue-time debt per tenant stays
    under a budget (the autoscaler + failover are actually absorbing
    the faults, not just surviving them);
  * **zero fresh XLA traces on survivors** — a replica that stayed
    HEALTHY never recompiles mid-soak (``trace_count`` from the health
    snapshot is flat between quarantine episodes);
  * **every admitted stream terminal** — each submitted handle ends
    ``done`` with a finish reason; nothing is silently dropped;
  * **clean retire** — ``router.close()`` completes without raising
    (the paged engines' block-pool leak assertion lives inside it).

``run_soak()`` is the driver (the quick-tier mini-soak of
tests/test_chaos.py runs it): replay a seeded (usually diurnal) trace
over a router whose ``faults=`` is a ``ChaosSchedule``, autoscaler
live, checker attached; it returns one report dict with the finish
accounting, SLO attainment, the per-fault-class recovery table
(injected → detected → recovered, MTTR percentiles) and the invariant
verdicts.
"""

from __future__ import annotations

import collections
import os
import time

from pytorchdistributed_tpu.faults.chaos import recovery_table
from pytorchdistributed_tpu.serving.router import HEALTHY
from pytorchdistributed_tpu.serving.traffic import replay

__all__ = ["InvariantChecker", "run_soak"]


def _percentile(values, q: float):
    if not values:
        return None
    vals = sorted(values)
    idx = min(len(vals) - 1, max(0, round(q * (len(vals) - 1))))
    return vals[idx]


class InvariantChecker:
    """Continuous invariant assertions over a running fleet.

    Attach via ``replay(..., on_tick=checker.on_tick)``; call
    ``finalize(handles)`` AFTER ``router.close()``. Violations
    accumulate in ``self.violations`` (each a dict with ``invariant``,
    the tick, and the evidence); ``strict=True`` makes ``finalize``
    raise AssertionError if any were recorded.

    The checker also taps the router's telemetry event stream into
    ``self.events`` — unbounded, unlike the telemetry ring — which is
    what feeds ``faults.recovery_table`` for MTTR attribution.
    """

    def __init__(self, router, *, compliant=(), debt_budget_s=None,
                 strict=True, check_every=25):
        self.router = router
        self.compliant = tuple(compliant)
        self.debt_budget_s = debt_budget_s
        self.strict = bool(strict)
        self.check_every = max(1, int(check_every))
        self.violations: list[dict] = []
        self.events: list[dict] = []
        self.checks = 0
        self._tick = -1
        self._pids: set[int] = set()
        self._shed_by_tenant: collections.Counter = collections.Counter()
        #: (replica index, process generation) -> trace_count baseline,
        #: dropped whenever the replica is seen non-HEALTHY so rejoin /
        #: respawn re-baselines instead of flagging recovery warmup
        self._trace_base: dict[tuple, int] = {}
        self._debt_flagged: set[str] = set()
        self._tap_events()

    # -- wiring --------------------------------------------------------

    def _tap_events(self) -> None:
        orig = self.router.telemetry.event

        def tap(event, **row):
            self.events.append(
                {"event": event, "time": time.time(), **row})
            if event == "shed":
                tenant = row.get("tenant")
                self._shed_by_tenant[tenant] += 1
                if tenant in self.compliant:
                    self._violate("compliant_tenant_shed",
                                  tenant=tenant,
                                  request=row.get("request"))
            orig(event, **row)

        self.router.telemetry.event = tap

    def _violate(self, invariant: str, **evidence) -> None:
        self.violations.append(
            dict(invariant=invariant, tick=self._tick, **evidence))

    # -- the per-tick sweep --------------------------------------------

    def on_tick(self, ticks: int, clock) -> None:
        self._tick = ticks
        # PID collection is every tick: a replica can be born and die
        # between two sweeps and its process must still be accounted for
        for r in self.router._replicas:
            proc = getattr(r, "proc", None)
            if proc is not None:
                self._pids.add(proc.pid)
        if ticks % self.check_every:
            return
        self.checks += 1
        self._check_traces()
        self._check_debt()

    def _check_traces(self) -> None:
        for r, h in zip(self.router._replicas, self.router.health()):
            count = h.get("trace_count")
            if count is None:
                continue
            gen = getattr(getattr(r, "proc", None), "pid", None) or id(r)
            key = (h["replica"], gen)
            if h.get("status") != HEALTHY:
                self._trace_base.pop(key, None)
                continue
            base = self._trace_base.setdefault(key, int(count))
            if count > base:
                self._violate("fresh_trace_on_survivor",
                              replica=h["replica"], baseline=base,
                              trace_count=int(count))
                self._trace_base[key] = int(count)  # flag once per jump

    def _check_debt(self) -> None:
        tracer = self.router.trace
        if tracer is None or self.debt_budget_s is None:
            return
        for tenant, rec in getattr(tracer, "slo_debt", {}).items():
            debt = float(rec.get("debt_s", 0.0))
            if debt > self.debt_budget_s and tenant not in self._debt_flagged:
                self._debt_flagged.add(tenant)
                self._violate("slo_debt_exceeded", tenant=tenant,
                              debt_s=round(debt, 4),
                              budget_s=self.debt_budget_s)

    # -- post-close ----------------------------------------------------

    def finalize(self, handles=None) -> dict:
        """Run AFTER ``router.close()``: the terminal-streams check and
        the orphan sweep. Returns the invariant report; raises
        AssertionError on any violation when ``strict``."""
        if handles is not None:
            stuck = [rr.id for rr in handles
                     if rr is not None and not rr.done]
            if stuck:
                self._violate("non_terminal_streams", count=len(stuck),
                              sample=stuck[:5])
        orphans = []
        for pid in sorted(self._pids):
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                continue
            except PermissionError:
                pass  # alive, just not ours to signal
            orphans.append(pid)
        if orphans:
            self._violate("orphan_processes", pids=orphans)
        report = dict(
            ok=not self.violations,
            checks=self.checks,
            violations=list(self.violations),
            pids_seen=len(self._pids),
            shed_by_tenant=dict(self._shed_by_tenant),
        )
        if self.strict and self.violations:
            raise AssertionError(
                f"soak invariants violated: {self.violations}")
        return report


def run_soak(router, trace, *, clock=None, tick_s: float = 0.02,
             autoscaler=None, compliant=(), debt_budget_s=None,
             strict: bool = True, check_every: int = 25,
             submit_kwargs: dict | None = None,
             max_ticks: int = 500_000) -> dict:
    """Drive ``router`` through ``trace`` under chaos and return the
    soak report. The router should have been built with
    ``faults=ChaosSchedule(...)`` (or a ``PTD_FAULTS`` spec carrying
    rate/period/wire kinds — the router auto-wraps those); pass the
    live ``autoscaler`` to exercise scaling under faults.

    Closes the router before returning. ``strict=False`` records
    violations in the report instead of raising, so that a failed soak
    is reported whole."""
    checker = InvariantChecker(
        router, compliant=compliant, debt_budget_s=debt_budget_s,
        strict=strict, check_every=check_every)
    t0 = time.perf_counter()
    handles = replay(router, trace, clock=clock, tick_s=tick_s,
                     autoscaler=autoscaler, on_tick=checker.on_tick,
                     submit_kwargs=submit_kwargs, max_ticks=max_ticks)
    wall_s = time.perf_counter() - t0
    summary = router.summary()
    chaos = router._faults
    injected = list(getattr(chaos, "injected", ()))
    try:
        router.close()
    except Exception as e:  # noqa: BLE001 — a leak assertion IS a finding
        checker._violate("close_failed", error=f"{type(e).__name__}: {e}")
    invariants = checker.finalize(handles)

    reasons = collections.Counter(
        rr.finish_reason for rr in handles if rr is not None)
    ok_reasons = {"stop", "length"}
    finished = sum(n for r, n in reasons.items() if r in ok_reasons)
    admitted = len(handles) - reasons.get("shed", 0)
    ttfts = sorted(rr.ttft_s for rr in handles
                   if rr is not None and rr.ttft_s is not None)
    report = dict(
        requests=len(handles),
        admitted=admitted,
        finish_reasons=dict(reasons),
        slo_attainment=round(finished / admitted, 4) if admitted else None,
        ttft_p50_s=_percentile(ttfts, 0.50),
        ttft_p95_s=_percentile(ttfts, 0.95),
        wall_s=round(wall_s, 3),
        faults_injected=len(injected),
        injected_by_kind=dict(collections.Counter(
            row.get("kind") for row in injected)),
        recovery=recovery_table(checker.events),
        router=summary,
        invariants=invariants,
    )
    if autoscaler is not None and hasattr(autoscaler, "summary"):
        report["autoscaler"] = autoscaler.summary()
    return report

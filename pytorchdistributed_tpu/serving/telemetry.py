"""Serving telemetry bridge — the engine's observability half.

Emits through the existing telemetry/ package rather than growing a
parallel stack: the engine's host spans (``serve/*``) are in the
process's one span ring (telemetry/spans.py) whether or not anybody
asked for files; this bridge dumps that ring at close to the same
``spans_rank{rank}.trace.json`` contract the Trainer uses (so `python -m
pytorchdistributed_tpu.telemetry merge-trace <dir>` folds serving and
training onto one timeline), and the serving metrics — per-tick queue
depth / slot occupancy / tick latency, per-request TTFT and decode
tokens-per-s — land as JSONL rows in ``serve_metrics_rank{rank}.jsonl`` via the shared
JsonlWriter (line-buffered append: rows survive a killed server).
"""

from __future__ import annotations

import collections
import os
import time

from pytorchdistributed_tpu.telemetry import spans
from pytorchdistributed_tpu.telemetry.events import (
    TELEMETRY_DIR_ENV,
    JsonlWriter,
)
from pytorchdistributed_tpu.telemetry.spans import SPAN_TRACE_FILE

# writer filename / reader glob pair (same contract discipline as
# events.py's EVENTS_FILE/EVENTS_GLOB — rename together)
SERVE_METRICS_FILE = "serve_metrics_rank{rank}.jsonl"
SERVE_METRICS_GLOB = "serve_metrics_rank*.jsonl"

# the replica ROUTER's stream (ISSUE 9): per-replica health/occupancy
# rows, failover/shed/quarantine event rows, and the close-time summary
ROUTER_METRICS_FILE = "router_metrics_rank{rank}.jsonl"
ROUTER_METRICS_GLOB = "router_metrics_rank*.jsonl"


class ServingTelemetry:
    """Serving-metric JSONL sink (and, at close, the span-trace dump)
    for one engine/rank."""

    def __init__(self, run_dir: str | os.PathLike, rank: int | None = None):
        self.run_dir = str(run_dir)
        os.makedirs(self.run_dir, exist_ok=True)
        self.rank = (rank if rank is not None
                     else int(os.environ.get("RANK", "0")))
        self.metrics = JsonlWriter(os.path.join(
            self.run_dir, SERVE_METRICS_FILE.format(rank=self.rank)))
        self._since = time.perf_counter()  # the ring may hold older runs

    @classmethod
    def from_env(cls) -> "ServingTelemetry | None":
        """Construct from the launcher's PTD_TELEMETRY_DIR contract
        (None when unset) — the same env the Trainer reads."""
        d = os.environ.get(TELEMETRY_DIR_ENV)
        return cls(d) if d else None

    def tick(self, **row) -> None:
        """One decode-tick metric row (queue depth, occupancy, latency)."""
        self.metrics.write({"kind": "tick", "time": round(time.time(), 3),
                            **row})

    def request(self, req) -> None:
        """One completed-request row: TTFT + per-request decode rate,
        plus the paged-engine lifecycle (prefix-cache tokens admitted by
        reference, prefill chunks paid, preempt round-trips — all 0 on
        the dense engine) and the speculative counters (draft proposals
        made / accepted — both 0 when spec is off)."""
        ttft = req.ttft_s
        # end-to-end TTFT (ISSUE 17 satellite): measured from the
        # ORIGIN router submit carried across the handoff wire — on a
        # handed-off stream this is the client-visible number, while
        # ``ttft_ms`` stays decode-replica-local so existing BENCH
        # baselines remain comparable
        e2e = getattr(req, "ttft_e2e_s", None)
        if e2e is None:
            e2e = ttft
        self.metrics.write({
            "kind": "request", "time": round(time.time(), 3),
            "id": req.id, "prompt_len": int(req.prompt.size),
            "new_tokens": len(req.new_tokens),
            "finish_reason": req.finish_reason,
            "ttft_ms": None if ttft is None else round(ttft * 1e3, 3),
            "ttft_e2e_ms": None if e2e is None else round(e2e * 1e3, 3),
            "decode_tokens_per_s": req.decode_tokens_per_s,
            "prefix_hit_tokens": getattr(req, "prefix_hit_tokens", 0),
            "prefill_chunks": getattr(req, "prefill_chunks", 0),
            "preemptions": getattr(req, "preemptions", 0),
            "draft_tokens": getattr(req, "draft_tokens", 0),
            "accepted_tokens": getattr(req, "accepted_tokens", 0),
            # > 0 when this request RESUMED from tokens (router
            # failover redispatch): the engine re-prefilled this many
            # already-generated tokens and only decoded past them
            "resumed_from": getattr(req, "resumed_from", 0),
        })

    def pool(self, **row) -> None:
        """One paged-pool summary row (engine close/summary time): the
        prefix-cache hit counters + block utilization the report CLI's
        serving table renders."""
        self.metrics.write({"kind": "pool", "time": round(time.time(), 3),
                            **row})

    def close(self) -> None:
        # in-process replicas share the ring: this rank's file holds the
        # spans under its own ``replica`` id and those that carry none
        spans.ring().dump(
            os.path.join(self.run_dir,
                         SPAN_TRACE_FILE.format(rank=self.rank)),
            rank=self.rank, replica=self.rank, since=self._since)
        self.metrics.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class SignalRing:
    """One bounded time series: an EMA plus the last-N raw samples.
    Pure host state — the autoscaler's decision inputs, so everything
    here must work without a run_dir or a wall clock."""

    def __init__(self, maxlen: int = 256, alpha: float = 0.2):
        self.samples: collections.deque[float] = collections.deque(
            maxlen=maxlen)
        self.alpha = alpha
        self.ema: float | None = None
        self.count = 0

    def push(self, value: float) -> None:
        v = float(value)
        self.samples.append(v)
        self.ema = (v if self.ema is None
                    else (1 - self.alpha) * self.ema + self.alpha * v)
        self.count += 1

    def stats(self, window: int | None = None) -> dict:
        xs = list(self.samples)
        if window is not None:
            xs = xs[-window:]
        if not xs:
            return {"last": None, "ema": None, "n": 0,
                    "sum": 0.0, "mean": None, "max": None}
        return {"last": xs[-1], "ema": self.ema, "n": len(xs),
                "sum": float(sum(xs)), "mean": float(sum(xs) / len(xs)),
                "max": float(max(xs))}


class RouterTelemetry:
    """The replica router's metric sink (ISSUE 9) — one JSONL stream per
    router under ``router_metrics_rank{rank}.jsonl``, next to the
    per-replica engines' own ``serve_metrics`` files. Three row kinds:

      * ``replica`` — a per-replica health/load sample (status, role,
        active, queued, parked KV handoffs, occupancy, progress
        watermark) at the router's sampling cadence;
      * ``event``   — one lifecycle transition (failover, redispatch,
        shed, quarantine, rejoin, drain, scale_up/scale_down) with its
        router tick: the post-mortem trail of WHY streams moved
        between replicas — and WHY the fleet grew or shrank;
      * ``router``  — the close-time summary (failovers,
        redispatched_requests, shed_requests, quarantines, rejoins,
        per-replica occupancy balance, per-tenant table) the report
        CLI's router table renders.

    ISSUE 15 adds the in-memory half the autoscaler consumes: every
    ``signal()`` call lands in a bounded per-signal ring (EMA + last-N
    samples; ``snapshot()`` reads them), and ``run_dir=None``
    constructs a RING-ONLY instance — no directory, no JSONL, just the
    live time series — so a router always has signals to offer even
    when nobody asked for files.
    """

    def __init__(self, run_dir: str | os.PathLike | None = None,
                 rank: int | None = None, *, ring: int = 256,
                 ema_alpha: float = 0.2):
        self.run_dir = None if run_dir is None else str(run_dir)
        self.rank = (rank if rank is not None
                     else int(os.environ.get("RANK", "0")))
        if self.run_dir is None:
            self.metrics = None
        else:
            os.makedirs(self.run_dir, exist_ok=True)
            self.metrics = JsonlWriter(os.path.join(
                self.run_dir, ROUTER_METRICS_FILE.format(rank=self.rank)))
        self._ring_len = ring
        self._ema_alpha = ema_alpha
        self.rings: dict[str, SignalRing] = {}
        self.recent_events: collections.deque[dict] = collections.deque(
            maxlen=ring)

    @classmethod
    def from_env(cls) -> "RouterTelemetry | None":
        d = os.environ.get(TELEMETRY_DIR_ENV)
        return cls(d) if d else None

    def signal(self, **signals) -> None:
        """Feed one sample per named signal into its ring (creating
        rings on first sight). None values are skipped — a signal with
        no reading this tick simply has no sample."""
        for name, value in signals.items():
            if value is None:
                continue
            ring = self.rings.get(name)
            if ring is None:
                ring = self.rings[name] = SignalRing(
                    maxlen=self._ring_len, alpha=self._ema_alpha)
            ring.push(value)

    def snapshot(self, window: int | None = None) -> dict[str, dict]:
        """Per-signal {last, ema, n, sum, mean, max} over the ring (or
        its last ``window`` samples) — the autoscaler's whole view of
        the world, and the metric snapshot its decisions are stamped
        with."""
        return {name: ring.stats(window)
                for name, ring in sorted(self.rings.items())}

    def replica(self, **row) -> None:
        if self.metrics is not None:
            self.metrics.write({"kind": "replica",
                                "time": round(time.time(), 3), **row})

    def event(self, event: str, **row) -> None:
        self.recent_events.append({"event": event, "time": time.time(),
                                   **row})
        if self.metrics is not None:
            self.metrics.write({"kind": "event", "event": event,
                                "time": round(time.time(), 3), **row})

    def summary(self, **row) -> None:
        if self.metrics is not None:
            self.metrics.write({"kind": "router",
                                "time": round(time.time(), 3), **row})

    def close(self) -> None:
        if self.metrics is not None:
            self.metrics.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

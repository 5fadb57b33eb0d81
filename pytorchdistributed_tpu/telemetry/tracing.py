"""Fleet-wide distributed request tracing (ISSUE 17).

`spans.py` answers "where did this RANK's host time go?"; nothing
answered "where did this REQUEST's 400 ms go?" once the fleet became
role-aware and self-scaling (PRs 11-16): one stream now crosses router
submit -> WDRR admission -> a prefill replica -> a parked-KV handoff ->
a decode replica, surviving failover and preemption on the way. This
module is the Dapper-style request-scoped half:

  * `TraceContext` — trace_id + root span id, minted once at
    `ReplicaRouter.submit` and carried by value across every process
    boundary (the line-JSON wire's submit op, the KV handoff payload),
    so a request's spans form ONE connected trace no matter how many
    replicas served it.
  * `RequestTracer` — the per-process writer: each completed stage
    lands as one JSONL row in ``trace_rank{rank}.jsonl`` (the same
    writer-FILE/reader-GLOB contract as serve_metrics). Rows carry
    unix-epoch microsecond timestamps via a once-per-process anchor
    (the spans.py convention), so independently-written ranks merge
    onto one timeline. Host-only by construction: recording a span is
    a dict + one line-buffered write, nothing touches the device or
    the jit cache.
  * readers — `read_trace` / `critical_path` / `chrome_trace` /
    `slo_debt`: the report CLI's fleet-wide merge. `critical_path`
    clips a trace's stage spans into a timeline PARTITION of the root
    interval (latest-starting span owns an overlapped instant;
    uncovered time is ``stall``), so per-stage sums tile
    [submit, finish] exactly — the breakdown always adds up to the
    request's terminal latency.

Stages (one request's life, router clock unless noted):

  queue      router submit -> WDRR dequeue (admission.popleft stamps)
  admission  dequeue -> accepted by a replica's engine
  prefill    engine submit -> first token / parked   (engine-side)
  handoff    parked-KV export -> import on the decode replica
  decode     first token (or import) -> retired      (engine-side)
  stall      anything the stages above did not cover (requeue backoff,
             parked-waiting-for-a-decode-slot, reap latency)

plus marker spans (``redispatch``) for failover/preemption requeues and
the root ``request`` span the whole trace parents to.

Off means off: every hook sits behind ``if tracer is not None`` — no
per-tick host work, no files, event/metric streams unchanged
(tests/test_tracing.py pins it, TRACE_COUNTS included).
"""

from __future__ import annotations

import glob
import itertools
import json
import os
import time
import uuid

from pytorchdistributed_tpu.telemetry.events import (
    TELEMETRY_DIR_ENV,
    JsonlWriter,
)

# writer filename / reader glob pair (rename together — report.py, the
# trace CLI and the tests all read through TRACE_GLOB)
TRACE_FILE = "trace_rank{rank}.jsonl"
TRACE_GLOB = "trace_rank*.jsonl"

#: request tracing master switch (default OFF): subprocess workers read
#: it; the router's ``trace="auto"`` honors it too.
TRACE_ENV = "PTD_TRACE"

#: the attributable stages, in sweep priority order (when two spans
#: cover the same instant the LATER-STARTING one owns it — a handoff
#: inside a long decode window attributes to the handoff)
STAGES = ("queue", "admission", "prefill", "handoff", "decode")

#: default per-request TTFT budget for SLO-debt attribution — matches
#: serving/autoscale.py's SLOConfig.ttft_target_ms default.
DEFAULT_SLO_TTFT_S = 0.5

# One-time wall-clock anchor (the spans.py convention): all repo
# timestamps are time.perf_counter() readings; the anchor maps them to
# unix-epoch so spans written by different processes merge. Every
# tracer in one process shares this module-level anchor, so durations
# and boundaries are EXACT within a process.
_ANCHOR_S = time.time() - time.perf_counter()


def to_unix(t_pc: float) -> float:
    """Map a time.perf_counter() reading to unix-epoch seconds."""
    return t_pc + _ANCHOR_S


def from_unix(t_unix: float) -> float:
    """Map unix-epoch seconds onto this process's perf_counter clock."""
    return t_unix - _ANCHOR_S


class TraceContext:
    """The by-value trace identity a request carries everywhere:
    ``trace_id`` names the trace, ``root`` the root span every stage
    span parents to (a FLAT chain on purpose: connectivity is a single
    equality check, and a late-joining emitter — the decode replica a
    handoff lands on — needs no span-stack handshake)."""

    __slots__ = ("trace_id", "root")

    def __init__(self, trace_id: str, root: str):
        self.trace_id = str(trace_id)
        self.root = str(root)

    def to_wire(self) -> dict:
        return {"trace_id": self.trace_id, "root": self.root}

    @classmethod
    def from_wire(cls, d) -> "TraceContext | None":
        if not d:
            return None
        return cls(d["trace_id"], d["root"])

    def __repr__(self):
        return f"TraceContext({self.trace_id}, root={self.root})"


class RequestTracer:
    """Per-process request-span writer + the live SLO-debt ledger the
    autoscaler reads. One instance per emitting process (the router
    owns one and shares it with its in-process engines; a subprocess
    worker builds its own from the env contract)."""

    def __init__(self, run_dir: str | os.PathLike,
                 rank: int | None = None, *,
                 slo_ttft_s: float = DEFAULT_SLO_TTFT_S):
        self.run_dir = str(run_dir)
        self.rank = (rank if rank is not None
                     else int(os.environ.get("RANK", "0")))
        self.slo_ttft_s = float(slo_ttft_s)
        # block-buffered, not line-buffered: a span is a memcpy, not a
        # write syscall (the < 1% overhead bar is measured against a
        # test-size model where a request completes in ~10 ms); rows
        # land on close()/flush(), and read_trace tolerates a torn tail
        self._w = JsonlWriter(os.path.join(
            self.run_dir, TRACE_FILE.format(rank=self.rank)),
            buffering=-1)
        self._seq = itertools.count()
        # {tenant: {"requests", "breaches", "debt_s"}} — updated at
        # router _finish time; Autoscaler._read folds the totals into
        # its decision snapshot
        self.slo_debt: dict[str, dict] = {}

    @classmethod
    def from_env(cls, rank: int | None = None) -> "RequestTracer | None":
        """The subprocess worker's constructor: PTD_TRACE=1 plus the
        launcher's telemetry-dir contract, else None (off means off)."""
        if os.environ.get(TRACE_ENV, "0").lower() not in ("1", "true",
                                                          "yes", "on"):
            return None
        d = os.environ.get(TELEMETRY_DIR_ENV)
        return cls(d, rank=rank) if d else None

    def new_trace(self) -> TraceContext:
        tid = uuid.uuid4().hex[:16]
        return TraceContext(tid, f"{tid}/0")

    def span(self, ctx: TraceContext | None, stage: str,
             t0: float, t1: float, *, root: bool = False,
             **attrs) -> None:
        """Record one COMPLETED stage: t0/t1 are perf_counter readings
        (mapped to unix µs here). Emitters call this at stage
        completion — no context-manager nesting to thread through the
        engine's callback-driven lifecycle."""
        if ctx is None:
            return
        sid = ctx.root if root else f"{self.rank}/{next(self._seq) + 1}"
        row = {"trace": ctx.trace_id, "span": sid,
               "parent": None if root else ctx.root,
               "stage": stage, "rank": self.rank,
               "t0_us": round(to_unix(t0) * 1e6, 1),
               "t1_us": round(to_unix(t1) * 1e6, 1)}
        row.update(attrs)
        self._w.write(row)

    def note_finish(self, tenant: str, ttft_s: float | None) -> None:
        """Accumulate the tenant's SLO debt (TTFT seconds beyond the
        budget) — the live aggregate the autoscaler stamps into its
        decision snapshots."""
        rec = self.slo_debt.setdefault(
            tenant, {"requests": 0, "breaches": 0, "debt_s": 0.0})
        rec["requests"] += 1
        if ttft_s is None:
            return
        debt = ttft_s - self.slo_ttft_s
        if debt > 0:
            rec["breaches"] += 1
            rec["debt_s"] += debt

    def debt_totals(self) -> dict:
        """{"slo_debt_s": total, "slo_debt_tenant": worst} — flat keys
        shaped for the autoscaler's metric snapshot."""
        if not self.slo_debt:
            return {}
        worst = max(self.slo_debt, key=lambda t: self.slo_debt[t]["debt_s"])
        return {"slo_debt_s": round(sum(
            r["debt_s"] for r in self.slo_debt.values()), 4),
            "slo_debt_tenant": worst}

    def close(self) -> None:
        self._w.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ---------------------------------------------------------------------------
# readers — the fleet-wide merge the report CLI and tests consume


def read_trace(run_dir: str | os.PathLike) -> list[dict]:
    """Every span row under ``run_dir`` (all ranks merged; torn final
    lines of a killed process skipped)."""
    rows: list[dict] = []
    for path in sorted(glob.glob(os.path.join(str(run_dir), TRACE_GLOB))):
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rows.append(json.loads(line))
                    except json.JSONDecodeError:
                        continue
        except OSError:
            continue
    return rows


def spans_by_trace(rows: list[dict]) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for r in rows:
        out.setdefault(r.get("trace", "?"), []).append(r)
    return out


def critical_path(spans: list[dict]) -> dict | None:
    """One trace's per-stage breakdown. Sweeps the elementary intervals
    of the root window: each instant belongs to the latest-starting
    stage span covering it, or to ``stall`` when none does — so
    ``queue_s + admission_s + prefill_s + handoff_s + decode_s +
    stall_s == total_s`` EXACTLY (the acceptance invariant). Also
    computes the same partition clipped to the TTFT window
    (``ttft_<stage>_s``) — which stage ate the TTFT budget."""
    root = next((s for s in spans if s.get("parent") is None), None)
    if root is None:
        return None
    t0, t1 = float(root["t0_us"]), float(root["t1_us"])
    stage_spans = [s for s in spans
                   if s is not root and s.get("stage") in STAGES]
    connected = all(s.get("parent") == root["span"]
                    for s in spans if s is not root)
    cuts = {t0, t1}
    for s in stage_spans:
        cuts.add(min(max(float(s["t0_us"]), t0), t1))
        cuts.add(min(max(float(s["t1_us"]), t0), t1))
    ttft_s = root.get("ttft_s")
    ttft_edge = t0 + ttft_s * 1e6 if ttft_s is not None else None
    if ttft_edge is not None:
        cuts.add(min(max(ttft_edge, t0), t1))
    edges = sorted(cuts)
    sums = dict.fromkeys(STAGES, 0.0)
    ttft_sums = dict.fromkeys(STAGES, 0.0)
    stall = ttft_stall = 0.0
    for a, b in zip(edges, edges[1:]):
        if b <= a:
            continue
        owner = None
        for s in stage_spans:
            if float(s["t0_us"]) <= a and float(s["t1_us"]) >= b:
                if owner is None or float(s["t0_us"]) >= float(
                        owner["t0_us"]):
                    owner = s
        dur = b - a
        in_ttft = ttft_edge is not None and b <= ttft_edge + 1e-9
        if owner is not None:
            sums[owner["stage"]] += dur
            if in_ttft:
                ttft_sums[owner["stage"]] += dur
        else:
            stall += dur
            if in_ttft:
                ttft_stall += dur
    out = {"trace": root.get("trace"), "request": root.get("request"),
           "tenant": root.get("tenant", "default"),
           "finish_reason": root.get("finish_reason"),
           "ttft_s": ttft_s, "retries": root.get("retries", 0),
           "total_s": (t1 - t0) / 1e6, "stall_s": stall / 1e6,
           "spans": len(spans), "connected": connected}
    for st in STAGES:
        out[f"{st}_s"] = sums[st] / 1e6
        out[f"ttft_{st}_s"] = ttft_sums[st] / 1e6
    out["ttft_stall_s"] = ttft_stall / 1e6
    return out


def critical_paths(rows: list[dict]) -> list[dict]:
    """Per-request breakdowns for every trace with a root span."""
    out = []
    for spans in spans_by_trace(rows).values():
        cp = critical_path(spans)
        if cp is not None:
            out.append(cp)
    return out


def slo_debt(paths: list[dict],
             slo_ttft_s: float = DEFAULT_SLO_TTFT_S) -> dict[str, dict]:
    """Per-tenant SLO-debt attribution from the merged critical paths:
    total debt seconds (TTFT beyond budget), breach count, and — over
    the BREACHING requests only — which stage their TTFT window spent
    its time in. The report table and ROADMAP item 4's per-tenant
    scaling signals read the same shape."""
    out: dict[str, dict] = {}
    for p in paths:
        rec = out.setdefault(p["tenant"], {
            "requests": 0, "breaches": 0, "debt_s": 0.0,
            **{f"ttft_{st}_s": 0.0 for st in STAGES},
            "ttft_stall_s": 0.0})
        rec["requests"] += 1
        if p["ttft_s"] is None:
            continue
        debt = p["ttft_s"] - slo_ttft_s
        if debt <= 0:
            continue
        rec["breaches"] += 1
        rec["debt_s"] += debt
        for st in STAGES:
            rec[f"ttft_{st}_s"] += p.get(f"ttft_{st}_s", 0.0)
        rec["ttft_stall_s"] += p.get("ttft_stall_s", 0.0)
    return out


def chrome_trace(rows: list[dict]) -> dict:
    """Trace Event JSON with ONE LANE PER REQUEST (pid = request lane,
    tid = emitting rank), so a handed-off stream reads as one lane
    crossing replica rows — open in ui.perfetto.dev."""
    events: list[dict] = []
    lanes: dict[str, int] = {}
    for r in rows:
        tid = r.get("replica", r.get("rank", 0))
        if not isinstance(tid, int):
            tid = -1   # the router's rank is the string "router"
        lane = lanes.get(r.get("trace", "?"))
        if lane is None:
            lane = lanes[r.get("trace", "?")] = len(lanes)
            root = r.get("parent") is None
            name = (f"req {r.get('request', '?')} "
                    f"({r.get('tenant', 'default')})"
                    if root else f"trace {r.get('trace', '?')}")
            events.append({"ph": "M", "name": "process_name",
                           "pid": lane, "args": {"name": name}})
        attrs = {k: v for k, v in r.items()
                 if k not in ("trace", "span", "parent", "stage",
                              "t0_us", "t1_us")}
        events.append({
            "ph": "X", "name": r.get("stage", "?"), "pid": lane,
            "tid": tid, "cat": "request",
            "ts": round(float(r["t0_us"]), 3),
            "dur": round(max(0.0, float(r["t1_us"])
                             - float(r["t0_us"])), 3),
            "args": attrs,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# rendering — shared by the `trace` CLI subcommand and report.py


def render_trace(run_dir: str | os.PathLike, *, top: int = 10,
                 tenant: str | None = None, stage: str | None = None,
                 slo_ttft_s: float = DEFAULT_SLO_TTFT_S) -> str:
    """The terminal answer: top-N slowest requests (by ``stage`` when
    given, else by total latency) + the per-tenant SLO-debt table."""
    rows = read_trace(str(run_dir))
    paths = critical_paths(rows)
    if tenant is not None:
        paths = [p for p in paths if p["tenant"] == tenant]
    if not paths:
        return ("request traces: none found (run with tracing on — "
                "ReplicaRouter(trace=True) or PTD_TRACE=1 — and a "
                "telemetry dir)")
    key = f"{stage}_s" if stage else "total_s"
    ranked = sorted(paths, key=lambda p: -p.get(key, 0.0))
    n_conn = sum(p["connected"] for p in paths)
    lines = [f"request traces: {len(paths)} requests, "
             f"{sum(p['spans'] for p in paths)} spans, "
             f"{n_conn}/{len(paths)} connected"
             + (f"  (tenant {tenant})" if tenant else "")]
    hdr = (f"  {'request':>7}  {'tenant':>10}  {'total':>8}  "
           f"{'queue':>7}  {'admit':>7}  {'prefill':>7}  {'handoff':>7}  "
           f"{'decode':>8}  {'stall':>7}  {'ttft':>7}  {'finish':>8}")
    lines.append(f"  slowest by {stage or 'total latency'}:")
    lines.append(hdr)

    def ms(v):
        return f"{v * 1e3:.1f}" if v is not None else "-"

    for p in ranked[:top]:
        lines.append(
            f"  {p['request'] if p['request'] is not None else '-':>7}  "
            f"{p['tenant']:>10}  {ms(p['total_s']):>8}  "
            f"{ms(p['queue_s']):>7}  {ms(p['admission_s']):>7}  "
            f"{ms(p['prefill_s']):>7}  {ms(p['handoff_s']):>7}  "
            f"{ms(p['decode_s']):>8}  {ms(p['stall_s']):>7}  "
            f"{ms(p['ttft_s']):>7}  {p['finish_reason'] or '-':>8}")
    debt = slo_debt(paths, slo_ttft_s)
    lines.append(f"  per-tenant SLO debt (ttft budget "
                 f"{slo_ttft_s * 1e3:.0f} ms; breach-window ms by stage):")
    lines.append(f"  {'tenant':>10}  {'reqs':>5}  {'breaches':>8}  "
                 f"{'debt':>9}  {'queue':>7}  {'admit':>7}  "
                 f"{'prefill':>7}  {'handoff':>7}  {'decode':>7}  "
                 f"{'stall':>7}")
    for name, r in sorted(debt.items()):
        lines.append(
            f"  {name:>10}  {r['requests']:>5}  {r['breaches']:>8}  "
            f"{r['debt_s'] * 1e3:>7.1f}ms  "
            f"{r['ttft_queue_s'] * 1e3:>7.1f}  "
            f"{r['ttft_admission_s'] * 1e3:>7.1f}  "
            f"{r['ttft_prefill_s'] * 1e3:>7.1f}  "
            f"{r['ttft_handoff_s'] * 1e3:>7.1f}  "
            f"{r['ttft_decode_s'] * 1e3:>7.1f}  "
            f"{r['ttft_stall_s'] * 1e3:>7.1f}")
    return "\n".join(lines)

"""Anomaly tripwires + structured telemetry events.

The NaN watchdog (utils/guards.py) RAISES on a non-finite metric — right
for halting, useless for post-mortem: the exception dies with the rank
and nothing durable says which step, which metric, what the loss was
doing beforehand. Tripwires here are the recording half: evaluated at
log cadence (piggybacking on the device sync the Trainer already pays
for — no extra blocking), they emit `TelemetryEvent` JSONL records,
one file per rank, that survive the process. The launcher
(`pytorchdistributed_tpu.run --telemetry-dir`) aggregates them per
incarnation next to its heartbeat state, and the report CLI folds them
into the run report.

Detectors:
  * non-finite: any logged metric (loss, grad_norm, ...) NaN/Inf —
    stamped with the in-graph NaN-provenance layer index
    (``diag/first_bad_layer``, telemetry/diagnostics.py) when the
    diagnostics subsystem supplies one;
  * metric spike: per-key EMA z-score — an independent EMA
    mean/variance per watched key (the loss, ``grad_norm``, and every
    ``diag/*`` scalar by default; PTD_ANOMALY_KEYS pins the set,
    PTD_ANOMALY_Z the threshold), an event when a new value sits more
    than ``z_threshold`` deviations above the mean (one-sided: dropping
    fast is not an anomaly). The EMA warmup suppresses the first noisy
    observations. The loss key keeps its original ``loss_spike`` event
    shape; other keys emit ``metric_spike``.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import math
import os
import time

TELEMETRY_DIR_ENV = "PTD_TELEMETRY_DIR"

# The run-dir file contract, shared by writer (Trainer) and readers
# (report CLI, the run.py agent) — rename in ONE place or readers
# silently find nothing.
EVENTS_FILE = "events_rank{rank}.jsonl"
EVENTS_GLOB = "events_rank*.jsonl"
METRICS_FILE = "metrics_rank{rank}.jsonl"
METRICS_GLOB = "metrics_rank*.jsonl"

# Canonical event kinds shared by the emitters (faults/, checkpoint,
# Trainer) and the readers (report CLI, run.py's per-incarnation
# summaries) — string constants so a typo'd kind is an import error at
# the call site, not a silently-unmatched row in the post-mortem.
EVENT_FAULT = "fault_injected"          # faults/inject.py hooks
EVENT_RETRY = "io_retry"                # faults/retry.py backoff
EVENT_PREEMPTED = "preempted"           # Trainer SIGTERM graceful exit
EVENT_CKPT_QUARANTINED = "ckpt_quarantined"  # integrity verify failed
EVENT_CKPT_FALLBACK = "ckpt_fallback"   # restore walked back a step
EVENT_REPLICA_RESTORE = "replica_restore"  # worker loaded a verified ckpt
EVENT_REPLICA_RESTORE_FALLBACK = "replica_restore_fallback"  # ckpt absent/
#                                         bad: worker fell back to init_seed


class JsonlWriter:
    """Append-only JSONL sink. Lazy (re)open in append mode — safe to
    ``close()`` at every epoch teardown and keep writing next epoch —
    and line-buffered, so each row is durable even if the process dies
    mid-epoch and the file is never left open or truncated. Zero-dep on
    purpose: the one durability implementation behind both the Trainer's
    metric sinks (training/logging.py re-exports it) and EventLog."""

    def __init__(self, path: str | os.PathLike, buffering: int = 1):
        # buffering=1 (default) = line-buffered: one write syscall per
        # row, durable through a crash. High-rate sinks whose readers
        # tolerate a torn tail (request tracing) pass -1 for block
        # buffering — a row becomes a memcpy, flushed on close().
        self.path = str(path)
        self._buffering = buffering
        self._f = None

    def write(self, obj: dict) -> None:
        self.write_line(json.dumps(obj))

    def write_line(self, line: str) -> None:
        if self._f is None:
            parent = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(parent, exist_ok=True)
            self._f = open(self.path, "a", buffering=self._buffering)
        self._f.write(line + "\n")

    def flush(self) -> None:
        if self._f is not None:
            self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


@dataclasses.dataclass(frozen=True)
class TelemetryEvent:
    """One structured anomaly/lifecycle record (a JSONL row)."""

    kind: str
    step: int
    rank: int
    time: float
    data: dict

    def to_json(self) -> str:
        return json.dumps({"kind": self.kind, "step": self.step,
                           "rank": self.rank, "time": self.time,
                           **self.data})

    @classmethod
    def from_json(cls, line: str) -> "TelemetryEvent":
        d = json.loads(line)
        return cls(kind=d.pop("kind"), step=int(d.pop("step", -1)),
                   rank=int(d.pop("rank", 0)), time=float(d.pop("time", 0.0)),
                   data=d)

    def describe(self) -> str:
        extras = " ".join(f"{k}={v}" for k, v in self.data.items())
        return f"rank {self.rank} step {self.step} {self.kind} {extras}"


class EventLog(JsonlWriter):
    """Per-rank TelemetryEvent sink: a JsonlWriter that stamps
    rank/time and returns the structured event from emit()."""

    def __init__(self, path: str | os.PathLike, rank: int = 0):
        super().__init__(path)
        self.rank = rank

    @classmethod
    def from_env(cls, rank: int) -> "EventLog | None":
        d = os.environ.get(TELEMETRY_DIR_ENV)
        if not d:
            return None
        os.makedirs(d, exist_ok=True)
        return cls(os.path.join(d, EVENTS_FILE.format(rank=rank)),
                   rank=rank)

    def emit(self, kind: str, *, step: int, **data) -> TelemetryEvent:
        ev = TelemetryEvent(kind=kind, step=step, rank=self.rank,
                            time=round(time.time(), 3), data=data)
        self.write_line(ev.to_json())
        return ev


# env knobs for the spike tripwires (ISSUE 6a): PTD_ANOMALY_Z overrides
# the z threshold, PTD_ANOMALY_KEYS (comma list) pins the watched-key set
# — unset, the detector watches the loss key, grad_norm, and every
# diag/* scalar the diagnostics subsystem emits.
ANOMALY_Z_ENV = "PTD_ANOMALY_Z"
ANOMALY_KEYS_ENV = "PTD_ANOMALY_KEYS"

#: diag scalars that are INDICES/counters, not magnitudes — z-scoring the
#: provenance layer index jumping -1 → L would only duplicate the
#: non_finite event that always accompanies it
_AUTO_WATCH_EXCLUDE = ("diag/first_bad_layer",)

#: metric key whose value (>= 0) names the first non-finite layer — the
#: in-graph NaN provenance (telemetry/diagnostics.py) the non-finite
#: events carry so a blowup is pinpointed to its origin layer
PROVENANCE_KEY = "diag/first_bad_layer"


class AnomalyDetector:
    """The tripwire logic, pure host arithmetic on already-synced metric
    floats — `check` adds no device work. Returns (kind, payload) pairs;
    the caller (Trainer) turns them into EventLog records.

    Per-key EMA state (ISSUE 6a): beyond ``loss_key`` the detector keeps
    an independent EMA mean/variance for every watched key —
    ``grad_norm`` and any ``diag/*`` scalar by default, or exactly the
    ``keys``/PTD_ANOMALY_KEYS set when given. Event shapes are
    backward-compatible: the loss key still emits ``loss_spike`` with the
    original payload; other keys emit ``metric_spike`` with the same
    fields plus ``metric``. Non-finite events additionally carry
    ``first_bad_layer`` whenever the in-graph provenance scalar is
    present and a layer is implicated."""

    def __init__(self, *, loss_key: str = "loss",
                 z_threshold: float | None = None,
                 ema: float = 0.98, warmup: int = 5,
                 min_rel_std: float = 0.05,
                 keys: tuple[str, ...] | None = None):
        self.loss_key = loss_key
        if z_threshold is None:
            env = os.environ.get(ANOMALY_Z_ENV, "").strip()
            z_threshold = float(env) if env else 6.0
        self.z_threshold = z_threshold
        self.ema = ema
        self.warmup = warmup
        # std floor as a fraction of the EMA mean: a smoothly-converging
        # loss drives the EMA variance toward zero, where any drift would
        # z-score as a "spike" — only excursions that are also material
        # relative to the loss level should trip
        self.min_rel_std = min_rel_std
        if keys is None:
            env = os.environ.get(ANOMALY_KEYS_ENV, "").strip()
            keys = tuple(k.strip() for k in env.split(",")
                         if k.strip()) if env else None
        self._keys = keys  # None = auto (loss + grad_norm + diag/*)
        # per-key EMA state: key -> [mean, var, seen]
        self._state: dict[str, list] = {}

    def _watched(self, metrics: dict) -> list[str]:
        if self._keys is not None:
            return [k for k in self._keys if k in metrics]
        return [k for k in metrics
                if (k == self.loss_key or k == "grad_norm"
                    or k.startswith("diag/"))
                and k not in _AUTO_WATCH_EXCLUDE]

    def check(self, metrics: dict[str, float],
              step: int) -> list[tuple[str, dict]]:
        out: list[tuple[str, dict]] = []
        prov = metrics.get(PROVENANCE_KEY)
        prov = (int(prov) if prov is not None and math.isfinite(float(prov))
                and float(prov) >= 0 else None)
        for k, v in metrics.items():
            v = float(v)
            if not math.isfinite(v):
                payload = {"metric": k, "value": str(v)}
                if prov is not None:
                    payload["first_bad_layer"] = prov
                out.append(("non_finite_metric", payload))
        for key in self._watched(metrics):
            v = metrics.get(key)
            if v is None or not math.isfinite(float(v)):
                continue
            v = float(v)
            mean, var, seen = self._state.get(key, (0.0, 0.0, 0))
            if seen >= self.warmup:
                std = max(math.sqrt(max(var, 0.0)),
                          self.min_rel_std * abs(mean), 1e-8)
                z = (v - mean) / std
                if z > self.z_threshold:
                    payload = {"value": round(v, 6),
                               "ema_mean": round(mean, 6),
                               "ema_std": round(std, 6), "z": round(z, 2)}
                    if key == self.loss_key:
                        out.append(("loss_spike", payload))
                    else:
                        out.append(("metric_spike",
                                    {"metric": key, **payload}))
            # fold AFTER judging: the spike itself must not pre-inflate
            # the variance it is measured against
            m = self.ema if seen else 0.0
            delta = v - mean
            mean += (1 - m) * delta
            var = m * (var + (1 - m) * delta * delta)
            self._state[key] = [mean, var, seen + 1]
        return out


def read_events(run_dir: str | os.PathLike) -> list[TelemetryEvent]:
    """Every TelemetryEvent under ``run_dir`` (all ranks, sorted by
    time) — the report CLI's reader."""
    events: list[TelemetryEvent] = []
    for path in sorted(glob.glob(os.path.join(str(run_dir), EVENTS_GLOB))):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        events.append(TelemetryEvent.from_json(line))
                    except (json.JSONDecodeError, KeyError):
                        continue  # torn final line of a killed rank
    return sorted(events, key=lambda e: e.time)


def summarize_new_events(run_dir: str | os.PathLike,
                         offsets: dict[str, int]) -> str | None:
    """Agent-side per-incarnation aggregation: counts of event kinds per
    rank appended past ``offsets`` (byte offsets per file, updated in
    place — call once per incarnation teardown). None when nothing new."""
    counts: dict[tuple[int, str], int] = {}
    for path in sorted(glob.glob(os.path.join(str(run_dir), EVENTS_GLOB))):
        start = offsets.get(path, 0)
        try:
            with open(path) as f:
                f.seek(start)
                chunk = f.read()
                offsets[path] = f.tell()
        except OSError:
            continue
        for line in chunk.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                ev = TelemetryEvent.from_json(line)
            except (json.JSONDecodeError, KeyError):
                continue
            counts[(ev.rank, ev.kind)] = counts.get((ev.rank, ev.kind), 0) + 1
    if not counts:
        return None
    parts = [f"rank {r} {kind} x{n}"
             for (r, kind), n in sorted(counts.items())]
    return f"{sum(counts.values())} event(s): " + ", ".join(parts)

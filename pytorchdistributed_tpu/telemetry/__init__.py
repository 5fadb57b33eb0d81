"""Unified telemetry subsystem (SURVEY.md §5, grown into a layer):

  * spans.py       — the process's one host-span ring (`span(name,
                     **ids)`: parent, ids, the profiler's clock →
                     Chrome-trace JSON)
  * accounting.py  — StepAccounting: MFU / tokens-per-s / comm-bytes from
                     the compiled step joined with wall-clock
  * events.py      — anomaly tripwires → per-rank TelemetryEvent JSONL
  * diagnostics.py — in-graph model health (ISSUE 6): per-layer
                     activation stats, grad/update health, NaN
                     provenance — extra jitted outputs, zero overhead
                     when off (``Trainer(diagnostics=...)`` /
                     PTD_DIAGNOSTICS)
  * tracing.py     — fleet-wide request tracing (ISSUE 17): one
                     TraceContext per router submit, propagated across
                     the wire; per-rank ``trace_rank*.jsonl`` spans
                     merged into critical-path / SLO-debt tables
                     (``... telemetry trace <dir>``)
  * report.py      — the cross-rank run report CLI
                     (``python -m pytorchdistributed_tpu.telemetry report``)

The Trainer enables all of it with one knob (``telemetry_dir=...`` or the
launcher's ``--telemetry-dir`` / PTD_TELEMETRY_DIR env).
"""

from pytorchdistributed_tpu.telemetry.accounting import (  # noqa: F401
    CPU_SIM_NOMINAL_ICI_BYTES_PER_S,
    CPU_SIM_NOMINAL_PEAK_FLOPS,
    ICI_BYTES_PER_S,
    PEAK_BF16_FLOPS,
    StepAccounting,
    device_memory_highwater,
    ici_bytes_per_s_for,
    peak_flops_for,
)
from pytorchdistributed_tpu.telemetry.diagnostics import (  # noqa: F401
    DIAGNOSTICS_ENV,
    DiagnosticsConfig,
)
from pytorchdistributed_tpu.telemetry.events import (  # noqa: F401
    TELEMETRY_DIR_ENV,
    AnomalyDetector,
    EventLog,
    TelemetryEvent,
    read_events,
    summarize_new_events,
)
from pytorchdistributed_tpu.telemetry.spans import (  # noqa: F401
    SpanTracer,
    merge_chrome_traces,
    span,
)
from pytorchdistributed_tpu.telemetry.tracing import (  # noqa: F401
    TRACE_ENV,
    RequestTracer,
    TraceContext,
    critical_paths,
    read_trace,
)

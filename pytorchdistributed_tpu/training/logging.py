"""Rank-0 structured logging (SURVEY.md §5 "Metrics / logging").

The reference prints from every rank, interleaving output
(02_ddp.ipynb:252-266). Here: a stdlib logger that only emits on the main
process, plus a tiny metric formatter, plus an optional machine-readable
JSONL sink (``jsonl_path`` / Trainer ``metrics_file``) so per-step metrics
are first-class data, not just console text. The sink is a `JsonlWriter`
— lazy-open, line-buffered, idempotent ``close()`` with reopen-on-next-
write — shared with the telemetry subsystem's per-rank metric files.
"""

from __future__ import annotations

import logging
import sys
import time

import jax

# The one JSONL-durability implementation (lazy reopen, line-buffered,
# idempotent close) lives with the telemetry subsystem; re-exported here
# so training-side callers keep their import path.
from pytorchdistributed_tpu.telemetry.events import JsonlWriter  # noqa: F401

_FMT = "[%(asctime)s rank{rank}] %(message)s"


class MetricLogger:
    """Console (rank-tagged) + optional JSONL metrics. Context-manager
    and ``close()`` support close the JSONL sink (the stdlib handler
    stays — it belongs to the process); a closed logger transparently
    reopens its sink on the next ``log_step``, so per-epoch teardown
    close() composes with multi-epoch ``fit``."""

    def __init__(self, name: str = "tpu-dist", jsonl_path: str | None = None):
        self._log = logging.getLogger(name)
        if not self._log.handlers:
            h = logging.StreamHandler(sys.stdout)
            h.setFormatter(
                logging.Formatter(
                    _FMT.format(rank=jax.process_index()), "%H:%M:%S"
                )
            )
            self._log.addHandler(h)
            self._log.setLevel(logging.INFO)
            self._log.propagate = False
        self._jsonl = JsonlWriter(jsonl_path) if jsonl_path else None

    def info(self, msg: str) -> None:
        self._log.info(msg)

    def log_step(self, epoch: int, step: int, metrics: dict[str, float]) -> None:
        parts = " ".join(f"{k}={v:.4g}" for k, v in metrics.items())
        self._log.info(f"epoch {epoch} step {step} | {parts}")
        if self._jsonl is not None:
            self._jsonl.write(
                {"time": round(time.time(), 3), "epoch": epoch, "step": step,
                 **{k: float(v) for k, v in metrics.items()}})

    def flush(self) -> None:
        if self._jsonl is not None:
            self._jsonl.flush()

    def close(self) -> None:
        if self._jsonl is not None:
            self._jsonl.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

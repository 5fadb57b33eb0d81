"""The arithmetic of the measured window: end-to-end metrics from the
events the driver recorded on the host's clock.

Every metric is taken over all the work and all the time of the window,
so a stall inside it moves each of them: a rate divides everything that
completed by the window's whole length, a percentile is over every gap or
every request, never over medians of chunks or of steps.
"""

from __future__ import annotations

import dataclasses
import math


def percentile(values, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


@dataclasses.dataclass
class RequestRecord:
    """One request as the client saw it; times on the host's clock."""

    due: float                    # when the schedule said to send it
    sent: float | None = None     # when the generator did
    token_times: list = dataclasses.field(default_factory=list)
    in_window: bool = True
    prompt_len: int = 0
    max_new_tokens: int = 0
    finish_reason: str | None = None
    handle: object = None         # the program's own request object


def serve_metrics(records: list[RequestRecord], t0: float, t1: float,
                  timeout_s: float) -> dict:
    """Whatever can be computed of the serve metrics. A request due in
    the window that never got a first token counts with the time-out as
    its TTFT (it missed any limit)."""
    out: dict = {}
    length = t1 - t0
    delivered = sum(1 for r in records for t in r.token_times
                    if t0 <= t < t1)
    out["serve_tokens_per_s"] = delivered / length
    gaps = [b - a for r in records
            for a, b in zip(r.token_times, r.token_times[1:])
            if t0 <= b < t1]
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    ttft = [((r.token_times[0] - r.due) if r.token_times else timeout_s)
            for r in records if r.in_window]
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
        out["ttft_p95_ms"] = percentile(ttft, 95) * 1e3
    return out


def lateness_p95_ms(records: list[RequestRecord]) -> float | None:
    late = [r.sent - r.due for r in records if r.sent is not None]
    return percentile(late, 95) * 1e3 if late else None


def train_metrics(steps_done: int, tokens_per_step: int, t0: float,
                  t_fence: float) -> dict:
    """`t_fence` is when the last step's loss was on the host: every step
    counted had completed by then, and the rate is over all that time."""
    return {"train_tokens_per_s":
            steps_done * tokens_per_step / (t_fence - t0)}

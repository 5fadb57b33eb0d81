"""Derived step metrics: turn wall-clock into MFU / tokens-per-s /
comm-bytes — the join of compile-time facts with runtime timing.

PR 1's HLO censuses (`utils/hlo.py`) already extract per-device flops and
the collective mix from a compiled train step, and the Trainer times
steps — but nobody joined the two, so the repo had no MFU or per-step
communication-volume number outside a hand-run profiler session.
`StepAccounting` is that join, built ONCE per (config, mesh, batch
shape) from the AOT-compiled step (`Trainer.lower_step(...).compile()`):

  * ``model_flops_per_step`` — XLA cost analysis, per device,
    post-partitioning (the same number the compiled-invariant tripwires
    pin, so an MFU-math regression trips in CI);
  * ``comm_bytes_per_step`` — `utils.hlo.collective_bytes` over the
    optimized HLO (collectives exist only post-SPMD-partitioning);
  * ``peak_flops_per_device`` — per-TPU-generation bf16 peak, with a
    NOMINAL CPU-sim fallback so the full metrics path runs (and is
    testable) without a chip; ``peak_source`` labels which was used so a
    sim MFU can never be mistaken for a hardware one.

Everything downstream is arithmetic on a measured sec/step: `mfu()`,
`tokens_per_s()`. The object is JSON-(de)serializable so rank 0 stamps
it into the telemetry run dir and the report CLI re-derives the numbers
offline.
"""

from __future__ import annotations

import dataclasses
import json
import os

from pytorchdistributed_tpu.utils.hlo import collective_bytes

# Peak bf16 matmul throughput per chip, by jax device_kind — the MFU
# denominator of the telemetry report (the benchmark keeps its own table,
# benchmark/peaks.py, where an unknown device is an error).
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

# The CPU-sim stand-in peak: a NOMINAL 1 TFLOP/s so MFU is computable
# (and deterministic in tests) on the 8-device simulator. The absolute
# value is meaningless by construction — peak_source carries the label
# so no report can pass a sim MFU off as utilization of real hardware.
CPU_SIM_NOMINAL_PEAK_FLOPS = 1e12

# Nominal aggregate ICI bandwidth per chip (bytes/s, all links), by jax
# device_kind — the comm_stall_frac denominator. These are public
# per-chip interconnect aggregates (v4 ≈ 2.4 Tb/s, v5e ≈ 1.6 Tb/s,
# v5p ≈ 4.8 Tb/s, v6e ≈ 3.6 Tb/s), NOT an achievable-bandwidth model:
# comm_stall_frac is an order-of-magnitude stall estimator and says so
# via ici_source, the same labeling discipline as the MFU peak table.
ICI_BYTES_PER_S = {
    "TPU v4": 3.0e11,
    "TPU v5 lite": 2.0e11,
    "TPU v5e": 2.0e11,
    "TPU v5": 6.0e11,
    "TPU v5p": 6.0e11,
    "TPU v6 lite": 4.5e11,
    "TPU v6e": 4.5e11,
}

# CPU-sim stand-in ICI (nominal 10 GB/s): meaningless absolutely, but it
# makes comm_stall_frac computable and DETERMINISTIC from the compiled
# artifact alone — which is what lets the structural compiled-invariant
# tier pin it (tests/test_compiled_invariants.py).
CPU_SIM_NOMINAL_ICI_BYTES_PER_S = 1e10


def ici_bytes_per_s_for(device_kind: str,
                        platform: str | None = None,
                        ) -> tuple[float | None, str]:
    """(per-chip nominal ICI bytes/s, source label) — comm_stall_frac's
    denominator, labeled like peak_flops_for so a sim estimate can never
    read as a hardware one."""
    bw = ICI_BYTES_PER_S.get(device_kind)
    if bw is not None:
        return bw, device_kind
    if platform == "cpu" or device_kind == "cpu":
        return CPU_SIM_NOMINAL_ICI_BYTES_PER_S, "cpu-sim-nominal"
    return None, f"unknown:{device_kind}"


def peak_flops_for(device_kind: str,
                   platform: str | None = None) -> tuple[float | None, str]:
    """(per-device peak bf16 flops, source label). Unknown TPU kinds get
    (None, "unknown:<kind>") — better to omit MFU than to invent a
    denominator for a chip generation this table predates."""
    peak = PEAK_BF16_FLOPS.get(device_kind)
    if peak is not None:
        return peak, device_kind
    if platform == "cpu" or device_kind == "cpu":
        return CPU_SIM_NOMINAL_PEAK_FLOPS, "cpu-sim-nominal"
    return None, f"unknown:{device_kind}"


def device_memory_highwater() -> int | None:
    """Max per-device HBM high-water (bytes) over the local devices, via
    ``device.memory_stats()`` — None where the backend has none (the CPU
    sim reports no stats). A host-side read of allocator counters: no
    device sync, cheap enough for log cadence."""
    import jax

    peak = None
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            return None
        if not stats:
            continue
        v = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
        if v is not None:
            peak = max(peak or 0, int(v))
    return peak


@dataclasses.dataclass(frozen=True)
class StepAccounting:
    """Compile-time facts of one train step, ready to join with wall-clock.

    ``model_flops_per_step`` and ``comm_bytes_per_step`` are PER DEVICE
    (post-partitioning, matching the compiled-invariant convention);
    ``tokens_per_step`` / ``samples_per_step`` are GLOBAL (the batch the
    step consumes), so ``tokens_per_s`` reports global throughput."""

    model_flops_per_step: float
    comm_bytes_per_step: int
    comm_bytes_by_op: dict[str, int]
    tokens_per_step: int
    samples_per_step: int
    peak_flops_per_device: float | None
    peak_source: str
    n_devices: int
    # ICI denominator for comm_stall_frac. Defaults keep accounting.json
    # files written before ISSUE 5 loading (from_json passes only the
    # recorded keys).
    ici_bytes_per_s: float | None = None
    ici_source: str = ""

    @classmethod
    def from_compiled(cls, compiled, *, batch, n_devices: int | None = None,
                      ) -> "StepAccounting":
        """Build from a `jax.stages.Compiled` train step (the output of
        `Trainer.lower_step(batch).compile()`) plus the batch that shaped
        it. ``batch`` may be arrays or ShapeDtypeStructs — only shapes
        are read."""
        import jax

        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):  # older jax wraps in a list
            cost = cost[0] if cost else {}
        by_op = collective_bytes(compiled.as_text())
        tokens, samples = _batch_tokens_samples(batch)
        dev = jax.devices()[0]
        peak, source = peak_flops_for(dev.device_kind, dev.platform)
        ici, ici_source = ici_bytes_per_s_for(dev.device_kind, dev.platform)
        return cls(
            model_flops_per_step=float(cost.get("flops", 0.0)),
            comm_bytes_per_step=int(sum(by_op.values())),
            comm_bytes_by_op=by_op,
            tokens_per_step=tokens,
            samples_per_step=samples,
            peak_flops_per_device=peak,
            peak_source=source,
            n_devices=(n_devices if n_devices is not None
                       else jax.device_count()),
            ici_bytes_per_s=ici,
            ici_source=ici_source,
        )

    # -- derived metrics ---------------------------------------------------

    def mfu(self, sec_per_step: float) -> float | None:
        """Model-flops utilization of ONE device: cost-analysis flops are
        already per-device, so no world-size factor enters."""
        if (self.peak_flops_per_device is None or sec_per_step <= 0
                or self.model_flops_per_step <= 0):
            return None
        return round(self.model_flops_per_step / sec_per_step
                     / self.peak_flops_per_device, 4)

    def tokens_per_s(self, sec_per_step: float) -> float | None:
        if sec_per_step <= 0:
            return None
        return round(self.tokens_per_step / sec_per_step, 1)

    def comm_bytes_per_s(self, sec_per_step: float) -> float | None:
        if sec_per_step <= 0:
            return None
        return round(self.comm_bytes_per_step / sec_per_step, 1)

    @property
    def a2a_bytes_per_step(self) -> int:
        """Per-device all-to-all bytes (plain + ragged) — the
        expert-parallel MoE dispatch/combine volume (ISSUE 14), already
        inside ``comm_bytes_per_step`` but surfaced on its own because
        it's the term the capacity factor, int8 payloads and chunked
        overlap all act on."""
        return int(sum(self.comm_bytes_by_op.get(k, 0)
                       for k in ("all-to-all", "ragged-all-to-all")))

    def comm_stall_frac(self, sec_per_step: float | None = None,
                        ) -> float | None:
        """Estimated fraction of the step stalled on collectives — the
        zero-overlap UPPER BOUND (ISSUE 5c): the time the step's
        per-device collective bytes would take at the chip's nominal ICI
        bandwidth, as a fraction of the step. With a measured
        ``sec_per_step`` (the Trainer's path) the denominator is the
        real step; without one (the structural compiled-invariant pins)
        it is the estimated serial compute + comm time at nominal peaks,
        so the number is a deterministic function of the compiled
        artifact. A step whose measured comm_stall_frac sits well below
        the structural estimate is one whose collectives the scheduler
        actually hid — read it next to utils.hlo.overlap_census, which
        says how (async pairs, ops inside the windows). ``ici_source``
        labels the denominator; cpu-sim-nominal estimates are for
        regression-pinning, not performance claims."""
        if self.ici_bytes_per_s is None:
            return None
        comm_s = self.comm_bytes_per_step / self.ici_bytes_per_s
        if sec_per_step is not None:
            if sec_per_step <= 0:
                return None
            return round(min(1.0, comm_s / sec_per_step), 4)
        if self.peak_flops_per_device is None or self.model_flops_per_step <= 0:
            return None
        compute_s = self.model_flops_per_step / self.peak_flops_per_device
        if comm_s + compute_s <= 0:
            return None
        return round(comm_s / (comm_s + compute_s), 4)

    # -- (de)serialization -------------------------------------------------

    def to_json(self) -> str:
        return json.dumps({"format": 1, **dataclasses.asdict(self)})

    @classmethod
    def from_json(cls, text: str) -> "StepAccounting":
        d = json.loads(text)
        d.pop("format", None)
        return cls(**d)

    def save(self, path: str | os.PathLike) -> None:
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            f.write(self.to_json())
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str | os.PathLike) -> "StepAccounting":
        with open(path) as f:
            return cls.from_json(f.read())


def _batch_tokens_samples(batch) -> tuple[int, int]:
    """(global tokens, global samples) from batch leaf shapes. LM batches
    carry a 2-D "tokens" leaf → tokens = B·S; everything else counts the
    leading dim (one "token" per sample, matching how samples/s and
    tokens/s coincide for vision workloads)."""
    shapes = {k: tuple(getattr(v, "shape", ()))
              for k, v in dict(batch).items()}
    samples = next((s[0] for s in shapes.values() if s), 0)
    tok = shapes.get("tokens")
    if tok is not None and len(tok) >= 2:
        n = 1
        for d in tok:
            n *= int(d)
        return n, int(samples)
    return int(samples), int(samples)

"""Published peaks of the chips this benchmark may run on, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default: a utilisation against a guessed peak is worse than none."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s, dense bf16 matmul
    hbm_bytes_per_s: float   # bytes/s
    hbm_bytes: float         # bytes of device memory
    source: str


PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9,
                         "Google Cloud documentation, TPU v5e"),
    "TPU v5e": Peaks(197e12, 819e9, 16e9,
                     "Google Cloud documentation, TPU v5e"),
}


def lookup(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it, "
            f"with its source, to benchmark/peaks.py") from None

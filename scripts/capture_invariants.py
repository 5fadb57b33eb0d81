"""Re-capture the committed compiled-artifact invariants.

    python scripts/capture_invariants.py             # all configs
    python scripts/capture_invariants.py gpt2s_2l    # a subset

Prints a ready-to-paste COMMITTED dict for
tests/test_compiled_invariants.py. The field list is derived from
`utils.hlo.compiled_invariants` itself, so every census it grows —
including the per-config model-flops ("flops") and per-device
collective-bytes ("comm_bytes") pair that feeds telemetry
StepAccounting's MFU/comm math — is stamped into the paste block
automatically. Run on the same frozen image the suite runs on (the
numbers are XLA-version-dependent by design — the image pins the
version). Record any deliberate change in BASELINE.md.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from pytorchdistributed_tpu.utils.hlo import compiled_invariants  # noqa: E402
from tests.test_compiled_invariants import (  # noqa: E402
    BUILDERS,
    SERVING_NAMES,
    decode_lowered,
    serving_lowered,
)


def main() -> None:
    names = sys.argv[1:] or list(BUILDERS) + ["decode"] + list(SERVING_NAMES)
    print("COMMITTED = {")
    for name in names:
        if name == "decode":  # the one-shot decode pin (DECODE_COMMITTED)
            inv = compiled_invariants(decode_lowered().compile())
        elif name in SERVING_NAMES:  # the serving pins (SERVE_COMMITTED)
            inv = compiled_invariants(serving_lowered(name).compile())
        else:
            trainer, batch = BUILDERS[name]()
            inv = compiled_invariants(trainer.lower_step(batch).compile())
        print(f'    "{name}": {{')
        # derive the field list from the dict so a new invariant in
        # utils/hlo.py can never be silently dropped from the paste block;
        # dict-valued censuses (collectives, int8_ops) print last
        scalar = [k for k in inv if not isinstance(inv[k], dict)]
        for key in scalar:
            print(f'        "{key}": {inv[key]},')
        for key in (k for k in inv if isinstance(inv[k], dict)):
            print(f'        "{key}": {inv[key]},')
        print("    },")
    print("}")


if __name__ == "__main__":
    main()

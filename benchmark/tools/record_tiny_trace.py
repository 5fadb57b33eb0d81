"""Records the small trace the tests reduce: four steps of the two-layer
toy of `tests/data/tiny_trace.json` (width 256, flash kernel) on one
chip, under the benchmark's spans.

    python3 benchmark/tools/record_tiny_trace.py   # on the chip

Writes `chiprun_out/tiny_train.xplane.pb`; copy it to
`benchmark/tests/data/`.
"""

import glob
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    import jax
    import numpy as np
    import optax

    from benchmark import manifest
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    cfg = json.loads((manifest.BENCH_DIR / "tests" / "data"
                      / "tiny_trace.json").read_text())
    family = manifest.load_family(manifest.BENCH_DIR, cfg["model_type"])
    model = family.program_model(cfg, {"attention": "pallas",
                                       "scan_layers": False})
    tr = Trainer(model, optax.adamw(3e-4), token_cross_entropy_loss,
                 mesh=create_mesh(devices=jax.devices()[:1]),
                 strategy="dp", log_every=10 ** 9)
    rng = np.random.default_rng(0)

    def batch():
        t = rng.integers(0, cfg["vocab_size"], (8, 257)).astype(np.int32)
        return {"tokens": t[:, :-1], "targets": t[:, 1:]}

    tr.init(batch())
    for _ in range(3):
        float(tr.train_step(batch())["loss"])
    out = ROOT / "chiprun_out" / "tiny_trace"
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("input.next_batch"):
                b = batch()
            with jax.profiler.TraceAnnotation("train_step"):
                loss = tr.train_step(b)["loss"]
            float(loss)
    jax.profiler.stop_trace()
    path = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, ROOT / "chiprun_out" / "tiny_train.xplane.pb")
    print("wrote", pathlib.Path(path).stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())

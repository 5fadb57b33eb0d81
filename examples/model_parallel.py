"""Lesson-3 parity: model / pipeline parallelism + the split-size sweep
(reference 03_model_parallel.ipynb).

The reference splits ResNet-50 across two GPUs by hand, adds micro-batch
pipelining, then sweeps the split size and saves `split_size_tradeoff.png`
(cells 5, 12, 13). The TPU-native equivalents:

  * "model parallel"  -> tensor parallelism (--tensor N): layers sharded
    *within* by the TP rule tables, no manual .to(device) hops;
  * "pipeline parallel" -> GPipe over the pipe mesh axis (--pipe N);
  * the split-size sweep -> micro-batch count sweep, same tradeoff curve
    (bubble fraction vs per-micro-batch overhead).

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/model_parallel.py --sweep

writes split_size_tradeoff.png next to this script (matplotlib optional).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _trainer(num_microbatches: int, *, pipe: int, tensor: int):
    import jax.numpy as jnp
    import optax

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    model = GPT2(gpt2_config(
        "test", num_layers=4, vocab_size=512, dtype=jnp.float32,
        pipeline_stages=pipe, pipeline_microbatches=num_microbatches))
    mesh = create_mesh(pipe=pipe, tensor=tensor)
    return Trainer(model, optax.adamw(1e-3), token_cross_entropy_loss,
                   mesh=mesh, strategy="tp" if tensor > 1 else "dp",
                   log_every=10**9)


def _time_step(trainer, batch, repeats: int = 5) -> float:
    trainer.train_step(batch)  # compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        m = trainer.train_step(batch)
    float(m["loss"])
    return (time.perf_counter() - t0) / repeats


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--pipe", type=int, default=2)
    parser.add_argument("--tensor", type=int, default=2)
    parser.add_argument("--sweep", action="store_true",
                        help="micro-batch sweep -> split_size_tradeoff.png")
    args = parser.parse_args()

    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    rng = np.random.default_rng(0)
    batch = {
        "tokens": rng.integers(0, 512, (32, 128)).astype(np.int32),
        "targets": rng.integers(0, 512, (32, 128)).astype(np.int32),
    }

    if not args.sweep:
        tr = _trainer(4, pipe=args.pipe, tensor=args.tensor)
        for step in range(5):
            m = tr.train_step(batch)
            print(f"step {step}: loss={float(m['loss']):.4f}")
        print(f"mean step time: {_time_step(tr, batch) * 1000:.1f} ms "
              f"(pipe={args.pipe}, tensor={args.tensor})")
        return

    # The reference sweeps split_size over [1,3,5,8,10,12,20,40,60]
    # (03_model_parallel.ipynb:589); micro-batch counts must divide the
    # batch, so the sweep grid differs but the tradeoff is the same.
    sizes = [1, 2, 4, 8, 16, 32]
    means, stds = [], []
    for m in sizes:
        tr = _trainer(m, pipe=args.pipe, tensor=1)
        times = [_time_step(tr, batch, repeats=1) for _ in range(5)]
        means.append(float(np.mean(times)))
        stds.append(float(np.std(times)))
        print(f"microbatches={m}: {means[-1]*1000:.1f} ± "
              f"{stds[-1]*1000:.1f} ms")

    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt

        fig, ax = plt.subplots(figsize=(7, 4))
        ax.errorbar(sizes, [t * 1000 for t in means],
                    yerr=[t * 1000 for t in stds], marker="o")
        ax.set_xscale("log", base=2)
        ax.set_xlabel("pipeline micro-batches (the reference's split_size)")
        ax.set_ylabel("step time (ms)")
        ax.set_title("GPipe micro-batch tradeoff "
                     "(reference: split_size_tradeoff.png)")
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "split_size_tradeoff.png")
        fig.savefig(out, dpi=120, bbox_inches="tight")
        print(f"wrote {out}")
    except ImportError:
        print("matplotlib unavailable; sweep numbers printed above")


if __name__ == "__main__":
    main()

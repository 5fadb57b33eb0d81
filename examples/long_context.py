"""Long-context training via sequence/context parallelism.

The reference has no long-context story at all (SURVEY.md §5: its only
"ring" is ring-allreduce of gradients, 02_ddp.ipynb:33-47); this example is
the framework-native one. The sequence dim is sharded over the "seq" mesh
axis, so each device holds S/n tokens of every batch row and attention runs
as either:

  * ring   — K/V shards rotate around the ICI ring (`lax.ppermute`), each
    hop folded into the flash recurrence; O(S_local · block) memory in
    forward AND backward (custom_vjp reverse ring, ops/ring_attention.py),
    the choice when S per device is the binding constraint;
  * ulysses — two all-to-alls re-shard heads↔sequence so each device runs
    full-sequence flash attention for its head subset; cheaper in
    communication when heads ≥ shards (ops/ulysses.py).

Run on the CPU sim (no TPU needed):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context.py --attention ring --seq_shards 4

The loss printed must match `--attention dense --seq_shards 1` to fp32
tolerance — context parallelism is a layout choice, not an approximation
(tests/test_attention.py pins this).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--attention", default="ring",
                        choices=["ring", "ulysses", "dense"])
    parser.add_argument("--seq_shards", type=int, default=4)
    parser.add_argument("--seq_len", type=int, default=512)
    parser.add_argument("--batch_size", type=int, default=8,
                        help="must be divisible by the data-axis size "
                             "(devices / seq_shards)")
    parser.add_argument("--steps", type=int, default=10)
    args = parser.parse_args()

    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    import jax.numpy as jnp
    import optax

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    # data axis takes whatever devices the seq axis leaves over
    mesh = create_mesh(data=-1, seq=args.seq_shards)
    cfg = gpt2_config("test", num_layers=4, max_seq_len=args.seq_len,
                      attention=args.attention, dtype=jnp.float32)
    trainer = Trainer(GPT2(cfg), optax.adamw(1e-3),
                      token_cross_entropy_loss, mesh=mesh, strategy="dp",
                      log_every=5)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": rng.integers(
            0, cfg.vocab_size,
            (args.batch_size, args.seq_len)).astype(np.int32),
        "targets": rng.integers(
            0, cfg.vocab_size,
            (args.batch_size, args.seq_len)).astype(np.int32),
    }
    for step in range(args.steps):
        metrics = trainer.train_step(batch)
        if (step + 1) % 5 == 0:
            print(f"step {step + 1} | loss {float(metrics['loss']):.4f} | "
                  f"{args.attention} x{args.seq_shards} seq shards")


if __name__ == "__main__":
    main()

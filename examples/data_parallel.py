"""Lesson-1 parity: single-process data parallelism
(reference 01_multi_gpus_data_parallelism.ipynb).

The reference wraps a 4-layer MLP in `nn.DataParallel`, which scatters each
batch across GPUs from ONE Python process — then spends a markdown cell
explaining why that design is slow (GIL, master-GPU bottleneck; cell 0).

On TPU the single-process form is the *good* path, not the anti-pattern:
one process drives all local chips, the batch is sharded by layout (not
scattered by threads), and outputs never gather to a master chip unless the
program asks. This example runs the same 4-layer MLP forward on every local
device and prints the per-device batch split the reference prints
("In Model: input size ...", cell 6). (Batch 32, not the notebook's 30:
SPMD layouts split evenly — uneven DataParallel scatter was part of the
critiqued design.)

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/data_parallel.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from pytorchdistributed_tpu.data.loader import shard_batch
from pytorchdistributed_tpu.models import MLP
from pytorchdistributed_tpu.runtime.mesh import batch_leaf_sharding, create_mesh


def main():
    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    mesh = create_mesh()  # all devices on the "data" axis
    model = MLP(features=(10, 20, 10, 5))  # the notebook's 4-layer demo net
    rng = np.random.default_rng(0)

    params = model.init(jax.random.key(0), np.zeros((1, 10), np.float32))
    apply = jax.jit(model.apply)

    n_dev = len(jax.devices())
    print(f"running on {n_dev} device(s): batch 32 splits into "
          f"{32 // n_dev} rows/device")
    for step in range(3):
        batch = {"x": rng.random((32, 10), dtype=np.float32)}
        batch = shard_batch(batch, lambda v: batch_leaf_sharding(mesh, v.ndim))
        out = apply(params, batch["x"])
        # the reference prints input/output sizes from inside the model
        # (cell 6); here the sharding itself is the evidence
        shards = batch["x"].sharding.shard_shape(batch["x"].shape)
        print(f"step {step}: In Model: per-device input {shards}, "
              f"Outside: output size {out.shape}")


if __name__ == "__main__":
    main()

"""DDP training example — the framework's `ddp_gpus.py` equivalent.

The reference launches one process per GPU and wraps the model in DDP
(reference ddp_gpus.py). On TPU the same job is ONE process per host with the
batch sharded over a device mesh; gradient all-reduce happens inside the
jitted step. Run on CPU with a simulated 8-chip mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/ddp_train.py --max_epochs 3 --batch_size 32

or on TPU hardware with no flags at all.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import optax

import pytorchdistributed_tpu as ptd
from pytorchdistributed_tpu.data import DataLoader, SyntheticRegressionDataset
from pytorchdistributed_tpu.models import LinearRegression
from pytorchdistributed_tpu.training import Trainer, mse_loss


def main():
    # Same CLI as the reference (ddp_gpus.py:88-92).
    parser = argparse.ArgumentParser(description="distributed training job")
    parser.add_argument("--max_epochs", type=int, default=5)
    parser.add_argument("--batch_size", type=int, default=32)
    parser.add_argument("--strategy", choices=["dp", "fsdp"], default="dp")
    args = parser.parse_args()

    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    ptd.init_process_group()
    try:
        dataset = SyntheticRegressionDataset(size=2048, in_dim=20, out_dim=1)
        loader = DataLoader(dataset, batch_size=args.batch_size)
        trainer = Trainer(
            LinearRegression(),
            optax.sgd(1e-3),
            mse_loss,
            strategy=args.strategy,
        )
        trainer.fit(loader, max_epochs=args.max_epochs)
    finally:
        ptd.destroy_process_group()


if __name__ == "__main__":
    main()

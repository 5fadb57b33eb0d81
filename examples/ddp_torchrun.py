"""Lesson-2 parity, torchrun variant (reference ddp_gpus_torchrun.py).

Identical training job to examples/ddp_train.py, but rank/world_size come
from the launcher's env contract instead of explicit arguments — the delta
between the reference's two scripts IS the lesson (SURVEY.md §3.2). Launch
with the framework's torchrun equivalent:

    python -m pytorchdistributed_tpu.run --nproc-per-node 2 \
        --devices-per-proc 1 examples/ddp_torchrun.py --max_epochs 3

Each process builds its dataset locally (no cross-process pickling — the
other deliberate delta from the spawn variant, SURVEY.md §3.2).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser(description="torchrun-style DDP job")
    parser.add_argument("--max_epochs", type=int, default=3)
    parser.add_argument("--batch_size", type=int, default=32)
    args = parser.parse_args()

    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    import optax

    import pytorchdistributed_tpu as ptd
    from pytorchdistributed_tpu.data import (
        DataLoader,
        SyntheticRegressionDataset,
    )
    from pytorchdistributed_tpu.models import LinearRegression
    from pytorchdistributed_tpu.training import Trainer, mse_loss

    ptd.init_process_group()  # rank/world from env — no explicit args
    try:
        dataset = SyntheticRegressionDataset(size=2048, in_dim=20, out_dim=1)
        loader = DataLoader(dataset, batch_size=args.batch_size)
        trainer = Trainer(LinearRegression(), optax.sgd(1e-3), mse_loss)
        trainer.fit(loader, max_epochs=args.max_epochs)
        print(f"[rank {ptd.get_rank()}] done", flush=True)
    finally:
        ptd.destroy_process_group()


if __name__ == "__main__":
    main()

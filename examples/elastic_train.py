"""Elastic training end to end: checkpoint as you go, die, resume.

The workflow the launcher's restart/resize machinery exists for — the
reference uses torchrun's elastic agent but never configures it beyond
--nproc_per_node (reference ddp_gpus_torchrun.py:102); here the full
loop is live:

    python -m pytorchdistributed_tpu.run --nproc-per-node 2 \
        --devices-per-proc 1 --max-restarts 2 --heartbeat-timeout 60 \
        examples/elastic_train.py --max_epochs 3 \
        --checkpoint_dir /tmp/elastic_ckpt --die_at_step 28

Rank 0 kills itself at step 28 of its first life (--die_at_step, the
fault injection — well past the step-8/16 periodic checkpoints, so a
save has durably FINALIZED: orbax saves are async, and a save initiated
moments before the crash legitimately doesn't survive it; resume then
falls back to the previous finalized step); the agent detects the
failure, relaunches the group,
and the second incarnation's ``fit(resume=True)`` restores the latest
sharded checkpoint and fast-forwards past the already-trained batches —
the run finishes with the same loss an uninterrupted job produces
(asserted exactly in tests/test_launch.py::
test_elastic_restart_resumes_real_training). The demo is one-shot per
checkpoint_dir: the died-once marker and the finished checkpoint both
live there, so a second identical invocation injects no fault and
resumes a completed run — `rm -rf` the directory to replay it (the
script prints a reminder). Capacity-reduction resize
(--elastic-min-nproc) needs a PERSISTENTLY failing rank and a
world-size-independent data shard, which this one-shot script doesn't
stage — see tests/test_launch.py::test_elastic_resize_* for that
workflow.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser(description="elastic training job")
    parser.add_argument("--max_epochs", type=int, default=3)
    parser.add_argument("--batch_size", type=int, default=32)
    parser.add_argument("--checkpoint_dir", type=str,
                        default="/tmp/ptd_elastic_ckpt")
    parser.add_argument("--checkpoint_every_steps", type=int, default=8)
    parser.add_argument("--die_at_step", type=int, default=0,
                        help="rank 0 exits at this step on its FIRST life "
                             "(0 = no fault injection)")
    args = parser.parse_args()

    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    import optax

    import pytorchdistributed_tpu as ptd
    from pytorchdistributed_tpu.data import (
        DataLoader,
        SyntheticRegressionDataset,
    )
    from pytorchdistributed_tpu.models import MLP
    from pytorchdistributed_tpu.training import Trainer, mse_loss

    ptd.init_process_group()
    try:
        dataset = SyntheticRegressionDataset(size=2048, in_dim=20, out_dim=1)
        loader = DataLoader(dataset, batch_size=args.batch_size)

        died_marker = os.path.join(args.checkpoint_dir, "died_once")
        if (args.die_at_step and ptd.get_rank() == 0
                and os.path.exists(died_marker)):
            print(f"[rank 0] marker {died_marker} present: fault injection "
                  f"off (rm -rf {args.checkpoint_dir} to replay the demo)",
                  flush=True)
        if args.die_at_step and ptd.get_rank() == 0 \
                and not os.path.exists(died_marker):
            # fault injection: wrap the loader so rank 0's first life ends
            # mid-epoch, after some checkpoints exist (the marker file is
            # the "only once" memory that survives the relaunch)
            real_iter = type(loader).__iter__

            class DieMidEpoch:
                def __init__(self, inner):
                    self._inner = inner
                    self.sampler = inner.sampler
                    self.batch_size = inner.batch_size
                    self._step = 0

                def set_epoch(self, epoch):
                    self._inner.set_epoch(epoch)

                def __len__(self):
                    return len(self._inner)

                def __iter__(self):
                    for batch in real_iter(self._inner):
                        self._step += 1
                        if self._step == args.die_at_step:
                            os.makedirs(args.checkpoint_dir, exist_ok=True)
                            open(died_marker, "w").close()
                            print(f"[rank 0] injected failure at step "
                                  f"{self._step}", flush=True)
                            os._exit(17)
                        yield batch

            loader = DieMidEpoch(loader)

        trainer = Trainer(MLP(features=(64, 1)), optax.sgd(1e-3), mse_loss,
                          checkpoint_dir=args.checkpoint_dir,
                          checkpoint_every_steps=args.checkpoint_every_steps)
        metrics = trainer.fit(loader, max_epochs=args.max_epochs,
                              resume=True)
        print(f"[rank {ptd.get_rank()}] done: {metrics}", flush=True)
    finally:
        ptd.destroy_process_group()


if __name__ == "__main__":
    main()

"""Generic config-driven training entrypoint.

The five BASELINE.json benchmark configs are presets:

    python examples/train.py --preset resnet18_cifar_smoke
    python examples/train.py --preset gpt2_medium_fsdp --backend cpu-sim8 \
        --model_size test --batch_size 16

Any config field is a flag (--strategy fsdp --tensor 2 ...); --backend
selects {auto, tpu, cpu-sim<N>} per SURVEY.md §5's config-system plan.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pytorchdistributed_tpu.config import (  # noqa: E402
    make_trainer,
    parse_cli,
    select_backend,
)


def main():
    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    cfg = parse_cli()
    select_backend(cfg.backend)

    import pytorchdistributed_tpu as ptd

    ptd.init_process_group()
    try:
        trainer, loader = make_trainer(cfg)
        trainer.fit(loader, cfg.max_epochs, resume=cfg.resume)
    finally:
        ptd.destroy_process_group()


if __name__ == "__main__":
    main()

"""tpu-distributed: a TPU-native distributed training framework.

Built from scratch in JAX/XLA (pjit, shard_map, Pallas) to provide the full
capability surface exercised by the reference tutorial repo
JoeyOL/PytorchDistributed (see SURVEY.md): process-group initialization and
per-chip launching, data-parallel training with deterministic sharded sampling
and gradient all-reduce over ICI, tensor/model sharding, micro-batched pipeline
parallelism (GPipe and 1F1B schedules), FSDP-style parameter+optimizer sharding
with bf16 and activation checkpointing, sequence/context parallelism (ring
attention, Ulysses) for long context, Switch-MoE expert parallelism over the
expert axis, memory-budgeted auto placement (the device_map="auto" analog),
a model zoo (GPT-2, Llama with RoPE/SwiGLU/GQA, BERT, ViT, ResNet) on one
shared Transformer core, KV-cache autoregressive generation
(inference.generate), and a continuous-batching serving engine over a
slot-based KV cache (serving.ServingEngine).

Design stance (SURVEY.md §7): the reference's wrapper classes
(DataParallel/DDP, reference ddp_gpus.py:35) become *sharding-spec choices over
a single jitted train step* on a `jax.sharding.Mesh`; collectives are XLA HLO
ops over ICI/DCN rather than a userspace NCCL; pipeline schedules remain real
framework code.
"""

__version__ = "0.1.0"

from pytorchdistributed_tpu.runtime.mesh import (  # noqa: F401
    Axis,
    MeshConfig,
    create_mesh,
    local_mesh,
)
from pytorchdistributed_tpu.runtime.dist import (  # noqa: F401
    init_process_group,
    destroy_process_group,
    get_rank,
    get_world_size,
    is_initialized,
)
from pytorchdistributed_tpu.inference import (  # noqa: F401
    generate,
    generate_bucketed,
    generate_speculative,
    truncated_draft,
)

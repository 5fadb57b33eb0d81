"""Batched, device-fed data loading.

Replaces the reference's `DataLoader(..., pin_memory=True)` + per-batch
`.to(gpu_id)` copies (reference ddp_gpus.py:71-76, 49-50) with the TPU
pattern: the host assembles its process-local batch with one vectorized
gather, and `shard_batch` turns it into a *global* jax.Array laid out by a
`NamedSharding` — `jax.device_put` single-process, or
`jax.make_array_from_process_local_data` on a multi-host pod. A small
double-buffered prefetcher overlaps host gather + H2D DMA with device compute
(the role `pin_memory=True` played on CUDA).
"""

from __future__ import annotations

import collections
from typing import Iterator

import jax
import numpy as np

from pytorchdistributed_tpu.data.sampler import ShardedSampler
from pytorchdistributed_tpu.faults import inject as _inject
from pytorchdistributed_tpu.telemetry.spans import span


class DataLoader:
    """Iterates per-process batches of a map-style array dataset.

    ``batch_size`` is the per-process batch (matching torch's per-rank
    meaning); the global batch is ``batch_size * num_replicas``. Iteration
    order is deterministic in (seed, epoch) across processes.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        *,
        num_replicas: int | None = None,
        rank: int | None = None,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = True,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = ShardedSampler(
            len(dataset),
            num_replicas if num_replicas is not None else jax.process_count(),
            rank if rank is not None else jax.process_index(),
            shuffle=shuffle,
            seed=seed,
            drop_last=drop_last,
        )
        self.drop_last = drop_last

    def set_epoch(self, epoch: int) -> None:
        self.sampler.set_epoch(epoch)

    def __len__(self) -> int:
        n = self.sampler.num_samples
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        indices = self.sampler.local_indices()
        nbatches = len(self)
        # Fault-injection hook (faults/inject.py, None without a
        # PTD_FAULTS plan): slow_io makes this rank's batch assembly
        # straggle, io_err crashes it mid-epoch — the loader-side faults
        # the chaos suite drives through run.py.
        inj = _inject.active()
        for b in range(nbatches):
            if inj is not None:
                inj.on_io("data_batch")
            batch_idx = indices[b * self.batch_size : (b + 1) * self.batch_size]
            yield self.dataset[batch_idx]


def shard_batch(batch: dict[str, np.ndarray], sharding) -> dict[str, jax.Array]:
    """Assemble the global device-laid-out batch from this process's shard.

    ``sharding`` may be one NamedSharding for every leaf, or a callable
    ``leaf -> NamedSharding`` (rank-aware per-leaf layout,
    mesh.batch_leaf_sharding)."""
    pick = sharding if callable(sharding) else (lambda _: sharding)
    if jax.process_count() > 1:
        return {
            k: jax.make_array_from_process_local_data(pick(v), v)
            for k, v in batch.items()
        }
    return {k: jax.device_put(v, pick(v)) for k, v in batch.items()}


def prefetch_to_device(
    iterator: Iterator[dict[str, np.ndarray]],
    sharding,
    size: int = 2,
) -> Iterator[dict[str, jax.Array]]:
    """Double-buffer: keep ``size`` batches in flight on device so the H2D
    transfer of batch k+1 overlaps the compute of batch k. Each shard/H2D
    handoff is a "train/h2d" host span (telemetry/spans.py) — note the
    span covers the *dispatch* of the transfer; the DMA itself overlaps
    compute by design.

    ``size`` is the configurable depth (Trainer(prefetch=N) /
    PTD_PREFETCH): 2 is the committed double-buffer default; deeper
    queues buy jitter tolerance at ``size`` batches of extra device
    memory; ``size=0`` degrades to fully synchronous transfer — each
    batch is sharded and handed over immediately, nothing queued ahead
    (the debugging/memory-floor mode, and the semantics every positive
    depth reduces to at iterator exhaustion)."""
    if size < 0:
        raise ValueError(f"prefetch size must be >= 0, got {size}")
    queue: collections.deque = collections.deque()
    for batch in iterator:
        with span("train/h2d"):
            queue.append(shard_batch(batch, sharding))
        if len(queue) >= size:  # size 0: always — fully synchronous
            yield queue.popleft()
    while queue:
        yield queue.popleft()

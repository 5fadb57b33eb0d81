"""Process launching — the framework's L2 (SURVEY.md §1).

Two entry styles, mirroring the reference's lesson pair:

  * ``launch(fn, nprocs)`` — the `mp.spawn` style (reference ddp_gpus.py:98):
    parent spawns one process per "device group", passing the rank as the
    first argument;
  * ``python -m pytorchdistributed_tpu.run --nproc-per-node N script.py``
    — the torchrun style (reference ddp_gpus_torchrun.py:102): an agent
    process sets the env contract (RANK / WORLD_SIZE / LOCAL_RANK /
    MASTER_ADDR / MASTER_PORT) and the script reads it via
    runtime.dist.init_process_group. Implemented in runtime/run.py with
    elastic restart (SURVEY.md §5 "Failure detection").

On a real TPU pod there is one process per host and the TPU runtime itself
provides topology metadata, so these launchers matter for (a) CPU-sim
multi-process testing — the analog of BASELINE's "gloo CPU smoke" — and
(b) driving jax.distributed rendezvous when infra (GKE/QueuedResources)
doesn't.

One process for each chip: a process that touches JAX on a TPU host takes
every chip it can see, and the next one fails or hangs. So a launcher
parent stays off JAX (it counts chips from /dev, `local_tpu_chips`) and
hands worker r the libtpu variables that show it chip r only
(`chip_binding`, `one_chip_env`). A worker count the host's chips cannot
back one-to-one is refused here, before anything is spawned.
"""

from __future__ import annotations

import glob
import multiprocessing
import os
import re
import socket
import time
from typing import Callable, Sequence


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def sim_device_flags(inherited: str, devices_per_proc: int) -> str:
    """XLA_FLAGS for a CPU-sim worker: strip any pre-existing
    device-count flag first (e.g. from a test/CI env), so the result holds
    exactly one — relying on XLA's last-flag-wins is brittle."""
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   inherited)
    return (f"{flags} --xla_force_host_platform_device_count="
            f"{devices_per_proc}").strip()


def local_tpu_chips() -> int:
    """How many TPU chips this host exposes — read from /dev, never from
    JAX: numbered vfio groups (v5e and later) or accel nodes (v4 and
    earlier). 0 when the process is held to the CPU (JAX_PLATFORMS=cpu),
    where nothing needs binding."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        return 0
    try:
        vfio = [n for n in os.listdir("/dev/vfio") if n.isdigit()]
    except OSError:
        vfio = []
    return len(vfio) or len(glob.glob("/dev/accel[0-9]*"))


def one_chip_env(chip: int) -> dict[str, str]:
    """libtpu variables that show a process chip ``chip`` alone, as a
    slice of its own — a serving replica, which never talks chip to
    chip."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


# libtpu's name for the chip grid of one host, by chip count. Only what
# has trained is listed (the 2x2 v5e host, PR 21); another host shape is
# refused until it has run.
_HOST_CHIP_BOUNDS = {4: "2,2,1"}


def chip_binding(nproc: int) -> list[dict[str, str]]:
    """Per-worker libtpu environment for a training group of ``nproc``
    non-sim workers on this host: ``[{}] * nproc`` where there is nothing
    to bind (no chips, or a single worker, which drives every chip
    itself), else worker r sees chip r alone and the workers form ONE
    slice — ICI collectives between them, the layout `jax.distributed`
    trains over. Raises when the chips cannot back the workers
    one-to-one: a second process on a chip fails or hangs."""
    chips = local_tpu_chips()
    if chips == 0 or nproc == 1:
        return [{} for _ in range(nproc)]
    if nproc != chips or chips not in _HOST_CHIP_BOUNDS:
        sizes = f"1 or {chips}" if chips in _HOST_CHIP_BOUNDS else "1"
        raise RuntimeError(
            f"{nproc} worker processes on a host with {chips} TPU chip(s): "
            f"one process drives one chip, so a group here is {sizes} "
            f"processes (one process can drive every chip itself)")
    ports = [_free_port() for _ in range(nproc)]
    addresses = ",".join(f"localhost:{p}" for p in ports)
    return [{**one_chip_env(r),
             "TPU_PROCESS_BOUNDS": _HOST_CHIP_BOUNDS[chips],
             "TPU_PROCESS_ADDRESSES": addresses,
             "TPU_PROCESS_PORT": str(ports[r]),
             "CLOUD_TPU_TASK_ID": str(r)} for r in range(nproc)]


def worker_envs(world_size: int, port: int,
                devices_per_proc: int | None) -> list[dict[str, str]]:
    """What each worker of a group adds to the environment it inherits:
    the rendezvous variables, then either its CPU-sim devices
    (``devices_per_proc``) or its chip (`chip_binding` — which raises,
    before anything is spawned, when the chips cannot back the group)."""
    if devices_per_proc is not None:
        # CPU-sim: each process gets its own simulated chips
        device = {"JAX_PLATFORMS": "cpu",
                  "XLA_FLAGS": sim_device_flags(
                      os.environ.get("XLA_FLAGS", ""), devices_per_proc)}
        devices = [device] * world_size
    else:
        devices = chip_binding(world_size)
    return [{"RANK": str(rank),
             "LOCAL_RANK": str(rank),
             "WORLD_SIZE": str(world_size),
             "MASTER_ADDR": "localhost",
             "MASTER_PORT": str(port),
             **devices[rank]} for rank in range(world_size)]


def _worker(fn: Callable, env: dict[str, str], rank: int,
            args: tuple) -> None:
    os.environ.update(env)
    fn(rank, *args)


def launch(
    fn: Callable,
    nprocs: int,
    *,
    args: Sequence = (),
    devices_per_proc: int | None = None,
    timeout: float | None = None,
) -> None:
    """Spawn ``nprocs`` processes running ``fn(rank, *args)`` with the
    rendezvous env set (the reference's ``mp.spawn(main, args=...,
    nprocs=world_size)``, ddp_gpus.py:98). Raises RuntimeError if any child
    exits nonzero — after terminating the rest (fail-fast, the behavior
    torchrun's agent provides)."""
    ctx = multiprocessing.get_context("spawn")
    envs = worker_envs(nprocs, _free_port(), devices_per_proc)
    procs = [
        ctx.Process(
            target=_worker,
            args=(fn, envs[rank], rank, tuple(args)),
            name=f"tpu-dist-rank{rank}",
        )
        for rank in range(nprocs)
    ]
    for p in procs:
        p.start()
    # Poll ALL children (like run.py's agent) rather than join()ing them in
    # order: a sequential join can hang forever when a later rank crashes
    # while an earlier one blocks in a collective waiting for it.
    deadline = None if timeout is None else time.monotonic() + timeout
    failed = None
    try:
        while failed is None:
            codes = {rank: p.exitcode for rank, p in enumerate(procs)}
            for rank, code in codes.items():
                if code not in (None, 0):
                    failed = (rank, f"exit code {code}")
                    break
            else:
                if all(c == 0 for c in codes.values()):
                    return
                if deadline is not None and time.monotonic() > deadline:
                    rank = next(r for r, c in codes.items() if c is None)
                    failed = (rank, "timeout")
                    break
                time.sleep(0.05)
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
        for p in procs:
            p.join(10)
    raise RuntimeError(
        f"rank {failed[0]} failed ({failed[1]}); terminated the rest")

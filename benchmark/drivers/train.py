"""The training driver: `Trainer.train_step` under the cell's strategy.

Set-up builds ONE Trainer, gives it the benchmark's weights, drives it
through its first steps on the seeded stream (the steps the reference
follows), and hands the same object to the window. The window calls
`train_step` on the stream until its time is up, at most `run_ahead`
steps ahead of the device, and ends by forcing the last loss.
"""

from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import loadgen, reference, window
from benchmark.drivers import common

CHECK_STEPS = 3


def adam_hp(mix: dict) -> dict:
    o = mix["optimizer"]
    return {"lr": o["lr"], "b1": o["b1"], "b2": o["b2"], "eps": o["eps"],
            "weight_decay": o["weight_decay"]}


class TrainSystem:
    """The Trainer of one cell and what the comparison reads from it."""

    def __init__(self, cell, devices, fault: str | None = None):
        import optax

        from pytorchdistributed_tpu.runtime.mesh import create_mesh
        from pytorchdistributed_tpu.training import (
            Trainer,
            token_cross_entropy_loss,
        )

        cfg, mix = cell.config, cell.mix
        self.family = cell.family
        self.cfg, self.mix, self.devices = cfg, mix, devices
        self.rows = int(mix["rows_per_chip"]) * len(devices)
        self.seq = int(mix["seq_len"])
        self.tokens_per_step = self.rows * self.seq
        self.fault = fault
        hp = adam_hp(mix)
        opt = optax.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"],
                          eps=hp["eps"], weight_decay=hp["weight_decay"])
        self.trainer = Trainer(
            self.family.program_model(cfg, mix), opt,
            token_cross_entropy_loss,
            mesh=create_mesh(devices=devices, **mix["mesh"]),
            strategy=mix["strategy"], log_every=10 ** 9, watchdog=False)

    def start(self, seed: int, stream) -> list:
        """Initialise, install the seed's weights, and return the first
        batches (the ones the reference will follow)."""
        first = [next(stream) for _ in range(CHECK_STEPS)]
        tr = self.trainer
        tr.init(first[0])
        sh = tr.state_shardings.params
        fam = self.family
        self._make = jax.jit(
            lambda s: fam.to_program_tree(
                fam.make_weights(self.cfg, s), self.cfg, self.mix),
            out_shardings=sh)
        self.install(seed)
        return first

    def install(self, seed: int) -> None:
        """Fresh state: the seed's weights, zeroed moments, step 0."""
        tr = self.trainer
        params = self._make(reference.seed_u32(seed))
        zeros = jax.tree.map(lambda x: jnp.zeros_like(x),
                             tr.state.opt_state)
        tr.state = tr.state.replace(
            step=jnp.zeros_like(tr.state.step), params=params,
            opt_state=zeros)

    def step(self, batch):
        if self.fault == "state_unchanged":
            # the planted fault of the harness's own test: a step that
            # returns its state as it was
            keep = jax.tree.map(lambda x: x.copy(), self.trainer.state)
            out = self.trainer.train_step(batch)
            self.trainer.state = keep
            return out
        if self.fault in ("half_batch", "no_exchange"):
            # half of the batch left out and the mean taken over the
            # rest; or every chip left with the first chip's rows, which
            # is what that chip computes when the exchange is left out
            parts = 2 if self.fault == "half_batch" else len(self.devices)
            keep = self.rows // parts
            batch = {k: np.concatenate([v[:keep]] * parts)
                     for k, v in batch.items()}
        return self.trainer.train_step(batch)

    def first_grad_norms(self) -> dict:
        """The norms of the first gradient as the optimizer got it: Adam's
        first moment after one step is (1 - b1) times it."""
        mu = next(s.mu for s in self.trainer.state.opt_state
                  if hasattr(s, "mu"))
        scale = 1.0 / (1.0 - self.mix["optimizer"]["b1"])
        fam = self.family

        @jax.jit
        def norms(tree):
            flat = fam.from_program_tree(tree, self.cfg, self.mix)
            flat = jax.tree.map(lambda x: x * scale, flat)
            return (reference.leaf_norms(fam, flat),
                    reference.sketch(fam, flat))

        with jax.set_mesh(self.trainer.mesh):
            return jax.device_get(norms(mu))

    def delta_norms(self, seed: int) -> dict:
        """Norms of (parameters now) - (the seed's weights)."""
        fam = self.family

        @jax.jit
        def norms(tree, s):
            now = fam.from_program_tree(tree, self.cfg, self.mix)
            was = fam.make_weights(self.cfg, s)
            return reference.leaf_norms(
                fam, jax.tree.map(jnp.subtract, now, was))

        with jax.set_mesh(self.trainer.mesh):
            return jax.device_get(norms(self.trainer.state.params,
                                        reference.seed_u32(seed)))

    def first_steps(self, seed: int, first: list) -> dict:
        """Steps 1..3 through the window's own call; what the reference
        is compared with."""
        losses = []
        gnorms = gsketch = None
        for i, batch in enumerate(first, 1):
            losses.append(self.step(batch)["loss"])
            if i == 1:
                gnorms, gsketch = self.first_grad_norms()
        dnorms = self.delta_norms(seed)
        return {"losses": [float(x) for x in losses],
                "grad_norms": gnorms, "grad_sketch": gsketch,
                "delta_norms": dnorms}

    def free(self) -> None:
        self.trainer.state = None
        self.trainer = None
        self._make = None
        gc.collect()


def reference_run(cell, devices, seed: int, first: list, mode="f32",
                  rows=None) -> dict:
    ref = reference.TrainReference(
        cell.family, cell.config, adam_hp(cell.mix), devices, mode=mode,
        block_rows=int(cell.mix.get("reference_block_rows", 1)))
    return ref.run(seed, first, rows=rows)


def run(cell, devices, args, phases, fault=None) -> dict:
    mix = cell.mix
    system = TrainSystem(cell, devices, fault=fault)
    stream = loadgen.BatchStream(cell.config["vocab_size"], system.rows,
                                 system.seq, args.seed)
    phases.mark("build")
    first = system.start(args.seed, stream)
    phases.mark("weights")
    prog = system.first_steps(args.seed, first)
    # one more step, so that every program the window uses has run with
    # the inputs of the steady state
    float(system.step(next(stream))["loss"])
    phases.mark("warm-up")

    ahead = int(mix.get("run_ahead", 2))
    pending: collections.deque = collections.deque()
    trace_at = None
    if args.trace:
        # the last seconds of the window: stopping the profiler blocks
        # the host for a while, and there it delays nothing
        trace_at = (args.seconds - float(mix["trace_s"]),
                    float(mix["trace_s"]))
    tracer = args.tracer
    steps = 0
    t0 = time.perf_counter()
    phases.window_start(t0)
    t_end = t0 + args.seconds
    last = None
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if tracer is not None:
            tracer.poll(now - t0, trace_at)
        with jax.profiler.TraceAnnotation("input.next_batch"):
            batch = next(stream)
        with jax.profiler.TraceAnnotation("train_step"):
            last = system.step(batch)["loss"]
        pending.append(last)
        steps += 1
        if len(pending) > ahead:
            float(pending.popleft())
    float(last)  # the fence: every step counted has completed
    t_fence = time.perf_counter()
    if tracer is not None:
        tracer.finish()
    metrics = window.train_metrics(steps, system.tokens_per_step, t0,
                                   t_fence)
    peak = common.memory_peak_bytes(devices)
    system.free()
    phases.note("window closed; running the reference")
    ref = reference_run(cell, devices, args.seed, first)
    cmp = common.compare_training(prog, ref)
    limits = mix["limits"]
    checks = [(k, cmp[k], limits[k]) for k in
              ("loss1_gap", "loss2_gap", "loss3_gap", "grad_gap",
               "grad_diff", "delta_gap") if k in limits]
    return {
        "metrics": metrics, "checks": checks, "attempted": steps,
        "failed": 0, "memory_peak_bytes": int(peak),
        "t0": t0, "t1": t_fence,
        "log": {"steps": steps, "tokens_per_step": system.tokens_per_step,
                "rows": system.rows, "seq_len": system.seq,
                "strategy": mix["strategy"], "mesh": mix["mesh"],
                "worst_grad_leaf": cmp["_grad_leaf"],
                "worst_delta_leaf": cmp["_delta_leaf"],
                "left_out_of_delta": cmp["_left_out"],
                "losses": prog["losses"], "ref_losses": ref["losses"]},
        "ctx": {"steps": steps, "tokens_per_step": system.tokens_per_step,
                "rows": system.rows, "seq_len": system.seq},
    }

"""The plain reference: a family's model as published, in straightforward
`jax.numpy` and float32, with no kernel, no cache and no batching tricks.

It imports nothing of the program and takes nothing the program made. The
model itself (its leaves' shapes, its weights from the seed, its forward
pass) is the family's (`families/<model_type>.py`: `shapes`,
`make_weights`, `forward`); `make_weights` is the same call the driver
uses to make the weights it hands to the program. Here are what every
family shares: the three precisions, mean token cross-entropy, AdamW as
published (decoupled weight decay, bias-corrected moments), the norms
and sketches the comparison reads, and the gaps of served tokens.

`mode` sets the precision: "f32" is the reference (float32 throughout,
matmuls at `highest`, which on a TPU is what makes a float32 matmul
float32). "bf16" is what the configurations state the program computes
in: float32 weights cast for use, activations kept in bfloat16 between
operations, float32 accumulation, norms and softmax worked out in
float32. "int8" is the control, the step below bf16 that a later PR would
be tempted by: the bf16 computation with the operands of every linear
layer and of the head rounded to symmetric int8 first (per row of the
activations, per column of the weights, straight-through backward). A
family's `forward` does its matmuls through `mm` and keeps its
activations in `act(mode)`, so the control means the same step in each.

Memory: a family's layers run under `lax.scan` with `jax.checkpoint`, and
a step walks the batch in blocks of rows, so the float32 activations of
one block of one layer are all that lives at once. On several chips every
leaf is split along its last axis that the chip count divides and the
compiler inserts the exchange.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST


def seed_u32(seed: int) -> np.uint32:
    """`--seed` may pass 2**31; the key takes its low 32 bits."""
    return np.uint32(int(seed) % (2 ** 32))


def leaf_sharding(mesh: Mesh, shape) -> NamedSharding:
    n = mesh.devices.size
    spec = [None] * len(shape)
    for ax in range(len(shape) - 1, -1, -1):
        if n > 1 and shape[ax] % n == 0 and shape[ax] >= n:
            spec[ax] = "x"
            break
    return NamedSharding(mesh, P(*spec))


def weight_shardings(family, cfg: dict, mesh: Mesh) -> dict:
    return {k: leaf_sharding(mesh, s)
            for k, s in family.shapes(cfg).items()}


def fake_int8(x, axis):
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def act(mode: str):
    """The type activations are kept in between operations."""
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def mm(x, w, mode: str):
    """A linear layer's matmul; the result is in the activations' type
    (the head's caller asks for float32 by `mm(...).astype`)."""
    if mode == "f32":
        return jnp.matmul(x, w, precision=HIGHEST)
    if mode == "int8":
        x, w = fake_int8(x, -1), fake_int8(w, 0)
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _ce_sum(family, cfg, mode, params, tokens, targets):
    logits = family.forward(cfg, params, tokens, mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return (lse - picked).sum()


def leaf_norms(family, tree: dict) -> dict:
    """L2 norm of every leaf the family compares
    (`family.compared_leaves`: a fused projection may count as several);
    of a leaf stacked by layer, one norm per layer."""
    leaves, stacked = family.compared_leaves(tree)
    out = {}
    for k, v in leaves.items():
        v = v.astype(jnp.float32)
        axes = tuple(range(1, v.ndim)) if k in stacked else None
        out[k] = jnp.sqrt((v * v).sum(axes))
    return out


SKETCH_PROBES = 4


def sketch(family, tree: dict) -> dict:
    """Every leaf (every layer of a stacked one) projected on a few fixed
    +-1 vectors. The norm of the difference of two sketches estimates the
    norm of the difference of the two trees without both having to be
    alive at once: a number that zero-mean rounding noise moves in the
    first order, where a gap of norms moves only in the second."""
    out = {}
    base = jax.random.key(20260930)
    for i, (k, v) in enumerate(sorted(tree.items())):
        v = v.astype(jnp.float32)
        axes = tuple(range(1, v.ndim)) if k in family.STACKED else None
        rows = []
        for p in range(SKETCH_PROBES):
            key = jax.random.fold_in(jax.random.fold_in(base, i), p)
            signs = jax.random.rademacher(key, v.shape, jnp.float32)
            rows.append((v * signs).sum(axes))
        out[k] = jnp.stack(rows)
    return out


def sketch_gap(got: dict, ref: dict) -> float:
    """||got - ref|| / ||ref|| as the sketches estimate it."""
    num = sum(float(((np.asarray(got[k], np.float64)
                      - np.asarray(ref[k], np.float64)) ** 2).sum())
              for k in ref)
    den = sum(float((np.asarray(ref[k], np.float64) ** 2).sum())
              for k in ref)
    return float(np.sqrt(num / den))


class TrainReference:
    """Three AdamW steps of the reference, in blocks of rows."""

    def __init__(self, family, cfg: dict, hp: dict, devices,
                 mode: str = "f32", block_rows: int = 1):
        self.cfg, self.hp, self.mode = cfg, hp, mode
        self.block_rows = block_rows
        self.mesh = Mesh(np.asarray(devices), ("x",))
        self.shardings = weight_shardings(family, cfg, self.mesh)
        rep = NamedSharding(self.mesh, P())
        sh = self.shardings
        self._make = jax.jit(functools.partial(family.make_weights, cfg),
                             out_shardings=sh)
        self._zeros = jax.jit(
            lambda p: jax.tree.map(jnp.zeros_like, p), out_shardings=sh)
        self._grad = jax.jit(
            jax.value_and_grad(
                functools.partial(_ce_sum, family, cfg, mode)),
            in_shardings=(sh, rep, rep), out_shardings=(rep, sh))
        self._add = jax.jit(
            lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,),
            out_shardings=sh)
        self._adam = jax.jit(self._adam_step, donate_argnums=(0, 2, 3),
                             out_shardings=(sh, sh, sh))
        self._norms = jax.jit(
            lambda t: (leaf_norms(family, t), sketch(family, t)))
        self._diff_norms = jax.jit(lambda a, b: leaf_norms(
            family, jax.tree.map(jnp.subtract, a, b)))

    def _adam_step(self, params, grads, mu, nu, step, n_tokens):
        hp = self.hp
        b1, b2 = hp["b1"], hp["b2"]
        g = jax.tree.map(lambda x: x / n_tokens, grads)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step

        def upd(p, m, v):
            u = (m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
            return p - hp["lr"] * (u + hp["weight_decay"] * p)

        return jax.tree.map(upd, params, mu, nu), mu, nu

    def run(self, seed: int, batches: list[dict], rows=None) -> dict:
        """Losses of each step, the norms of the first gradient and of
        the parameters' change after the last step. `rows` keeps only
        those rows of every batch (the planted faults: half the batch
        left out, or one chip's share alone)."""
        params = self._make(seed_u32(seed))
        first = self._make(seed_u32(seed))
        mu, nu = self._zeros(params), self._zeros(params)
        losses, gnorms = [], None
        for step, batch in enumerate(batches, 1):
            tok, tgt = batch["tokens"], batch["targets"]
            if rows is not None:
                tok, tgt = tok[rows], tgt[rows]
            n_tokens = float(tok.size)
            total, grads = 0.0, None
            for i in range(0, tok.shape[0], self.block_rows):
                sl = slice(i, i + self.block_rows)
                loss, g = self._grad(params, jnp.asarray(tok[sl]),
                                     jnp.asarray(tgt[sl]))
                grads = g if grads is None else self._add(grads, g)
                total += float(loss)
            losses.append(total / n_tokens)
            if step == 1:
                gnorms, gsketch = jax.device_get(self._norms(
                    jax.tree.map(lambda x: x / n_tokens, grads)))
            params, mu, nu = self._adam(params, grads, mu, nu,
                                        float(step), n_tokens)
        dnorms = jax.device_get(self._diff_norms(params, first))
        return {"losses": losses, "grad_norms": gnorms,
                "grad_sketch": gsketch, "delta_norms": dnorms}


class ServeReference:
    """One teacher-forced forward over prompt + served tokens, padded to
    one shape so that it compiles once."""

    def __init__(self, family, cfg: dict, devices):
        self.family, self.cfg = family, cfg
        self.pad_to = family.positions(cfg)
        self.mesh = Mesh(np.asarray(devices[:1]), ("x",))
        sh = weight_shardings(family, cfg, self.mesh)
        self._make = jax.jit(functools.partial(family.make_weights, cfg),
                             out_shardings=sh)
        self._gaps = jax.jit(self._gaps_fn)
        self.params = None

    def load(self, seed: int) -> None:
        self.params = self._make(seed_u32(seed))

    def _gaps_fn(self, params, seq, served):
        """For every position, by how much the served token's reference
        logit lies below the reference's best, and the same for the token
        the int8 control puts first. `served[i]` is the token emitted
        after position i (or -1 where none was)."""
        ref = self.family.forward(self.cfg, params, seq[None], "f32")[0]
        low = self.family.forward(self.cfg, params, seq[None], "int8")[0]
        best = ref.max(-1)
        pick = jnp.take_along_axis(
            ref, jnp.maximum(served, 0)[:, None], -1)[:, 0]
        ctl = jnp.take_along_axis(
            ref, jnp.argmax(low, -1)[:, None], -1)[:, 0]
        live = served >= 0
        return (jnp.where(live, best - pick, 0.0),
                jnp.where(live, best - ctl, 0.0))

    def gaps(self, prompt: np.ndarray, tokens: np.ndarray):
        """(program's gaps, control's gaps), one per served token."""
        n, m = len(prompt), len(tokens)
        seq = np.zeros(self.pad_to, np.int32)
        seq[:n] = prompt
        seq[n:n + m - 1] = tokens[:-1]
        served = np.full(self.pad_to, -1, np.int32)
        served[n - 1:n - 1 + m] = tokens
        g, c = self._gaps(self.params, jnp.asarray(seq),
                          jnp.asarray(served))
        sl = slice(n - 1, n - 1 + m)
        return np.asarray(g)[sl], np.asarray(c)[sl]

"""The engine's weights in the model's compute type and the served layout.

A model with ``dtype=bfloat16`` and ``param_dtype=float32`` casts every
matrix it multiplies by to bfloat16 inside the compiled program: right
for a training step, whose master weights move every step, and wasted in
a serving tick, whose weights never move (the whole tree read in float32
and written in bfloat16, every call). The engine therefore casts once,
where it takes a tree (`ServingEngine._compute_copy`), and the programs
get leaves their own casts leave alone.

Which leaves is read off the forward pass itself, not off a list of
modules: `cast_only` traces the model's call and marks the leaves whose
every number reaches arithmetic only through a cast to the compute type
(a Dense kernel, an embedding table looked up and attended to, a position
table whose rows are gathered and then cast). A leaf the model reads at
its stored width (a norm's gain, a router that scores in float32) is
kept as it came, and so is every leaf of a tree that is already stored
in the compute type. The cast rounds each value exactly as the program's
own cast did, so logits and tokens are bitwise what they were.

The same place lays out once what the programs would otherwise copy
every call. A fused kernel of a scanned stack (`models/transformer.py:
fused_kernel`: ``wi_kernel [layers, embed, 2, ffn]``, ``qkv_kernel`` /
``kv_kernel [layers, embed, 3|2, width]``) has its fused axis as the
second-minor dimension, and XLA copies each layer's slice into the
layout its product reads before every product, in every tick and chunk.
`served` holds such a leaf as planes, ``<name>_planes [layers, 3|2,
embed, width]``, whose slice the product reads where it lies. The
numbers and their sums are the same; a checkpoint, `init` and the
`Trainer` keep the fused layout, and a tree that already holds planes
is taken as it is.
"""

from __future__ import annotations

import functools
from collections.abc import Mapping

import jax
import jax.numpy as jnp
from flax.core import meta
from jax.extend import core as jex_core

from pytorchdistributed_tpu.models.transformer import PLANES

#: equations that hand their operands on to a body, and how many leading
#: operands the body does not take (a cond's branch index). An equation
#: with a body that is not listed (a while loop, whose operands are
#: split between two bodies) counts as a use of its own.
_BODY_SKIPS = {"jit": 0, "pjit": 0, "closed_call": 0, "checkpoint": 0,
               "custom_jvp_call": 0, "custom_vjp_call": 0, "scan": 0,
               "cond": 1}

#: equations that move their first operand's numbers and compute nothing:
#: a cast after one of them reads what a cast before it would have made
_MOVES = frozenset({"gather", "dynamic_slice", "slice", "reshape",
                    "squeeze", "transpose", "broadcast_in_dim"})


def wider(leaf, dtype) -> bool:
    """Whether `leaf` is a floating array of more bytes a number than
    `dtype`."""
    have = getattr(leaf, "dtype", None)
    return (have is not None and jnp.issubdtype(have, jnp.floating)
            and jnp.dtype(have).itemsize > jnp.dtype(dtype).itemsize)


def _bodies(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jex_core.Jaxpr):
                yield item


def _casts(jaxpr, var, dtype) -> int | None:
    """How many ``convert_element_type`` to `dtype` the uses of `var` in
    `jaxpr` end in, followed into the bodies of jits, scans (a scanned
    leaf's slice is cast layer by layer), remats and conds and through
    equations that only move numbers; None if any use is something else."""
    if any(out is var for out in jaxpr.outvars):
        return None
    found = 0
    for eqn in jaxpr.eqns:
        places = [i for i, v in enumerate(eqn.invars) if v is var]
        if not places:
            continue
        name = eqn.primitive.name
        if name == "convert_element_type":
            inner = [1 if eqn.params["new_dtype"] == dtype else None]
        elif name in _MOVES and places == [0]:
            inner = [_casts(jaxpr, out, dtype) for out in eqn.outvars]
        elif name in _BODY_SKIPS:
            skip = _BODY_SKIPS[name]
            inner = [
                _casts(body, body.invars[i - skip], dtype)
                if i >= skip and len(body.invars) == len(eqn.invars) - skip
                else None
                for body in _bodies(eqn) for i in places]
        else:
            return None
        if None in inner:
            return None
        found += sum(inner)
    return found


def cast_only(forward, tree, dtype, *operands) -> tuple[bool, ...]:
    """One flag a leaf of `tree`, in `jax.tree.leaves` order: whether
    ``forward(tree, *operands)`` uses the leaf, and computes with its
    numbers only after casting them to `dtype`. Traced abstractly: nothing
    runs and nothing compiles."""
    closed = jax.make_jaxpr(forward)(tree, *operands)
    dtype = jnp.dtype(dtype)
    count = len(jax.tree.leaves(tree))
    return tuple(bool(_casts(closed.jaxpr, var, dtype))
                 for var in closed.jaxpr.invars[:count])


@functools.partial(jax.jit, static_argnums=1)
def _serve(leaves, how):
    """Each leaf in the type `how` gives it and, where its flag says,
    with the fused axis of ``[.., embed, c, width]`` moved before the
    embedding's: ``[.., c, embed, width]``."""
    return [jnp.moveaxis(leaf.astype(dtype), -2, -3) if planes
            else leaf.astype(dtype)
            for leaf, (dtype, planes) in zip(leaves, how, strict=True)]


def _fused(path, leaf) -> bool:
    """Whether a leaf is a fused kernel of a scanned stack: one of
    `PLANES` with the stack's leading axis, ``[layers, embed, c,
    width]``."""
    names = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)]
    return bool(names) and names[-1] in PLANES and leaf.ndim == 4


def served(tree, flags, dtype):
    """`tree` as the engine's programs take it: each flagged leaf that is
    `wider` than `dtype` cast to it (`flags`: `cast_only`'s, or None where
    nothing is cast), each fused kernel of a scanned stack re-laid as
    planes under its plane name (`PLANES`; unboxed), and every other leaf
    the very object it was. Returns the tree, the bytes the cast leaves
    held before, and the bytes the re-laid leaves hold. One program makes
    them all, where one a leaf would compile a small program a shape; a
    tree with nothing to do comes back as it is."""
    pairs, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [leaf for _, leaf in pairs]
    flags = flags or (False,) * len(leaves)
    how = [(jnp.dtype(dtype) if flag and wider(leaf, dtype)
            else leaf.dtype, _fused(path, leaf))
           for (path, leaf), flag in zip(pairs, flags, strict=True)]
    picks = [i for i, (to, planes) in enumerate(how)
             if planes or to != leaves[i].dtype]
    if not picks:
        return tree, 0, 0
    cast_bytes = sum(leaves[i].nbytes for i in picks
                     if how[i][0] != leaves[i].dtype)
    for i, leaf in zip(picks, _serve([leaves[i] for i in picks],
                                     tuple(how[i] for i in picks))):
        leaves[i] = leaf
    relaid = {tuple(k.key for k in pairs[i][0]
                    if isinstance(k, jax.tree_util.DictKey))
              for i in picks if how[i][1]}

    def renamed(node, path):
        if not isinstance(node, Mapping):
            return node
        out = {}
        for key, sub in node.items():
            at = path + (key,)
            if at in relaid:
                out[PLANES[key]] = meta.unbox(sub)
            else:
                out[key] = renamed(sub, at)
        return out

    out = treedef.unflatten(leaves)
    return (renamed(out, ()) if relaid else out, cast_bytes,
            sum(leaves[i].nbytes for i in picks if how[i][1]))

"""The engine's weights in the model's compute type.

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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

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
def _cast(leaves, dtype):
    return [leaf.astype(dtype) for leaf in leaves]


def narrowed(tree, flags, dtype):
    """`tree` with each flagged leaf that is `wider` than `dtype` cast to
    it and every other leaf the very object it was, and the bytes the
    cast leaves held before. One program casts them all, where a cast a
    leaf would compile one small program a shape."""
    leaves, treedef = jax.tree.flatten(tree)
    picks = [i for i, (leaf, flag) in enumerate(zip(leaves, flags,
                                                    strict=True))
             if flag and wider(leaf, dtype)]
    cast_bytes = sum(leaves[i].nbytes for i in picks)
    if picks:
        for i, leaf in zip(picks, _cast([leaves[i] for i in picks],
                                        jnp.dtype(dtype))):
            leaves[i] = leaf
    return treedef.unflatten(leaves), cast_bytes

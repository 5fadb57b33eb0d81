"""The grouped matrix product of the experts (`models/moe.py:DroplessMoE`):
rows sorted by group, each group multiplied by its own matrix of a BANK
that may hold more matrices than the call names.

    out[r] = lhs[r] @ bank[first_group + i]     for r in group i of sizes

`lax.ragged_dot` is the same product over a bank of exactly the groups
named, and on a TPU it compiles to a kernel of XLA's whose operands are
whole buffers: handed one layer's slice of a stack of layers (the scanned
stack's `[periods, experts, ...]` leaf) it has the slice copied out first,
every layer of every call (ISSUE 37: 39% of SmallThinker's busy time). The
kernel here reads the bank where it lies: its block of the right-hand side
is chosen by a scalar-prefetched group id, so the kernel's own DMAs fetch
`[expert, k-tile, n-tile]` from the stack in HBM, a group of no rows gets
no tile, and consecutive tiles of one group fetch its matrix once.

The kernel is megablox's `gmm` (`jax.experimental.pallas.ops.tpu.megablox`
of the installed jax) in this repo's image: the same grid of (row tile,
group) visits, with the bank's first group as a prefetched scalar, tiles
chosen from the call's shapes, a schedule worked out in a dozen
operations (`_schedule`) and a name of its own in a trace. Off a TPU the
product is `lax.ragged_dot` on the bank's slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bytes of one block of the bank in VMEM (Pallas keeps two in flight)
BANK_BLOCK_BYTES = 4 * 2 ** 20
#: what the kernel may hold there in all: two such blocks, two row tiles,
#: two tiles of the result and the accumulator
VMEM_LIMIT_BYTES = 32 * 2 ** 20


def tiling(m: int, k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """(rows, k, n) of a tile, from the shapes a call sees. The whole of
    ``k`` where a ``[k, 128]`` block fits (one visit of a group then
    holds its matrix's block across its row tiles, and the accumulator
    is written once), over the widest ``n`` tile of whole 128-lane
    columns that divides ``n`` and fits; 128 rows where there are many
    (a group of a prefill chunk spans a tile or more), 64 where few (a
    decode tick's groups are a few rows each, and the rows of a tile are
    all multiplied, a group's own or not)."""
    tm = 128 if m >= 1024 else 64
    tk = k
    while tk * 128 * itemsize > BANK_BLOCK_BYTES and tk % 256 == 0:
        tk //= 2
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and tk * t * itemsize <= BANK_BLOCK_BYTES]
    return tm, tk, max(fits, default=n)


def sliced_product(lhs, bank, sizes, first_group=0):
    """`lax.ragged_dot` on ``bank[first_group : first_group + g]``: the
    product off a TPU, and what the kernel is held to."""
    g = sizes.shape[0]
    if bank.shape[0] != g:
        bank = lax.dynamic_slice_in_dim(bank, first_group, g)
    return lax.ragged_dot(lhs, bank, sizes,
                          preferred_element_type=jnp.float32)


def _schedule(sizes, tiles_m: int, tm: int):
    """Which group and which row tile each grid step works on: a group
    visits every tile it has rows in, groups in order and a group's tiles
    in order, so a tile shared by several groups is revisited by
    consecutive steps only and a group of no rows has no step. Returns
    the groups' row offsets ``[g + 1]``, ``group_ids`` and ``tile_ids``
    ``[tiles_m + g - 1]`` (as many steps as there can be; those past the
    count repeat the last group, unused) and the count of steps."""
    g = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    visits = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    done = jnp.cumsum(visits)                  # steps up to a group's end
    step = jnp.arange(tiles_m + g - 1, dtype=jnp.int32)
    group_ids = jnp.minimum(
        (step[:, None] >= done[None, :]).sum(-1, dtype=jnp.int32), g - 1)
    tile_ids = jnp.minimum(
        first_tile[group_ids] + step - (done - visits)[group_ids],
        tiles_m - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group_ids, tile_ids, done[-1]


def _kernel(offsets, group_ids, tile_ids, first, lhs, rhs, out, acc, *,
            tm: int, tiles_k: int):
    """One grid step: a row tile times a ``[tk, tn]`` block of its
    group's matrix onto the accumulator; after the last ``k`` block the
    group's own rows of the tile are stored and the others kept (the
    tile's other groups wrote them in the steps before, or will)."""
    del first
    step, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(lhs[...], rhs[...],
                        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _():
        group = group_ids[step]
        row = tile_ids[step] * tm + lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= offsets[group]) & (row < offsets[group + 1])
        out[...] = jnp.where(mine, acc[...], out[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_product(lhs, bank, sizes, first_group=0, *, interpret=False):
    """The product through the kernel, the bank read in place. Jitted
    for the tracing's sake: a layer's products of one shape share one
    trace and one lowered kernel (traced anew by each of a set-up's 96
    calls, megablox's schedule of thirty operations cost an engine half
    a minute)."""
    m, k = lhs.shape
    n = bank.shape[2]
    tm, tk, tn = tiling(m, k, n, bank.dtype.itemsize)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tiles_k = k // tk
    offsets, group_ids, tile_ids, steps = _schedule(sizes, (m + pad) // tm,
                                                    tm)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, steps, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, s, k_i, off, grp, tile,
                             first: (tile[s], k_i)),
                pl.BlockSpec((None, tk, tn), lambda n_i, s, k_i, off, grp,
                             tile, first: (grp[s] + first[0], k_i, n_i)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, s, k_i, off, grp,
                                   tile, first: (tile[s], n_i)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="expert_banks",
    )(offsets, group_ids, tile_ids,
      jnp.asarray(first_group, jnp.int32).reshape(1), lhs, bank)
    # a tile no group has rows in was never written, and a tile's rows
    # past the last group hold what the buffer held
    return jnp.where((jnp.arange(m) < sizes.sum())[:, None], out[:m], 0.0)


def grouped_product(lhs, bank, sizes, first_group=0):
    """``lhs [m, k]`` rows sorted by group, ``sizes [g]`` (int32) the
    rows a group, ``bank [G, k, n]`` with ``G >= g``: group ``i``
    multiplies by ``bank[first_group + i]``. Operands as they come,
    float32 accumulation and float32 out ``[m, n]``; rows past
    ``sum(sizes)`` are nought. On a TPU the kernel, elsewhere
    `lax.ragged_dot` on the slice (the choice every kernel entry point
    makes: ``jax.default_backend()``)."""
    if jax.default_backend() == "tpu":
        return kernel_product(lhs, bank, sizes, first_group)
    return sliced_product(lhs, bank, sizes, first_group)

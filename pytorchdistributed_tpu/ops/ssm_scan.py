"""Mamba-1's selective scan over a chunk of positions: one Pallas kernel
on a TPU (`ssm_scan` in a trace), `lax.scan` over the positions
elsewhere (`scan_reference`, which the tests hold the kernel to).

For channel ``d`` and state ``n`` the recurrence is

    s_t = exp(delta_t A) s_{t-1} + delta_t B_t h_t,      y_t = s_t . C_t

with a decay that differs per ``(d, n)``: no matrix form (Mamba-2's)
applies, and the work is ``exp`` and multiply-adds on the VPU, a position
after another. The state is float32 throughout, laid out ``[N, D]`` (the
channels on the 128 lanes, the 16 states on sublanes: ``[D, N]`` would
pad 16 lanes to 128).

The kernel runs a program a block of ``BLOCK_D`` channels of one row of
the batch; each holds its block's state ``[N, BLOCK_D]`` in registers
while it walks the chunk's positions in order, and reads ``delta`` and
``u = delta * h`` once, writes ``y`` once and writes the final state.
``B`` and ``C`` come transposed, ``[2N, L]`` (the positions on lanes):
position ``t``'s sixteen numbers of each are a column, taken out of a
128-lane tile of them by a masked lane sum and broadcast over the block's
lanes. A position past a row's last real token is a step with ``delta``
and ``u`` nought: the state goes through it unchanged (the caller masks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: channels of one program's block (the state block is [16, 512] float32:
#: eight vector registers)
BLOCK_D = 512
#: positions whose B and C columns one 128-lane tile holds
LANES = 128
#: the type the state is kept in from one position to the next (the
#: tests plant a bfloat16 state here)
STATE_DTYPE = jnp.float32


def kept(s):
    """The state as it is kept between two positions (`STATE_DTYPE`)."""
    return s.astype(STATE_DTYPE).astype(jnp.float32)


def scan_reference(delta, u, b, c, a, state):
    """`lax.scan` over the positions. ``delta``, ``u`` ``[bt, L, D]``
    float32 (``u = delta * h``), ``b``, ``c`` ``[bt, L, N]`` float32,
    ``a`` ``[N, D]`` (negative), ``state`` ``[bt, N, D]`` float32 ->
    (``y [bt, L, D]``, the state after the last position), both
    float32. The products are elementwise: no matrix unit rounds them."""
    def step(s, xs):
        d_t, u_t, b_t, c_t = xs                       # [bt, D], [bt, N]
        s = kept(jnp.exp(d_t[:, None, :] * a) * s
                 + b_t[:, :, None] * u_t[:, None, :])
        return s, (s * c_t[:, :, None]).sum(1)
    s, y = lax.scan(step, state, tuple(
        x.astype(jnp.float32).swapaxes(0, 1) for x in (delta, u, b, c)))
    return y.swapaxes(0, 1), s


def _kernel(d_ref, u_ref, bc_ref, a_ref, s0_ref, y_ref, s1_ref, *,
            n: int, tile: int):
    """One row's block of channels over the chunk: ``d_ref``, ``u_ref``,
    ``y_ref`` ``[L, bd]``, ``bc_ref`` ``[2N, L]``, ``a_ref``, ``s0_ref``,
    ``s1_ref`` ``[N, bd]``."""
    steps = d_ref.shape[0]
    a = a_ref[...]

    def tile_of(k, s):
        base = pl.multiple_of(k * tile, tile)
        bc = bc_ref[:, pl.ds(base, tile)]                     # [2N, tile]
        lane = lax.broadcasted_iota(jnp.int32, bc.shape, 1)

        def step(i, s):
            t = base + i
            d_t = d_ref[pl.ds(t, 1), :]                       # [1, bd]
            col = jnp.sum(jnp.where(lane == i, bc, 0.0), axis=1,
                          keepdims=True)                      # [2N, 1]
            s = kept(jnp.exp(d_t * a) * s + col[:n] * u_ref[pl.ds(t, 1), :])
            y_ref[pl.ds(t, 1), :] = jnp.sum(s * col[n:], axis=0,
                                            keepdims=True)
            return s

        return lax.fori_loop(0, tile, step, s)

    s1_ref[...] = lax.fori_loop(0, steps // tile, tile_of, s0_ref[...])


def kernel_scan(delta, u, b, c, a, state, *, interpret=False):
    """`scan_reference` through the kernel (same operands and
    results)."""
    bt, steps, d = delta.shape
    n = a.shape[0]
    bd = BLOCK_D if d % BLOCK_D == 0 else d
    tile = LANES if steps % LANES == 0 else steps
    bc = jnp.concatenate([b, c], axis=-1).astype(jnp.float32).swapaxes(
        1, 2)                                                 # [bt, 2N, L]
    rows = pl.BlockSpec((None, steps, bd), lambda r, j: (r, 0, j))
    states = pl.BlockSpec((None, n, bd), lambda r, j: (r, 0, j))
    y, s = pl.pallas_call(
        functools.partial(_kernel, n=n, tile=tile),
        grid=(bt, d // bd),
        in_specs=[rows, rows,
                  pl.BlockSpec((None, 2 * n, steps), lambda r, j: (r, 0, 0)),
                  pl.BlockSpec((n, bd), lambda r, j: (0, j)), states],
        out_specs=[rows, states],
        out_shape=[jax.ShapeDtypeStruct((bt, steps, d), jnp.float32),
                   jax.ShapeDtypeStruct((bt, n, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_scan",
    )(delta.astype(jnp.float32), u.astype(jnp.float32), bc,
      a.astype(jnp.float32), state.astype(jnp.float32))
    return y, s


def selective_scan(delta, u, b, c, a, state):
    """The chunk's scan: the kernel on a TPU (the choice every kernel
    entry point makes, ``jax.default_backend()``), `scan_reference`
    elsewhere."""
    if jax.default_backend() == "tpu":
        return kernel_scan(delta, u, b, c, a, state)
    return scan_reference(delta, u, b, c, a, state)

"""A Mamba-1 mixer in attention's place: the ``"mamba"`` layers of
`models/transformer.py`'s period (`TransformerConfig.period`), as Jamba
serves them. One scanned stack holds them beside the attention layers
(`PeriodBlock`); this module is the mixer and its cache.

For a normed input ``u`` [b, s, width], with inner width ``D``
(`ssm_inner`), ``N`` states a channel (`ssm_state`), a convolution of
``K`` taps (`ssm_conv`) and the step's rank ``R`` (`ssm_dt_rank`):

  * ``[h, z] = u W_in`` (width -> 2 x D, no bias);
  * ``h = silu(causal depthwise conv_K(h) + b_conv)``, the convolution
    reading the stream's last ``K - 1`` inputs before the call;
  * ``[delta, B, C] = h W_x`` (D -> R + 2N), each RMS-normed (Jamba's
    ``dt_layernorm``, ``b_layernorm``, ``c_layernorm``; eps `norm_eps`);
  * ``Delta = softplus(delta W_dt + b_dt)`` [b, s, D], ``A = -exp(A_log)``
    [N, D];
  * ``s_t = exp(Delta_t A) s_{t-1} + Delta_t B_t h_t`` per channel and
    state, ``y_t = s_t . C_t + D_skip h_t``, then ``y * silu(z)`` and
    ``W_out`` (D -> width, no bias).

The state and its update are float32 (`ops/ssm_scan.STATE_DTYPE`), the
convolution window, the matrices and the activations `cfg.dtype`.

The cache is one fixed state a slot, no rows and no blocks
(`TransformerConfig.state_leaves`, carried through the scanned stack
beside the K/V pools, as deep as the stack has mamba layers):
``cached_ssm_state`` [slots, N, D] and ``cached_conv_state`` [slots, (K -
1) x D]. Every call reads a slot's state and writes it back in place.
Which positions of a call are the stream's tokens is ``paging["index"]``
(the first) and ``paging["stop"]`` (past the last): a position at or past
``stop`` is a step with ``Delta`` nought, through which the state goes
unchanged, so a padded chunk's tail, a free slot and a slot whose stream
is still prefilling (each at length 0 in the tick's view) keep their
state. A row that starts at position 0 with tokens to take starts from
zeros: a new stream's first chunk, decided here from the chunk's start,
with nothing written by the host.

A chunk reads its recurrence through `ops/ssm_scan.selective_scan` (the
``ssm_scan`` kernel on a TPU); a tick's one position is XLA's elementwise
step, a few operations over [slots, N, D] that it fuses into passes bound
by the state's bytes, where a kernel would add a launch a layer and
nothing to fuse.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorchdistributed_tpu.ops import ssm_scan
from pytorchdistributed_tpu.parallel.tp import Logical

#: the device-side scalars of one call this module adds to the "counters"
#: collection's vector, summed over the mamba layers: the live streams'
#: states read (a stream that starts at position 0 reads none) and
#: written, and the positions the live streams' scans took
COUNTERS = ("ssm_states_read", "ssm_states_written", "ssm_scan_positions")


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


class MambaMixer(nn.Module):
    """One mamba layer's mixer over the scanned stack's state leaves:
    ``(out [b, s, width], pool)``, the slot's states written back at
    `layer` (its index among the stack's mamba layers)."""

    cfg: "TransformerConfig"  # noqa: F821

    @nn.compact
    def __call__(self, u, paging=None, pool=None, layer=None):
        from pytorchdistributed_tpu.models.transformer import (
            COUNTS,
            _site_dot_general,
        )

        cfg = self.cfg
        if not (cfg.decode and cfg.kv_block_size) or pool is None:
            raise NotImplementedError(
                "a mamba layer is served through the paged engine "
                "(ServingEngine(model, params, block_size=...)); training "
                "and a cacheless forward are not built (the benchmark's "
                "plain reference is one)")
        b, s, _ = u.shape
        di, n, k, r = (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv,
                       cfg.ssm_dt_rank)
        dt, f32 = cfg.dtype, jnp.float32

        def param(name, shape, axes, init=nn.initializers.normal(0.02),
                  dtype=None):
            return self.param(name, nn.with_logical_partitioning(init, axes),
                              shape, dtype or cfg.param_dtype)

        def matmul(x, kernel, eq, out=None, parallel="column"):
            return jnp.einsum(eq, x, kernel.astype(dt),
                              preferred_element_type=out,
                              _dot_general=_site_dot_general(
                                  cfg, parallel, jax.lax.dot_general))

        ones = nn.initializers.ones_init()
        # [h | z] side by side in one [width, 2 D] matrix: a stacked
        # [width, 2, D] kernel is copied out of the scanned stack into
        # another layout before every product (the Llama dialect's fused
        # kernels are served as [2, width, D] planes for the same reason:
        # transformer.py:fused_kernel)
        hz = matmul(u.astype(dt), param("in_kernel", (u.shape[-1], 2 * di),
                                        (Logical.EMBED, Logical.MLP)),
                    "bse,ef->bsf")
        h, z = hz[..., :di], hz[..., di:]

        idx = paging["index"]
        valid = jnp.clip(paging["stop"] - idx, 0, s)            # [b]
        fresh = ((idx == 0) & (valid > 0))[:, None, None]
        live = jnp.arange(s)[None, :] < valid[:, None]          # [b, s]

        # -- the causal convolution over the stream's last k - 1 inputs
        conv_w = param("conv_kernel", (k, di), (None, Logical.MLP))
        conv_b = param("conv_bias", (di,), (Logical.MLP,),
                       nn.initializers.zeros_init(), f32)
        prev = pool["cached_conv_state"][layer].reshape(b, k - 1, di)
        xs = jnp.concatenate(
            [jnp.where(fresh, 0, prev).astype(dt), h], axis=1)
        conv = sum(xs[:, j:j + s].astype(f32) * conv_w[j].astype(f32)
                   for j in range(k)) + conv_b.astype(f32)
        h = nn.silu(conv).astype(dt)
        # the window after the call: the k - 1 inputs before `stop`
        keep = jnp.take_along_axis(
            xs, (valid[:, None] + jnp.arange(k - 1))[..., None], axis=1)

        # -- the step, B and C
        dbc = matmul(h, param("x_kernel", (di, r + 2 * n),
                              (Logical.MLP, None)), "bsd,df->bsf", f32)
        delta, bb, cc = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
        delta, bb, cc = (
            _rms(v, param(f"{name}_norm", (v.shape[-1],), (None,), ones,
                          f32).astype(f32), cfg.norm_eps)
            for name, v in (("dt", delta), ("b", bb), ("c", cc)))
        step = matmul(delta.astype(dt),
                      param("dt_kernel", (r, di), (None, Logical.MLP)),
                      "bsr,rd->bsd", f32)
        dt_bias = param("dt_bias", (di,), (Logical.MLP,),
                        nn.initializers.zeros_init(), f32)
        step = jnp.where(live[..., None], jax.nn.softplus(
            step + dt_bias.astype(f32)), 0.0)                  # [b, s, D]
        a = -jnp.exp(param("A_log", (n, di), (None, Logical.MLP),
                           nn.initializers.zeros_init(),
                           f32).astype(f32))                     # [N, D]
        hf = h.astype(f32)
        state = jnp.where(fresh, 0.0,
                          pool["cached_ssm_state"][layer].astype(f32))
        if s == 1:
            # a tick: one elementwise step over every slot
            state = ssm_scan.kept(
                jnp.exp(step[:, 0, None, :] * a) * state
                + bb[:, 0, :, None] * (step * hf)[:, 0, None, :])
            y = (state * cc[:, 0, :, None]).sum(1)[:, None]
        else:
            y, state = ssm_scan.selective_scan(step, step * hf, bb, cc, a,
                                               state)
        skip = param("D", (di,), (Logical.MLP,), ones, f32)
        y = (y + skip.astype(f32) * hf) * nn.silu(z.astype(f32))
        out = matmul(y.astype(dt), param("out_kernel", (di, u.shape[-1]),
                                         (Logical.MLP, Logical.EMBED)),
                     "bsd,de->bse", parallel="row")

        pool = dict(pool)
        pool["cached_ssm_state"] = pool["cached_ssm_state"].at[layer].set(
            state)
        pool["cached_conv_state"] = pool["cached_conv_state"].at[layer].set(
            keep.reshape(b, -1).astype(pool["cached_conv_state"].dtype))
        taken = valid > 0
        counted = dict(zip(COUNTERS, (
            (taken & (idx > 0)).sum(), taken.sum(), valid.sum())))
        pool[COUNTS] = pool[COUNTS] + jnp.stack([
            jnp.asarray(counted.get(name, 0), jnp.float32)
            for name in cfg.counter_names])
        return out, pool

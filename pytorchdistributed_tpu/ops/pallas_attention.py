"""Flash attention as Pallas TPU kernels (SURVEY.md §5: "blockwise /
Flash-style Pallas attention kernel").

Forward: one fused kernel, grid (batch·heads, q_blocks, k_blocks). The
online-softmax accumulator (m, l, acc) lives in VMEM scratch and is carried
across the sequentially-executed k_blocks grid dimension; HBM traffic is one
read of each Q/K/V block and one write of each O block — the flash
recurrence. The per-row logsumexp (LSE = m + log l) is written out as a
second kernel output; it is the only softmax statistic the backward needs.

Backward: two fused Pallas kernels under `jax.custom_vjp`, the
FlashAttention-2 split:

  * dKV kernel, grid (batch·heads, k_blocks, q_blocks): for its K/V block,
    scans Q/dO blocks accumulating  dV = Pᵀ·dO  and  dK = dSᵀ·Q  in VMEM
    scratch, where  P = exp(S − LSE)  is recomputed from Q·Kᵀ (no S×S
    residual is ever stored) and  dS = P ∘ (dP − Δ)·scale  with
    dP = dO·Vᵀ and the precomputed row statistic Δ = rowsum(dO ∘ O);
  * dQ kernel, grid (batch·heads, q_blocks, k_blocks): same recompute,
    accumulating  dQ = dS·K  across K blocks.

Residuals are (Q, K, V, O, LSE) — O(s·d) memory, gradients numerically
identical to dense attention (tests/test_attention.py).

Causal blocks strictly above the diagonal are skipped in all three kernels
(their contribution is exactly zero). Padded Q/K tails (seq_len not
divisible by the block size) are masked. On non-TPU backends (the CPU test
sim) the kernels run in Pallas interpret mode automatically.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _out_sds(shape, dtype, like):
    """pallas_call out_shape typed after operand ``like``: under a
    check_vma=True shard_map (ring_attention_sharded / ulysses_attention
    compiled on hardware) every kernel output must declare its
    varying-manual-axes set, and the outputs vary exactly like the
    operands they are computed from — the empty set included (operands
    replicated over the whole mesh), which the checker tells apart from
    an undeclared ``None``. Outside a checked trace the set is empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _fwd_kernel(q_ref, k_ref, v_ref, *refs,
                block_q: int, block_k: int, causal: bool, scale: float,
                num_k_blocks: int, seq_len: int, carry: bool = False):
    """Online-softmax forward, one definition for both attention paths.

    ``carry`` is static and selects the ref layout at trace time (no HBM
    zero-read is ever emitted for the carry=False flagship path):
      * False (single-chip flash): refs = (o_ref, lse_ref, acc_s, m_s, l_s)
        — (m, l, acc) init to zeros/-inf in VMEM and the last k-block
        normalizes into (o, lse);
      * True (one ring-attention hop, ops/ring_attention.py): refs =
        (m_in, l_in, acc_in, m_out, l_out, acc_out, acc_s, m_s, l_s) — the
        statistics enter and leave through HBM so they survive across ring
        steps, and normalization happens once after the last hop."""
    if carry:
        (m_in, l_in, acc_in, m_out, l_out, acc_out,
         acc_s, m_s, l_s) = refs
    else:
        o_ref, lse_ref, acc_s, m_s, l_s = refs
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        if carry:
            m_s[...] = m_in[0]
            l_s[...] = l_in[0]
            acc_s[...] = acc_in[0]
        else:
            acc_s[...] = jnp.zeros_like(acc_s)
            m_s[...] = jnp.full_like(m_s, _NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)

    qi = pl.program_id(1)
    q_start = qi * block_q
    k_start = ki * block_k

    run = True
    if causal:
        # skip blocks strictly above the diagonal
        run = q_start + block_q - 1 >= k_start

    @pl.when(run)
    def _compute():
        # Dots stay in the input dtype (bf16 on the training path) with fp32
        # accumulation — upcasting operands first would push the matmul off
        # the MXU's fast path (fp32 matmul is ~4x slower on TPU). The scale
        # is applied to the fp32 logits, not the operands.
        q = q_ref[0]                                      # [bq, d]
        k = k_ref[0]                                      # [bk, d]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        # mask the padded K tail (seq_len not divisible by block_k) and,
        # for causal, positions above the diagonal
        k_pos = k_start + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        valid = k_pos < seq_len
        if causal:
            q_pos = q_start + lax.broadcasted_iota(jnp.int32, logits.shape, 0)
            valid = valid & (q_pos >= k_pos)
        logits = jnp.where(valid, logits, _NEG_INF)
        m_prev = m_s[...]
        blk_max = jnp.max(logits, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, blk_max)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)  # [bq, bk]
        l_s[...] = l_s[...] * corr + jnp.sum(p, -1, keepdims=True)
        m_s[...] = m_new
        # zero the padded V tail: p is 0 there, but 0·garbage(NaN) = NaN
        v = _zero_pad_rows(v_ref[0], k_start, seq_len)     # [bk, d]
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        if carry:
            m_out[0] = m_s[...]
            l_out[0] = l_s[...]
            acc_out[0] = acc_s[...]
        else:
            l = jnp.maximum(l_s[...], 1e-30)
            o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)
            lse_ref[0] = m_s[...] + jnp.log(l)    # [bq, 1]


def _flash_fwd(q, k, v, *, causal: bool, scale: float, block_q: int,
               block_k: int, interpret: bool):
    bh, s, d = q.shape
    # Grouped-query attention, kernel-native: k/v may carry fewer heads
    # (shape [B·H_kv, S, D]); each q-head program reads its group's shared
    # K/V block via the index map — the 4x-materialized jnp.repeat the
    # caller would otherwise need never hits HBM.
    group = bh // k.shape[0]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq, nk = pl.cdiv(s, block_q), pl.cdiv(s, block_k)

    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, num_k_blocks=nk, seq_len=s)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki: (b // group, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki: (b // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            # row statistics ride as [bh, s, 1] with block (1, block_q, 1):
            # the trailing 1 equals the array dim, so the TPU tiling
            # constraint reduces to block_q % 8 == 0 — identical to the Q
            # block's own constraint (a rank-2 [bh, s] slice can't satisfy it)
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            _out_sds((bh, s, d), q.dtype, q),
            _out_sds((bh, s, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            _vmem_scratch((block_q, d)),
            _vmem_scratch((block_q, 1)),
            _vmem_scratch((block_q, 1)),
        ],
        interpret=interpret,
    )(q, k, v)


def _vmem_scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _zero_pad_rows(x, start, seq_len):
    """Zero rows of a [rows, d] block that fall beyond seq_len: padded tail
    blocks load unspecified garbage (NaN in interpret mode), and a matmul
    against even a zeroed operand turns 0·NaN into NaN."""
    pos = start + lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(pos < seq_len, x, jnp.zeros_like(x))


def _recompute_p_ds(q_blk, k_blk, v_blk, do_blk, lse_blk, delta_blk, *,
                    scale, causal, q_start, k_start, seq_len):
    """Shared bwd math: rebuild P = exp(S − LSE) for one (q, k) block pair
    and form dS = P ∘ (dO·Vᵀ − Δ)·scale. Blocks stay in their input dtype
    for the dots (MXU fast path); accumulation is fp32. lse_blk/delta_blk
    are [bq, 1] column statistics. Returns (p, ds), both [bq, bk] fp32,
    zero on masked (padded / acausal) positions."""
    s_blk = jax.lax.dot_general(
        q_blk, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [bq, bk]
    shape = s_blk.shape
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = k_start + lax.broadcasted_iota(jnp.int32, shape, 1)
    valid = (q_pos < seq_len) & (k_pos < seq_len)
    if causal:
        valid = valid & (q_pos >= k_pos)
    p = jnp.where(valid, jnp.exp(s_blk - lse_blk), 0.0)    # lse: [bq, 1]
    dp = jax.lax.dot_general(
        do_blk, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [bq, bk]
    # where, not rely on p==0: on masked rows dp/Δ hold garbage from padded
    # tail blocks, and 0·NaN = NaN
    ds = jnp.where(valid, p * (dp - delta_blk) * scale, 0.0)
    return p, ds


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                    block_q: int, block_k: int, causal: bool, scale: float,
                    num_q_blocks: int, seq_len: int, group: int,
                    carry: bool = False):
    # grid (B·H_kv, k_blocks, group, q_blocks): for one (kv-head, K block)
    # the group's q-heads and their q blocks run CONSECUTIVELY, so the
    # VMEM accumulator legally carries dK/dV across all of them — the
    # grouped-query reduction happens inside the kernel instead of an XLA
    # sum over a 4x-repeated dk tensor.
    #
    # ``carry`` (static, see _fwd_kernel): False → refs = (dk_ref, dv_ref,
    # dk_acc, dv_acc), zero-init specialized at trace time (the flagship
    # path never reads zeros from HBM); True → refs = (dk_in, dv_in,
    # dk_ref, dv_ref, dk_acc, dv_acc), the ring's co-travelling dK/dV
    # accumulators entering/leaving through HBM each hop (group is 1
    # there — the ring path is not GQA-folded).
    if carry:
        dk_in, dv_in, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    gi, qi = pl.program_id(2), pl.program_id(3)

    @pl.when((qi == 0) & (gi == 0))
    def _init():
        if carry:
            dk_acc[...] = dk_in[0]
            dv_acc[...] = dv_in[0]
        else:
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    ki = pl.program_id(1)
    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        # this K block only sees Q rows at or below the diagonal
        run = q_start + block_q - 1 >= k_start

    @pl.when(run)
    def _compute():
        q = _zero_pad_rows(q_ref[0], q_start, seq_len)
        k = _zero_pad_rows(k_ref[0], k_start, seq_len)
        v = _zero_pad_rows(v_ref[0], k_start, seq_len)
        do = _zero_pad_rows(do_ref[0], q_start, seq_len)
        p, ds = _recompute_p_ds(
            q, k, v, do, lse_ref[0], delta_ref[0], scale=scale,
            causal=causal, q_start=q_start, k_start=k_start, seq_len=seq_len)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # pᵀ·dO [bk, d]
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # dsᵀ·q [bk, d]

    @pl.when((qi == num_q_blocks - 1) & (gi == group - 1))
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                   block_q: int, block_k: int, causal: bool, scale: float,
                   num_k_blocks: int, seq_len: int, carry: bool = False):
    # ``carry`` (static, see _fwd_kernel): False → refs = (dq_ref, dq_acc),
    # zero-init at trace time; True → refs = (dq_in, dq_ref, dq_acc), the
    # ring hop's dQ accumulator entering through HBM.
    if carry:
        dq_in, dq_ref, dq_acc = refs
    else:
        dq_ref, dq_acc = refs
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = dq_in[0] if carry else jnp.zeros_like(dq_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = q_start + block_q - 1 >= k_start

    @pl.when(run)
    def _compute():
        q = _zero_pad_rows(q_ref[0], q_start, seq_len)
        k = _zero_pad_rows(k_ref[0], k_start, seq_len)
        v = _zero_pad_rows(v_ref[0], k_start, seq_len)
        do = _zero_pad_rows(do_ref[0], q_start, seq_len)
        _, ds = _recompute_p_ds(
            q, k, v, do, lse_ref[0], delta_ref[0], scale=scale,
            causal=causal, q_start=q_start, k_start=k_start, seq_len=seq_len)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # ds·k [bq, d]

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, *, causal: bool, scale: float,
               block_q: int, block_k: int, interpret: bool):
    bh, s, d = q.shape
    group = bh // k.shape[0]  # grouped-query: see _flash_fwd
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq, nk = pl.cdiv(s, block_q), pl.cdiv(s, block_k)

    # Δ_i = dOᵢ·Oᵢ — tiny elementwise reduce; XLA fuses it into the
    # surrounding graph, no reason to burn a kernel launch on it. Shaped
    # [bh, s, 1] to match the LSE layout (see _flash_fwd out_specs).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))

    # dKV: grid (b·kv_heads, k_blocks, group, q_blocks) — the group and q
    # dims run sequentially innermost so dK/dV accumulate across the whole
    # q-head group (see _bwd_dkv_kernel).
    def qmap(bkv, ki, gi, qi):
        return (bkv * group + gi, qi, 0)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, num_q_blocks=nq, seq_len=s, group=group)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh // group, nk, group, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), qmap),
            pl.BlockSpec((1, block_k, d),
                         lambda bkv, ki, gi, qi: (bkv, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bkv, ki, gi, qi: (bkv, ki, 0)),
            pl.BlockSpec((1, block_q, d), qmap),
            pl.BlockSpec((1, block_q, 1), qmap),
            pl.BlockSpec((1, block_q, 1), qmap),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d),
                         lambda bkv, ki, gi, qi: (bkv, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bkv, ki, gi, qi: (bkv, ki, 0)),
        ],
        out_shape=[
            _out_sds(k.shape, k.dtype, k),
            _out_sds(v.shape, v.dtype, v),
        ],
        scratch_shapes=[
            _vmem_scratch((block_k, d)),
            _vmem_scratch((block_k, d)),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dQ: grid (bh, q_blocks, k_blocks) — k is the sequential inner dim.
    dq_kernel = functools.partial(
        _bwd_dq_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, num_k_blocks=nk, seq_len=s)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, nq, nk),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki: (b // group, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki: (b // group, ki, 0)),
            q_spec,
            row_spec,
            row_spec,
        ],
        out_specs=q_spec,
        out_shape=_out_sds(q.shape, q.dtype, q),
        scratch_shapes=[_vmem_scratch((block_q, d))],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, causal=causal, scale=scale, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    # Named so remat policies can keep the kernel's residuals: without
    # these, `jax.checkpoint` re-runs the forward kernel during backward
    # just to regenerate (out, lse) — a full extra attention pass per layer
    # (models/transformer.py checkpoint_policy saves both names).
    out = jax.ad_checkpoint.checkpoint_name(out, "attn_out")
    lse = jax.ad_checkpoint.checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal=causal, scale=scale,
                      block_q=block_q, block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None, block_q: int = 1024,
                    block_k: int = 1024, interpret: bool | None = None):
    """[B, S, H, D] fused flash attention; drop-in for dense_attention.

    Default block 1024 (measured, v5e, S=1024 D=64 BH=256): 0.75 ms/call
    vs 1.92 at block 512 — fewer, fatter grid programs beat the 25% causal
    block-skip at this scale; VMEM per program stays ~1.5 MB even at
    D=128. For much longer sequences the 1024 grid still tiles and skips
    acausal blocks.

    Grouped-query attention is kernel-native: k/v may carry fewer heads
    than q (num_heads divisible by kv_heads); each q-head program streams
    its group's shared K/V blocks via the index maps, so the repeated K/V
    never materializes in HBM and the grouped dK/dV reduction happens in
    the kernel accumulator."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    if h % hk:
        raise ValueError(f"q heads {h} not divisible by kv heads {hk}")
    scale = (d**-0.5) if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def fold(t):  # [B,S,Hx,D] -> [B*Hx, S, D]
        return t.transpose(0, 2, 1, 3).reshape(-1, s, d)

    out = _flash(fold(q), fold(k), fold(v), causal, scale, block_q, block_k,
                 interpret)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def flash_attention_sharded(q, k, v, *, causal: bool = False,
                            scale: float | None = None, block_q: int = 1024,
                            block_k: int = 1024,
                            interpret: bool | None = None):
    """`flash_attention` for a multi-device jit: XLA cannot partition a
    Mosaic kernel itself ("Mosaic kernels cannot be automatically
    partitioned"), so under an ambient mesh of more than one device the
    call runs inside `jax.shard_map`, each device on its own batch rows
    and heads. The specs come from the logical rules in force — the ones
    the activations' own `with_logical_constraint` reads (parallel/tp.py):
    batch over (data, fsdp), heads over tensor under the tp strategies,
    replicated otherwise. The sequence stays whole per device; a
    seq-sharded mesh belongs to ring/ulysses attention. No collective is
    added — attention is independent per (batch row, head). Without a
    mesh, or on one device, this is the plain call. So it is inside
    another shard_map's manual region (a pipeline stage body): there the
    kernel stays bare, and a TPU compile still refuses it when an axis
    left automatic is larger than 1 (PERF.md, open questions)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    import flax.linen as nn
    from jax.sharding import PartitionSpec as P

    from pytorchdistributed_tpu.parallel.tp import Logical

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    spec = P(*nn.logical_to_mesh_axes(
        (Logical.BATCH, None, Logical.HEADS, None)))
    fn = jax.shard_map(
        functools.partial(flash_attention, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # same rule as ring/ulysses: checked when the kernels compile,
        # off under interpret mode, whose internals trip the checker
        check_vma=not interpret,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Paged decode attention (ISSUE 7): the Pallas twin of
# ops/attention.paged_attention. One decode tick's q ([slots, heads, d])
# attends each slot's block-table-mapped KV blocks streamed STRAIGHT from
# the shared pool — the [slots, blocks*block_size, ...] gathered copy the
# reference path materializes in HBM never exists here. The block table,
# the per-slot lengths and the layer ride as scalar-prefetch operands so
# the KV BlockSpec index maps can chase the table (pool block
# `[layer, tables[slot, j]]` is DMA'd as grid step j), the canonical
# PagedAttention dataflow.
#
# The pool is lane-dense: one token's K (or V) row is all its kv heads
# side by side, `[num_blocks, block_size, kv_heads*head_dim]`, so a block
# is `(block_size, kv_heads*head_dim)` under the (8, 128) tile with no
# padded lanes, the TPU client keeps the array row-major, and the kernel
# and the model's in-place row write agree on its layout: nothing
# pool-sized is ever copied or transposed round the call.


def _head_sums(x, head_dim: int):
    """``x [rows, kv_heads*head_dim]`` -> the same shape, every lane
    holding the sum of ``x`` over its own head's lanes. Mosaic has no
    reshape that splits lanes into heads, so each head is a masked lane
    reduce, spread back over the head by the select that masks it; done
    in static lane slices that hold whole heads and, where the sizes
    allow, whole 128-lane tiles (``head_dim`` 64: two heads a tile), so a
    slice costs nothing and a reduce stays inside a vector register."""
    width = x.shape[-1]
    step = head_dim * 128 // math.gcd(head_dim, 128)   # lcm
    if width % step:
        step = width
    lane = lax.broadcasted_iota(jnp.int32, (1, step), 1)
    masks = [(lane >= lo) & (lane < lo + head_dim)
             for lo in range(0, step, head_dim)]
    parts = []
    for lo in range(0, width, step):
        part = x[:, lo:lo + step]
        sums = jnp.zeros_like(part)
        for mask in masks:
            total = jnp.sum(jnp.where(mask, part, 0.0), axis=-1,
                            keepdims=True)
            sums = jnp.where(mask, total, sums)
        parts.append(sums)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def _paged_kernel(tables_ref, lengths_ref, layer_ref, q_ref, k_ref, v_ref,
                  *rest, block_size: int, num_blocks: int, head_dim: int,
                  scale: float, quantized: bool, sink: int, window: int):
    """Online-softmax over one slot's table blocks; grid
    (slots, blocks_per_slot). One program sees every kv head of its pool
    block: refs are q/o ``[group, kv_heads*d]`` and k/v ``[block_size,
    kv_heads*d]``, head ``h`` in lanes ``[h*d, (h+1)*d)``. The per-head
    reduce of ``k*q`` leaves each head's logit in all of that head's
    lanes (``_head_sums``), after which softmax, ``p*v`` and the
    accumulator are plain elementwise work on whole rows: every lane of
    a head carries that head's logit, max and sum. All float32 on the
    VPU, no MXU: a decode tick is bandwidth-bound. ``quantized`` adds
    two scale refs
    (int8 pool, fp32 ``[block_size, kv_heads]`` per-row scales, spread
    over each head's lanes by a 0/1 matrix product at full precision and
    dequantized in VMEM right before the products); ``window`` > 0
    applies the sink+sliding-window mask and skips fully-dead middle
    blocks — the blocks the serving engine retires to the allocator."""
    if quantized:
        ks_ref, vs_ref, o_ref, acc_s, m_s, l_s = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc_s, m_s, l_s = rest
    slot, ji = pl.program_id(0), pl.program_id(1)
    width = k_ref.shape[-1]

    @pl.when(ji == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    length = lengths_ref[slot]
    # skip blocks wholly past the slot's live window (the current token
    # sits at position `length`, so positions <= length are attendable);
    # dead slots (length 0) still run block 0 — masked rows are exact
    # zeros, the same garbage-tolerance contract as the reference path
    run = ji * block_size <= length
    if window:
        # sliding window: a middle block whose last position already fell
        # out of every live query's window (and past the sinks) is fully
        # masked — and its table entry points at trash once the engine
        # retires it — so skip it outright
        dead = ((ji * block_size >= sink)
                & ((ji + 1) * block_size <= length - window + 1))
        run = run & ~dead

    @pl.when(run)
    def _compute():
        if quantized:
            # the scale of head h goes to lanes [h*d, (h+1)*d): a product
            # with a 0/1 matrix, one nonzero term a lane, exact at full
            # precision
            kv_heads = ks_ref.shape[-1]
            lane = lax.broadcasted_iota(jnp.int32, (kv_heads, width), 1)
            head = lax.broadcasted_iota(jnp.int32, (kv_heads, width), 0)
            spread = ((lane >= head * head_dim)
                      & (lane < (head + 1) * head_dim)).astype(jnp.float32)

        def load(ref, s_ref):
            x = ref[...].astype(jnp.float32)               # [bs, hk*d]
            if quantized:
                # canonical dequant (ops/quant.kv_dequantize spelling):
                # int8 → fp32 × per-row scale → compute dtype
                x = (x * jnp.dot(s_ref[...], spread,
                                 precision=lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
                     ).astype(q_ref.dtype).astype(jnp.float32)
            return x

        k, v = load(k_ref, ks_ref), load(v_ref, vs_ref)
        pos = ji * block_size + lax.broadcasted_iota(
            jnp.int32, (block_size, 1), 0)
        valid = pos <= length
        if window:
            valid &= (pos < sink) | (pos > length - window)
        for g in range(q_ref.shape[0]):  # static: the kv head's q group
            q = q_ref[pl.ds(g, 1), :].astype(jnp.float32)  # [1, hk*d]
            logits = jnp.where(valid, _head_sums(k * q, head_dim) * scale,
                               _NEG_INF)
            m_prev = m_s[pl.ds(g, 1), :]                   # [1, hk*d]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, axis=0, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
            l_s[pl.ds(g, 1), :] = (l_s[pl.ds(g, 1), :] * corr
                                   + jnp.sum(p, axis=0, keepdims=True))
            m_s[pl.ds(g, 1), :] = m_new
            acc_s[pl.ds(g, 1), :] = (
                acc_s[pl.ds(g, 1), :] * corr
                + jnp.sum(p * v, axis=0, keepdims=True))

    @pl.when(ji == num_blocks - 1)
    def _finalize():
        o_ref[...] = (acc_s[...]
                      / jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


def paged_flash_attention(q, k_pool, v_pool, block_tables, lengths, *,
                          layer=None, k_scale=None, v_scale=None,
                          sink_tokens: int = 0, window_tokens: int = 0,
                          scale: float | None = None,
                          interpret: bool | None = None):
    """One decode tick of paged attention, pool-native — the serving
    engine's default decode hot path on TPU (gather elsewhere; see
    ``ServingEngine(paged_attn=...)``).

    Args:
      q: ``[slots, heads, head_dim]`` — each slot's single current-token
        query (its K/V already written into the pool, the decode
        contract).
      k_pool / v_pool: ``[num_blocks, block_size, kv_heads*head_dim]``,
        the model dtype or int8 (compressed pool); or the layer-stacked
        ``[num_layers, num_blocks, block_size, kv_heads*head_dim]`` pool
        of a scanned stack, with ``layer`` the (traced) int32 layer to
        read — the index map starts with it, so no layer's pool is ever
        sliced out of the stack.
      block_tables: ``[slots, blocks_per_slot]`` int32 physical block ids
        (entries past a slot's live length — and retired window blocks —
        point at the trash block 0).
      lengths: ``[slots]`` int32 — the query attends positions <= length.
      k_scale / v_scale: ``[num_blocks, block_size, kv_heads]`` fp32
        per-(token, head) dequant scales (layer-stacked like the pool);
        required iff the pool is int8.
      sink_tokens / window_tokens: static sink+sliding-window mask
        (window_tokens 0 = full attention): position j is attendable iff
        ``j < sink_tokens or j > length - window_tokens``; fully-dead
        middle blocks are skipped — they are the blocks the engine
        retires back to the allocator mid-stream.

    Returns ``[slots, heads, head_dim]``. Matches
    ops.attention.paged_attention to fp32 online-softmax tolerance (the
    reassociated flash recurrence is not bitwise — the bitwise-parity
    contract vs generate() holds on the reference gather path; this
    kernel never materializes the [slots, blocks*block_size, ...]
    gathered copy). Grouped-query native: each (slot, block) program
    streams the shared KV block once for the whole q group."""
    slots, h, d = q.shape
    if layer is None:      # one layer's own pool: a stack of one
        layer = 0
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scale is not None and v_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    _, nb, bs, width = k_pool.shape
    hk = width // d
    if width % d or h % hk:
        raise ValueError(
            f"pool rows of {width} lanes do not hold whole heads of {d} "
            f"that divide the {h} q heads")
    quantized = k_pool.dtype == jnp.int8
    if quantized != (k_scale is not None and v_scale is not None):
        raise ValueError(
            "k_scale/v_scale must be provided iff the pool is int8 "
            f"(pool {k_pool.dtype}, k_scale "
            f"{'set' if k_scale is not None else 'None'})")
    if quantized and (k_scale.shape[1:] != (nb, bs, hk)
                      or v_scale.shape[1:] != (nb, bs, hk)):
        raise ValueError(
            f"scale planes must be [num_blocks, block_size, kv_heads] = "
            f"{(nb, bs, hk)}; got {k_scale.shape[1:]} / "
            f"{v_scale.shape[1:]}")
    if window_tokens < 0 or sink_tokens < 0 or (
            window_tokens and (window_tokens % bs or sink_tokens % bs)):
        raise ValueError(
            f"sink_tokens {sink_tokens} / window_tokens {window_tokens} "
            f"must be non-negative multiples of block_size {bs}")
    group = h // hk
    mb = block_tables.shape[1]
    scale = (d**-0.5) if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    from jax.experimental.pallas import tpu as pltpu

    # kv head g owns q rows g·group+; group-major so row g of a slot's
    # block holds, head by head, the g-th query of every kv head — laid
    # out like a pool row
    qf = q.reshape(slots, hk, group, d).swapaxes(1, 2).reshape(
        slots, group, width)
    # every block's two minor dims equal the array's own, which the TPU
    # lowering takes at any width (leading dims are squeezed, not
    # blocked at 1)
    q_spec = pl.BlockSpec((None, group, width),
                          lambda s, j, tbl, ln, ly: (s, 0, 0))
    kv_spec = pl.BlockSpec(
        (None, None, bs, width),
        lambda s, j, tbl, ln, ly: (ly[0], tbl[s, j], 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qf, k_pool, v_pool]
    if quantized:
        scale_spec = pl.BlockSpec(
            (None, None, bs, hk),
            lambda s, j, tbl, ln, ly: (ly[0], tbl[s, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots, mb),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[_vmem_scratch((group, width))] * 3,
    )
    kernel = functools.partial(
        _paged_kernel, block_size=bs, num_blocks=mb, head_dim=d,
        scale=scale, quantized=quantized, sink=int(sink_tokens),
        window=int(window_tokens))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    return out.reshape(slots, group, hk, d).swapaxes(1, 2).reshape(
        slots, h, d)

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

from pytorchdistributed_tpu.ops.attention import paged_gather

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
# A prefill chunk's attention over rows at stated positions: the flash
# recurrence of `_fwd_kernel`, its mask from positions instead of indices.
# A serving chunk's queries stand at `pos .. pos + s - 1` of one stream and
# its keys are the stream's rows gathered from the pool in position order
# (`models/periodic.py:_gathered_read`); XLA's read of them builds float32
# scores of every query head over every row, several passes each: at 128
# heads and 33,792 rows XLA's cost analysis of the v5e compile counts ~50
# GB of them a chunk, against ~5 GB at 2,048 rows. Here a kv head's group
# of query heads rides as rows of one program (``group x s`` rows over the
# head's keys), so a block of keys is fetched once for all of them, and no
# score leaves VMEM.


def _chunk_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref,
                  acc_s, m_s, l_s, *, scale: float, window: int,
                  num_k_blocks: int):
    """One (kv head, block of query rows) over one block of keys: a key
    at ``kp`` is live for a query at ``qp`` iff ``kp >= 0`` and ``0 <= qp
    - kp`` (and ``< window`` where there is one)."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    logits = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # [bq, bk]
    kp = kpos_ref[0]                                      # [1, bk]
    dist = qpos_ref[0] - kp                               # [bq, bk]
    live = (kp >= 0) & (dist >= 0)
    if window:
        live = live & (dist < window)
    logits = jnp.where(live, logits, _NEG_INF)
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(live, jnp.exp(logits - m_new), 0.0)
    l_s[...] = l_s[...] * corr + jnp.sum(p, -1, keepdims=True)
    m_s[...] = m_new
    v = v_ref[0]
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        o_ref[0] = acc_s[...] / jnp.maximum(l_s[...], 1e-30)


#: keys a block of `chunk_attention` walks (rows of a gather made whole
#: blocks of it need no padding)
CHUNK_BLOCK_K = 512


def chunk_attention(q, k, v, qpos, kpos, *, window: int = 0,
                    scale: float | None = None, block_q: int = 512,
                    block_k: int = CHUNK_BLOCK_K,
                    interpret: bool | None = None):
    """Queries `q` [b, s, heads, d] at `qpos` [b, s] over the rows `k`,
    `v` [b, m, kv_heads * d] (lane-dense, as a pool holds them) at `kpos`
    [b, m] (a negative position is no row): a key is attended iff its
    position is at most the query's (and within `window` of it where
    `window` is not 0). Returns [b, s, heads, d] float32; a query with no
    live key reads zeros.

    The same softmax as XLA's masked read in `models/periodic.py`, in the
    flash recurrence: grid (batch x kv heads, blocks of `block_q` of the
    ``group x s`` query rows of a kv head, blocks of `block_k` keys), the
    running maximum, sum and output in VMEM across the key blocks, the
    probabilities cast to the values' type for their product as there.
    A block of keys is a kv head's `d` lanes of `block_k` rows where
    they lie, so the rows are neither transposed nor copied; they are
    padded to whole blocks (at position -1) only where they are not
    whole. Every block of keys is computed: a chunk's rows are chosen to
    cover its queries, so few are dead."""
    b, s, h, d = q.shape
    m, width = k.shape[1], k.shape[2]
    hk = width // d
    if width % d or h % hk:
        raise ValueError(f"rows of {width} lanes do not hold whole heads "
                         f"of {d} that divide the {h} q heads")
    group = h // hk
    rows = group * s
    block_q = min(block_q, rows)
    if rows % block_q:
        raise ValueError(f"{rows} query rows a kv head are not whole "
                         f"blocks of {block_q}")
    scale = (d**-0.5) if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kpos = kpos.astype(jnp.int32)
    pad = -m % block_k
    if pad:
        k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (k, v))
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=-1)
    nk = (m + pad) // block_k
    # kv head j's rows: its group's heads one after another, each the s
    # queries in order, so row r stands at qpos[r mod s]
    qf = q.reshape(b, s, hk, group, d).transpose(0, 2, 3, 1, 4).reshape(
        b * hk, rows, d)
    qpos = jnp.tile(qpos.astype(jnp.int32), (1, group))[..., None]

    kernel = functools.partial(_chunk_kernel, scale=scale,
                               window=int(window), num_k_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b * hk, rows // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda i, qi, ki: (i // hk, ki, i % hk)),
            pl.BlockSpec((1, block_k, d),
                         lambda i, qi, ki: (i // hk, ki, i % hk)),
            # the positions ride as [b, rows, 1] and [b, 1, m]: a block's
            # unit dim equals the array's, as the row statistics' do above
            pl.BlockSpec((1, block_q, 1),
                         lambda i, qi, ki: (i // hk, qi, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda i, qi, ki: (i // hk, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, qi, ki: (i, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hk, rows, d), jnp.float32),
        scratch_shapes=[
            _vmem_scratch((block_q, d)),
            _vmem_scratch((block_q, 1)),
            _vmem_scratch((block_q, 1)),
        ],
        interpret=interpret,
    )(qf, k, v, qpos, kpos[:, None, :])
    return out.reshape(b, hk, group, s, d).transpose(0, 3, 1, 2, 4).reshape(
        b, s, h, d)


# ---------------------------------------------------------------------------
# Paged decode attention (ISSUE 7; this dataflow ISSUE 33): the Pallas
# twin of ops/attention.paged_attention. One decode tick's q ([slots,
# heads, d]) attends each slot's block-table-mapped KV blocks streamed
# STRAIGHT from the shared pool — the [slots, blocks*block_size, ...]
# gathered copy the reference path materializes in HBM never exists here.
#
# PagedAttention dataflow. The grid is one program a slot, run in order.
# The pools stay in HBM (`pl.ANY` operands); the block table, the per-slot
# lengths and the layer ride as scalar-prefetch operands, and the program
# walks its slot's table a TILE at a time: a tile is several consecutive
# table entries (`_tile_blocks`: 128 positions where the shapes allow,
# 8 entries at a block of 16), each live entry's pool block `[layer,
# tables[slot, j]]` copied into VMEM scratch by the kernel's own
# asynchronous copies, the next live tile's copies (the next slot's first
# tile after a slot's last) in flight while this one is computed. Inside
# a tile the live blocks are computed one at a time. A block past the
# slot's length, or before the slot's first attendable position (`starts`,
# a per-slot operand beside the lengths since ISSUE 35: a sliding window,
# a tumbling one or none), starts no copy and costs nothing, and so does
# a tile that holds only such blocks, and a slot that attends nothing:
# the cost of a tick follows its live tokens, where a grid of (slots,
# table entries) paid a program for every entry, live or not (49,152 a
# tick of gpt2-medium at 32 slots, ~0.108 us each: PERF.md section 6,
# PR 33). A model that keeps two pools of such rows under one softmax
# (models/eva.py) calls the kernel once a pool, asks each call for its
# heads' log-sum-exp and merges the two (`merge_attention_parts`).
# Mosaic copies whole 128-lane tiles only, so a compiled call needs
# `kv_heads*head_dim` to be a multiple of 128 (the serving engine picks
# the gather path elsewhere), and an int8 pool's scale rows, `kv_heads`
# lanes wide, are gathered by XLA before the call and ride in a slot at
# a time.
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
    reduce, spread back over the head by a select under its mask; done
    in static lane slices that hold whole heads and, where the sizes
    allow, whole 128-lane tiles (``head_dim`` 64: two heads a tile), so a
    slice costs nothing and a reduce stays inside a vector register.
    Where a slice is one head (``head_dim`` a multiple of 128: EvaByte's
    heads are a tile each) it is one lane reduce and a broadcast, with
    no mask and no select. Half of the kernel's time on a v5e at two
    heads a tile (PERF.md section 6, PR 33)."""
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
        if len(masks) == 1:
            sums = jnp.sum(part, axis=-1, keepdims=True)
        else:
            sums = None
            for mask in masks:
                total = jnp.sum(jnp.where(mask, part, 0.0), axis=-1,
                                keepdims=True)
                sums = (total if sums is None
                        else jnp.where(mask, total, sums))
        parts.append(jnp.broadcast_to(sums, part.shape))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


# What a tile may hold: positions, and bytes of VMEM for the two tiles in
# flight (every leaf of the pool together).
_TILE_ROWS = 128
_TILE_VMEM_BYTES = 4 << 20


def _tile_blocks(block_size: int, row_bytes: int, table_len: int) -> int:
    """Table entries a tile holds, from the shapes the call sees:
    ``_TILE_ROWS`` positions (8 entries at a block of 16), fewer where a
    position's row over all pool leaves (``row_bytes``) is so wide that
    two such tiles would pass ``_TILE_VMEM_BYTES``, never more than the
    table has and never under one."""
    rows = min(_TILE_ROWS, _TILE_VMEM_BYTES // (2 * row_bytes))
    return max(1, min(rows // block_size, table_len))


def _paged_kernel(tables_ref, lengths_ref, starts_ref, layer_ref, q_ref,
                  *rest, block_size: int, tile_blocks: int, table_len: int,
                  head_dim: int, scale: float, quantized: bool, sink: int,
                  from_zero: bool, with_lse: bool):
    """Online softmax over one slot's live table blocks; grid (slots,),
    run in order. ``rest`` is the k and v pools in HBM, for an int8 pool
    the slot's fp32 scale rows ``[table positions, kv_heads]`` of each,
    the output and, where the caller asked for it, each head's
    log-sum-exp, one VMEM buffer a pool ``[2, tile_blocks, block_size,
    lanes]`` (two tiles: one computed, one in flight), a DMA semaphore a
    buffer half, the (acc, m, l) accumulators and the half the next tile
    lands in (SMEM: it outlives the program, because a slot's last tile
    starts the next slot's first).

    A slot attends the positions ``start <= j <= length`` and, where
    ``sink`` is set, ``j < sink`` beside them: one rule for a sliding
    window (``start = length - window + 1``), a tumbling one (``window *
    (length // window)``) and none (``0``). The blocks between the sinks
    and ``start`` — whose table entries point at trash once the serving
    engine hands them back to the allocator — are neither copied nor
    computed. A slot with no position to attend (``start > length``; with
    sinks, ``length < 0``) starts no copy and computes nothing: its
    output is zeros and its log-sum-exp ``-inf``, and the slot before it
    starts the first copies of the next slot that has any. ``from_zero``
    (static) says that every ``start`` is 0, as in a pool that keeps
    every position: the starts are then not read, and a table entry and
    a block cost what they cost before there was a ``start``.

    One block is computed at a time, every kv head of it at once: q/o are
    ``[group, kv_heads*d]`` and a k/v block ``[block_size, kv_heads*d]``,
    head ``h`` in lanes ``[h*d, (h+1)*d)``. The per-head reduce of
    ``k*q`` leaves each head's logit in all of that head's lanes
    (``_head_sums``), after which softmax, ``p*v`` and the accumulator
    are plain elementwise work: every lane of a head carries that head's
    logit, max and sum. The accumulators are ``[8, kv_heads*d]`` a query
    row, one online softmax a sublane: row ``r`` of a block goes to
    stream ``r % 8``, so no block reduces over its rows, and the eight
    partial softmaxes are merged once, when the slot is done, the way two
    blocks' are. All float32 on the VPU, no MXU. An int8 block is
    dequantized in VMEM right before the products (its scales spread over
    each head's lanes by a 0/1 matrix product at full precision)."""
    from jax.experimental.pallas import tpu as pltpu

    k_hbm, v_hbm, *rest = rest
    ks_ref = vs_ref = lse_ref = None
    if quantized:
        ks_ref, vs_ref, *rest = rest
    o_ref, *rest = rest
    if with_lse:
        lse_ref, *rest = rest
    k_buf, v_buf, sems, acc_s, m_s, l_s, half_ref = rest
    slot, slots = pl.program_id(0), pl.num_programs(0)
    top = table_len * block_size - 1     # the table's last position
    streams = acc_s.shape[1]
    layer = layer_ref[0]
    width = q_ref.shape[-1]

    # The walk reckons in blocks: a slot's live blocks run from the one
    # its `start` lies in (`sb`) to the one its current token sits in
    # (`rb`), with the blocks that hold sinks beside them; a few scalar
    # operations a table entry, which is what a program of few live
    # blocks is made of.
    sink_blocks = -(-sink // block_size)

    def span(s):
        """Slot ``s``'s last and first attended block past the sinks."""
        return (jnp.minimum(lengths_ref[s], top) // block_size,
                0 if from_zero else starts_ref[s] // block_size)

    def live_from(t, sb):
        """Tile ``t``, or the tile block ``sb`` lies in when ``t`` holds
        only blocks past the sinks and before it (they are one run)."""
        if from_zero:
            return t
        return jnp.where((t * tile_blocks >= sink_blocks)
                         & ((t + 1) * tile_blocks <= sb),
                         sb // tile_blocks, t)

    def from_start(at, begin, sinks):
        """``at`` (a block or a position) is attendable from below: at or
        past ``begin``, or among the ``sinks``."""
        return (at >= begin) | (at < sinks) if sink else at >= begin

    def block_live(j, rb, sb):
        # the current token sits at position `length`, so positions
        # <= length are attendable: a dead slot of one pool (length 0,
        # start 0) still reads its first block, as the reference path
        # does
        live = j <= rb
        return live if from_zero else live & from_start(j, sb, sink_blocks)

    def attends(s):
        """Slot ``s`` has a position to attend."""
        return lengths_ref[s] >= (0 if sink or from_zero else starts_ref[s])

    def next_live(s):
        """The first slot from ``s`` on that attends anything; ``slots``
        where there is none."""
        return lax.while_loop(
            lambda s: (s < slots) & ~attends(jnp.minimum(s, slots - 1)),
            lambda s: s + 1, s)

    def tile_copies(s, rb, sb, t, half, op):
        """``op`` ("start" or "wait") the copies of slot ``s``'s tile
        ``t`` into buffer half ``half``: a K and a V block for every
        live table entry (``rb``, ``sb``: the slot's `span`)."""
        for i in range(tile_blocks):
            j = t * tile_blocks + i

            @pl.when(block_live(j, rb, sb))
            def _():
                blk = tables_ref[s, j]
                for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    getattr(pltpu.make_async_copy(
                        pool.at[layer, blk], buf.at[half, i],
                        sems.at[half]), op)()

    def start_first_tile(s, half):
        """The first live tile of the first slot from ``s`` on that
        attends anything, into buffer half ``half``."""
        s = next_live(s)

        @pl.when(s < slots)
        def _():
            ahead_rb, ahead_sb = span(s)
            tile_copies(s, ahead_rb, ahead_sb, live_from(0, ahead_sb),
                        half, "start")

    length = lengths_ref[slot]
    rb, sb = span(slot)
    last = rb // tile_blocks

    @pl.when(slot == 0)
    def _first():
        half_ref[0] = 0
        start_first_tile(slot, 0)

    acc_s[...] = jnp.zeros_like(acc_s)
    m_s[...] = jnp.full_like(m_s, _NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)

    if quantized:
        # the scale of head h goes to lanes [h*d, (h+1)*d): a product
        # with a 0/1 matrix, one nonzero term a lane, exact at full
        # precision
        kv_heads = ks_ref.shape[-1]
        lane = lax.broadcasted_iota(jnp.int32, (kv_heads, width), 1)
        head = lax.broadcasted_iota(jnp.int32, (kv_heads, width), 0)
        spread = ((lane >= head * head_dim)
                  & (lane < (head + 1) * head_dim)).astype(jnp.float32)

    # static: the kv head's q group, a row [1, hk*d] a member
    queries = [q_ref[pl.ds(g, 1), :].astype(jnp.float32)
               for g in range(q_ref.shape[0])]

    def load(buf, s_ref, half, i, j):
        x = buf[half, i].astype(jnp.float32)               # [bs, hk*d]
        if quantized:
            # canonical dequant (ops/quant.kv_dequantize spelling):
            # int8 → fp32 × per-row scale → compute dtype
            rows = pl.ds(pl.multiple_of(j * block_size, block_size),
                         block_size)
            x = (x * jnp.dot(s_ref[rows, :], spread,
                             precision=lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
                 ).astype(q_ref.dtype).astype(jnp.float32)
        return x

    def attend_block(half, i, j):
        k = load(k_buf, ks_ref, half, i, j)
        v = load(v_buf, vs_ref, half, i, j)
        groups = [slice(r, r + streams)
                  for r in range(0, block_size, streams)]
        valid = []
        for rows in groups:
            pos = j * block_size + rows.start + lax.broadcasted_iota(
                jnp.int32, (streams, 1), 0)
            ok = pos <= length
            valid.append(ok if from_zero else ok & from_start(
                pos, starts_ref[slot], sink))
        for g, q in enumerate(queries):
            # a masked logit is -inf under a running max that starts
            # finite, so its probability is an exact 0 with no select
            logits = [
                jnp.where(ok, _head_sums(k[rows] * q, head_dim) * scale,
                          -jnp.inf)
                for rows, ok in zip(groups, valid)]
            m_prev = m_s[g]                                # [streams, hk*d]
            m_new = functools.reduce(jnp.maximum, logits, m_prev)
            corr = jnp.exp(m_prev - m_new)
            l, acc = l_s[g] * corr, acc_s[g] * corr
            for rows, x in zip(groups, logits):
                p = jnp.exp(x - m_new)
                l += p
                acc += p * v[rows]
            m_s[g], l_s[g], acc_s[g] = m_new, l, acc

    def tile_step(t):
        half = half_ref[0]
        nxt = live_from(t + 1, sb)

        @pl.when(nxt <= last)
        def _next_tile():
            tile_copies(slot, rb, sb, nxt, 1 - half, "start")

        @pl.when(nxt > last)
        def _next_slot():
            start_first_tile(slot + 1, 1 - half)

        tile_copies(slot, rb, sb, t, half, "wait")
        first = t * tile_blocks

        def block_step(i, carry):
            if sink and not from_zero:
                @pl.when(block_live(first + i, rb, sb))
                def _():
                    attend_block(half, i, first + i)
            else:
                attend_block(half, i, first + i)
            return carry

        # blocks of this tile from the one `start` lies in (past the
        # sinks, a block at a time) up to the one the current token sits
        # in
        lax.fori_loop(0 if sink or from_zero
                      else jnp.maximum(sb - first, 0),
                      jnp.minimum(tile_blocks, rb - first + 1),
                      block_step, 0)
        half_ref[0] = 1 - half
        return nxt

    # a slot that attends nothing walks no tile: nothing was started for
    # it, so nothing may be waited for
    lax.while_loop(lambda t: t <= last, tile_step,
                   jnp.where(attends(slot), live_from(0, sb), last + 1))
    for g in range(len(queries)):
        # the streams' partial softmaxes, merged as two blocks' are
        m = m_s[g]
        top_m = jnp.max(m, axis=0, keepdims=True)
        w = jnp.exp(m - top_m)
        total = jnp.sum(l_s[g] * w, axis=0, keepdims=True)
        o_ref[pl.ds(g, 1), :] = (
            jnp.sum(acc_s[g] * w, axis=0, keepdims=True)
            / jnp.maximum(total, 1e-30)).astype(o_ref.dtype)
        if with_lse:
            lse_ref[pl.ds(g, 1), :] = jnp.where(
                total > 0, top_m + jnp.log(jnp.maximum(total, 1e-30)),
                -jnp.inf)


def merge_attention_parts(parts):
    """One softmax over the rows of several calls: ``parts`` is a list of
    (normalised output ``[..., heads, d]``, log-sum-exp ``[..., heads]``),
    each the attention of the same queries over its own rows; returns
    the attention over all of them, in float32: the outputs weighted by
    ``exp(lse - max)`` over the sum of the weights. A part that attended
    nothing (``lse = -inf``) weighs nothing; where no part attended
    anything the result is zeros."""
    lse = jnp.stack([l for _, l in parts])
    top = jnp.max(lse, axis=0)
    w = jnp.exp(lse - jnp.where(jnp.isfinite(top), top, 0.0))
    out = sum(o.astype(jnp.float32) * wi[..., None]
              for (o, _), wi in zip(parts, w))
    return out / jnp.maximum(jnp.sum(w, axis=0), 1e-30)[..., None]


def paged_flash_attention(q, k_pool, v_pool, block_tables, lengths, *,
                          starts=None, layer=None, k_scale=None,
                          v_scale=None, sink_tokens: int = 0,
                          window_tokens: int = 0,
                          return_lse: bool = False,
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
        read — every copy's source starts with it, so no layer's pool is
        ever sliced out of the stack.
      block_tables: ``[slots, blocks_per_slot]`` int32 physical block ids
        (entries past a slot's live length — and retired window blocks —
        point at the trash block 0).
      lengths: ``[slots]`` int32 — the query attends positions <= length.
      starts: ``[slots]`` int32, or None for 0 — the first position the
        query attends: position j is attendable iff ``start <= j <=
        length`` (or ``j < sink_tokens``). Blocks wholly before it are
        neither copied nor computed. A slot with ``start > length`` (with
        sinks: ``length < 0``) has nothing to attend: it costs no copy
        and no arithmetic, its output is zeros and its log-sum-exp
        ``-inf``. So a pool whose rows a query sees ``n`` of is read with
        ``lengths = n - 1``, and a tumbling window of ``W`` with
        ``starts = W * (lengths // W)``.
      k_scale / v_scale: ``[num_blocks, block_size, kv_heads]`` fp32
        per-(token, head) dequant scales (layer-stacked like the pool);
        required iff the pool is int8.
      sink_tokens / window_tokens: static sink+sliding-window mask
        (window_tokens 0 = full attention): position j is attendable iff
        ``j < sink_tokens or j > length - window_tokens``, which is
        ``starts = max(0, lengths - window_tokens + 1)`` beside the
        sinks, and is handed to the kernel so (``starts`` must then be
        None); fully-dead middle blocks are neither copied nor computed
        — they are the blocks the engine retires back to the allocator
        mid-stream.
      return_lse: also return each head's log-sum-exp over the rows it
        attended, ``[slots, heads]`` float32 (``m + log l`` of the
        softmax's accumulators): what `merge_attention_parts` needs to
        make one softmax of several calls over several pools. Not
        written unless asked for.

    Returns ``[slots, heads, head_dim]``, or with ``return_lse`` the pair
    (that, the log-sum-exp). Matches ops.attention.paged_attention to
    fp32 online-softmax tolerance (the reassociated flash recurrence is
    not bitwise — the bitwise-parity contract vs generate() holds on the
    reference gather path; this kernel never materializes the [slots,
    blocks*block_size, ...] gathered copy). One program a slot walks the
    slot's live table entries a tile at a time (``_tile_blocks``
    entries, chosen from the shapes; the table is padded with the trash
    block to whole tiles), fetching each live block from the pool in HBM
    by its own asynchronous copy while the tile before is computed: a
    block, a tile or a slot with nothing live costs no copy and no
    arithmetic. Grouped-query native: a block is fetched once for the
    whole q group."""
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
    if starts is not None and window_tokens:
        raise ValueError(
            "starts and window_tokens both say where a slot's live rows "
            "begin: pass one")
    lengths = lengths.astype(jnp.int32)
    # a pool read from its first row: the kernel is told so statically
    # and leaves the starts unread
    from_zero = starts is None and not window_tokens
    if from_zero:
        starts = jnp.zeros_like(lengths)
    elif starts is None:
        starts = jnp.maximum(lengths - window_tokens + 1, 0)
    group = h // hk
    scale = (d**-0.5) if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    from jax.experimental.pallas import tpu as pltpu

    table_len = block_tables.shape[1]
    tile_blocks = _tile_blocks(bs, 2 * width * k_pool.dtype.itemsize,
                               table_len)
    # whole tiles: the entries added lie past the table's last position,
    # so they are never live
    block_tables = jnp.pad(block_tables.astype(jnp.int32),
                           ((0, 0), (0, -table_len % tile_blocks)))
    # kv head g owns q rows g·group+; group-major so row g of a slot's
    # block holds, head by head, the g-th query of every kv head — laid
    # out like a pool row
    qf = q.reshape(slots, hk, group, d).swapaxes(1, 2).reshape(
        slots, group, width)
    # the block's two minor dims equal the array's own, which the TPU
    # lowering takes at any width (the leading dim is squeezed, not
    # blocked at 1)
    q_spec = pl.BlockSpec((None, group, width),
                          lambda s, tbl, ln, st, ly: (s, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, hbm, hbm]
    operands = [qf, k_pool, v_pool]
    if quantized:
        # Mosaic copies whole 128-lane tiles, and a scale row is kv_heads
        # lanes: the scale rows of every table entry are gathered here
        # (a 1/head_dim of the pool's bytes a position, the pool's own
        # rows stay where they are) and ride in a slot at a time
        rows = block_tables.shape[1] * bs
        in_specs += [pl.BlockSpec((None, rows, hk),
                                  lambda s, tbl, ln, st, ly: (s, 0, 0))] * 2
        operands += [
            paged_gather(x, block_tables, layer).astype(jnp.float32)
            for x in (k_scale, v_scale)]
    # a head's log-sum-exp rides out as the output does, in every lane of
    # the head
    out_shape = [jax.ShapeDtypeStruct(qf.shape, q.dtype)]
    if return_lse:
        out_shape.append(jax.ShapeDtypeStruct(qf.shape, jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(slots,),
        in_specs=in_specs,
        out_specs=[q_spec] * len(out_shape),
        scratch_shapes=[
            *[pltpu.VMEM((2, tile_blocks, bs, width), k_pool.dtype)] * 2,
            pltpu.SemaphoreType.DMA((2,)),
            *[_vmem_scratch((group, math.gcd(bs, 8), width))] * 3,
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, block_size=bs, tile_blocks=tile_blocks,
        table_len=table_len, head_dim=d, scale=scale, quantized=quantized,
        sink=int(sink_tokens), from_zero=from_zero, with_lse=return_lse)
    out, *lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # in order: a slot's last tile starts the next slot's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, lengths, starts.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)

    def heads(x):   # [slots, group, hk*d] -> [slots, heads, d]
        return x.reshape(slots, group, hk, d).swapaxes(1, 2).reshape(
            slots, h, d)

    if return_lse:
        return heads(out), heads(lse[0])[..., 0]
    return heads(out)

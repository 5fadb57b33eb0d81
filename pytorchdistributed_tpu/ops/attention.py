"""Dense (reference) attention — the baseline every parallel variant is
tested against.

The reference repo contains no attention model at all (its LLaMA cell,
03_model_parallel.ipynb:86, never ran — SURVEY.md §5 "Long-context"), so this
is the framework's own reference implementation: numerically-stable softmax
attention on [batch, seq, heads, head_dim] tensors, fp32 accumulation (MXU
inputs stay bf16, sums run fp32 — parallel/precision.py policy).

Sharded variants (ring, Ulysses, Pallas flash) must match this function to
tolerance; see tests/test_attention.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_mask(q_len: int, kv_len: int, *, q_offset: int = 0,
                kv_offset: int = 0, dtype=jnp.float32) -> jax.Array:
    """[q_len, kv_len] additive mask; offsets position the blocks within the
    global sequence (used by blockwise/ring variants)."""
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    kv_pos = kv_offset + jnp.arange(kv_len)[None, :]
    return jnp.where(q_pos >= kv_pos, 0.0, -jnp.inf).astype(dtype)


def paged_gather(pool, block_tables, layer=None):
    """Gather block-table paged K or V back into position order.

    ``pool`` is the engine's shared block pool ``[num_blocks, block_size,
    kv_heads*head_dim]`` (one lane-dense row a token; a scale plane is
    ``[num_blocks, block_size, kv_heads]``), or the layer-stacked
    ``[num_layers, num_blocks, ...]`` pool of a scanned stack with
    ``layer`` the (traced) layer to read — one gather either way, never
    a slice of a whole layer's pool. ``block_tables`` maps each slot's
    logical block j (positions [j*bs, (j+1)*bs)) to a physical pool
    block: ``[slots, blocks_per_slot]`` int32. Returns ``[slots,
    blocks_per_slot*block_size, kv_heads*head_dim]`` — split into heads,
    the exact tensor the dense per-slot cache would hold over that
    window, so downstream masked attention is bitwise-identical to the
    dense path. Table entries past a slot's live length point at the
    reserved trash block (0); their rows are finite garbage the position
    mask zeroes exactly.
    """
    g = (pool[block_tables] if layer is None
         else pool[layer, block_tables])       # [slots, nb, bs, width]
    slots, nb, bs = g.shape[:3]
    return g.reshape(slots, nb * bs, *g.shape[3:])


def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    k_scale=None, v_scale=None, sink_tokens: int = 0,
                    window_tokens: int = 0,
                    scale: float | None = None) -> jax.Array:
    """Reference paged decode attention — the math twin of the serving
    tick's in-model path (models/transformer.py paged branch), exposed so
    the parity tests and the Pallas kernel have a standalone oracle.

    Args:
      q: ``[slots, q_len, heads, head_dim]`` current-chunk queries (q_len
        is 1 for a decode tick, >1 for a chunked-prefill step).
      k_pool / v_pool: ``[num_blocks, block_size, kv_heads*head_dim]``,
        model dtype or int8 (the compressed pool — pass the scales).
      block_tables: ``[slots, blocks_per_slot]`` int32.
      lengths: ``[slots]`` int32 — tokens already cached per slot; query
        token i of a slot sits at absolute position lengths + i and
        attends cache positions <= it. The CURRENT chunk's K/V must
        already be written into the pool (the model writes before it
        attends), exactly like the dense decode contract.
      k_scale / v_scale: ``[num_blocks, block_size, kv_heads]`` fp32
        per-(token, head) dequant scales for an int8 pool (the canonical
        ops/quant.kv_dequantize math, cast to q's dtype — bitwise-equal
        to the in-model int8 gather read).
      sink_tokens / window_tokens: sink+sliding-window mask
        (window_tokens 0 = full attention): position j is attendable by
        the query at position p iff ``j < sink_tokens or
        j > p - window_tokens`` (and j <= p).

    Returns ``[slots, q_len, heads, head_dim]`` in q's dtype. Bitwise
    equal (fp32 accumulate, fp32 softmax) to the dense cache path over
    the same window — including the ``/ sqrt(d)`` spelling of the scale
    (multiplying by the reciprocal rounds differently), when ``scale`` is
    left at None.
    """
    head_dim = q.shape[-1]

    def heads(rows):                 # [slots, j, kv_heads*d] -> [.., hk, d]
        return rows.reshape(*rows.shape[:2], -1, head_dim)

    kc = heads(paged_gather(k_pool, block_tables))
    vc = heads(paged_gather(v_pool, block_tables))
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if k_scale is not None:
        from pytorchdistributed_tpu.ops.quant import kv_dequantize

        kc = kv_dequantize(kc, paged_gather(k_scale, block_tables), q.dtype)
        vc = kv_dequantize(vc, paged_gather(v_scale, block_tables), q.dtype)
    rep = q.shape[2] // kc.shape[2]
    if rep > 1:
        kc = jnp.repeat(kc, rep, axis=2)
        vc = jnp.repeat(vc, rep, axis=2)
    pos = lengths[:, None] + jnp.arange(q.shape[1])          # [slots, q]
    valid = jnp.arange(kc.shape[1]) <= pos[..., None]        # [slots, q, j]
    if window_tokens:
        j = jnp.arange(kc.shape[1])
        valid &= (j < sink_tokens) | (j > pos[..., None] - window_tokens)
    scores = jnp.einsum("bihd,bjhd->bhij", q, kc,
                        preferred_element_type=jnp.float32)
    if scale is None:
        scores = scores / jnp.sqrt(head_dim).astype(jnp.float32)
    else:
        scores = scores * scale
    scores = jnp.where(valid[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhij,bjhd->bihd", probs.astype(vc.dtype), vc,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """softmax(q·kᵀ/√d [+mask])·v over [B, S, H, D] tensors."""
    head_dim = q.shape[-1]
    scale = (head_dim**-0.5) if scale is None else scale
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        logits = logits + causal_mask(q.shape[1], k.shape[1])[None, None]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)

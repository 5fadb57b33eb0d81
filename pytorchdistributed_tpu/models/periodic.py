"""Layers of two kinds over two K/V pools: an attention kind of
`models/transformer.py`'s decoder (`TransformerConfig.period`), whose
projections, RoPE (where the layer has it) and output projection are
`SelfAttention`'s own. SmallThinker's pattern: one layer in four attends
every position and has no positional encoding, the other three attend a
sliding window of 4,096 under RoPE; 28 query heads read 4 key/value
heads.

Both kinds keep per-head keys and values side by side, a lane-dense row of
``kv_heads * head_dim`` numbers a position, in pools the scanned stack
carries through its periods (`TransformerConfig.kv_pool_leaves`, each as
deep as its kind has layers, `pool_layers`):

  * ``cached_key`` / ``cached_value``: a full layer's rows, at the blocks
    of ``block_table``; the pool grows with the stream and never retires;
  * ``cached_window_key`` / ``cached_window_value``: a window layer's
    rows, at the blocks of ``window_table``; the engine hands a block back
    once every position in it has left the window of the earliest query
    still to come (`serving/paging.py:SlotPool`, sliding), so a stream
    holds the window's blocks however long it runs.

A query at ``t`` of a layer with window ``W`` attends ``s`` with ``0 <= t -
s <= W - 1`` (the window counts the query itself), of a full layer every
``s <= t``.

Two reads, chosen by `cfg.paged_attn`. A tick under ``"pallas"`` (the
engine's choice on a TPU: the rows are whole 128-lane tiles) reads its
layer's pool through the paged decode kernel
(`ops/pallas_attention.paged_flash_attention`), one call a layer and
nothing to merge: a full layer from the stream's first row, a window layer
under ``window_tokens``, whose dead blocks are neither copied nor
computed. Grouped queries are the kernel's own: a block is fetched once
for the seven heads that share it. Everything else (``"gather"``: the CPU,
the tests' reference; a chunk's many queries under either) is read by XLA
through whole-block gathers: a full layer's follow the longest context of
the call in `CONTEXT_STEPS` steps of the longest sequence (a branch a
step, chosen on the device, so a chunk early in a prompt does not score
against the whole table), a window layer's cover the blocks from its
first query's window to its last query. A chunk walks its queries in
blocks of `query_block` rows (`QUERY_BLOCK`, fewer where the heads are
many: `QUERY_HEAD_ROWS`), so that no ``[heads, chunk, context]`` tensor is
built (float32 scores of 512 queries over 16,384 keys and 28 heads are
0.94 GB a copy). Where the heads cut that block below `QUERY_BLOCK` rows
(Command A+'s 128), a chunk under ``"pallas"`` gathers the same rows for
all its queries at once and attends them through the flash kernel
`ops/pallas_attention.chunk_attention`, which keeps the scores in VMEM:
XLA's scores of 128 heads, in blocks of 32 queries, cost a chunk time in
proportion to its context, several passes of float32 over every row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the device-side scalars of one call this module adds to the "counters"
#: collection's vector: rows the live streams' queries attended in the
#: full layers' pool and in the window layers' pool, summed over the
#: layers (from the masks the gathered read ran under, or from the
#: positions where the kernel reads: the same numbers)
COUNTERS = ("attn_full_rows", "attn_window_rows")

#: a full layer's gathered read follows the longest live context of the
#: call in this many steps of the longest sequence
CONTEXT_STEPS = 16

#: rows of one block of queries inside a prefill chunk, and the most
#: query rows times query heads one block may hold: 128 rows up to 32
#: heads, 32 rows at Command A+'s 128 (float32 scores of 8 kv heads x 16
#: x 32 queries over 33,792 keys are 0.55 GB a copy; 128 queries would
#: be 2.2 GB)
QUERY_BLOCK = 128
QUERY_HEAD_ROWS = 4096


def query_block(heads: int, s: int) -> int:
    """Query rows a block of a call of `s` positions walks at `heads`
    query heads."""
    return max(1, min(QUERY_BLOCK, QUERY_HEAD_ROWS // heads, s))


def paged_attention(cfg, q, k, v, paging, pool, layer, window: int):
    """One layer's attention of `q` [b, s, heads, d], `k`, `v` [b, s,
    kv_heads, d] at the positions ``paging["index"][:, None] +
    arange(s)``: writes the call's rows into the layer's kind's pool at
    `layer`, attends (every position, or the last `window`), and returns
    (out [b, s, heads, d] in `cfg.dtype`, the pool with the rows attended
    added to its counts)."""
    from pytorchdistributed_tpu.models.transformer import COUNTS

    b, s, h, d = q.shape
    bs, dt = cfg.kv_block_size, cfg.dtype
    lanes = cfg.kv_heads * d
    idx = paging["index"]                                       # [b]
    table = paging["window_table" if window else "block_table"]
    names = (("cached_window_key", "cached_window_value") if window
             else ("cached_key", "cached_value"))
    pos = idx[:, None] + jnp.arange(s)                          # [b, s]

    # -- this call's rows into the pool, in place; a position past the
    # context (a padded chunk's tail) drops into trash block 0
    blk = jnp.take_along_axis(
        table, jnp.clip(pos // bs, 0, table.shape[1] - 1), axis=1)
    blk = jnp.where(pos < cfg.max_seq_len, blk, 0)
    pool = dict(pool)
    for name, rows in zip(names, (k, v)):
        pool[name] = pool[name].at[layer, blk, pos % bs].set(
            rows.reshape(b, s, lanes).astype(dt))

    if s == 1 and cfg.paged_attn == "pallas":
        from pytorchdistributed_tpu.ops.pallas_attention import (
            paged_flash_attention,
        )

        out = paged_flash_attention(
            q[:, 0], pool[names[0]], pool[names[1]], table, idx,
            layer=layer, window_tokens=window)[:, None]
        attended = pos + 1
        if window:
            attended = jnp.minimum(attended, window)
    else:
        out, attended = _gathered_read(
            cfg, q, pool[names[0]], pool[names[1]], layer, table, pos,
            window, kernel=(cfg.paged_attn == "pallas"
                            and query_block(h, s) < min(QUERY_BLOCK, s)))

    # a stream's own positions alone (`paging["live"]`)
    n = jnp.where(paging["live"], attended, 0).sum().astype(jnp.float32)
    at = cfg.counter_names.index(COUNTERS[bool(window)])
    pool[COUNTS] = pool[COUNTS].at[at].add(n)
    return out.astype(dt), pool


def _gathered_read(cfg, q, k_pool, v_pool, layer, table, pos, window,
                   kernel: bool = False):
    """The read by XLA's gathers, of a tick or a chunk: `q` [b, s, heads,
    d] at `pos` [b, s] -> (out [b, s, heads, d] float32, the rows each
    query attended [b, s], summed from the mask). With `kernel`, the rows
    gathered are attended by `ops/pallas_attention.chunk_attention`, all
    the call's queries in one block."""
    b, s, h, d = q.shape
    bs, dt = cfg.kv_block_size, cfg.dtype
    hk = cfg.kv_heads
    pages = table.shape[1]
    scale = d ** -0.5
    qb = s if kernel else query_block(h, s)
    whole = 1
    if kernel:
        from pytorchdistributed_tpu.ops.pallas_attention import (
            CHUNK_BLOCK_K,
            chunk_attention,
        )

        # the kernel's blocks of keys are whole blocks of the gather
        whole = max(1, CHUNK_BLOCK_K // bs)
    if s % qb:
        raise ValueError(f"a chunk of {s} tokens is not a multiple of "
                         f"the query block {qb}")

    def rows_from(first, count):
        """`count` consecutive blocks of each stream from logical block
        `first` [b] on: keys and values [b, count * bs, kv_heads, d] in
        position order, and the position of each row (a block before the
        stream's first is no row: its position is negative)."""
        lb = first[:, None] + jnp.arange(count)
        blk = jnp.take_along_axis(table, jnp.clip(lb, 0, pages - 1),
                                  axis=1)
        kpos = (lb[:, :, None] * bs + jnp.arange(bs)).reshape(b, -1)
        kpos = jnp.where(jnp.repeat(lb < pages, bs, axis=1), kpos, -1)
        return (k_pool[layer, blk].reshape(b, count * bs, hk, d),
                v_pool[layer, blk].reshape(b, count * bs, hk, d), kpos)

    def attend(qq, qpos, kk, vv, kpos):
        """Queries `qq` [b, n, heads, d] at `qpos` [b, n] over the rows
        `kk`, `vv` at `kpos` [b, m]."""
        n = qq.shape[1]
        dist = qpos[..., None] - kpos[:, None, :]               # [b, n, m]
        live = (kpos[:, None, :] >= 0) & (dist >= 0)
        if window:
            live &= dist < window
        if kernel:
            return (chunk_attention(qq, kk.reshape(b, -1, hk * d),
                                    vv.reshape(b, -1, hk * d), qpos, kpos,
                                    window=window, scale=scale),
                    live.sum(-1, dtype=jnp.int32))
        scores = jnp.einsum("bnkgd,bmkd->bkgnm",
                            qq.reshape(b, n, hk, h // hk, d), kk,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(live[:, None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1).astype(dt)
        out = jnp.einsum("bkgnm,bmkd->bnkgd", p, vv,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, n, h, d), live.sum(-1, dtype=jnp.int32)

    def blocks_of_queries(one):
        """`one(qq, qpos)` over the call's queries, `qb` at a time."""
        if s == qb:
            return one(q, pos)
        out = jax.lax.map(
            lambda a: one(*a),
            tuple(t.reshape((b, s // qb, qb) + t.shape[2:]).swapaxes(0, 1)
                  for t in (q, pos)))
        return jax.tree.map(
            lambda t: t.swapaxes(0, 1).reshape((b, s) + t.shape[3:]), out)

    if window:
        # the blocks from the first query's window to the last query
        nb = -(-(qb + window - 1) // bs) + 1
        nb = -(-nb // whole) * whole

        def one(qq, qpos):
            return attend(qq, qpos,
                          *rows_from((qpos[:, 0] - (window - 1)) // bs, nb))

        return blocks_of_queries(one)

    def upto(nblocks: int):
        """Where no stream of the call is longer than `nblocks` blocks."""
        def read():
            rows = rows_from(jnp.zeros((b,), jnp.int32),
                             -(-nblocks // whole) * whole)
            return blocks_of_queries(
                lambda qq, qpos: attend(qq, qpos, *rows))
        return read

    spans = sorted({-(-pages * i // CONTEXT_STEPS)
                    for i in range(1, CONTEXT_STEPS + 1)})
    need = jnp.minimum(jnp.max(pos) // bs + 1, spans[-1])
    return jax.lax.switch(jnp.searchsorted(jnp.asarray(spans), need),
                          [upto(n) for n in spans])

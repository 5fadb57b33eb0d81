"""EVA attention through the paged pools (Zheng et al., "Efficient
Attention via Control Variates", ICLR 2023, in the form EvaByte serves):
an attention kind of `models/transformer.py`'s decoder
(`TransformerConfig.eva_window > 0`), whose projections, RoPE and output
projection are `SelfAttention`'s own.

A stream at position ``n`` keeps, in every layer, the exact keys and
values of its current window (``n mod W`` positions) and one summary row
for each chunk of ``C`` positions of every finished window: for chunk
``c`` and head ``h``, ``a_m = softmax over the chunk's positions of
(phi_h . k_m)``, ``kbar_c = sum_m a_m k_m + mu_h``, ``vbar_c = sum_m a_m
v_m`` (keys after RoPE, ``phi . k`` unscaled). The query at ``t``, in
window ``w = t // W``, attends in ONE softmax the exact rows ``W w <= j <=
t`` and the summaries ``c < (W / C) w``: a query of window 0 sees no
summary, and a chunk of the query's own window is never seen as one.

Both kinds of row are per-head keys and values side by side, a lane-dense
row of ``heads * head_dim`` numbers, in two pools the scanned stack
carries through its layers (`TransformerConfig.kv_pool_leaves`):

  * ``cached_key`` / ``cached_value``: the window's rows, at the blocks of
    ``window_table`` (indexed by a position's block, ``t // block_size``);
    the engine hands all of a window's blocks back when the stream
    crosses into the next (`serving/paging.py:SlotPool`, tumbling);
  * ``cached_summary_key`` / ``cached_summary_value``: row ``c`` of a
    stream is chunk ``c``'s summary, at the blocks of ``block_table``
    (indexed by ``c // block_size``); it grows with the stream and never
    retires.

The cache has a write that is no K/V append: when a chunk fills, its
summary is computed from the rows just written and stored. A tick does so
for the streams whose position fills a chunk (from the block that holds
the chunk, the row of this tick included); a prefill chunk for every
chunk it covers. A stored summary becomes visible by the mask ``c < (W /
C) w`` alone, when its window is finished: nothing is copied when a
window closes. A summary of a chunk that is not full yet (the padded tail
of a prompt's last prefill chunk) is garbage nobody sees: the tick that
fills the chunk writes the row again.

Two reads, chosen by `cfg.paged_attn`. A tick under ``"pallas"`` (the
engine's choice on a TPU) reads each pool through the paged decode kernel
(`ops/pallas_attention.paged_flash_attention`): the window pool through
``window_table`` from the window's first position to the query's own, the
summary pool through ``block_table`` as far as the finished windows'
rows, each live row copied from HBM once, and the two calls' partial
softmaxes merged by their log-sum-exp into the one softmax above
(`merge_attention_parts`); a stream of window 0 has no summary to read,
and that call costs it nothing. Everything else (``"gather"``: the CPU,
the tests' reference; a chunk's many queries under either) is read by XLA
through whole-block gathers. There the summaries' part follows the
longest stream of the call: a branch a count of finished windows, chosen
on the device, gathers that many windows' summary blocks and no more
(against one gather of every block, at EvaByte's size on a v5e: a tick
of 16 streams 40.6 ms for 45.1, the prefill of their prompts 13.6 s for
17.7; PERF.md section 6, PR 34). A
gathered tick's single query a stream is multiplied with the gathered
rows where they lie, whole lane-dense rows through the matrix unit; a
chunk's queries are many, and there the rows are split into heads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the device-side scalars of one call, in the order of the "counters"
#: collection's one vector: rows the live streams' queries attended in the
#: window pool and in the summary pool (summed over the layers: from the
#: masks the gathered read ran under, or from the positions where the
#: kernel reads, the same numbers), and summary rows written
COUNTERS = ("eva_window_rows", "eva_summary_rows", "eva_summaries_written")

#: the key under which the counts ride the scanned stack's carry, beside
#: the pools
COUNTS = "eva_counts"


def window_of(pos, win: int):
    """The window a position lies in: its exact rows are those of this
    window up to itself. The engine retires a window's blocks by the same
    rule (`SlotPool.retired_before`, tumbling): the two must agree, or a
    query would read rows that were handed back."""
    return pos // win


def summaries_seen(w, per: int):
    """How many of a stream's summary rows a query of window `w` sees:
    those of the finished windows, ``c < per * w``."""
    return per * w


def summarise(k, v, phi, mu):
    """Summaries of chunks: `k`, `v` [..., C, heads, d] (a chunk's rows),
    `phi`, `mu` [heads, d] -> (kbar, vbar) [..., heads, d], float32."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    a = jax.nn.softmax(jnp.einsum("...mhd,hd->...mh", kf, phi), axis=-2)
    kbar = jnp.einsum("...mh,...mhd->...hd", a, kf) + mu
    return kbar, jnp.einsum("...mh,...mhd->...hd", a, vf)


def paged_attention(cfg, q, k, v, phi, mu, paging, pool, layer):
    """One layer's EVA attention of `q`, `k`, `v` [b, s, heads, d] (RoPE
    applied) at the positions ``paging["index"][:, None] + arange(s)``:
    writes the call's rows and the summaries of the chunks it fills into
    `pool` at `layer`, attends, and returns (out [b, s, heads, d] in
    `cfg.dtype`, the pool with the layer's counts added)."""
    b, s, h, d = q.shape
    win, chunk, bs = cfg.eva_window, cfg.eva_chunk, cfg.kv_block_size
    dt = cfg.dtype
    lanes = h * d
    idx = paging["index"]                                       # [b]
    wtable, stable = paging["window_table"], paging["block_table"]
    pos = idx[:, None] + jnp.arange(s)                          # [b, s]
    if s > 1 and (s % chunk or win % s):
        raise ValueError(
            f"a prefill chunk of {s} positions must be whole summary "
            f"chunks of {chunk} and divide the window of {win}: a chunk "
            f"starts at a multiple of its length, so it lies in one window")
    pool = dict(pool)

    # -- this call's rows into the window pool, in place ----------------
    blk = jnp.take_along_axis(
        wtable, jnp.clip(pos // bs, 0, wtable.shape[1] - 1), axis=1)
    blk = jnp.where(pos < cfg.max_seq_len, blk, 0)  # past the context: trash
    kr, vr = (t.reshape(b, s, lanes).astype(dt) for t in (k, v))
    pool["cached_key"] = pool["cached_key"].at[layer, blk, pos % bs].set(kr)
    pool["cached_value"] = pool["cached_value"].at[
        layer, blk, pos % bs].set(vr)

    # -- the summaries of the chunks this call fills ---------------------
    if s == 1:
        # a tick: the chunk that holds each stream's position, read back
        # from its block (this tick's row included); stored where the
        # position is the chunk's last
        def chunk_rows(leaf):
            rows = leaf[layer, blk[:, 0]].reshape(b, bs // chunk, chunk,
                                                  h, d)
            sub = (pos[:, 0] % bs) // chunk
            return jnp.take_along_axis(
                rows, sub[:, None, None, None, None], axis=1)   # [b,1,..]

        kbar, vbar = summarise(chunk_rows(pool["cached_key"]),
                               chunk_rows(pool["cached_value"]), phi, mu)
        cpos = pos                                              # [b, 1]
        fills = (pos + 1) % chunk == 0
    else:
        kbar, vbar = summarise(kr.reshape(b, s // chunk, chunk, h, d),
                               vr.reshape(b, s // chunk, chunk, h, d),
                               phi, mu)
        cpos = pos[:, ::chunk]                                  # [b, s/C]
        fills = jnp.ones_like(cpos, bool)
    crow = cpos // chunk                    # the summary's row of a stream
    sblk = jnp.take_along_axis(
        stable, jnp.clip(crow // bs, 0, stable.shape[1] - 1), axis=1)
    sblk = jnp.where(fills & (cpos < cfg.max_seq_len), sblk, 0)
    for name, rows in (("cached_summary_key", kbar),
                       ("cached_summary_value", vbar)):
        pool[name] = pool[name].at[layer, sblk, crow % bs].set(
            rows.reshape(*crow.shape, lanes).astype(dt))

    # -- attention: the window's rows and the finished windows' summaries
    read = (_kernel_tick if s == 1 and cfg.paged_attn == "pallas"
            else _gathered_read)
    out, n_window, n_summary = read(cfg, q, pool, layer, wtable, stable, pos)

    # -- the counts, of a stream's own positions alone
    live = paging["live"]
    # a summary is a stream's own where its chunk's last position is
    written = live if s == 1 else live[:, chunk - 1::chunk]
    counts = jnp.stack([
        jnp.where(live, n_window, 0).sum(),
        jnp.where(live, n_summary, 0).sum(),
        jnp.where(written & (sblk > 0), 1, 0).sum()]).astype(jnp.float32)
    pool[COUNTS] = pool[COUNTS] + counts
    return out.astype(dt), pool


def _kernel_tick(cfg, q, pool, layer, wtable, stable, pos):
    """A tick's read through the paged decode kernel, a call a pool: the
    window's rows from its first position to the query's own, the summary
    rows of the finished windows (none in window 0: that slot costs the
    second call nothing), merged by their log-sum-exp into the one
    softmax. `q` [b, 1, heads, d], `pos` [b, 1] -> (out [b, 1, heads, d]
    float32, the window rows and the summary rows each query attended
    [b, 1])."""
    from pytorchdistributed_tpu.ops.pallas_attention import (
        merge_attention_parts,
        paged_flash_attention,
    )

    win = cfg.eva_window
    at = pos[:, 0]
    w = window_of(at, win)
    first, seen = win * w, summaries_seen(w, win // cfg.eva_chunk)
    out = merge_attention_parts([
        paged_flash_attention(
            q[:, 0], pool["cached_key"], pool["cached_value"], wtable, at,
            starts=first, layer=layer, return_lse=True),
        paged_flash_attention(
            q[:, 0], pool["cached_summary_key"],
            pool["cached_summary_value"], stable, seen - 1, layer=layer,
            return_lse=True)])
    return out[:, None], (at - first + 1)[:, None], seen[:, None]


def _gathered_read(cfg, q, pool, layer, wtable, stable, pos):
    """The read by XLA's gathers, of a tick or a chunk: `q` [b, s, heads,
    d] at `pos` [b, s] -> (out [b, s, heads, d] float32, the window rows
    and the summary rows each query attended [b, s], summed from the
    masks)."""
    b, s, h, d = q.shape
    win, bs, dt = cfg.eva_window, cfg.kv_block_size, cfg.dtype
    per = win // cfg.eva_chunk               # summaries a window
    lanes = h * d
    scale = d ** -0.5
    w = window_of(pos, win)                                     # [b, s]
    first = w[:, 0] * (win // bs)           # a stream's window's first block
    wblk = jnp.take_along_axis(
        wtable, jnp.clip(first[:, None] + jnp.arange(win // bs), 0,
                         wtable.shape[1] - 1), axis=1)          # [b, W/bs]
    kw = pool["cached_key"][layer, wblk].reshape(b, win, lanes)
    vw = pool["cached_value"][layer, wblk].reshape(b, win, lanes)
    kpos = first[:, None] * bs + jnp.arange(win)                # [b, W]
    live_w = kpos[:, None, :] <= pos[..., None]                 # [b, s, W]
    if s == 1:
        # a tick's one query a stream, on the rows as they were gathered
        # (lane-dense, [b, rows, heads * d]): splitting a row into heads
        # re-lays every gathered row out, and widening it for a product
        # on the vector unit writes it out again, which together were 55
        # of a tick's 103 ms. So both products go through the matrix
        # unit over whole rows: the scores against the query spread
        # block-diagonally over the heads ([heads * d, heads]: a head's
        # column holds its own d numbers), the values under every head's
        # weights at once, of which a head keeps its own d lanes. 32
        # times the FLOPs, all spare: the read is bound by its bytes.
        own = jnp.arange(lanes)[:, None] // d == jnp.arange(h)  # [lanes, h]
        qblk = jnp.where(own, q[:, 0].reshape(b, lanes, 1), 0).astype(dt)

        def scores(rows):
            return jnp.einsum("bni,bih->bhn", rows, qblk,
                              preferred_element_type=jnp.float32)[
                                  :, :, None, :] * scale        # [b,h,1,n]

        def mix(p, rows):
            full = jnp.einsum("bhn,bni->bhi", p[:, :, 0], rows,
                              preferred_element_type=jnp.float32)
            full = full.reshape(b, h, h, d)
            return full[:, jnp.arange(h), jnp.arange(h)][:, None]
    else:
        def scores(rows):
            return jnp.einsum("bihd,bjhd->bhij", q,
                              rows.reshape(b, -1, h, d),
                              preferred_element_type=jnp.float32) * scale

        def mix(p, rows):
            return jnp.einsum("bhij,bjhd->bihd", p,
                              rows.reshape(b, -1, h, d),
                              preferred_element_type=jnp.float32)

    sw = jnp.where(live_w[:, None], scores(kw), -jnp.inf)
    seen = summaries_seen(w, per)                               # [b, s]

    def attend(windows: int):
        """(the output, the summary rows each query attended) where no
        stream of the call has more than `windows` finished windows:
        their summary blocks are gathered, no more."""
        if not windows:
            p = jax.nn.softmax(sw, axis=-1).astype(dt)
            return mix(p, vw), jnp.zeros((b, s), jnp.int32)
        nsb = -(-windows * per // bs)
        ks = pool["cached_summary_key"][layer, stable[:, :nsb]].reshape(
            b, nsb * bs, lanes)
        vs = pool["cached_summary_value"][layer, stable[:, :nsb]].reshape(
            b, nsb * bs, lanes)
        live_s = jnp.arange(nsb * bs) < seen[..., None]      # [b, s, rows]
        ss = jnp.where(live_s[:, None], scores(ks), -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([sw, ss], -1),
                           axis=-1).astype(dt)
        return (mix(p[..., :win], vw) + mix(p[..., win:], vs),
                live_s.sum(-1, dtype=jnp.int32))

    n_win = cfg.max_seq_len // win
    out, n_summary = jax.lax.switch(
        jnp.clip(jnp.max(w), 0, n_win - 1),
        [lambda n=n: attend(n) for n in range(n_win)])
    return out, live_w.sum(-1), n_summary

"""A causal LM whose layers differ by kind: latent attention (MLA, the
DeepSeek-V2 form) with a learned sparse selection (the DeepSeek-V3.2
indexer) in the FULL layers, the same latent form over a sliding window
in the SLIDING layers, a headwise sigmoid gate on every head's output,
and sigmoid-routed experts (`models/moe.py:DroplessMoE`) after the
leading dense SwiGLU layers.

Served through the paged engine only (`serving/engine.py`), where it
keeps TWO kinds of cache, one block table each:

  * a full layer's pool grows with the stream: a position's row is the
    ``kv_rank + rope`` numbers ``[c_kv; k_rope]`` that all heads share
    (``cached_latent``; the row is padded with zeros to whole 128-lane
    tiles, `LatentDims.pool_row`, so that the pool stays row-major), and
    beside it the indexer's key of ``index_dim`` numbers
    (``cached_index_key``); both live at the blocks of ``block_table``;
  * a sliding layer's pool (``cached_window``, at the blocks of
    ``window_table``) holds the same kind of row for the window's
    positions only: the engine hands a block back to the allocator once
    every position in it has left the window.

Attention is computed in the absorbed form, so nothing per head is ever
cached or rebuilt: ``q_nope W_uk`` is scored against ``c_kv``, the value
is read from the same row, and ``W_uv`` is applied to the attended
latent. A full layer scores every live position with the indexer
(``I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])``, bf16 keys and
queries, float32 products and sums), keeps the ``index_topk`` largest
``s <= t`` and gathers exactly those rows, so the core attention's work
follows ``min(context, index_topk)``; a sliding layer gathers the
window's blocks. A prefill chunk walks its queries in blocks
(`QUERY_BLOCK`), so no ``[chunk, heads, context]`` tensor is built.

The layers are unrolled (they differ structurally; `nn.scan` would fold
them into one body). The stack owns the per-slot state every layer reads
(``index``, ``block_table``, ``window_table``: "cache" variables the
engine stamps from host state on every call) and returns, in the
"counters" collection, a few device-side scalars a tick: the experts'
load, and the positions attended and live in the full layers as the
masks the attention ran under counted them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorchdistributed_tpu.models.moe import DROPLESS_COUNTERS, DroplessMoE
from pytorchdistributed_tpu.models.transformer import (
    CacheKind,
    Embedder,
    _cfg_dot_general,
    _layer_norm,
    apply_rope,
    rope_tables,
)

#: the device-side scalars of one call, in the order of the "counters"
#: collection's one vector
COUNTERS = DROPLESS_COUNTERS + ("sparse_selected", "sparse_live")

#: a full layer's indexer pass and sort follow the longest live context
#: of the call in this many steps of the longest sequence. A step is a
#: level of a chunk's duration, and the gap between a stream's tokens is
#: a chunk and a tick: at 4 steps the 95th percentile of those gaps hopped
#: between levels 8% apart from seed to seed; at 16 they lie 2% apart.
CONTEXT_STEPS = 16

#: rows of one block of queries inside a prefill chunk
QUERY_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class LatentDims:
    """Heads and ranks of one kind of latent attention layer."""
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float

    @property
    def row(self) -> int:
        """Numbers a position keeps: the latent and the shared RoPE key."""
        return self.kv_rank + self.rope

    @property
    def pool_row(self) -> int:
        """A pool row's width: `row` padded to whole 128-lane tiles, so
        that a row write and a row gather see the pool row-major (a
        width of 576 makes the compiler re-lay the whole pool out, twice
        a call)."""
        return -(-self.row // 128) * 128


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    vocab_size: int
    embed_dim: int
    layer_kinds: tuple          # "full" | "sliding", one a layer
    full: LatentDims
    sliding: LatentDims
    sliding_window: int         # positions a query sees, itself included
    index_heads: int
    index_dim: int
    index_topk: int
    dense_layers: int           # leading layers with a dense SwiGLU
    mlp_dim: int
    moe_dim: int
    router_experts: int         # the router's published width
    experts_held: tuple         # [lo, hi) of them live here
    experts_per_token: int
    shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    # `DroplessMoE`'s scoring and gate, of which this family has one each
    # (constants of the class, not fields)
    moe_scoring = "sigmoid"
    moe_activation = "silu"
    lora_rescale: bool = True   # c_q, c_kv *= sqrt(embed/rank) after norm
    norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    quant: str = "none"
    # what the serving engine sets (the names `TransformerConfig` has)
    decode: bool = False
    decode_slots: int = 0
    kv_block_size: int = 0
    kv_blocks: int = 0          # blocks of the full layers' pool
    window_blocks: int = 0      # blocks of the sliding layers' pool
    kv_dtype: str = "bf16"
    kv_sink_tokens: int = 0
    kv_window_tokens: int = 0
    paged_attn: str = "gather"
    per_slot_kv_limits: bool = False
    attention: str = "dense"
    decode_attend_len: int | None = None
    # what `Embedder` and `_layer_norm` read
    rope: bool = True
    norm: str = "rmsnorm"
    fused_norms: bool = False

    def __post_init__(self):
        if any(k not in ("full", "sliding") for k in self.layer_kinds):
            raise ValueError(f"layer_kinds {self.layer_kinds}: each is "
                             f"'full' or 'sliding'")
        if self.decode and not self.kv_block_size:
            raise ValueError(
                "a model with two cache kinds is served through the "
                "paged engine only (block_size > 0): the dense per-slot "
                "cache has one layout for every layer")
        if self.kv_dtype != "bf16":
            raise ValueError(
                "kv_dtype='int8' is not built for latent rows: the "
                "scale planes are per (token, kv head) and a latent row "
                "has no heads")
        if self.kv_window_tokens or self.kv_sink_tokens:
            raise ValueError(
                "kv_window_tokens / kv_sink_tokens retire blocks for the "
                "whole stack at once; this model's window belongs to its "
                "sliding layers (sliding_window) and is retired per kind")
        if self.kv_block_size:
            if self.max_seq_len % self.kv_block_size:
                raise ValueError(
                    f"max_seq_len {self.max_seq_len} must be a multiple "
                    f"of kv_block_size {self.kv_block_size}")
            if self.kv_blocks < 2 or self.window_blocks < 2:
                raise ValueError(
                    "kv_blocks and window_blocks must be >= 2 (block 0 "
                    "of each pool is its trash block)")

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def kv_pages(self) -> int:
        return (self.max_seq_len // self.kv_block_size
                if self.kv_block_size else 0)

    @property
    def cache_kinds(self) -> tuple:
        """The pools the engine keeps, the stream's own first."""
        return (CacheKind("latent", "block_table"),
                CacheKind("window", "window_table", self.sliding_window))


def _linear(mod, cfg, name, shape, x):
    """`x @ W` for a stored matrix, through the config's contraction (the
    int8 control swaps it); float32 out."""
    w = mod.param(name, nn.initializers.normal(stddev=0.02), shape,
                  cfg.param_dtype)
    dg = _cfg_dot_general(cfg, jax.lax.dot_general)
    return dg(x.astype(cfg.dtype), w.astype(cfg.dtype),
              (((x.ndim - 1,), (0,)), ((), ())),
              preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope_rows(x, pos, theta, max_len):
    """RoPE on the last axis of `x` [b, s, (h,) d] at positions `pos`
    [b, s] (`transformer.apply_rope`'s split-halves pairing)."""
    cos, sin = rope_tables(max_len, x.shape[-1], theta)
    p = jnp.clip(pos, 0, max_len - 1)
    if x.ndim == 3:
        return apply_rope(x[:, :, None, :], cos[p], sin[p])[:, :, 0, :]
    return apply_rope(x, cos[p], sin[p])


def _attend(q, rows, live, latent: int, scale: float, dtype):
    """Absorbed attention of `g` streams' queries `q` [g, n, h, row] over
    `rows` ([g, n, k, row]: its own rows a query; or [g, k, row]: the
    same rows for all of a stream's) where `live` [g, n, k]; returns the
    attended latent [g, n, h, latent]. A query with no live row reads
    garbage that nobody samples from (a pad position)."""
    own = rows.ndim == 4
    scores = jnp.einsum("gnhc,gnkc->gnhk" if own else "gnhc,gkc->gnhk",
                        q, rows,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(live[:, :, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("gnhk,gnkc->gnhc" if own else "gnhk,gkc->gnhc", p,
                      rows[..., :latent],
                      preferred_element_type=jnp.float32)


class LatentAttention(nn.Module):
    """One layer's attention, of either kind. `x` is the layer's normed
    input [b, s, embed]; `paging` the stack's per-slot state. Returns the
    layer's output and, of a full layer, how many positions its queries
    attended and how many were live for them (both [b, s], summed from
    the masks the attention ran under; zeros for a sliding layer)."""

    cfg: LatentConfig
    kind: str

    @nn.compact
    def __call__(self, x, paging):
        cfg, full = self.cfg, self.kind == "full"
        g = cfg.full if full else cfg.sliding
        b, s, d = x.shape
        h, bs = g.heads, cfg.kv_block_size
        dt = cfg.dtype
        idx = paging["index"]                                   # [b]
        table = paging["block_table" if full else "window_table"]
        pos = idx[:, None] + jnp.arange(s)                      # [b, s]

        def gain(name, n):
            return self.param(name, nn.initializers.ones_init(), (n,),
                              jnp.float32)

        # -- projections (absorbed: no per-head key or value is built) --
        c_q = _rms(_linear(self, cfg, "wq_a", (d, g.q_rank), x),
                   gain("q_norm", g.q_rank), cfg.norm_eps)
        kv = _linear(self, cfg, "wkv_a", (d, g.row), x)
        c_kv = _rms(kv[..., :g.kv_rank], gain("kv_norm", g.kv_rank),
                    cfg.norm_eps)
        if cfg.lora_rescale:
            c_q = c_q * math.sqrt(d / g.q_rank)
            c_kv = c_kv * math.sqrt(d / g.kv_rank)
        c_q, c_kv = c_q.astype(dt), c_kv.astype(dt)
        k_rope = _rope_rows(kv[..., g.kv_rank:].astype(dt), pos, g.theta,
                            cfg.max_seq_len)
        q = _linear(self, cfg, "wq_b", (g.q_rank, h * (g.nope + g.rope)),
                    c_q).astype(dt).reshape(b, s, h, g.nope + g.rope)
        q_rope = _rope_rows(q[..., g.nope:], pos, g.theta, cfg.max_seq_len)
        wkv_b = self.param(
            "wkv_b", nn.initializers.normal(stddev=0.02),
            (g.kv_rank, h * (g.nope + g.v)),
            cfg.param_dtype).astype(dt).reshape(g.kv_rank, h,
                                                g.nope + g.v)
        q_abs = jnp.einsum("bshn,chn->bshc", q[..., :g.nope],
                           wkv_b[..., :g.nope],
                           preferred_element_type=jnp.float32).astype(dt)
        qq = jnp.concatenate(                       # [b, s, h, pool_row]
            [q_abs, q_rope,
             jnp.zeros((b, s, h, g.pool_row - g.row), dt)], -1)
        gate = jax.nn.sigmoid(_linear(self, cfg, "wg", (d, h), x))

        # -- this call's rows into the pool, in place -------------------
        blocks = cfg.kv_blocks if full else cfg.window_blocks
        rows_leaf = "cached_latent" if full else "cached_window"
        pools = {rows_leaf: g.pool_row}
        new = {rows_leaf: jnp.concatenate(
            [c_kv, k_rope,
             jnp.zeros((b, s, g.pool_row - g.row), dt)], -1)}
        if full:
            k_i = _linear(self, cfg, "wik", (d, cfg.index_dim), x)
            mu = k_i.mean(-1, keepdims=True)
            var = ((k_i - mu) ** 2).mean(-1, keepdims=True)
            k_i = ((k_i - mu) * jax.lax.rsqrt(var + cfg.index_norm_eps)
                   * gain("ik_norm_g", cfg.index_dim)
                   + self.param("ik_norm_b", nn.initializers.zeros_init(),
                                (cfg.index_dim,), jnp.float32)).astype(dt)
            k_i = jnp.concatenate(
                [_rope_rows(k_i[..., :g.rope], pos, g.theta,
                            cfg.max_seq_len), k_i[..., g.rope:]], -1)
            pools["cached_index_key"] = cfg.index_dim
            new["cached_index_key"] = k_i
            q_i = _linear(self, cfg, "wiq",
                          (g.q_rank, cfg.index_heads * cfg.index_dim),
                          c_q).astype(dt).reshape(
                              b, s, cfg.index_heads, cfg.index_dim)
            q_i = jnp.concatenate(
                [_rope_rows(q_i[..., :g.rope], pos, g.theta,
                            cfg.max_seq_len), q_i[..., g.rope:]], -1)
            w_i = (_linear(self, cfg, "wiw", (d, cfg.index_heads), x)
                   / math.sqrt(cfg.index_heads * cfg.index_dim))
        var_of = {name: self.variable("cache", name, jnp.zeros,
                                      (blocks, bs, width), dt)
                  for name, width in pools.items()}
        pool = {name: v.value for name, v in var_of.items()}
        if not self.is_initializing():
            # a position past the context (a padded chunk's tail) or past
            # the table drops into trash block 0
            inb = jnp.clip(pos // bs, 0, cfg.kv_pages - 1)
            blk = jnp.take_along_axis(table, inb, axis=1)
            blk = jnp.where(pos < cfg.max_seq_len, blk, 0)
            for name in pool:
                pool[name] = pool[name].at[blk, pos % bs].set(
                    new[name].astype(dt))
                var_of[name].value = pool[name]
        scale = 1.0 / math.sqrt(g.nope + g.rope)
        qb = min(QUERY_BLOCK, s)
        if s % qb:
            raise ValueError(f"a chunk of {s} tokens is not a multiple "
                             f"of QUERY_BLOCK {qb}")

        def run(streams, *extra):
            """`streams(tables [g, pages], tpos [g, n], qq [g, n, h, row],
            *extra)` over the call: a tick's slots are `g` streams of one
            query each; a chunk is one stream, its blocks of `qb` queries
            in turn."""
            if s == qb:
                return streams(table, pos, qq, *extra)

            def blocked(t):
                return t.reshape((s // qb, 1, qb) + t.shape[2:])

            out = jax.lax.map(
                lambda a: streams(table, *a),
                tuple(blocked(t) for t in (pos, qq) + extra))
            return jax.tree.map(
                lambda t: t.reshape((1, s) + t.shape[3:]), out)

        def stream_rows(leaf, tables, first, count):
            """`count` consecutive blocks of each stream from logical
            block `first` [g] on, whole blocks at a time: [g, count * bs,
            width] in position order, and which of them exist."""
            lb = first[:, None] + jnp.arange(count)
            ok = (lb >= 0) & (lb < cfg.kv_pages)
            blk = jnp.take_along_axis(
                tables, jnp.clip(lb, 0, cfg.kv_pages - 1), axis=1)
            return (leaf[blk].reshape(lb.shape[0], count * bs,
                                      leaf.shape[-1]),
                    jnp.repeat(ok, bs, axis=1))

        if full:
            zero = jnp.zeros((b,), jnp.int32)

            def selecting(ctx):
                """Attention over the first `ctx` positions of each
                stream's table."""
                topk = min(cfg.index_topk, ctx)
                spos = jnp.arange(ctx)

                def streams(tables, tpos, qq_, q_i_, w_i_):
                    n = tpos.shape[1]
                    causal = spos <= tpos[..., None]        # [g, n, ctx]
                    n_live = causal.sum(-1)
                    if ctx <= topk:
                        # nothing to choose from yet: every live position
                        rows, _ = stream_rows(pool["cached_latent"],
                                              tables, zero, ctx // bs)
                        return (_attend(qq_, rows, causal, g.kv_rank,
                                        scale, dt), n_live, n_live)
                    keys, _ = stream_rows(pool["cached_index_key"],
                                          tables, zero, ctx // bs)
                    dots = jnp.einsum("gnjd,gsd->gnjs", q_i_, keys,
                                      preferred_element_type=jnp.float32)
                    score = (jax.nn.relu(dots) * w_i_[..., None]).sum(2)
                    score = jnp.where(causal, score, -jnp.inf)
                    best, sel = jax.lax.top_k(
                        score.reshape(-1, ctx), topk)   # one sort, 2-D
                    best = best.reshape(-1, n, topk)
                    sel = sel.reshape(-1, n, topk)
                    if n == 1:
                        # a tick: translate the chosen positions through
                        # the table (a few thousand scalars a stream)
                        blk = jnp.take_along_axis(
                            tables, sel[:, 0] // bs, axis=1)
                        phys = (blk * bs + sel[:, 0] % bs)[:, None]
                        rows = pool["cached_latent"].reshape(
                            blocks * bs, -1)[phys]
                    else:
                        # a chunk: the stream's rows once in position
                        # order, whole blocks at a time, then the chosen
                        # rows by position (no scalar a row)
                        line, _ = stream_rows(pool["cached_latent"],
                                              tables, zero, ctx // bs)
                        rows = jax.vmap(lambda l, i: l[i])(line, sel)
                    chosen = best > -jnp.inf
                    return (_attend(qq_, rows, chosen, g.kv_rank, scale,
                                    dt), chosen.sum(-1), n_live)

                return lambda: run(streams, q_i, w_i)

            # the indexer's pass and the sort behind the selection follow
            # the longest live context of the call: a branch a step of
            # the longest sequence (and one at `index_topk`, up to which
            # nothing is selected), chosen on the device
            spans = sorted({-(-cfg.kv_pages * i // CONTEXT_STEPS) * bs
                            for i in range(1, CONTEXT_STEPS + 1)}
                           | {min(-(-cfg.index_topk // bs) * bs,
                                  cfg.kv_pages * bs)})
            need = jnp.minimum(jnp.max(pos) + 1, spans[-1])
            lat, *counts = jax.lax.switch(
                jnp.searchsorted(jnp.asarray(spans), need),
                [selecting(c) for c in spans])
        else:
            back = cfg.sliding_window - 1
            nb = -(-(qb + back) // bs) + 1
            span = jnp.arange(nb * bs)

            def streams(tables, tpos, qq_):
                """Consecutive queries of each stream: the blocks from
                the first query's window to the last query."""
                first = (tpos[:, 0] - back) // bs       # may be negative
                rows, ok = stream_rows(pool["cached_window"], tables,
                                       first, nb)
                kpos = first[:, None] * bs + span           # [g, nb*bs]
                dist = tpos[..., None] - kpos[:, None, :]
                live = ok[:, None, :] & (dist >= 0) & (dist <= back)
                return _attend(qq_, rows, live, g.kv_rank, scale, dt)

            lat = run(streams)
            counts = [jnp.zeros((b, s), jnp.int32)] * 2
        out = jnp.einsum("bshc,chv->bshv", lat.astype(dt),
                         wkv_b[..., g.nope:],
                         preferred_element_type=jnp.float32)
        out = (out * gate[..., None]).astype(dt).reshape(b, s, h * g.v)
        y = _linear(self, cfg, "wo", (h * g.v, d), out).astype(dt)
        return y, counts


class SwiGLU(nn.Module):
    """The dense feed-forward of the leading layers."""

    cfg: LatentConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d, f = cfg.embed_dim, cfg.mlp_dim
        hdn = (nn.silu(_linear(self, cfg, "w_gate", (d, f), x))
               * _linear(self, cfg, "w_up", (d, f), x))
        return _linear(self, cfg, "w_down", (f, d), hdn).astype(cfg.dtype)


class Head(nn.Module):
    """The untied vocabulary projection, `LMHead`'s parameter
    (``lm_head/kernel``) with float32 logits: bf16 products summed in
    float32 and left there, so that near-ties between logits are not
    decided by a rounding of the sum."""

    cfg: LatentConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        return _linear(self, cfg, "kernel",
                       (cfg.embed_dim, cfg.vocab_size), x)


class LatentLayer(nn.Module):
    """Pre-norm: x + Attn(RMSNorm(x)); x + FFN(RMSNorm(x))."""

    cfg: LatentConfig
    kind: str
    moe: bool

    @nn.compact
    def __call__(self, x, paging, live):
        cfg = self.cfg
        a, sparse = LatentAttention(cfg, self.kind, name="attn")(
            _layer_norm(cfg, "attn_norm")(x).astype(cfg.dtype), paging)
        x = x + a
        xn = _layer_norm(cfg, "ffn_norm")(x).astype(cfg.dtype)
        if self.moe:
            y, counters = DroplessMoE(cfg, name="ffn")(xn, live)
        else:
            y, counters = SwiGLU(cfg, name="ffn")(xn), {}
        # a stream's own positions alone (`LatentLM`'s `live`)
        counters = dict(counters,
                        sparse_selected=jnp.where(live, sparse[0], 0).sum(),
                        sparse_live=jnp.where(live, sparse[1], 0).sum())
        return x + y, counters


class LatentLM(nn.Module):
    cfg: LatentConfig
    #: names of the "counters" collection's vector (the engine's summary)
    counters = COUNTERS

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        if not cfg.decode:
            raise NotImplementedError(
                "LatentLM is served through the paged engine "
                "(ServingEngine(model, params, block_size=...)); a "
                "cacheless forward is the benchmark's plain reference")
        b, s = tokens.shape
        slots, pages = cfg.decode_slots, cfg.kv_pages
        if b != slots:
            raise ValueError(f"slot-decode batch {b} != decode_slots "
                             f"{slots} (the engine owns the batch dim)")
        paging = {
            "index": self.variable("cache", "index", jnp.zeros, (slots,),
                                   jnp.int32).value,
            **{table: self.variable("cache", table, jnp.zeros,
                                    (slots, pages), jnp.int32).value
               for _, table, *_ in cfg.cache_kinds}}
        # where each slot's tokens of the call end (exclusive): what is
        # counted is a stream's own positions, a live slot's in a tick (a
        # free one ticks along at length 0, computed and never counted)
        # and those before the prompt's end in a chunk
        stop = self.variable("cache", "stop", jnp.zeros, (slots,),
                             jnp.int32).value
        live = (paging["index"][:, None] + jnp.arange(s)
                < stop[:, None])
        x = Embedder(cfg, name="embed")(tokens)
        total = dict.fromkeys(COUNTERS, jnp.zeros((), jnp.float32))
        for i, kind in enumerate(cfg.layer_kinds):
            x, counters = LatentLayer(
                cfg, kind, moe=i >= cfg.dense_layers,
                name=f"layer_{i}")(x, paging, live)
            for name, v in counters.items():
                total[name] = total[name] + v.astype(jnp.float32)
        x = _layer_norm(cfg, "ln_f")(x)
        if self.is_mutable_collection("counters"):
            self.variable("counters", "tick", jnp.zeros,
                          (len(COUNTERS),)).value = jnp.stack(
                              [total[n] for n in COUNTERS])
        return Head(cfg, name="lm_head")(x)

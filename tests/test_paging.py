"""Paged KV cache tests (ISSUE 7: serving/paging.py + the paged engine).

Correctness bar, same as the dense engine's (test_serving.py): for ANY
admission order — now including prefix-cache hits, chunked prefills,
block growth and preempt-requeue round-trips — greedy per-request
outputs must be BITWISE-equal to inference.generate()'s. On top: the
paged-attention kernel parity ladder (reference gather vs dense cache
math at ragged/block-boundary lengths, fp32; the Pallas pool-native twin
to online-softmax tolerance), the block allocator / radix-cache units,
the every-exit-path block-leak invariant, and the zero-recompile
steady-state guarantee over the paged program pair.
"""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from pytorchdistributed_tpu.inference import generate
from pytorchdistributed_tpu.models import GPT2, Llama, gpt2_config
from pytorchdistributed_tpu.models import llama_config
from pytorchdistributed_tpu.ops.attention import paged_attention
from pytorchdistributed_tpu.serving import (
    BlockAllocator,
    RadixPrefixCache,
    ServingEngine,
)
from pytorchdistributed_tpu.serving import engine as serving_engine
from pytorchdistributed_tpu.serving.engine import (
    paged_decode_tick,
    paged_prefill_chunk,
)


def _init(model, seed=1):
    return model.init(jax.random.key(seed), jnp.zeros((1, 4), jnp.int32))


# ---------------------------------------------------------------------------
# host bookkeeping units


class TestBlockAllocator:
    def test_alloc_free_refcount(self):
        a = BlockAllocator(8, 4)
        assert a.usable == 7 and a.free_count == 7
        blocks = a.alloc(3)
        assert blocks is not None and 0 not in blocks
        assert a.free_count == 4 and a.resident == 3
        a.incref(blocks[0])
        assert not a.decref(blocks[0])  # still shared
        assert a.decref(blocks[0])      # now freed
        assert a.free_count == 5
        assert a.alloc(6) is None       # over-ask leaves state untouched
        assert a.free_count == 5
        for b in blocks[1:]:
            a.decref(b)
        a.check_leaks(0)

    def test_trash_block_reserved(self):
        a = BlockAllocator(4, 2)
        got = set(a.alloc(3))
        assert 0 not in got
        with pytest.raises(ValueError):
            a.incref(0)

    def test_leak_check_raises(self):
        a = BlockAllocator(4, 2)
        a.alloc(1)
        with pytest.raises(AssertionError, match="leak"):
            a.check_leaks(0)


class TestRadixPrefixCache:
    def test_match_insert_block_granularity(self):
        a = BlockAllocator(16, 4)
        r = RadixPrefixCache(a)
        toks = np.arange(10, dtype=np.int32)  # 2 full blocks + tail
        blocks = a.alloc(3)
        assert r.match(toks) == []
        r.insert(toks[:8], blocks[:2])        # only full blocks cached
        assert r.block_count == 2
        assert [a.refcount(b) for b in blocks[:2]] == [2, 2]
        assert r.match(toks) == blocks[:2]
        # divergence INSIDE the second block misses it (copy-on-write by
        # construction: the divergent request prefills a private copy)
        other = toks.copy()
        other[5] = 99
        assert r.match(other) == blocks[:1]

    def test_reclaim_lru_sole_owner_only(self):
        a = BlockAllocator(16, 4)
        r = RadixPrefixCache(a)
        b1 = a.alloc(2)
        b2 = a.alloc(2)
        r.insert(np.arange(8, dtype=np.int32), b1)
        r.insert(np.arange(100, 108, dtype=np.int32), b2)
        for b in b1 + b2:  # the admitting slots release their refs
            a.decref(b)
        # touch chain 1 -> chain 2's tail is the LRU evictable leaf
        r.match(np.arange(8, dtype=np.int32))
        free0 = a.free_count
        assert r.reclaim(1) == 1
        assert a.free_count == free0 + 1
        assert r.match(np.arange(100, 108, dtype=np.int32)) == b2[:1]
        # a block an active slot still holds is never reaped
        a.incref(b1[1])
        assert r.reclaim(10) >= 1  # everything sole-owner goes
        assert a.refcount(b1[1]) >= 1
        a.decref(b1[1])
        r.clear()
        a.check_leaks(0)


# ---------------------------------------------------------------------------
# paged attention parity ladder


def _dense_decode_oracle(q, k_rows, v_rows, lengths):
    """The dense cache-masked decode math, verbatim from the model's
    dense branch (fp32 softmax, /sqrt(d) spelling)."""
    attend = k_rows.shape[1]
    pos = lengths[:, None] + jnp.arange(q.shape[1])
    valid = jnp.arange(attend) <= pos[..., None]
    scores = jnp.einsum("bihd,bjhd->bhij", q, k_rows,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    scores = jnp.where(valid[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhij,bjhd->bihd", probs.astype(v_rows.dtype), v_rows,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _paged_fixture(lengths, *, bs=8, heads=4, kvh=2, d=16, seed=0, mb=8):
    """Build a pool + tables whose gathered content equals dense rows
    holding the same K/V — the two layouts of one logical cache."""
    rng = np.random.default_rng(seed)
    slots = len(lengths)
    attend = mb * bs
    k_rows = rng.normal(size=(slots, attend, kvh, d)).astype(np.float32)
    v_rows = rng.normal(size=(slots, attend, kvh, d)).astype(np.float32)
    # the pool's row: one token's kv heads side by side
    pool_k = np.zeros((slots * mb + 1, bs, kvh * d), np.float32)
    pool_v = np.zeros_like(pool_k)
    tables = np.zeros((slots, mb), np.int32)
    nxt = 1
    for s in range(slots):
        for j in range(mb):
            pool_k[nxt] = k_rows[s, j * bs:(j + 1) * bs].reshape(bs, -1)
            pool_v[nxt] = v_rows[s, j * bs:(j + 1) * bs].reshape(bs, -1)
            tables[s, j] = nxt
            nxt += 1
    q = rng.normal(size=(slots, 1, heads, d)).astype(np.float32)
    rep = heads // kvh
    k_full = np.repeat(k_rows, rep, axis=2)
    v_full = np.repeat(v_rows, rep, axis=2)
    return (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(tables), jnp.asarray(np.asarray(lengths, np.int32)),
            jnp.asarray(k_full), jnp.asarray(v_full))


@pytest.mark.parametrize("lengths", [
    (5, 17, 40),          # ragged
    (16, 15, 17),         # block boundary: k*bs, k*bs - 1, k*bs + 1
    (0, 63, 32),          # empty slot, last row, boundary
])
def test_paged_attention_bitwise_vs_dense(lengths):
    """The gather layout is invisible to the math: paged attention over
    a block pool is BITWISE-equal (fp32) to the dense cache path for
    ragged and block-boundary (len == k*bs +/- 1) slot lengths."""
    q, pk, pv, tbl, lens, kf, vf = _paged_fixture(lengths)
    ref = _dense_decode_oracle(q, kf, vf, lens)
    got = paged_attention(q, pk, pv, tbl, lens)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_paged_attention_trash_garbage_is_masked():
    """Table entries past the live window point at the trash block; its
    content must not perturb outputs (0-prob x finite garbage == 0)."""
    q, pk, pv, tbl, lens, kf, vf = _paged_fixture((5, 9, 2))
    ref = paged_attention(q, pk, pv, tbl, lens)
    # poison the trash block and every block past each slot's window
    pk = pk.at[0].set(1e6)
    pv = pv.at[0].set(-1e6)
    bs = pk.shape[1]
    tbl_np = np.asarray(tbl).copy()
    for s, n in enumerate((5, 9, 2)):
        tbl_np[s, (n // bs) + 1:] = 0
    got = paged_attention(q, pk, pv, jnp.asarray(tbl_np), lens)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize("lengths", [
    (5, 17, 40, 64),       # the original mixed-ragged set
    (1, 7, 9, 23),         # every length off the block grid (bs=8),
                           # final block 1..7 rows full
    (8, 15, 16, 63),       # exact boundary, last-row-of-block, and the
                           # last row of the final block
])
def test_paged_flash_matches_reference(lengths):
    """The Pallas pool-native twin (scalar-prefetched block tables, no
    gathered HBM copy) matches the reference gather to online-softmax
    tolerance, GQA included — including lengths NOT multiples of
    block_size, where the final block is only partially filled and the
    kernel's in-block masking does the cut."""
    from pytorchdistributed_tpu.ops.pallas_attention import (
        paged_flash_attention,
    )

    q, pk, pv, tbl, lens, _, _ = _paged_fixture(lengths, kvh=2)
    ref = paged_attention(q, pk, pv, tbl, lens)
    got = paged_flash_attention(q[:, 0], pk, pv, tbl, lens)
    np.testing.assert_allclose(np.asarray(ref[:, 0]), np.asarray(got),
                               atol=2e-5, rtol=2e-5)


def _quantize_fixture_pool(pk, pv, d=16):
    """int8 codes in the pool's own row shape + the [blocks, bs, kv_heads]
    scale planes (quantized per head, as the model's write does)."""
    from pytorchdistributed_tpu.ops.quant import kv_quantize

    kc, ks = kv_quantize(pk.reshape(*pk.shape[:2], -1, d))
    vc, vs = kv_quantize(pv.reshape(*pv.shape[:2], -1, d))
    return kc.reshape(pk.shape), ks, vc.reshape(pv.shape), vs


@pytest.mark.parametrize("lengths", [(5, 17, 40, 64), (1, 9, 23, 63)])
def test_paged_flash_int8_matches_reference(lengths):
    """The ISSUE 13 compressed hot path: the Pallas kernel reading the
    int8 pool + fp32 scale planes matches the reference gather running
    the SAME canonical dequant (ops.quant.kv_dequantize) to
    online-softmax tolerance — the tolerance-pinned int8 twin."""
    from pytorchdistributed_tpu.ops.pallas_attention import (
        paged_flash_attention,
    )

    q, pk, pv, tbl, lens, _, _ = _paged_fixture(lengths, kvh=2)
    kc, ks, vc, vs = _quantize_fixture_pool(pk, pv)
    ref = paged_attention(q, kc, vc, tbl, lens, k_scale=ks, v_scale=vs)
    got = paged_flash_attention(q[:, 0], kc, vc, tbl, lens,
                                k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(ref[:, 0]), np.asarray(got),
                               atol=2e-5, rtol=2e-5)
    # and the quantization error itself is bounded: int8 per-(token,
    # head) absmax scaling stays close to the fp32 oracle
    full = paged_attention(q, pk, pv, tbl, lens)
    np.testing.assert_allclose(np.asarray(full[:, 0]), np.asarray(got),
                               atol=0.05, rtol=0.05)


def test_paged_flash_sink_window_matches_reference():
    """Sink + sliding-window masking agrees between the kernel and the
    reference gather (fp32 and int8 pools): only the first sink_tokens
    and the trailing window_tokens positions contribute, and a
    fully-dead middle block's content is irrelevant (the kernel skips
    its DMA; the engine retires it back to the allocator)."""
    from pytorchdistributed_tpu.ops.pallas_attention import (
        paged_flash_attention,
    )

    lengths = (40, 64, 23)
    q, pk, pv, tbl, lens, _, _ = _paged_fixture(lengths, kvh=2)
    kw = dict(sink_tokens=8, window_tokens=16)
    ref = paged_attention(q, pk, pv, tbl, lens, **kw)
    got = paged_flash_attention(q[:, 0], pk, pv, tbl, lens, **kw)
    np.testing.assert_allclose(np.asarray(ref[:, 0]), np.asarray(got),
                               atol=2e-5, rtol=2e-5)
    # windowing changed the answer (the mask is real)
    full = paged_attention(q, pk, pv, tbl, lens)
    assert not np.allclose(np.asarray(full[:, 0]), np.asarray(got),
                           atol=1e-3)
    # dead middle blocks are never read: poison them, nothing moves
    bs = pk.shape[1]
    tbl_np = np.asarray(tbl).copy()
    for s, n in enumerate(lengths):
        for bi in range(tbl_np.shape[1]):
            if bi * bs >= 8 and (bi + 1) * bs <= n - 16 + 1:
                tbl_np[s, bi] = 0  # retire: point at trash
    pk = pk.at[0].set(1e6)
    pv = pv.at[0].set(-1e6)
    got2 = paged_flash_attention(q[:, 0], pk, pv, jnp.asarray(tbl_np),
                                 lens, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(got2),
                               atol=2e-5, rtol=2e-5)
    # int8 pool through the same mask
    kc, ks, vc, vs = _quantize_fixture_pool(pk, pv)
    refq = paged_attention(q, kc, vc, tbl, lens, k_scale=ks, v_scale=vs,
                           **kw)
    gotq = paged_flash_attention(q[:, 0], kc, vc, tbl, lens,
                                 k_scale=ks, v_scale=vs, **kw)
    np.testing.assert_allclose(np.asarray(refq[:, 0]), np.asarray(gotq),
                               atol=2e-5, rtol=2e-5)


# the kernel's lane arithmetic at the widths that matter: rows of a whole
# number of 128-lane tiles (what the serving cells run), the `test`
# model's 64, a head size that is no power of two, GQA groups
LANE_CASES = {
    "w128_mha": dict(heads=8, kvh=8, d=16),
    "w128_gqa4": dict(heads=8, kvh=2, d=64),
    "w256": dict(heads=4, kvh=4, d=64),
    "test_size": dict(heads=4, kvh=4, d=16),
    "test_size_gqa": dict(heads=4, kvh=2, d=16),
    "d24": dict(heads=6, kvh=3, d=24),
}


@pytest.mark.parametrize("pool", ["float", "int8", "window", "stacked"])
@pytest.mark.parametrize("case", LANE_CASES)
def test_paged_flash_lane_dense_parity(case, pool):
    """The kernel against the gather path over the lane-dense pool, at
    every width above: float and int8 pools, sink + window, a dead slot
    (length 0) and a full one, and one layer read out of a layer-stacked
    pool by its index (the scanned stack's carry)."""
    from pytorchdistributed_tpu.ops.pallas_attention import (
        paged_flash_attention,
    )

    geo = LANE_CASES[case]
    q, pk, pv, tbl, lens, _, _ = _paged_fixture((0, 64, 23, 9), **geo)
    kw = {}
    if pool == "int8":
        pk, ks, pv, vs = _quantize_fixture_pool(pk, pv, geo["d"])
        kw = dict(k_scale=ks, v_scale=vs)
    if pool == "window":
        kw = dict(sink_tokens=8, window_tokens=16)
    ref = paged_attention(q, pk, pv, tbl, lens, **kw)[:, 0]
    if pool == "stacked":
        other = jnp.full_like(pk, 1e6)
        got = paged_flash_attention(
            q[:, 0], jnp.stack([other, pk, other]),
            jnp.stack([other, pv, other]), tbl, lens, layer=jnp.int32(1))
    else:
        got = paged_flash_attention(q[:, 0], pk, pv, tbl, lens, **kw)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               atol=2e-5, rtol=2e-5)


def test_paged_flash_bf16_pool_matches_reference():
    """The serving dtype: a bf16 pool read by the kernel (float32 inside)
    stays within bf16 output rounding of the gather path."""
    from pytorchdistributed_tpu.ops.pallas_attention import (
        paged_flash_attention,
    )

    q, pk, pv, tbl, lens, _, _ = _paged_fixture((5, 17, 40, 64), heads=4,
                                                kvh=2, d=64)
    q, pk, pv = (a.astype(jnp.bfloat16) for a in (q, pk, pv))
    ref = paged_attention(q, pk, pv, tbl, lens)[:, 0]
    got = paged_flash_attention(q[:, 0], pk, pv, tbl, lens)
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(got, np.float32),
                               atol=1e-2, rtol=1e-2)


# The kernel walks a slot's table a tile of entries at a time (ISSUE 33):
# 16 entries at this fixture's block of 8, so a table of 40 entries is
# two tiles and a half (padded to three), and positions 127 | 128 are a
# tile's edge.
TILE_TABLE = 40
TILE_LENGTHS = (0, 127, 128, 129, TILE_TABLE * 8 - 1)
TILE_CASES = {
    "float": dict(),
    "bf16": dict(dtype=jnp.bfloat16, tol=1e-2),
    "int8": dict(int8=True),
    "grouped": dict(geo=dict(heads=8, kvh=2, d=64)),
    # the run of retired blocks covers whole tiles of the longest slot
    "window": dict(kw=dict(sink_tokens=8, window_tokens=16)),
    # and, with no sink, its first tile: the walk starts where the
    # window does
    "window_nosink": dict(kw=dict(sink_tokens=0, window_tokens=16)),
}


def test_paged_flash_tile_rule():
    """The tile comes from the shapes: 128 positions where the table and
    VMEM allow (8 entries at the serve cells' block of 16), never more
    than the table holds, fewer where two tiles of K and V rows would
    pass the VMEM the kernel gives them, and never under one entry."""
    from pytorchdistributed_tpu.ops.pallas_attention import _tile_blocks

    assert _tile_blocks(16, 2 * 1024 * 2, 64) == 8     # the serve cells
    assert _tile_blocks(8, 2 * 32 * 4, TILE_TABLE) == 16
    assert _tile_blocks(8, 2 * 32 * 4, 8) == 8         # a short table
    assert _tile_blocks(16, 2 * 8192 * 4, 64) == 2     # rows of 64 KB
    assert _tile_blocks(16, 2 * 65536 * 4, 64) == 1


@pytest.mark.parametrize("case", TILE_CASES)
def test_paged_flash_tile_edges(case):
    """Parity with the gather path where the kernel's tiles end: a slot
    one position under, at and one over a tile's edge, a dead slot
    (length 0) beside one that fills a table the tile does not divide;
    for float, bf16, int8, grouped and windowed pools."""
    from pytorchdistributed_tpu.ops.pallas_attention import (
        _tile_blocks,
        paged_flash_attention,
    )

    spec = TILE_CASES[case]
    geo = spec.get("geo", dict(kvh=2))
    q, pk, pv, tbl, lens, _, _ = _paged_fixture(
        TILE_LENGTHS, mb=TILE_TABLE, **geo)
    assert TILE_TABLE % _tile_blocks(
        pk.shape[1], 2 * pk.shape[2] * 4, TILE_TABLE)
    kw = dict(spec.get("kw", {}))
    if spec.get("int8"):
        pk, ks, pv, vs = _quantize_fixture_pool(pk, pv, geo.get("d", 16))
        kw.update(k_scale=ks, v_scale=vs)
    if "dtype" in spec:
        q, pk, pv = (a.astype(spec["dtype"]) for a in (q, pk, pv))
    ref = paged_attention(q, pk, pv, tbl, lens, **kw)[:, 0]
    got = paged_flash_attention(q[:, 0], pk, pv, tbl, lens, **kw)
    tol = spec.get("tol", 2e-5)
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(got, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("kw", [{}, dict(sink_tokens=8, window_tokens=16)],
                         ids=["full", "window"])
def test_paged_flash_dead_tiles_never_read(kw):
    """The kernel's cost follows the live tokens: a tile wholly past a
    slot's length — or wholly inside the run of blocks a sliding window
    has retired — starts no copy and computes nothing. Its table entries
    pointed at a block of NaN leave the output equal and finite (one
    product with a NaN, even at probability 0, would show)."""
    from pytorchdistributed_tpu.ops.pallas_attention import (
        _tile_blocks,
        paged_flash_attention,
    )

    q, pk, pv, tbl, lens, _, _ = _paged_fixture(
        TILE_LENGTHS, mb=TILE_TABLE, kvh=2)
    bs = pk.shape[1]
    tile = bs * _tile_blocks(bs, 2 * pk.shape[2] * 4, TILE_TABLE)
    want = paged_flash_attention(q[:, 0], pk, pv, tbl, lens, **kw)
    poison = pk.shape[0]
    pk = jnp.concatenate([pk, jnp.full_like(pk[:1], jnp.nan)])
    pv = jnp.concatenate([pv, jnp.full_like(pv[:1], jnp.nan)])
    tbl_np = np.asarray(tbl).copy()
    dead = 0
    for s, n in enumerate(TILE_LENGTHS):
        for lo in range(0, TILE_TABLE * bs, tile):
            retired = kw and lo >= 8 and lo + tile <= n - 16 + 1
            if lo > n or retired:
                tbl_np[s, lo // bs:(lo + tile) // bs] = poison
                dead += 1
    assert dead == (7 if kw else 6)      # of the 15 tiles
    got = paged_flash_attention(q[:, 0], pk, pv, jnp.asarray(tbl_np), lens,
                                **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# Where a slot's live rows start is an operand (ISSUE 35): one rule for a
# sliding window, a tumbling one and none. Cases of (lengths, starts,
# table entries, sinks); a length of -1 with a start of 0 is how a pool
# is read of which the query sees no row yet.
def _tumbling(lengths, win):
    return tuple(win * (n // win) for n in lengths)


START_CASES = {
    "zero": dict(lengths=(5, 17, 40, 63), starts=(0, 0, 0, 0)),
    "inside_a_block": dict(lengths=(5, 17, 40, 63), starts=(3, 10, 21, 60)),
    # a tumbling window of two blocks: the start is a block's first row
    "tumbling": dict(lengths=(5, 16, 40, 63),
                     starts=_tumbling((5, 16, 40, 63), 16)),
    # 16 entries a tile at this fixture's block of 8: the walk starts in
    # the second and the third tile, and at a tile's second position
    "past_the_first_tile": dict(lengths=(200, 300, 319, 130),
                                starts=(136, 256, 264, 129), mb=40),
    # a start past the length, and a pool none of whose rows is seen
    "nothing_to_attend": dict(lengths=(5, -1, 40, 9, -1, 33),
                              starts=(0, 0, 41, 10, 5, 0)),
    "nothing_at_all": dict(lengths=(-1, -1), starts=(0, 0)),
    # a free slot ticks along at length 0 over a table of trash
    "free_slot": dict(lengths=(0, 33, 0), starts=(0, 0, 0), free=(0, 2)),
    # the sliding window and the sinks, as the static mask hands them over
    "sliding": dict(lengths=(40, 64, 23, 9),
                    starts=(25, 49, 8, 0), window=16),
    "sink_sliding": dict(lengths=(40, 64, 23, 9), starts=(25, 49, 8, 0),
                         window=16, sink=8),
}


def _masked_oracle(q, k_full, v_full, mask):
    """(out [slots, heads, d], log-sum-exp [slots, heads]) of one softmax
    of ``q [slots, heads, d]`` over the rows ``[slots, n, heads, d]``
    that ``mask [slots, n]`` lets through, in float64; zeros and -inf
    where it lets none."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k_full, v_full))
    scores = np.einsum("shd,snhd->shn", q, k) / np.sqrt(q.shape[-1])
    scores = np.where(mask[:, None], scores, -np.inf)
    top = scores.max(-1, keepdims=True)
    p = np.exp(scores - np.where(np.isfinite(top), top, 0.0))
    total = p.sum(-1)
    out = np.einsum("shn,snhd->shd", p, v) / np.maximum(total, 1e-300)[
        ..., None]
    with np.errstate(divide="ignore"):
        return out, np.where(total > 0, top[..., 0] + np.log(total), -np.inf)


def _span_mask(rows, lengths, starts, sink=0):
    """[slots, rows]: the positions ``start <= j <= length`` and, up to
    the length, ``j < sink`` beside them."""
    pos = np.arange(rows)
    n, st = np.asarray(lengths)[:, None], np.asarray(starts)[:, None]
    return (pos <= n) & ((pos >= st) | (pos < sink))


@pytest.mark.parametrize("case", START_CASES)
def test_paged_flash_starts(case):
    """A slot attends ``start <= j <= length``: the output and each
    head's log-sum-exp against one dense softmax under that mask, with
    every table entry wholly before the start (past the sinks) or past
    the length pointed at a block of NaN, which must never be read; a
    slot with nothing to attend gives zeros and -inf; where the case is
    a sliding window, the static ``window_tokens`` gives the same."""
    from pytorchdistributed_tpu.ops.pallas_attention import (
        paged_flash_attention,
    )

    spec = START_CASES[case]
    lengths, starts = spec["lengths"], spec["starts"]
    sink, mb = spec.get("sink", 0), spec.get("mb", 8)
    q, pk, pv, tbl, _, kf, vf = _paged_fixture(
        tuple(max(n, 0) for n in lengths), kvh=2, mb=mb)
    bs = pk.shape[1]
    poison = pk.shape[0]
    pk = jnp.concatenate([pk, jnp.full_like(pk[:1], jnp.nan)])
    pv = jnp.concatenate([pv, jnp.full_like(pv[:1], jnp.nan)])
    tbl_np = np.asarray(tbl).copy()
    for s, (n, st) in enumerate(zip(lengths, starts)):
        for j in range(mb):
            if j * bs > n or (j * bs >= sink and (j + 1) * bs <= st):
                tbl_np[s, j] = poison
    for s in spec.get("free", ()):
        # every entry the trash block, whose rows are zeros here
        tbl_np[s] = 0
        kf, vf = kf.at[s].set(0.0), vf.at[s].set(0.0)
    lens = jnp.asarray(lengths, jnp.int32)
    want, want_lse = _masked_oracle(
        q[:, 0], kf, vf, _span_mask(kf.shape[1], lengths, starts, sink))
    got, lse = paged_flash_attention(
        q[:, 0], pk, pv, jnp.asarray(tbl_np), lens,
        starts=jnp.asarray(starts, jnp.int32), sink_tokens=sink,
        return_lse=True)
    got, lse = np.asarray(got), np.asarray(lse)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-5)
    empty = ~np.isfinite(want_lse[:, 0])
    assert empty.sum() == sum(
        n < 0 or (st > n and not sink) for n, st in zip(lengths, starts))
    assert (got[empty] == 0).all()
    if not any(starts):
        # no `starts` at all: the kernel is told statically that every
        # slot attends from its first row, and leaves them unread
        same = paged_flash_attention(
            q[:, 0], pk, pv, jnp.asarray(tbl_np), lens, return_lse=True)
        np.testing.assert_array_equal(np.asarray(same[0]), got)
        np.testing.assert_array_equal(np.asarray(same[1]), lse)
    if "window" in spec:
        # the static mask is the same rule, handed over as `starts`
        same = paged_flash_attention(
            q[:, 0], pk, pv, jnp.asarray(tbl_np), lens, sink_tokens=sink,
            window_tokens=spec["window"])
        np.testing.assert_array_equal(np.asarray(same), got)
        with pytest.raises(ValueError, match="pass one"):
            paged_flash_attention(q[:, 0], pk, pv, tbl, lens, starts=lens,
                                  window_tokens=spec["window"])


def test_two_pools_merged_by_their_log_sum_exp_are_one_softmax():
    """Two calls, each over its own pool through its own table, merged by
    their log-sum-exp, equal one softmax over the rows of both: what a
    model with two kinds of row under one softmax needs of the kernel
    (models/eva.py). One slot sees no row of the second pool, one none
    of either."""
    from pytorchdistributed_tpu.ops.pallas_attention import (
        merge_attention_parts,
        paged_flash_attention,
    )

    la, sa = (40, 17, 63, -1), (16, 0, 48, 0)
    lb, sb = (23, -1, 5, -1), (0, 0, 0, 0)
    q, pka, pva, tbla, _, kfa, vfa = _paged_fixture((40, 17, 63, 0), kvh=2)
    _, pkb, pvb, tblb, _, kfb, vfb = _paged_fixture((23, 0, 5, 0), kvh=2,
                                                    seed=1)
    parts = [paged_flash_attention(
        q[:, 0], pk, pv, tbl, jnp.asarray(n, jnp.int32),
        starts=jnp.asarray(st, jnp.int32), return_lse=True)
        for pk, pv, tbl, n, st in ((pka, pva, tbla, la, sa),
                                   (pkb, pvb, tblb, lb, sb))]
    got = np.asarray(merge_attention_parts(parts))
    # the second pool's rows after the first's
    rows = kfa.shape[1]
    want, _ = _masked_oracle(
        q[:, 0], jnp.concatenate((kfa, kfb), axis=1),
        jnp.concatenate((vfa, vfb), axis=1),
        np.concatenate([_span_mask(rows, la, sa), _span_mask(rows, lb, sb)],
                       axis=1))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert (got[3] == 0).all() and np.abs(got[:3]).min() > 0


def test_head_sums_at_whole_tile_heads():
    """A head that is a whole number of 128-lane tiles (EvaByte's 128) is
    one lane reduce a slice, and sums as the masked form does."""
    from pytorchdistributed_tpu.ops.pallas_attention import _head_sums

    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 512)),
                    jnp.float32)
    for d in (16, 64, 128, 256):
        want = jnp.repeat(x.reshape(8, -1, d).sum(-1), d, axis=-1)
        np.testing.assert_allclose(np.asarray(_head_sums(x, d)),
                                   np.asarray(want), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the paged engine: parity, reuse, chunking, preemption, leaks


def _mixed_requests(vocab, seed=0, n=5, lens=None, news=None):
    rng = np.random.default_rng(seed)
    lens = lens or [5, 9, 3, 13, 7, 11, 4, 8, 6][:n]
    news = news or [6, 3, 8, 5, 4, 7, 2, 5, 3][:n]
    prompts = [rng.integers(0, vocab, (m,)).astype(np.int32) for m in lens]
    return prompts, news


def _assert_paged_parity(model_cls, cfg, *, num_slots, lens=None,
                         news=None, n=5, **engine_kw):
    model = model_cls(cfg)
    params = _init(model)
    dm = model_cls(dataclasses.replace(cfg, decode=True))
    prompts, news = _mixed_requests(cfg.vocab_size, n=n, lens=lens,
                                    news=news)
    engine = ServingEngine(model, params, num_slots=num_slots,
                           prefill_bucket=16, block_size=8, **engine_kw)
    assert engine.paged
    engine.warmup(prompt_lens=(8, 16))
    reqs = []
    for p, n_new in zip(prompts, news):
        reqs.append(engine.submit(p, max_new_tokens=n_new))
        engine.step()  # staggered arrivals interleave with decoding
    engine.run_until_idle()
    for p, n_new, r in zip(prompts, news, reqs):
        ref = generate(dm, params, jnp.asarray(p)[None],
                       max_new_tokens=n_new)
        np.testing.assert_array_equal(
            r.output_ids, np.asarray(ref)[0],
            err_msg=f"request {r.id} (preemptions={r.preemptions})")
    engine.close()  # the leak invariant runs on every parity drive
    return reqs


def test_parity_paged_engine():
    """The ISSUE 7 acceptance anchor: greedy paged-engine outputs are
    bitwise-equal to generate() for a staggered mixed-length admission
    order (chunked prefill + block growth on every request)."""
    _assert_paged_parity(GPT2, gpt2_config("test", num_layers=2,
                                           max_seq_len=64),
                         num_slots=3, n=5)


def test_parity_paged_block_boundary_lengths():
    """Prompt lengths straddling the block grid (k*bs - 1, k*bs,
    k*bs + 1 at bs=8) — the partial-tail-block and exact-boundary write
    paths — plus generations that cross block boundaries mid-decode."""
    _assert_paged_parity(GPT2, gpt2_config("test", num_layers=2,
                                           max_seq_len=64),
                         num_slots=3, lens=[7, 8, 9, 16, 17],
                         news=[9, 8, 7, 6, 5], n=5)


def test_parity_paged_llama_gqa():
    """Per-row RoPE offsets + grouped-query heads through the pool
    scatter/gather layout."""
    _assert_paged_parity(Llama, llama_config("test", max_seq_len=64),
                         num_slots=2, n=4)


def test_parity_paged_int8():
    """--quant int8_fwd composes with paging: the chunk/tick run the
    same quantized projections, outputs bitwise-equal to quantized
    generate()."""
    _assert_paged_parity(GPT2, gpt2_config("test", num_layers=2,
                                           max_seq_len=64,
                                           quant="int8_fwd"),
                         num_slots=2, n=3)


def test_parity_paged_unrolled_layers():
    """scan_layers=False: per-layer (unstacked) pool/table leaves ride
    the same name-based override plumbing."""
    _assert_paged_parity(GPT2, gpt2_config("test", num_layers=2,
                                           max_seq_len=64,
                                           scan_layers=False),
                         num_slots=2, n=3)


def test_prefix_reuse_hits_and_parity():
    """Shared-system-prompt admissions reuse cached blocks (hit tokens
    > 0, fewer prefill chunks) and stay bitwise-equal: reused K/V is
    bit-identical to recomputed K/V."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=128)
    model = GPT2(cfg)
    params = _init(model)
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    rng = np.random.default_rng(2)
    system = rng.integers(0, cfg.vocab_size, (40,)).astype(np.int32)
    engine = ServingEngine(model, params, num_slots=2, prefill_bucket=16,
                           block_size=8, prefill_chunk=16)
    engine.warmup(prompt_lens=(16, 48))
    reqs = []
    for i in range(3):
        tail = rng.integers(0, cfg.vocab_size, (5 + i,)).astype(np.int32)
        p = np.concatenate([system, tail])
        reqs.append((p, engine.submit(p, max_new_tokens=5)))
        engine.run_until_idle()  # serialize so each later one can hit
    first, later = reqs[0][1], [r for _, r in reqs[1:]]
    assert first.prefix_hit_tokens == 0
    assert all(r.prefix_hit_tokens >= 40 - 8 for r in later)
    assert all(r.prefill_chunks < first.prefill_chunks for r in later)
    for p, r in reqs:
        ref = generate(dm, params, jnp.asarray(p)[None], max_new_tokens=5)
        np.testing.assert_array_equal(r.output_ids, np.asarray(ref)[0])
    s = engine.summary()
    assert s["prefix_hit_rate"] > 0
    assert s["prefix_cache"]["hits"] == 2
    engine.close()


def test_chunked_prefill_interleaves_with_decode():
    """A long admission must not head-of-line-block resident streams:
    while request B's prompt prefills chunk by chunk, resident request A
    keeps receiving one token per step."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=128)
    model = GPT2(cfg)
    engine = ServingEngine(model, _init(model), num_slots=2,
                           prefill_bucket=16, block_size=8,
                           prefill_chunk=16)
    engine.warmup(prompt_lens=(16, 64))
    rng = np.random.default_rng(4)
    a = engine.submit(rng.integers(0, cfg.vocab_size, (5,)),
                      max_new_tokens=20)
    engine.step()
    assert len(a.new_tokens) >= 1
    # 60-token prompt = 4 chunks of 16: admission spans multiple steps
    b = engine.submit(rng.integers(0, cfg.vocab_size, (60,)),
                      max_new_tokens=4)
    deliveries = []
    while b.slot is None and not b.done:
        before = len(a.new_tokens)
        engine.step()
        deliveries.append(len(a.new_tokens) - before)
    assert len(deliveries) >= 3  # the admission really was chunked
    assert all(d == 1 for d in deliveries[:-1]), (
        f"resident stream starved during chunked prefill: {deliveries}")
    engine.run_until_idle()
    assert a.finish_reason == "length" and b.finish_reason == "length"
    engine.close()


def test_run_until_idle_finishes_stranded_prefill():
    """Regression: a resident stream retiring on the very step a
    neighbor's chunked prefill is mid-flight used to leave queue and
    slots empty with the admission stranded — run_until_idle must keep
    stepping until the in-flight prefill completes too."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=128)
    model = GPT2(cfg)
    engine = ServingEngine(model, _init(model), num_slots=2,
                           prefill_bucket=16, block_size=8,
                           prefill_chunk=16)
    engine.warmup(prompt_lens=(16, 64))
    rng = np.random.default_rng(6)
    a = engine.submit(rng.integers(0, cfg.vocab_size, (5,)),
                      max_new_tokens=3)
    engine.step()  # admission + tick deliver 2: one-token budget left
    b = engine.submit(rng.integers(0, cfg.vocab_size, (60,)),
                      max_new_tokens=3)
    engine.step()  # chunk 1 of b + a's final token: a retires here
    assert a.done and not b.done and engine.prefilling_count == 1
    assert engine.active_count == 0 and engine.queue_depth == 0
    engine.run_until_idle()
    assert b.done and b.finish_reason == "length"
    assert len(b.new_tokens) == 3
    engine.close()


def test_preemption_requeues_and_stays_bitwise():
    """A pool too small for the offered load preempts the youngest
    resident (blocks freed, request requeued); its continuation resumes
    by re-prefilling prompt + generated — every request's final output
    stays bitwise-equal to generate(), and nothing retraces."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=128)
    model = GPT2(cfg)
    params = _init(model)
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    engine = ServingEngine(model, params, num_slots=3, prefill_bucket=16,
                           block_size=8, num_blocks=21, prefill_chunk=16)
    engine.warmup(prompt_lens=(16,))
    traces0 = dict(serving_engine.TRACE_COUNTS)
    rng = np.random.default_rng(0)
    ps, rs = [], []
    for i in range(4):
        p = rng.integers(0, cfg.vocab_size, (20 + 7 * i,)).astype(np.int32)
        ps.append(p)
        rs.append(engine.submit(p, max_new_tokens=30))
        engine.step()
    engine.run_until_idle()
    assert sum(r.preemptions for r in rs) >= 1, "pool never pressured"
    assert engine.summary()["preemptions"] >= 1
    for p, r in zip(ps, rs):
        ref = generate(dm, params, jnp.asarray(p)[None], max_new_tokens=30)
        np.testing.assert_array_equal(
            r.output_ids, np.asarray(ref)[0],
            err_msg=f"request {r.id} (preemptions={r.preemptions})")
    assert dict(serving_engine.TRACE_COUNTS) == traces0
    engine.close()


def test_blocks_freed_on_every_exit_path(tmp_path):
    """The ISSUE 7 leak satellite: stop-id retirement, budget
    retirement, deadline expiry (queued / resident / MID-PREFILL) and
    the SIGTERM drain all return their blocks — the pool invariant
    (free + resident == usable) holds mid-run and close()'s teardown
    assertion passes with only radix-cached blocks resident."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=128)
    model = GPT2(cfg)
    engine = ServingEngine(model, _init(model), num_slots=2,
                           prefill_bucket=16, block_size=8,
                           prefill_chunk=16, telemetry_dir=str(tmp_path))
    engine.warmup(prompt_lens=(16, 64))
    rng = np.random.default_rng(1)

    def pool_consistent():
        a = engine._alloc
        assert a.free_count + a.resident == a.usable

    # budget ("length") + stop-id retirement
    r1 = engine.submit(rng.integers(0, cfg.vocab_size, (5,)),
                       max_new_tokens=3)
    engine.run_until_idle()
    stop = r1.new_tokens[0]
    r2 = engine.submit(rng.integers(0, cfg.vocab_size, (5,)),
                       max_new_tokens=50, stop_ids=(stop, 10 ** 6))
    engine.run_until_idle()
    pool_consistent()
    # deadline on queue (no blocks ever allocated) and mid-decode
    r3 = engine.submit(rng.integers(0, cfg.vocab_size, (6,)),
                       max_new_tokens=40, deadline_s=60.0)
    engine.step()
    assert r3.slot is not None
    r3.submit_time -= 120.0
    engine.step()
    assert r3.finish_reason == "deadline"
    pool_consistent()
    # deadline mid-chunked-prefill: blocks allocated, never decoded (a
    # resident stream keeps the admission chunked across steps)
    r5 = engine.submit(rng.integers(0, cfg.vocab_size, (5,)),
                       max_new_tokens=40)
    engine.step()
    r4 = engine.submit(rng.integers(0, cfg.vocab_size, (60,)),
                       max_new_tokens=4, deadline_s=60.0)
    engine.step()
    assert r4.slot is None and engine._prefilling is not None
    r4.submit_time -= 120.0
    engine.step()
    assert r4.finish_reason == "deadline" and engine._prefilling is None
    pool_consistent()
    # SIGTERM drain: the mid-stream resident + a queued request both shed
    r6 = engine.submit(rng.integers(0, cfg.vocab_size, (90,)),
                       max_new_tokens=4)
    engine.request_drain()
    engine.step()
    assert r5.finish_reason == "drained" and r6.finish_reason == "drained"
    assert 0 < len(r5.new_tokens) < 40
    pool_consistent()
    engine.close()  # asserts free + resident == pool, radix-only residue
    rows = [json.loads(x) for x in
            (tmp_path / "serve_metrics_rank0.jsonl")
            .read_text().strip().splitlines()]
    reasons = [r["finish_reason"] for r in rows if r["kind"] == "request"]
    assert reasons.count("deadline") == 2
    assert reasons.count("drained") == 2
    assert any(r["kind"] == "pool" for r in rows)


def test_zero_recompiles_steady_state_paged():
    """After warmup, a mixed paged load — any in-bucket prompt length,
    prefix hits AND misses, block growth, retire + readmit — triggers
    ZERO retraces and zero recompiles of the paged tick/chunk pair."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    engine = ServingEngine(model, _init(model), num_slots=3,
                           prefill_bucket=16, block_size=8)
    engine.warmup(prompt_lens=(8, 16))
    traces = dict(serving_engine.TRACE_COUNTS)
    sizes = (paged_prefill_chunk._cache_size(),
             paged_decode_tick._cache_size())
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    for i in range(8):
        if i % 3 == 0:  # prefix-cache hits exercise the reuse path
            p = np.concatenate([shared, rng.integers(
                0, cfg.vocab_size, (int(rng.integers(1, 8)),))]).astype(
                    np.int32)
        else:
            p = rng.integers(0, cfg.vocab_size,
                             (int(rng.integers(1, 16)),)).astype(np.int32)
        engine.submit(p, max_new_tokens=int(rng.integers(1, 6)))
        engine.step()
    engine.run_until_idle()
    assert dict(serving_engine.TRACE_COUNTS) == traces
    assert (paged_prefill_chunk._cache_size(),
            paged_decode_tick._cache_size()) == sizes
    engine.close()


def test_report_cli_renders_serving_table(tmp_path):
    """The telemetry report CLI grows a serving / prefix-cache section
    from the serve_metrics JSONL (ISSUE 7 satellite)."""
    from pytorchdistributed_tpu.telemetry.report import render

    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    engine = ServingEngine(model, _init(model), num_slots=2,
                           prefill_bucket=16, block_size=8,
                           telemetry_dir=str(tmp_path))
    engine.warmup(prompt_lens=(16,))
    rng = np.random.default_rng(0)
    p = rng.integers(0, cfg.vocab_size, (20,)).astype(np.int32)
    engine.submit(p, max_new_tokens=4)
    engine.run_until_idle()
    engine.submit(p, max_new_tokens=4)  # guaranteed prefix hit
    engine.run_until_idle()
    engine.close()
    out = render(tmp_path)
    assert "serving (per rank" in out
    assert "prefix cache" in out
    assert "-token blocks" in out
    # KV compression columns (ISSUE 13): high-water resident bytes and
    # the pool's effective capacity at its storage dtype
    assert "kv resident" in out
    assert "tokens @ bf16" in out
    # the hit tokens column is non-zero: reuse reached the report
    import re
    m = re.search(r"^\s+0\s+\d+\s+\S+ ms\s+(\d+)", out, re.M)
    assert m and int(m.group(1)) > 0, out


# ---------------------------------------------------------------------------
# KV compression (ISSUE 13): int8 pool, window retirement, Pallas default


def test_allocator_midstream_decref_recycles():
    """ISSUE 13 regression: blocks decref'd MID-STREAM (window
    retirement) go straight back onto the free list and are handed out
    again while the retiring owner still holds its other blocks —
    and once everyone exits, check_leaks is clean."""
    a = BlockAllocator(8, 4)
    mine = a.alloc(5)
    retired = mine[1:3]
    for b in retired:
        assert a.decref(b)          # mid-stream retirement frees NOW
    assert a.free_count == 4
    theirs = a.alloc(4)             # a newcomer is backed by them
    assert theirs is not None and set(retired) <= set(theirs)
    for b in [mine[0], *mine[3:], *theirs]:
        a.decref(b)
    a.check_leaks()                 # stream finish leaves no residue


def test_parity_paged_engine_pallas():
    """The Pallas decode tick forced on CPU (interpret=True): greedy
    token streams match the gather engine's exactly on this seeded
    mixed workload. (Flash reassociates the softmax, so the pinned
    cross-engine contract is token equality on a deterministic
    backend; the BITWISE-vs-generate() contract stays on gather.)"""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    params = _init(model)
    outs = {}
    for mode in ("gather", "pallas"):
        engine = ServingEngine(model, params, num_slots=3,
                               prefill_bucket=16, block_size=8,
                               paged_attn=mode)
        assert engine.paged_attn == mode
        assert engine.summary()["paged_attn"] == mode
        engine.warmup(prompt_lens=(8, 16))
        prompts, news = _mixed_requests(cfg.vocab_size, n=4)
        rs = []
        for p, n in zip(prompts, news):
            rs.append(engine.submit(p, max_new_tokens=n))
            engine.step()
        engine.run_until_idle()
        outs[mode] = [list(r.new_tokens) for r in rs]
        engine.close()
    assert outs["pallas"] == outs["gather"]


def test_parity_paged_engine_int8_readers_agree():
    """kv_dtype="int8" end-to-end: blocks are quantized at write time
    and both pool readers — the reference gather and the Pallas kernel
    — decode the SAME greedy streams from the same compressed pool
    (one canonical dequant, ops.quant.kv_dequantize, pinned across
    readers). The int8 pool is smaller than bf16's at equal blocks."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    params = _init(model)
    outs, hbm = {}, {}
    for mode in ("gather", "pallas"):
        engine = ServingEngine(model, params, num_slots=2,
                               prefill_bucket=16, block_size=8,
                               kv_dtype="int8", paged_attn=mode)
        assert engine.summary()["kv_dtype"] == "int8"
        engine.warmup(prompt_lens=(8, 16))
        prompts, news = _mixed_requests(cfg.vocab_size, seed=5, n=3)
        rs = []
        for p, n in zip(prompts, news):
            rs.append(engine.submit(p, max_new_tokens=n))
            engine.step()
        engine.run_until_idle()
        assert all(r.finish_reason == "length" for r in rs)
        outs[mode] = [list(r.new_tokens) for r in rs]
        hbm[mode] = engine.kv_hbm_bytes
        engine.close()
    assert outs["pallas"] == outs["gather"]
    bf16 = ServingEngine(model, params, num_slots=2, prefill_bucket=16,
                         block_size=8)
    # int8 codes + fp32 scale planes vs bf16: (d + 4) / 2d bytes per
    # token-head — a real shrink at any head_dim > 4
    assert hbm["gather"] < bf16.kv_hbm_bytes
    bf16.close()


def test_window_retirement_recycles_blocks_midstream():
    """Sink+window streams hand their fully-dead middle blocks back to
    the pool WHILE STILL DECODING: two long streams that would
    overflow the pool at full attention (and preempt) instead run to
    completion preemption-free on the blocks retirement recycles —
    and close()'s leak invariant still passes."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=128)
    model = GPT2(cfg)
    params = _init(model)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
               for _ in range(2)]

    def run(**kw):
        engine = ServingEngine(model, params, num_slots=2,
                               prefill_bucket=16, block_size=8,
                               num_blocks=21, **kw)
        engine.warmup(prompt_lens=(16,))
        rs = [engine.submit(p, max_new_tokens=80) for p in prompts]
        engine.run_until_idle()
        assert all(r.finish_reason == "length" for r in rs)
        assert all(len(r.new_tokens) == 80 for r in rs)
        s = engine.summary()
        engine.close()
        return s

    full = run()
    win = run(kv_sink_tokens=8, kv_window_tokens=32)
    # full attention can't hold 2 x 96 tokens in 20 usable blocks
    assert full["preemptions"] >= 1
    # windowed: middle blocks retire back mid-stream, nobody preempts
    assert win["preemptions"] == 0
    assert win["retired_blocks"] > 0
    assert win["peak_blocks_used"] < full["peak_blocks_used"]
    assert win["kv_window_tokens"] == 32 and win["kv_sink_tokens"] == 8


def test_zero_recompiles_compressed_path():
    """The ISSUE 13 tripwire: steady-state decode on the int8 +
    windowed engine — block growth, MID-STREAM window retirement,
    retire + readmit — triggers ZERO retraces and zero recompiles
    after warmup (scale planes and the static window mask are baked
    into the compiled pair, never re-traced per step)."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    engine = ServingEngine(model, _init(model), num_slots=2,
                           prefill_bucket=16, block_size=8,
                           kv_dtype="int8", kv_sink_tokens=8,
                           kv_window_tokens=16)
    engine.warmup(prompt_lens=(8, 16))
    traces = dict(serving_engine.TRACE_COUNTS)
    sizes = (paged_prefill_chunk._cache_size(),
             paged_decode_tick._cache_size())
    rng = np.random.default_rng(13)
    for i in range(6):
        p = rng.integers(0, cfg.vocab_size,
                         (int(rng.integers(1, 16)),)).astype(np.int32)
        engine.submit(p, max_new_tokens=int(rng.integers(25, 40)))
        engine.step()
    engine.run_until_idle()
    s = engine.summary()
    assert s["retired_blocks"] > 0, "retirement never exercised"
    assert dict(serving_engine.TRACE_COUNTS) == traces
    assert (paged_prefill_chunk._cache_size(),
            paged_decode_tick._cache_size()) == sizes
    engine.close()


def test_paged_attn_env_and_auto_resolution(monkeypatch):
    """PTD_PAGED_ATTN seeds the default; "auto" resolves per backend
    (pallas on TPU, gather elsewhere — this suite runs on CPU); an
    explicit constructor arg beats the env."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    params = _init(model)

    def attn(**kw):
        e = ServingEngine(model, params, num_slots=2, block_size=8, **kw)
        mode = e.paged_attn
        e.close()
        return mode

    monkeypatch.delenv("PTD_PAGED_ATTN", raising=False)
    assert attn() == "gather"                      # auto on CPU
    monkeypatch.setenv("PTD_PAGED_ATTN", "pallas")
    assert attn() == "pallas"                      # env seeds default
    assert attn(paged_attn="gather") == "gather"   # arg beats env
    monkeypatch.setenv("PTD_PAGED_ATTN", "auto")
    assert attn() == "gather"


def test_kv_compression_validations():
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    params = _init(model)
    with pytest.raises(ValueError, match="paged-engine knobs"):
        ServingEngine(model, params, num_slots=2, kv_dtype="int8")
    with pytest.raises(ValueError, match="paged_attn"):
        ServingEngine(model, params, num_slots=2, block_size=8,
                      paged_attn="bogus")
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(model, params, num_slots=2, block_size=8,
                      kv_dtype="fp8")
    with pytest.raises(ValueError, match="multiple"):
        ServingEngine(model, params, num_slots=2, block_size=8,
                      kv_window_tokens=12)
    with pytest.raises(ValueError, match="kv_window_tokens"):
        ServingEngine(model, params, num_slots=2, block_size=8,
                      kv_sink_tokens=8)


def test_paged_validations():
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    params = _init(model)
    with pytest.raises(ValueError, match="divide"):
        ServingEngine(model, params, num_slots=2, block_size=7)
    with pytest.raises(ValueError, match="full-context"):
        ServingEngine(model, params, num_slots=2, block_size=8,
                      num_blocks=4)
    from pytorchdistributed_tpu.models.transformer import TransformerConfig
    with pytest.raises(ValueError, match="decode"):
        TransformerConfig(kv_block_size=8, kv_blocks=4)
    with pytest.raises(ValueError, match="multiple"):
        TransformerConfig(decode=True, decode_slots=2, kv_block_size=7,
                          kv_blocks=4, max_seq_len=64)

"""Continuous-batching serving engine: a slot-based KV-cache scheduler
over a single compiled decode step.

`inference.generate()` is a one-shot batch call: every request in a batch
must start together and run to the same max_new_tokens, so short requests
pay for long ones and new arrivals wait for the whole batch to drain.
This module is the Orca-style fix (iteration-level scheduling) with a
vLLM-style fixed-slot cache, realized TPU-natively:

  * the engine owns ONE persistent KV cache of ``num_slots`` rows
    (`[slots, max_seq_len, kv_heads, head_dim]` per layer — the model's
    existing ``decode=True`` cache collection at ``decode_slots > 0``,
    where every position counter is a per-row vector);
  * a jitted **decode tick** (`decode_tick`) advances ALL slots one token
    per call — per-slot lengths ride the position counters/masks inside
    the model, per-request sampling params are dynamic `[slots]` arrays
    (`inference.sample_slots`), and the cache is donated, so steady-state
    decode is one fixed-shape program with zero retraces and zero cache
    copies;
  * a jitted **prefill** (`prefill_into_slot`) runs one request's chunked
    prompt forward (batch 1, prompts right-padded to a bucket multiple so
    variable lengths hit a handful of programs) and writes the resulting
    cache rows into a free slot via `dynamic_update_slice`, rewinding
    that slot's position counters to the true prompt length;
  * a host-side scheduler (`ServingEngine`) keeps the request queue,
    admits a prefill whenever a slot frees, retires on stop-ids /
    max-token budget, streams tokens per request (callbacks or the
    `stream()` iterator), and bridges TTFT / tokens-per-s / queue depth /
    slot occupancy into telemetry/ (serving.telemetry).

Paged mode (ISSUE 7, ``block_size > 0``) swaps the dense per-slot cache
for a block-table **paged KV pool** (vLLM's PagedAttention,
TPU-natively): one donated pool of fixed-size KV blocks + per-slot
block tables gathered inside the same compiled tick, a host-side
**radix prefix cache** admitting shared prompt prefixes by refcounted
block reference instead of re-prefilling, **chunked prefill**
interleaving long admissions with decode ticks, and preempt-requeue
under pool pressure — HBM then bounds actual resident tokens, not
slots x max_seq_len. Tables/lengths are host numpy stamped into each
call as dynamic arguments, so all of it is host bookkeeping between
two fixed compiled programs (paged_decode_tick / paged_prefill_chunk).

Speculative mode (ISSUE 8, ``spec_k > 0``, paged only) replaces the
one-token tick with **draft-and-verify**: a draft model proposes
``spec_k`` tokens per slot inside one fused compiled program
(`spec_decode_tick` — draft rollout scan + ONE k+1-wide target forward
through the same paged scatter/gather + the lossless rejection kernel,
both pools donated), and each slot advances by its accepted length + 1.
Decode is memory-bound, so accepted tokens per target forward is the
decode-rate multiplier; losslessness means draft quality can only cost
acceptance rate, never correctness.

Composition: params may be dp/tp sharded (pass the mesh) and quantized
(`--quant` int8 policies) exactly as generate() accepts them — the tick
and prefill run the same decode einsums under the same logical rules.
Greedy outputs are bitwise-equal to generate()'s per request, for any
admission order — prefix hits, chunk boundaries, preemptions and
speculation included (tests/test_serving.py + tests/test_paging.py +
tests/test_spec.py pin it).
"""

from __future__ import annotations

import base64
import collections
import contextlib
import dataclasses
import functools
import hashlib
import itertools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from pytorchdistributed_tpu.inference import (
    KV_POOL_LEAVES,
    STATE_LEAVES,
    _zero_cache,
    draft_and_verify,
    draft_and_verify_heads,
    kv_cache_bytes,
    sample_slots,
    stop_ids_tuple,
)
from pytorchdistributed_tpu.serving.paging import (
    RadixPrefixCache,
    SlotPool,
)
from pytorchdistributed_tpu.serving.telemetry import ServingTelemetry
from pytorchdistributed_tpu.serving.weights import cast_only, served, wider
from pytorchdistributed_tpu.telemetry.spans import span
from pytorchdistributed_tpu.telemetry.tracing import (
    TraceContext,
    from_unix as _trace_from_unix,
    to_unix as _trace_to_unix,
)

# Traced-body invocation counter (same discipline as inference.
# TRACE_COUNTS): the zero-recompiles-after-warmup guarantee is asserted
# against these — a steady-state serving loop must never move them.
TRACE_COUNTS: collections.Counter = collections.Counter()


def slot_models(model, num_slots: int):
    """(tick_model, prefill_model) for a causal LM module.

    The tick model decodes with per-row position counters
    (``decode_slots=num_slots``; batch == slots); the prefill model is the
    plain scalar-counter decode model at batch 1 (a single request starts
    from position 0, so it needs no per-row state). Both attend over the
    full max_seq_len window (slots sit at arbitrary positions) on the
    cache-masked dense path — the training-time attention backend knob
    does not apply to decode, so it is pinned to "dense" here to keep the
    clone warning-free."""
    cfg = dataclasses.replace(
        model.cfg, decode=True, attention="dense", decode_attend_len=None,
        decode_slots=0)
    return (model.clone(cfg=dataclasses.replace(
                cfg, decode_slots=num_slots)),
            model.clone(cfg=cfg))


def _leaf_name(path) -> str:
    return getattr(path[-1], "key", str(path[-1]))


# The paged pool's cache-collection leaves, with the offset of the block
# axis from the END of each leaf's shape (the scanned stack's pool has a
# leading layer axis, so the end is the stable anchor): K/V pools are
# [..., kv_blocks, block_size, kv_heads*head_dim] (one lane-dense row a
# token), the int8 scale planes [..., kv_blocks, block_size, kv_heads];
# the block axis is ndim-3 in both, and in the latent, indexer-key and
# window rows of a model with two cache kinds. Everything that moves
# blocks — the compiled gather/scatter pair, the prefill-chunk merge,
# the export/import payloads and the fleet prefix stream — keys off this
# one table, which is how the int8 pool's scales ride every existing
# block-transport path without a second code path.
POOL_LEAF_AXIS = dict.fromkeys(KV_POOL_LEAVES, 3)

def _pool_block_axis(name: str, ndim: int) -> int:
    """Block-axis index for a pool leaf, by its (path or bare) name."""
    return ndim - POOL_LEAF_AXIS[name.rsplit("/", 1)[-1]]


#: KV wire-payload schema version (ISSUE 13): bumped when the payload's
#: pool-leaf set or meaning changes (v2 added kv_dtype + the int8 scale
#: planes; v3 carries K/V rows lane-dense, ``[..., blocks, block_size,
#: kv_heads*head_dim]``, and a scanned stack's leaves under the stack's
#: own path). import_kv_blocks rejects any other version loudly — a bf16
#: replica must never scatter an int8 payload's codes into its pool.
KV_WIRE_VERSION = 3


@functools.partial(
    jax.jit,
    static_argnames=("model", "candidates"),
    donate_argnames=("cache",))
def decode_tick(model, weights, cache, tokens, key_data, counts,
                temperature, top_k, top_p, *, candidates: int):
    """Advance every slot one token: ONE model apply over ``[slots, 1]``
    last-tokens (each slot reads/writes its own cache row at its own
    position) + the per-slot sampler. Free/retired slots tick along as
    greedy garbage — the fixed-shape price of zero retraces; the host
    simply ignores their outputs.

    ``key_data``/``counts`` carry each request's seeded stream: token n of
    a request is sampled with fold_in(key(seed), n), so outputs are
    deterministic per request no matter which slot or admission order it
    got (the determinism test's property)."""
    TRACE_COUNTS["decode_tick"] += 1
    logits, mut = model.apply({"params": weights, "cache": cache},
                              tokens[:, None], mutable=["cache"])
    keys = jax.random.wrap_key_data(key_data)
    subs = jax.vmap(jax.random.fold_in)(keys, counts)
    nxt = sample_slots(logits[:, 0].astype(jnp.float32), subs,
                       temperature, top_k, top_p, candidates=candidates)
    return mut["cache"], nxt


@functools.partial(
    jax.jit,
    static_argnames=("model", "candidates"),
    donate_argnames=("cache",))
def prefill_into_slot(model, weights, cache, prompt, true_len, slot,
                      key_data, count, temperature, top_k, top_p, *,
                      candidates: int):
    """Admit one request: a chunked prompt forward (batch 1, prompt
    right-padded to the bucket length — ``true_len`` is dynamic) fills a
    fresh single-row cache, whose rows are written into ``slot`` of the
    engine cache via dynamic_update_slice; the slot's position counters
    are rewound to ``true_len`` (pad rows sit beyond the position mask
    until decode overwrites them — the same trick as
    inference.generate_bucketed). Returns (cache, first_token): sampling
    the first token here is what makes TTFT one prefill, not
    prefill + a decode tick. ``count`` is the sampled token's fold_in
    index — 0 on a fresh admission, the generated-so-far length when a
    request RESUMES from tokens (submit(generated=...) — the router's
    failover path), so a resumed sampled stream continues its seeded
    PRNG sequence exactly where the dead replica left it."""
    TRACE_COUNTS["prefill"] += 1
    fresh = _zero_cache(model, prompt)
    logits, mut = model.apply({"params": weights, "cache": fresh}, prompt,
                              mutable=["cache"])
    last = jax.lax.dynamic_slice_in_dim(logits, true_len - 1, 1, axis=1)
    keys = jax.random.wrap_key_data(key_data[None])
    subs = jax.vmap(jax.random.fold_in)(keys, count[None])
    first = sample_slots(last[:, 0].astype(jnp.float32), subs,
                         temperature[None], top_k[None], top_p[None],
                         candidates=candidates)[0]

    def merge(path, big, small):
        if _leaf_name(path) in ("index", "pos_index"):
            # rewind to the true prompt length (the padded prefill
            # advanced the single-row counters to the bucket length)
            return jnp.where(jnp.arange(big.shape[-1]) == slot,
                             true_len, big)
        # K/V rows: [..., slots, max_seq_len, kv_heads, head_dim] — the
        # slot axis is always 4 dims from the end, scanned-layer or not
        axis = big.ndim - 4
        start = tuple(slot if d == axis else 0 for d in range(big.ndim))
        return jax.lax.dynamic_update_slice(big, small, start)

    new_cache = jax.tree_util.tree_map_with_path(merge, cache, mut["cache"])
    return new_cache, first


def paged_slot_models(model, num_slots: int, block_size: int,
                      num_blocks: int, *, kv_dtype: str = "bf16",
                      kv_sink_tokens: int = 0, kv_window_tokens: int = 0,
                      paged_attn: str = "gather",
                      per_slot_kv_limits: bool = False):
    """(tick_model, chunk_model) for the PAGED engine: both share the one
    block pool (pool shapes carry no slot dim); the tick model decodes
    all ``num_slots`` rows, the chunk model runs one request's prefill
    chunk at batch 1 (``decode_slots=1``) against the same pool. Same
    dense-path pinning rationale as slot_models. The KV-compression
    knobs (ISSUE 13) ride here: ``kv_dtype`` picks the pool's storage
    dtype (int8 adds the scale-plane cache leaves), sink/window set the
    STATIC attention-window mask, and ``paged_attn`` picks the decode
    tick's attention implementation (the chunked-prefill path always
    gathers — chunks run s > 1, the Pallas kernel is decode-only).
    ``per_slot_kv_limits`` (ISSUE 15) swaps the static window mask for
    per-slot ``kv_sinks``/``kv_windows`` cache leaves on the TICK model
    only — the chunk model keeps the static mask (one request's prefill
    has no slot row to read), so prefill always masks under the pool
    window and the per-request override takes effect from the first
    decoded token."""
    cfg = dataclasses.replace(
        model.cfg, decode=True, attention="dense", decode_attend_len=None,
        decode_slots=num_slots, kv_block_size=block_size,
        kv_blocks=num_blocks, kv_dtype=kv_dtype,
        kv_sink_tokens=kv_sink_tokens, kv_window_tokens=kv_window_tokens,
        paged_attn=paged_attn, per_slot_kv_limits=per_slot_kv_limits)
    return (model.clone(cfg=cfg),
            model.clone(cfg=dataclasses.replace(
                cfg, decode_slots=1, per_slot_kv_limits=False)))


def _override_paging(cache, tables, lengths):
    """Stamp the host scheduler's block tables + per-slot lengths over
    the cache collection's counter/table leaves (the paged stack holds
    one of each for all its layers, beside the embedder's). The device
    copies are write-through scratch: the engine re-stamps from host
    state on every compiled call, which is what makes prefix sharing,
    block growth and preemption pure host bookkeeping. ``tables`` is
    {table leaf: [slots, pages]}, one entry a kind of cache the model
    keeps (``block_table`` alone where it keeps one pool). A model with
    a recurrent state also reads where each slot's tokens of the call
    end (``stop``): one past its length for a live slot, its length 0
    for a free one, whose state the tick then leaves as it is."""
    def fix(path, leaf):
        name = _leaf_name(path)
        if name in ("index", "pos_index"):
            return jnp.broadcast_to(lengths, leaf.shape).astype(leaf.dtype)
        if name == "stop":
            return jnp.broadcast_to(jnp.where(lengths > 0, lengths + 1, 0),
                                    leaf.shape).astype(leaf.dtype)
        if name in tables:
            return jnp.broadcast_to(tables[name],
                                    leaf.shape).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def paged_tick_logits(model, weights, cache, tables, lengths, tokens):
    """The model's part of a paged tick: logits ``[slots, 1, vocab]`` and
    the mutated collections. Apart from `paged_decode_tick` so that a test
    can read every slot's logits at the engine's own operands."""
    cache = _override_paging(cache, tables, lengths)
    return model.apply({"params": weights, "cache": cache},
                       tokens[:, None], mutable=["cache", "counters"])


@functools.partial(
    jax.jit,
    static_argnames=("model", "candidates"),
    donate_argnames=("cache",))
def paged_decode_tick(model, weights, cache, tables, lengths, tokens,
                      key_data, counts, temperature, top_k, top_p, *,
                      candidates: int):
    """The paged twin of decode_tick: same one-apply-over-[slots, 1]
    shape, but K/V live in the donated block POOL and each slot's rows
    are table-gathered inside the compiled program
    (models/transformer.py paged branch). ``tables``/``lengths`` arrive
    from host state every call — free slots carry all-trash tables and
    length 0, so their garbage ticks write the reserved trash block and
    can never corrupt a live request's blocks."""
    TRACE_COUNTS["paged_decode_tick"] += 1
    logits, mut = paged_tick_logits(model, weights, cache, tables, lengths,
                                    tokens)
    keys = jax.random.wrap_key_data(key_data)
    subs = jax.vmap(jax.random.fold_in)(keys, counts)
    nxt = sample_slots(logits[:, 0].astype(jnp.float32), subs,
                       temperature, top_k, top_p, candidates=candidates)
    # the third output is what the model counted on the device this tick
    # (the "counters" collection: a few scalars that ride back with the
    # tokens); empty, and absent from the program, for a model that
    # counts nothing
    return mut["cache"], nxt, dict(mut.get("counters", {}))


def paged_chunk_logits(model, weights, cache, chunk, start, table_row,
                       stop=None, slot=None):
    """The model's part of a prefill chunk: logits ``[1, C, vocab]`` of
    every position of the chunk and the mutated cache. Apart from
    `paged_prefill_chunk` for the same reason as `paged_tick_logits`.
    ``table_row`` is {table leaf: [pages]}: the one request's rows of
    `_override_paging`'s tables. ``stop`` is where the request's tokens
    end (its true length; by default the chunk's end): a recurrent state
    takes no step past it and the device's counters count no position
    past it. A model with a recurrent state (`STATE_LEAVES`) also takes
    its ``slot``, whose state row the chunk reads (from zeros where
    ``start`` is 0: the model's own rule). The mutated collections are
    the cache and what the model counted ("counters")."""
    if stop is None:
        stop = start + chunk.shape[1]

    def shrink(path, leaf):
        # the chunk model is the same module tree at decode_slots=1:
        # pool leaves pass through untouched (no slot dim), counter and
        # table leaves shrink to the one-request row, a state leaf to
        # the slot's row
        name = _leaf_name(path)
        if name in ("index", "pos_index"):
            return jnp.broadcast_to(
                start, leaf.shape[:-1] + (1,)).astype(leaf.dtype)
        if name == "stop":
            return jnp.broadcast_to(
                stop, leaf.shape[:-1] + (1,)).astype(leaf.dtype)
        if name in STATE_LEAVES:
            return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)
        if name in table_row:
            row = table_row[name]
            return jnp.broadcast_to(
                row, leaf.shape[:-2] + (1,) + row.shape).astype(leaf.dtype)
        return leaf

    small = jax.tree_util.tree_map_with_path(shrink, cache)
    return model.apply({"params": weights, "cache": small}, chunk,
                       mutable=["cache", "counters"])


@functools.partial(
    jax.jit,
    static_argnames=("model", "candidates"),
    donate_argnames=("cache",))
def paged_prefill_chunk(model, weights, cache, chunk, start, table_row,
                        true_len, key_data, count, temperature, top_k,
                        top_p, slot=None, *, candidates: int):
    """One fixed-size prefill chunk of one request, written straight
    into ITS blocks of the shared pool. ``chunk`` is [1, C] tokens
    covering absolute positions [start, start+C) (right-padded past
    true_len — pad K/V lands beyond the position mask, or in the trash
    block past max_seq_len, until decode overwrites it); ``start`` is
    dynamic, so a prefix-cache hit just starts chunking at the first
    unmatched block with the SAME compiled program. Chunking long
    prompts into C-token calls is what lets the scheduler interleave
    resident slots' decode ticks between chunks — a long admission no
    longer head-of-line-blocks their TTFT. Samples the request's next
    token at the (dynamic) last true position — only the final chunk's
    sample is used; ``count`` is its fold_in index (> 0 when a preempted
    request resumes mid-generation). ``slot``: the request's slot, where
    the model keeps a recurrent state a slot (`paged_chunk_logits`); its
    row is written back in place. The third output is what the model
    counted on the device over the chunk's positions before
    ``true_len``, as the tick's is (empty for a model that counts
    nothing)."""
    TRACE_COUNTS["paged_prefill_chunk"] += 1
    logits, mut = paged_chunk_logits(model, weights, cache, chunk, start,
                                     table_row, true_len, slot)

    def merge(path, big, new):
        # only the pools mutated (K/V codes AND, on an int8 pool, their
        # scale planes) and the slot's state row; the big cache's
        # counter/table leaves are scratch the engine re-stamps anyway
        name = _leaf_name(path)
        if name in STATE_LEAVES:
            return jax.lax.dynamic_update_slice_in_dim(big, new, slot, 1)
        return new if name in POOL_LEAF_AXIS else big

    new_cache = jax.tree_util.tree_map_with_path(merge, cache, mut["cache"])
    off = jnp.clip(true_len - 1 - start, 0, chunk.shape[1] - 1)
    last = jax.lax.dynamic_slice_in_dim(logits, off, 1, axis=1)
    keys = jax.random.wrap_key_data(key_data[None])
    subs = jax.vmap(jax.random.fold_in)(keys, count[None])
    first = sample_slots(last[:, 0].astype(jnp.float32), subs,
                         temperature[None], top_k[None], top_p[None],
                         candidates=candidates)[0]
    return new_cache, first, dict(mut.get("counters", {}))


@functools.partial(
    jax.jit,
    static_argnames=("model", "draft_model", "spec_k", "candidates"),
    donate_argnames=("cache", "draft_cache"))
def spec_decode_tick(model, draft_model, weights, draft_weights, cache,
                     draft_cache, tables, lengths, tokens, key_data, counts,
                     temperature, top_k, top_p, k_eff=None, *, spec_k: int,
                     candidates: int):
    """The speculative twin of paged_decode_tick (ISSUE 8): ONE compiled
    program per tick that (a) rolls the draft model ``spec_k + 1``
    single-token steps from each slot's last token (k proposals, plus one
    extra step that only writes the last proposal's K/V so a
    fully-accepted slot's next round attends a complete draft cache),
    (b) scores all k+1 positions with ONE target forward — the verify
    chunk [last_tok, d_1..d_k] rides the same paged scatter/gather path,
    so draft K/V lands in table-mapped blocks and anything past
    max_seq_len drops into trash block 0 — and (c) runs the lossless
    rejection kernel (inference.speculative_accept) per slot.

    Both caches share the SAME host-stamped block tables: the draft pool
    is a second (shallower) set of block arrays addressed by identical
    block ids, so growth/preemption/trash bookkeeping is one table. No
    rollback pass exists anywhere: the host advances each slot's length
    by its accepted count + 1, and the NEXT round's k+1 writes at
    [len, len+k] always cover this round's rejected-suffix K/V before
    anything can attend it (the position mask bounds reads at len).

    Returns ``(cache, draft_cache, tokens [slots, k+1], n_accept
    [slots])`` — the host delivers exactly n_accept+1 tokens per slot.
    Randomness: a round at generated-count c derives every stream from
    fold_in(request_key, c) (draft step j → fold_in twice with tag 1 and
    j; accept uniforms tag 2; residual tag 3), so sampled outputs are a
    function of (prompt, sampling params, seed, scheduling) alone — the
    same request in any admission order reproduces its tokens. One
    honest caveat vs the plain tick: a preempt-RESUME re-derives the
    resumed token from the prefill sampler rather than the interrupted
    round's streams, so a SAMPLED stream's post-resume suffix is a
    different (equally target-distributed) sample than the
    uninterrupted run's; greedy streams are bitwise-stable across
    preemption either way (tests/test_spec.py pins that).

    ``k_eff`` (optional [slots] int32, ISSUE 16) is the per-slot
    EFFECTIVE proposal depth — a DYNAMIC operand of this fixed
    spec_k-wide program, so the host can move it every tick (adaptive k)
    with zero recompiles; see inference.speculative_accept for why the
    masked width stays lossless."""
    TRACE_COUNTS["spec_decode_tick"] += 1
    cache = _override_paging(cache, tables, lengths)
    draft_cache = _override_paging(draft_cache, tables, lengths)
    keys = jax.random.wrap_key_data(key_data)
    base = jax.vmap(jax.random.fold_in)(keys, counts)
    step1 = jax.vmap(jax.random.fold_in, in_axes=(0, None))(base, 1)
    draft_keys = jax.vmap(
        lambda j: jax.vmap(jax.random.fold_in, in_axes=(0, None))(step1, j)
    )(jnp.arange(spec_k + 1))
    acc_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(base, 2)
    unif = jax.vmap(lambda k_: jax.random.uniform(k_, (spec_k,)))(acc_keys)
    res_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(base, 3)
    return draft_and_verify(
        model, draft_model, weights, draft_weights, cache, draft_cache,
        tokens, draft_keys, unif, res_keys, temperature, top_k, top_p,
        spec_k=spec_k, candidates=candidates, k_eff=k_eff)


@functools.partial(
    jax.jit,
    static_argnames=("model", "draft_model", "spec_k", "candidates"),
    donate_argnames=("cache", "draft_cache"))
def spec_decode_tick_heads(model, draft_model, weights, draft_weights,
                           cache, draft_cache, tables, lengths,
                           draft_lengths, prev_tokens, prev_idx, tokens,
                           key_data, counts, temperature, top_k, top_p,
                           k_eff=None, *, spec_k: int, candidates: int):
    """spec_decode_tick for a draft carrying multi-token proposal heads
    (ISSUE 16): the draft's spec_k+1-step sequential rollout collapses to
    ONE forward over each slot's PREVIOUS round's emitted buffer
    (``prev_tokens`` [slots, spec_k+1], live up to ``prev_idx``), whose
    writes land at ``draft_lengths`` — the previous round's start, one
    round behind the target's ``lengths`` — through the SAME host-stamped
    block tables. The verify forward, rejection kernel, PRNG stream
    derivation, and host advance-by-n+1 contract are byte-for-byte
    spec_decode_tick's, so losslessness and stream reproducibility never
    fork; only the number of draft forwards per round changes (k+1 → 1).
    Same returns; extra host duty: after the round, ``prev_tokens`` :=
    this round's emitted buffer, ``prev_idx`` := n_accept,
    ``draft_lengths`` := the pre-advance length + 1."""
    TRACE_COUNTS["spec_decode_tick_heads"] += 1
    cache = _override_paging(cache, tables, lengths)
    draft_cache = _override_paging(draft_cache, tables, draft_lengths)
    keys = jax.random.wrap_key_data(key_data)
    base = jax.vmap(jax.random.fold_in)(keys, counts)
    step1 = jax.vmap(jax.random.fold_in, in_axes=(0, None))(base, 1)
    draft_keys = jax.vmap(
        lambda j: jax.vmap(jax.random.fold_in, in_axes=(0, None))(step1, j)
    )(jnp.arange(spec_k + 1))
    acc_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(base, 2)
    unif = jax.vmap(lambda k_: jax.random.uniform(k_, (spec_k,)))(acc_keys)
    res_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(base, 3)
    return draft_and_verify_heads(
        model, draft_model, weights, draft_weights, cache, draft_cache,
        tokens, prev_tokens, prev_idx, draft_keys, unif, res_keys,
        temperature, top_k, top_p, spec_k=spec_k, candidates=candidates,
        k_eff=k_eff)


def nan_params(weights):
    """Every inexact leaf replaced with NaN — the serving chaos twin of
    the training ``nan@step`` fault, shared by the in-process replica
    and the subprocess worker so both chaos modes poison IDENTICALLY
    (params_finite is the tripwire that must catch either)."""
    return jax.tree_util.tree_map(
        lambda x: (jnp.full_like(x, jnp.nan)
                   if jnp.issubdtype(x.dtype, jnp.inexact) else x),
        weights)


@jax.jit
def params_finite(weights):
    """ONE device scalar answering "are these params all finite?" — the
    engine-health tripwire the replica router polls (a NaN'd replica
    must be declared sick from its *params*, not inferred from garbage
    token ids, which stay perfectly finite ints). One reduction per
    leaf + a stacked all(): cheap enough to run every few ticks, and a
    separate compiled program, so the committed tick/prefill HLO pins
    never move."""
    TRACE_COUNTS["params_finite"] += 1
    leaves = [jnp.all(jnp.isfinite(x))
              for x in jax.tree_util.tree_leaves(weights)
              if jnp.issubdtype(x.dtype, jnp.inexact)]
    if not leaves:
        return jnp.bool_(True)
    return jnp.all(jnp.stack(leaves))


@jax.jit
def kv_block_gather(cache, block_ids):
    """Pool gather for the KV block stream (ISSUE 12): pull
    ``block_ids`` rows out of every pool leaf in one compiled call.
    ``block_ids`` is always padded to kv_pages with the trash block, so
    EVERY export — any request length, any prefix offset — is this one
    fixed-shape program; the host slices the trash rows off after the
    sync. Returns the pool leaves (cached_key/cached_value per layer
    stack) in tree-flatten order."""
    TRACE_COUNTS["kv_block_gather"] += 1
    return [jnp.take(leaf, block_ids,
                     axis=_pool_block_axis(_leaf_name(path), leaf.ndim))
            for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
            if _leaf_name(path) in POOL_LEAF_AXIS]


@functools.partial(jax.jit, donate_argnames=("cache",))
def kv_block_scatter(cache, block_ids, payload):
    """The import half: scatter ``payload`` (one array per pool leaf,
    block axis padded to kv_pages like ``block_ids``) into the donated
    pool at ``block_ids``. The pad rows carry zeros addressed at the
    trash block — duplicate index-0 writes land harmlessly where
    garbage already goes — so this too is ONE program for every
    import."""
    TRACE_COUNTS["kv_block_scatter"] += 1
    it = iter(payload)

    def put(path, leaf):
        if _leaf_name(path) not in POOL_LEAF_AXIS:
            return leaf
        axis = _pool_block_axis(_leaf_name(path), leaf.ndim)
        return leaf.at[(slice(None),) * axis + (block_ids,)].set(
            next(it).astype(leaf.dtype))

    return jax.tree_util.tree_map_with_path(put, cache)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs (dynamic per slot — any mix of requests
    shares the one compiled tick). temperature 0 = greedy; top_k <= 0 and
    top_p >= 1 disable their filters; seed starts the request's private
    PRNG stream."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0


@dataclasses.dataclass
class KVBlockPayload:
    """One parked request's complete handoff state on the KV block
    stream (ISSUE 12): everything a decode-role replica needs to
    activate the stream mid-flight, bitwise-equal to a colocated
    engine — the prompt, the tokens generated so far (the prefill-role
    engine's sampled first token rides here, already delivered), the
    sampling contract, and the exact K/V of positions [0, true_len)
    gathered off the exporter's pool. ``leaves`` pairs each pool leaf's
    tree-path name with its ``[num_blocks, ...]`` host array — the
    importer checks the names against its own pool so a geometry or
    model mismatch fails loudly instead of decoding garbage."""

    prompt: np.ndarray
    generated: list[int]
    true_len: int
    block_size: int
    max_new_tokens: int
    sampling: SamplingParams
    stop_ids: tuple
    leaves: list
    # pool storage dtype the leaves were gathered from ("bf16"|"int8" —
    # int8 payloads also carry the scale-plane leaves) and the payload
    # schema version; both are checked at import so a mismatched fleet
    # fails with a sentence, not garbage tokens
    kv_dtype: str = "bf16"
    wire_version: int = KV_WIRE_VERSION
    # the ORIGIN router submit as unix-epoch seconds (ISSUE 17
    # satellite): the importer maps it onto its own clock so a
    # handed-off stream's end-to-end TTFT measures from the FIRST
    # router submit, not decode-replica-local; None from pre-ISSUE-17
    # exporters
    origin_t: float | None = None
    # the request's TraceContext wire dict — the handoff keeps the
    # stream on ONE connected trace across replicas
    trace: dict | None = None
    # per-request sliding-window override (ISSUE 18 satellite): the
    # EFFECTIVE kv_sink/kv_window the exporting slot ran under, so a
    # reattached/handed-off stream keeps its tightened mask — without
    # these, retired-block positions (gathered as trash) would be
    # ATTENDED on the importer. None = the importer's pool defaults
    kv_sink: int | None = None
    kv_window: int | None = None

    @property
    def num_blocks(self) -> int:
        return -(-self.true_len // self.block_size)

    @property
    def nbytes(self) -> int:
        return int(self.prompt.nbytes
                   + sum(a.nbytes for _, a in self.leaves))


@dataclasses.dataclass
class PrefixBlockPayload:
    """A radix-cached prefix shipped over the same KV stream (the
    fleet prefix cache's remote-hit path): whole cached blocks of
    ``tokens`` (a block-multiple), gathered from the owning replica's
    pool, for the receiver to adopt into its pool + radix as REMOTE
    entries — prefilled once per fleet, served everywhere."""

    tokens: np.ndarray
    block_size: int
    leaves: list
    kv_dtype: str = "bf16"
    wire_version: int = KV_WIRE_VERSION

    @property
    def num_blocks(self) -> int:
        return len(self.tokens) // self.block_size

    @property
    def nbytes(self) -> int:
        return int(self.tokens.nbytes
                   + sum(a.nbytes for _, a in self.leaves))


def _np_dtype(name: str):
    """np.dtype by name, reaching into ml_dtypes for the low-precision
    names (bfloat16 et al.) numpy itself cannot resolve."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _leaves_to_wire(leaves) -> list:
    return [dict(name=n, dtype=str(a.dtype), shape=list(a.shape),
                 data=base64.b64encode(
                     np.ascontiguousarray(a).tobytes()).decode("ascii"))
            for n, a in leaves]


def _leaves_from_wire(rows) -> list:
    return [(r["name"],
             np.frombuffer(base64.b64decode(r["data"]),
                           dtype=_np_dtype(r["dtype"]))
             .reshape(r["shape"]))
            for r in rows]


def kv_payload_to_wire(p: KVBlockPayload) -> dict:
    """Serialize a KVBlockPayload for the subprocess worker's line-JSON
    protocol (base64 block arrays — the same wire the submit/step ops
    ride, so disagg needs no second transport)."""
    return dict(prompt=[int(t) for t in p.prompt],
                generated=list(p.generated), true_len=p.true_len,
                block_size=p.block_size,
                max_new_tokens=p.max_new_tokens,
                sampling=dataclasses.asdict(p.sampling),
                stop_ids=list(p.stop_ids),
                leaves=_leaves_to_wire(p.leaves),
                kv_dtype=p.kv_dtype, wire_version=p.wire_version,
                origin_t=p.origin_t, trace=p.trace,
                kv_sink=p.kv_sink, kv_window=p.kv_window)


def kv_payload_from_wire(d: dict) -> KVBlockPayload:
    return KVBlockPayload(
        prompt=np.asarray(d["prompt"], np.int32),
        generated=[int(t) for t in d["generated"]],
        true_len=int(d["true_len"]), block_size=int(d["block_size"]),
        max_new_tokens=int(d["max_new_tokens"]),
        sampling=SamplingParams(**d["sampling"]),
        stop_ids=tuple(d["stop_ids"]),
        leaves=_leaves_from_wire(d["leaves"]),
        # pre-v2 senders carried neither field: report them as v1 so the
        # importer's version check names the mismatch instead of KeyError
        kv_dtype=str(d.get("kv_dtype", "bf16")),
        wire_version=int(d.get("wire_version", 1)),
        origin_t=d.get("origin_t"), trace=d.get("trace"),
        # absent on pre-ISSUE-18 senders: None = pool defaults, the
        # exact pre-18 behavior
        kv_sink=(None if d.get("kv_sink") is None
                 else int(d["kv_sink"])),
        kv_window=(None if d.get("kv_window") is None
                   else int(d["kv_window"])))


def prefix_payload_to_wire(p: PrefixBlockPayload) -> dict:
    return dict(tokens=[int(t) for t in p.tokens],
                block_size=p.block_size,
                leaves=_leaves_to_wire(p.leaves),
                kv_dtype=p.kv_dtype, wire_version=p.wire_version)


def prefix_payload_from_wire(d: dict) -> PrefixBlockPayload:
    return PrefixBlockPayload(
        tokens=np.asarray(d["tokens"], np.int32),
        block_size=int(d["block_size"]),
        leaves=_leaves_from_wire(d["leaves"]),
        kv_dtype=str(d.get("kv_dtype", "bf16")),
        wire_version=int(d.get("wire_version", 1)))


class Request:
    """One submitted generation: prompt + budget + sampling + stop ids,
    and the engine-filled lifecycle (tokens as they stream, timestamps,
    finish reason). Host-side only — nothing here touches the device."""

    _ids = itertools.count()

    def __init__(self, prompt, max_new_tokens: int,
                 sampling: SamplingParams, stop_ids: tuple[int, ...],
                 on_token=None, deadline_s: float | None = None,
                 generated=None):
        self.id = next(Request._ids)
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = max_new_tokens
        self.sampling = sampling
        self.stop_ids = stop_ids
        self.on_token = on_token
        self.deadline_s = deadline_s
        # resume-from-tokens (the router's failover redispatch): the
        # stream's already-generated suffix is pre-seeded, so admission
        # re-prefills prompt+generated and the engine only ever DELIVERS
        # tokens past ``resumed_from`` — on_token never re-fires for
        # tokens the client already has
        self.new_tokens: list[int] = ([int(t) for t in generated]
                                      if generated is not None else [])
        self.resumed_from = len(self.new_tokens)
        self.slot: int | None = None
        self.done = False
        self.finish_reason: str | None = None
        self.submit_time: float | None = None
        # when the request left the engine's queue for the prefill lane
        # (paged) or a slot (dense), stamped ONCE: a preempted request
        # that is admitted again keeps it. first_token_time - submit_time
        # == (admit_time - submit_time) + (first_token_time - admit_time):
        # queue wait plus prefill span
        self.admit_time: float | None = None
        self.first_token_time: float | None = None
        self.finish_time: float | None = None
        # paged-engine lifecycle (zero on the dense engine): prompt
        # tokens admitted from the prefix cache instead of prefill
        # compute, chunked-prefill calls paid, and preempt-requeue
        # round-trips survived (a preempted request resumes by
        # re-prefilling prompt + already-generated tokens — its output
        # stream is unchanged)
        self.prefix_hit_tokens = 0
        self.prefill_chunks = 0
        self.preemptions = 0
        # disaggregation lifecycle (ISSUE 12): prompt tokens admitted
        # from REMOTE (fleet-shipped) prefix blocks, and the
        # prefill-role handoff flags — a prefill_only request parks
        # after its first token for export_kv_blocks instead of
        # decoding in place
        self.remote_hit_tokens = 0
        self.prefill_only = False
        self.parked = False
        # speculative-decoding lifecycle (zero when spec is off): draft
        # proposals made for this request and how many the target kept —
        # accepted/draft is the request's acceptance rate
        self.draft_tokens = 0
        self.accepted_tokens = 0
        # per-request KV window/sink override (ISSUE 15): the EFFECTIVE
        # values after submit() clamps to the pool config; None = the
        # engine-static defaults
        self.kv_window: int | None = None
        self.kv_sink: int | None = None
        # persistent sessions (ISSUE 18): a tagged stream's KV parks in
        # the engine's HBM-resident session tier at retirement instead
        # of freeing; ``tenant`` rides along for the store's per-tenant
        # session budgets
        self.session_id: str | None = None
        self.tenant: str = "default"
        # distributed tracing (ISSUE 17): the router-minted
        # TraceContext this request's engine-side spans attach to, and
        # the ORIGIN router submit mapped onto THIS process's
        # perf_counter clock (equal to submit_time for a locally-born
        # request; earlier for one that arrived via handoff/redispatch)
        self.trace = None
        self.origin_submit_time: float | None = None

    @property
    def ttft_e2e_s(self) -> float | None:
        """Time to first token measured from the ORIGIN router submit
        (ISSUE 17 satellite) — on a handed-off stream this spans queue
        + prefill + handoff end-to-end, where ``ttft_s`` restarts at
        the import. Falls back to ``ttft_s`` when no origin rode in."""
        if self.first_token_time is None:
            return None
        if self.origin_submit_time is None:
            return self.ttft_s
        return self.first_token_time - self.origin_submit_time

    @property
    def output_ids(self) -> np.ndarray:
        """prompt + generated continuation (int32 [len])."""
        return np.concatenate(
            [self.prompt, np.asarray(self.new_tokens, np.int32)])

    @property
    def ttft_s(self) -> float | None:
        """Time to first token, queue wait included."""
        if self.first_token_time is None or self.submit_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def decode_tokens_per_s(self) -> float | None:
        """Post-prefill decode rate of this request (None until done or
        when the request finished at its first token)."""
        if self.finish_time is None or self.first_token_time is None:
            return None
        dt = self.finish_time - self.first_token_time
        # resumed tokens were generated elsewhere — only tokens THIS
        # engine decoded belong in its rate
        n = len(self.new_tokens) - self.resumed_from - 1
        if n <= 0 or dt <= 0:
            return None
        return round(n / dt, 3)


class ServingEngine:
    """The host scheduler over the compiled tick/prefill pair.

    Args:
      model: a causal LM module (GPT2 / Llama ...) — decode or train
        config; the engine derives its slot-decode twin either way.
      params: the trained variables, possibly sharded (pass ``mesh``).
        Held as ONE tree in the model's compute type: the leaves the
        forward pass would only cast to ``cfg.dtype`` are cast here,
        once, not in every tick (``set_params``; serving/weights.py).
      num_slots: concurrent requests resident in the KV cache — the
        engine's batch dim, fixed at compile time.
      prefill_bucket: prompts are right-padded up to this multiple so
        variable lengths reuse a handful of prefill programs (clamped to
        max_seq_len).
      candidates: static top-k candidate width of the per-slot sampler
        (per-request top_k caps here; see inference.sample_slots).
      mesh: optional jax mesh the params live on (tp/dp) — tick/prefill
        trace under it, exactly like generate().
      telemetry / telemetry_dir: a ServingTelemetry (or a run dir to
        build one) for spans + serve-metric JSONL; None = off.
      block_size: > 0 switches to the PAGED KV cache (ISSUE 7): one pool
        of ``num_blocks`` blocks of this many tokens replaces the dense
        per-slot rows — HBM is then bounded by tokens actually resident,
        not slots x max_seq_len. Must divide max_seq_len. 0 = the dense
        engine (unchanged). A model whose config already sets
        kv_block_size/kv_blocks turns paging on implicitly.
      num_blocks: pool size in blocks (block 0 is the reserved trash
        block). Default = dense-equivalent HBM (num_slots full contexts
        + 1); SHRINK it to oversubscribe slots — exhaustion first evicts
        prefix-cache LRU entries, then preempts the youngest resident
        request (requeued; it resumes by re-prefilling prompt +
        generated, its output stream unchanged).
      prefill_chunk: paged prompts prefill in fixed chunks of this many
        tokens (default prefill_bucket, rounded to a block multiple)
        interleaved with decode ticks, so a long admission cannot
        head-of-line-block resident streams' tokens.
      prefix_cache: host-side radix cache over full prompt blocks —
        prompts sharing a cached prefix admit by block REFERENCE
        (refcounted, copy-on-write by construction: shared blocks are
        never written) instead of re-running prefill. On by default in
        paged mode.
      prefill_chunks_per_step: chunk calls per step() once slots are
        decoding (1 = maximally latency-protective interleaving).
      spec_k: > 0 turns on SPECULATIVE decoding (ISSUE 8): every tick a
        draft model proposes spec_k tokens per slot and the target
        verifies all of them in ONE forward (spec_decode_tick) with
        lossless rejection sampling — greedy outputs stay bitwise-equal
        to generate()'s, sampled outputs distribution-equal, whatever
        the draft quality; only the acceptance rate (and the speedup)
        depends on it. Requires the paged engine (block_size > 0):
        rejected-suffix and past-context K/V drop into the trash block
        instead of needing a rollback. 0 = the plain tick (default, no
        behavior change).
      draft_config: the draft's TransformerConfig (same vocab; usually a
        reduced-depth clone of the target — inference.truncated_draft
        builds config+params from the target in one call). None
        self-drafts with the target model itself: acceptance ~1, the
        correctness/bring-up configuration.
      draft_params: the draft's variables (required with draft_config).
        A draft whose config sets ``spec_heads > 0`` (ISSUE 16 —
        inference.make_draft builds one) switches the tick to the
        head-parallel program (spec_decode_tick_heads): one draft
        forward proposes all spec_k tokens instead of a spec_k+1-step
        rollout; needs spec_heads >= spec_k - 1.
      adaptive_k: with spec_k > 0, drive each slot's EFFECTIVE proposal
        depth from its measured acceptance EMA (ISSUE 16): a slot whose
        draft keeps missing proposes fewer tokens next round, one whose
        draft keeps landing proposes the full spec_k. The depth is a
        masked width inside the fixed spec_k-wide compiled program — a
        dynamic operand, ZERO recompiles as it moves — and the rejection
        kernel stays lossless at any depth (the forced-stop bonus token
        draws from the FULL target distribution; greedy streams are
        bitwise-invariant to the mask). Default off: the accounting
        (draft_tokens counts the effective depth) and the extra operand
        change nothing unless asked for.
      kv_dtype: paged pool storage dtype (ISSUE 13): "bf16" (default —
        the model dtype; the bitwise-vs-generate() contract holds) or
        "int8" — blocks store int8 codes plus per-(token, head) fp32
        scale planes (extra cache leaves), quantized at block-write
        time and dequantized inside the attention read
        (ops/quant.kv_quantize / kv_dequantize). ~1.9x more resident
        tokens per HBM byte at equal pool bytes; outputs are
        tolerance-accurate, not bitwise. None inherits the model cfg.
      kv_sink_tokens / kv_window_tokens: sink + sliding-window
        attention over the paged cache (StreamingLLM-style): a query at
        position p attends cache position j iff ``j < kv_sink_tokens or
        j > p - kv_window_tokens``. Both are STATIC block-multiples
        (no retrace as streams grow). Middle blocks that fall fully
        dead are RETIRED mid-stream — decref'd back to the allocator
        while the stream lives — so a long stream holds sink + window
        blocks, not its whole history, and the freed capacity
        immediately backs new admissions. 0/0 = full attention
        (default). None inherits the model cfg.
      paged_attn: the decode tick's attention implementation:
        "gather" (XLA gather + masked dense — the bitwise reference),
        "pallas" (the fused paged flash kernel,
        ops/pallas_attention.paged_flash_attention — no [slots,
        attend_len] gather materialization), or None (default) →
        the PTD_PAGED_ATTN env var, else "auto" = pallas on TPU
        backends where every pool the model keeps (`cfg.cache_kinds`)
        holds per-head key and value rows of whole 128-lane tiles (the
        kernel's own copies move whole tiles): GPT-2's one pool, EVA's
        two, a call each, merged by their log-sum-exp; gather
        elsewhere, and for a model one of whose pools holds latent
        rows ("pallas" is refused there, by the pool's name).
        Prefill chunks and the spec tick's draft rollout always use
        the gather read.
    """

    #: adaptive-k acceptance-EMA smoothing (ISSUE 16): high enough to
    #: track a request moving between easy and hard spans within its own
    #: lifetime, low enough that one unlucky round doesn't crater the
    #: depth
    SPEC_EMA_ALPHA = 0.2

    def __init__(self, model, params, *, num_slots: int = 4,
                 prefill_bucket: int = 128, candidates: int = 64,
                 mesh=None, telemetry: ServingTelemetry | None = None,
                 telemetry_dir=None, block_size: int = 0,
                 num_blocks: int | None = None,
                 prefill_chunk: int | None = None,
                 prefix_cache: bool = True,
                 prefill_chunks_per_step: int = 1,
                 spec_k: int = 0, draft_config=None, draft_params=None,
                 adaptive_k: bool = False,
                 kv_dtype: str | None = None,
                 kv_sink_tokens: int | None = None,
                 kv_window_tokens: int | None = None,
                 paged_attn: str | None = None, trace=None,
                 session_store=None, session_hbm_max: int = 4):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.num_slots = num_slots
        # the kinds of cache the model keeps, the stream's own first
        # (`CacheKind`: the `pool` id on the spans, the table leaf, the
        # window its layers see or 0 for every position, the positions a
        # row stands for, and whether the window slides or tumbles). One
        # pool, unless the model declares more (models/latent.py: a latent
        # pool that grows with the stream, and a window pool whose blocks
        # retire per layer kind; models/eva.py: a pool of one summary row
        # a chunk that grows with the stream, and a pool of the current
        # window's exact rows that retires a whole window at a time)
        self._kinds = tuple(model.cfg.cache_kinds)
        # the kinds with rows (a `SlotPool` each); a kind without a table
        # is a recurrent state a slot (models/ssm.py), sized by the slot
        # count in the model's own cache and moved by no block path
        paged_kinds = [k for k in self._kinds if k.table]
        self._stateful = len(paged_kinds) < len(self._kinds)
        self._pools: list[SlotPool] = []
        self._refuse_two_kinds(
            "the radix prefix cache" if prefix_cache else
            "a speculative tick (spec_k > 0)" if spec_k else
            "a session store" if session_store is not None else
            "the int8 pool" if kv_dtype == "int8" else None)
        self.candidates = candidates
        self.mesh = mesh
        if block_size == 0 and model.cfg.kv_block_size:
            # a model already configured paged carries the knobs
            block_size = model.cfg.kv_block_size
            num_blocks = num_blocks or model.cfg.kv_blocks
        self.paged = block_size > 0
        # KV-compression knobs (ISSUE 13): None inherits the model cfg,
        # so a model already configured int8/windowed just works
        kv_dtype = model.cfg.kv_dtype if kv_dtype is None else kv_dtype
        kv_sink_tokens = (model.cfg.kv_sink_tokens
                          if kv_sink_tokens is None else kv_sink_tokens)
        kv_window_tokens = (model.cfg.kv_window_tokens
                            if kv_window_tokens is None
                            else kv_window_tokens)
        if paged_attn is None:
            paged_attn = (model.cfg.paged_attn
                          if model.cfg.paged_attn != "gather"
                          else os.environ.get("PTD_PAGED_ATTN", "auto"))
        if paged_attn not in ("auto", "gather", "pallas"):
            raise ValueError(
                f"paged_attn must be 'auto', 'gather' or 'pallas', got "
                f"{paged_attn!r}")
        on_tpu = jax.default_backend() == "tpu"
        # the kernel reads per-head key and value rows (`CacheKind.lanes`
        # of them a row), a call a pool, and copies them by its own DMAs,
        # which Mosaic takes in whole 128-lane tiles only
        rowless = [k.kind for k in paged_kinds if not k.lanes]
        ragged = [k.lanes for k in paged_kinds if k.lanes % 128]
        if paged_attn == "auto":
            # backend-aware default: the fused kernel is the hot path on
            # real accelerators wherever every pool of the model is such
            # rows; CPU (tests, dev) keeps the gather read, whose decode
            # tick is bitwise generate()'s
            paged_attn = ("pallas" if on_tpu and not rowless and not ragged
                          else "gather")
        if paged_attn == "pallas" and rowless:
            raise ValueError(
                f"paged_attn='pallas' is not built for this model's "
                f"{rowless[0]!r} pool: the fused kernel is an online "
                f"softmax over per-head key and value rows (one pool, or "
                f"several whose calls are merged by their log-sum-exp), "
                f"and that pool's rows are latents that all heads share, "
                f"read under a learned selection, which it has neither "
                f"the products nor the mask for: read by XLA "
                f"(paged_attn='gather')")
        if paged_attn == "pallas" and on_tpu and ragged:
            raise ValueError(
                f"paged_attn='pallas' on a TPU needs pool rows of whole "
                f"128-lane tiles, and kv_heads*head_dim is {ragged[0]}: "
                f"the kernel's own copies move whole tiles "
                f"(paged_attn='gather' reads any width)")
        if not self.paged and (kv_dtype != "bf16" or kv_sink_tokens
                               or kv_window_tokens):
            raise ValueError(
                "kv_dtype / kv_sink_tokens / kv_window_tokens are "
                "paged-engine knobs (ISSUE 13) — pass block_size > 0")
        self.kv_dtype = kv_dtype
        self.kv_sink_tokens = int(kv_sink_tokens)
        self.kv_window_tokens = int(kv_window_tokens)
        self.paged_attn = paged_attn if self.paged else "gather"
        if self.paged:
            max_len = model.cfg.max_seq_len
            if max_len % block_size:
                raise ValueError(
                    f"block_size {block_size} must divide max_seq_len "
                    f"{max_len}")
            # the stream's own pool's blocks a full-context slot
            pages = paged_kinds[0].pages(max_len // block_size)
            if num_blocks is None:
                # dense-equivalent HBM by default: one full context per
                # slot, plus the trash block — shrink it to oversubscribe
                num_blocks = num_slots * pages + 1
            if num_blocks < pages + 1:
                raise ValueError(
                    f"num_blocks {num_blocks} cannot back even one "
                    f"full-context request (need >= {pages + 1}: "
                    f"max_seq_len/block_size + the trash block)")
            self.block_size = block_size
            self.num_blocks = num_blocks
            for kind in paged_kinds[1:]:
                model = self._with_window_pool(
                    model, kind, block_size,
                    prefill_chunk or prefill_bucket)
            # per-request window/sink overrides (ISSUE 15) need the
            # per-slot mask leaves; the Pallas kernel takes sink/window
            # STATICALLY, so overrides stay gather-only and a pallas
            # pool keeps the exact PR 12 program
            self.per_slot_limits = bool(self.kv_window_tokens
                                        and self.paged_attn != "pallas")
            self._tick_model, self._chunk_model = paged_slot_models(
                model, num_slots, block_size, num_blocks,
                kv_dtype=kv_dtype, kv_sink_tokens=self.kv_sink_tokens,
                kv_window_tokens=self.kv_window_tokens,
                paged_attn=self.paged_attn,
                per_slot_kv_limits=self.per_slot_limits)
            self._prefill_model = None
        else:
            self.block_size = 0
            self.num_blocks = 0
            self.per_slot_limits = False
            self._tick_model, self._prefill_model = slot_models(
                model, num_slots)
        self.cfg = self._tick_model.cfg
        # the scalars the model counts on the device a tick, by name (the
        # tick's third output; none for a model that counts nothing)
        self._counter_names = tuple(getattr(model, "counters", ()))
        self.bucket = max(1, min(prefill_bucket, self.cfg.max_seq_len))
        if self.paged:
            chunk = prefill_chunk if prefill_chunk else self.bucket
            # chunks must tile the block grid (a chunk's writes stay in
            # whole blocks) and fit the context
            self.chunk = min(self._round_up(chunk, block_size),
                             self.cfg.max_seq_len)
            for kind in paged_kinds:
                if kind.tumbling and kind.window % self.chunk:
                    raise ValueError(
                        f"prefill_chunk {self.chunk} does not divide the "
                        f"{kind.kind} pool's tumbling window of "
                        f"{kind.window}: a chunk that straddles two "
                        f"windows would need the first one's rows after "
                        f"they are retired")
            self._chunks_per_step = max(1, prefill_chunks_per_step)
            # one `SlotPool` a kind. The first is the stream's own:
            # `_alloc`, `_tables` and `_slot_blocks` are its members, which
            # the prefix cache, preemption, export and sessions work on;
            # the others (windowed, sized by `_with_window_pool`) are
            # backed and retired beside it
            self._pools = [
                SlotPool.empty(k.kind, k.table,
                               self.cfg.window_blocks if k.window
                               else num_blocks,
                               block_size, num_slots,
                               k.pages(self.cfg.kv_pages),
                               k.window, k.stride, k.tumbling)
                for k in paged_kinds]
            self._alloc = self._pools[0].alloc
            self._tables = self._pools[0].tables
            self._slot_blocks = self._pools[0].blocks
            self._radix = (RadixPrefixCache(self._alloc) if prefix_cache
                           else None)
            self._lengths = np.zeros(num_slots, np.int32)
            self._admit_order = np.zeros(num_slots, np.int64)
            self._admit_seq = itertools.count(1)
            self._prefilling: dict | None = None
            # prefill_only requests parked after their first token,
            # keyed by request id: {req, slot, length} — the slot holds
            # the blocks but leaves the tick's view (all-trash table,
            # length 0) until export_kv_blocks takes custody
            self._prefilled: dict[int, dict] = {}
            # per-slot EFFECTIVE sink/window (ISSUE 15): engine defaults
            # until a request with an override activates in the slot.
            # Host truth for both the compiled mask (stamped into the
            # kv_sinks/kv_windows cache leaves when dirty) and the
            # block-retirement sweep — the two MUST agree, or retirement
            # would point still-attended positions at the trash block
            self._slot_sinks = np.full(num_slots, self.kv_sink_tokens,
                                       np.int32)
            self._slot_windows = np.full(num_slots, self.kv_window_tokens,
                                         np.int32)
            # dirty from birth: _zero_cache zeroes the kv_sinks/
            # kv_windows leaves too (Flax init defaults never run), so
            # the engine defaults must be stamped before the first tick
            self._limits_dirty = self.per_slot_limits
            # persistent sessions (ISSUE 18): the HBM-RESIDENT tier —
            # finished session streams keyed by session_id, each
            # holding its slot's block list (refcounts transferred off
            # the slot at retirement). dict order == LRU; past
            # ``session_hbm_max`` the eldest demotes into a
            # KVBlockPayload (gather — the existing program) bound
            # for ``session_store`` (host-DRAM/disk tiers) or the
            # spill queue a router drains over the wire
            self._sessions: dict[str, dict] = {}
            self._session_spill: list[tuple[str, str, KVBlockPayload]] = []
        self.session_store = session_store
        self.session_hbm_max = max(0, int(session_hbm_max))
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.spec_k = spec_k
        if adaptive_k and not spec_k:
            raise ValueError(
                "adaptive_k without spec_k > 0 — per-slot proposal depth "
                "is a speculative-decode knob")
        self.adaptive_k = bool(adaptive_k)
        self._spec_heads = 0
        self.draft_swaps = 0
        if spec_k:
            if not self.paged:
                raise ValueError(
                    "spec_k > 0 requires the paged engine (block_size > "
                    "0): the verify forward's rejected-suffix K/V writes "
                    "must drop into the trash block, not clamp onto live "
                    "dense rows")
            if draft_config is not None and draft_params is None:
                raise ValueError(
                    "draft_config without draft_params — pass both "
                    "(inference.truncated_draft builds the pair), or "
                    "neither to self-draft with the target")
            if draft_config is None:
                draft_config, draft_params = model.cfg, params
            if draft_config.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_config.vocab_size} != target "
                    f"vocab {model.cfg.vocab_size}")
            # multi-token proposal heads (ISSUE 16): the base head
            # proposes token 1, head j token j+2 — spec_k proposals need
            # spec_k - 1 heads
            self._spec_heads = int(draft_config.spec_heads)
            if 0 < self._spec_heads < spec_k - 1:
                raise ValueError(
                    f"draft has {self._spec_heads} proposal heads but "
                    f"spec_k={spec_k} needs {spec_k - 1} (build the "
                    f"draft with inference.make_draft("
                    f"spec_heads=spec_k-1))")
            # the draft shares the target's block TABLES (same block ids
            # into its own shallower pool), so its geometry must match
            draft_base = model.clone(cfg=dataclasses.replace(
                draft_config, max_seq_len=model.cfg.max_seq_len))
            # the draft pool rides the same compression + window (it
            # shares block IDS with the target, so a retired block must
            # be dead for both) but keeps the gather read: its rollout
            # runs inside a scanned spec tick, not the plain decode tick
            self._draft_tick_model, self._draft_chunk_model = \
                paged_slot_models(draft_base, num_slots, self.block_size,
                                  self.num_blocks, kv_dtype=kv_dtype,
                                  kv_sink_tokens=self.kv_sink_tokens,
                                  kv_window_tokens=self.kv_window_tokens,
                                  per_slot_kv_limits=self.per_slot_limits)
        with self._mesh_ctx():
            self._cache = _zero_cache(
                self._tick_model, jnp.zeros((num_slots, 1), jnp.int32))
            if spec_k:
                self._draft_cache = _zero_cache(
                    self._draft_tick_model,
                    jnp.zeros((num_slots, 1), jnp.int32))
        # (model, treedef) -> which leaves of such a tree the model only
        # casts to its compute type: traced the first time a tree brings
        # a leaf wider than that type (_compute_copy)
        self._cast_only: dict = {}
        self.set_params(params)
        if spec_k:
            import flax.linen as nn

            # a self-draft shares the target's leaves, so it takes them
            # as the target now holds them
            draft_params = (self._weights if draft_params is params
                            else draft_params)
            # unbox (nn.meta) at boot: callers hand model.init output
            # with LogicallyPartitioned boxes as often as plain trees,
            # and the hot-swap path compares TREEDEFS — a boxed boot
            # tree would refuse every trainer-produced (unboxed) swap
            self._draft_weights, _, _ = self._compute_copy(
                self._draft_tick_model, self._draft_cache, nn.meta.unbox(
                    draft_params["params"] if "params" in draft_params
                    else draft_params))
        # the KV cache HBM footprint (pool or dense rows); the draft
        # pool is accounted
        # separately (it shares block IDs, not bytes)
        self.kv_hbm_bytes = kv_cache_bytes(self._cache)
        self.draft_kv_hbm_bytes = (
            kv_cache_bytes(self._draft_cache) if spec_k else 0)
        kd = np.asarray(jax.random.key_data(jax.random.key(0)))
        self._key_data = np.zeros((num_slots,) + kd.shape, kd.dtype)
        self._tokens = np.zeros(num_slots, np.int32)
        self._counts = np.zeros(num_slots, np.int32)
        self._temps = np.zeros(num_slots, np.float32)
        self._top_ks = np.zeros(num_slots, np.int32)
        self._top_ps = np.ones(num_slots, np.float32)
        if spec_k:
            # per-slot speculative round state (ISSUE 16). Adaptive k:
            # acceptance EMA drives each slot's effective proposal depth
            # (a DYNAMIC operand of the fixed spec_k-wide tick — zero
            # recompiles as it moves). Heads mode: the previous round's
            # emitted buffer / live index / draft write position — the
            # head-parallel draft forward's input (one round behind the
            # target, see spec_decode_tick_heads).
            self._accept_ema = np.ones(num_slots, np.float64)
            self._k_eff = np.full(num_slots, spec_k, np.int32)
            self._spec_prev_tokens = np.zeros((num_slots, spec_k + 1),
                                              np.int32)
            self._spec_prev_idx = np.zeros(num_slots, np.int32)
            self._spec_prev_start = np.zeros(num_slots, np.int32)
        self._free = list(reversed(range(num_slots)))  # pop() -> slot 0
        self._queue: collections.deque[Request] = collections.deque()
        self._active: dict[int, Request] = {}
        self._draining = False
        self._steps = 0  # the `step=` id of this engine's host spans
        # health-snapshot state (ISSUE 9): ``_progress`` is a MONOTONIC
        # device-work watermark (never reset by reset_stats) — it moves
        # exactly when a compiled call completed and synced, so a router
        # watching it can tell a hung replica from an idle one; the TTFT
        # EMA is the router's load-balancing latency signal; ``_sick``
        # holds the last params-finite probe verdict
        self._progress = 0
        self._ttft_ema: float | None = None
        self._sick = False
        if telemetry is None and telemetry_dir is not None:
            telemetry = ServingTelemetry(telemetry_dir)
        self.telemetry = telemetry
        # request tracing (ISSUE 17): a telemetry.tracing.RequestTracer
        # — the router shares its own with in-process engines, a
        # subprocess worker builds one from PTD_TRACE + the telemetry
        # dir. None (the default) means OFF: every emit site guards on
        # it, so off costs nothing per tick. The engine never closes it
        # (the owner does); rows are line-buffered, so a crashed worker
        # loses nothing.
        self.trace = trace
        self.reset_stats()

    # ------------------------------------------------------------------
    # submission

    def submit(self, prompt, *, max_new_tokens: int,
               sampling: SamplingParams | None = None, stop_ids=None,
               on_token=None, deadline_s: float | None = None,
               generated=None, prefill_only: bool = False,
               kv_window: int | None = None,
               kv_sink: int | None = None,
               trace=None, origin_t: float | None = None,
               session_id: str | None = None,
               tenant: str = "default") -> Request:
        """Queue one request; returns its handle (tokens stream into
        ``handle.new_tokens`` / the on_token callback as the engine
        steps). ``stop_ids`` accepts a single id or a sequence.
        ``deadline_s`` is a wall-clock budget from submission: a request
        past it — queued or mid-decode — is retired with finish_reason
        "deadline" (whatever tokens it produced stay delivered) and its
        slot is freed for the next arrival; the other slots are never
        disturbed. The robustness knob a serving tier needs under
        overload — a stuck client budget must shed, not wedge, the
        engine.

        ``generated`` resumes a stream FROM TOKENS (the replica
        router's mid-stream failover, ISSUE 9): admission re-prefills
        prompt+generated — the exact mechanism the paged engine's
        preempt-requeue already uses, factored up to the public API —
        and decoding continues with the per-token fold_in count at
        ``len(generated)``, so the continuation is bitwise what the
        uninterrupted run would have produced (greedy AND seeded
        sampling). ``max_new_tokens`` still bounds the TOTAL new-token
        stream, generated prefix included; only tokens past it are
        delivered/streamed.

        ``prefill_only`` (ISSUE 12, paged only) is the PREFILL-ROLE
        half of disaggregation: the request runs chunked prefill,
        delivers its first token, then PARKS instead of decoding — its
        K/V blocks wait for ``export_kv_blocks`` to hand them to a
        decode-role replica. A request already done at its first token
        (stop id / max_new_tokens == 1) finishes normally and never
        parks.

        ``kv_window`` / ``kv_sink`` (ISSUE 15) TIGHTEN this request's
        sliding-window attention below the pool's static config: values
        are clamped to the pool's (you can never widen past what every
        slot's HBM budget was sized for) and rounded up to whole
        blocks (retirement granularity). They take effect from the
        first DECODED token — prefill masks under the pool window —
        and the retirement sweep frees the request's dead blocks at
        its own tighter horizon. Requires a windowed gather-path pool:
        a dense engine, a windowless pool (there are no mask leaves to
        stamp — the compiled programs are exactly PR 12's) and the
        Pallas kernel (sink/window are STATIC kernel parameters there)
        all reject loudly. The KV handoff wire CARRIES the effective
        override (ISSUE 18), so a ``prefill_only`` stream keeps its
        tightened mask on the decode replica.

        ``session_id`` (ISSUE 18) tags the stream as a persistent
        SESSION: at retirement its KV blocks park in the engine's
        HBM-resident session tier instead of freeing, and a later
        submit with the same id rides them as a radix prefix hit (or
        pulls them back up from the attached ``session_store``'s
        host-DRAM/disk tiers). ``tenant`` rides along for the store's
        per-tenant session budgets."""
        if kv_window is not None or kv_sink is not None:
            if not self.paged:
                raise ValueError(
                    "per-request kv_window/kv_sink need the paged engine "
                    "(block_size > 0)")
            if not self.kv_window_tokens:
                raise ValueError(
                    "per-request kv_window/kv_sink need a windowed pool "
                    "(engine kv_window_tokens > 0): a windowless pool "
                    "compiles no per-slot mask leaves")
            if not self.per_slot_limits:
                raise ValueError(
                    "per-request kv_window/kv_sink need paged_attn="
                    "'gather' — the Pallas kernel takes sink/window as "
                    "STATIC parameters")
            if kv_window is not None and kv_window < 1:
                raise ValueError(
                    f"kv_window must be >= 1, got {kv_window}")
            if kv_sink is not None and kv_sink < 0:
                raise ValueError(f"kv_sink must be >= 0, got {kv_sink}")
        self._refuse_two_kinds(
            "prefill_only (a parked stream for export)" if prefill_only
            else "session_id" if session_id is not None else None)
        if prefill_only:
            if not self.paged:
                raise ValueError(
                    "prefill_only requires the paged engine "
                    "(block_size > 0): KV blocks are the handoff unit")
            if self.spec_k:
                raise ValueError(
                    "prefill_only does not compose with spec_k > 0 "
                    "(the draft pool is not on the KV stream)")
        if session_id is not None:
            if not self.paged:
                raise ValueError(
                    "session_id requires the paged engine "
                    "(block_size > 0): sessions park KV blocks")
            if self.spec_k:
                raise ValueError(
                    "session_id does not compose with spec_k > 0 "
                    "(the draft pool is not on the session tier)")
            from pytorchdistributed_tpu.serving.sessions import \
                session_id_ok
            if not session_id_ok(session_id):
                raise ValueError(
                    f"malformed session_id {session_id!r} (want "
                    f"[A-Za-z0-9][A-Za-z0-9._:-]*, <= 128 chars)")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must have at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if deadline_s is not None and deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0, got {deadline_s}")
        if generated is not None and len(generated) >= max_new_tokens:
            raise ValueError(
                f"generated carries {len(generated)} tokens but "
                f"max_new_tokens is {max_new_tokens} — nothing left to "
                f"resume")
        if prompt.size + max_new_tokens > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt_len {prompt.size} + max_new_tokens "
                f"{max_new_tokens} exceeds max_seq_len "
                f"{self.cfg.max_seq_len}")
        req = Request(prompt, max_new_tokens, sampling or SamplingParams(),
                      stop_ids_tuple(stop_ids), on_token,
                      deadline_s=deadline_s, generated=generated)
        req.prefill_only = prefill_only
        if kv_window is not None or kv_sink is not None:
            req.kv_sink, req.kv_window = self._clamp_limits(
                kv_sink, kv_window)
        req.session_id = session_id
        req.tenant = str(tenant)
        if session_id is not None:
            # reattach (ISSUE 18): a parked resident session's blocks
            # publish into the radix (turn-2 prefill rides them as a
            # prefix hit, bitwise-equal to a full prefill); a session
            # in the store's host-DRAM/disk tiers seeds its full
            # blocks back into the pool the same way. A miss or a
            # declined tier just means a plain re-prefill — lossless.
            self._reattach_session(session_id)
        req.submit_time = time.perf_counter()
        # distributed tracing + origin timestamp (ISSUE 17): ``trace``
        # is the router-minted TraceContext (a wire dict from the
        # subprocess protocol is accepted as-is); ``origin_t`` the
        # FIRST router submit as unix-epoch seconds, mapped onto this
        # process's clock so TTFT-e2e survives redispatch across
        # processes
        if trace is not None:
            req.trace = (trace if isinstance(trace, TraceContext)
                         else TraceContext.from_wire(trace))
        req.origin_submit_time = (
            req.submit_time if origin_t is None
            else _trace_from_unix(float(origin_t)))
        self._queue.append(req)
        return req

    # ------------------------------------------------------------------
    # the scheduler loop

    def step(self) -> dict:
        """One scheduler iteration: shed deadline-expired requests, admit
        prefills while slots are free (paged: at most
        ``prefill_chunks_per_step`` chunks once slots are decoding, so a
        long admission interleaves with — instead of blocking — resident
        streams), then ONE decode tick over all slots; deliver + retire
        from the synced tokens. Returns a small stats dict."""
        if self._draining:
            self.drain()
            return {"admitted": 0, "decoded": 0, "expired": 0,
                    "active": 0, "queued": 0}
        self._steps += 1
        with span("serve/engine_step", step=self._steps):
            expired = self._expire_deadlines()
            admitted = 0
            with span("serve/admit"):
                if self.paged:
                    admitted = self._paged_admissions()
                else:
                    while self._free and self._queue:
                        self._admit(self._queue.popleft())
                        admitted += 1
            decoded = 0
            if self.paged and self._active:
                with span("serve/grow_slots", **self._pools[0].ids):
                    self._grow_slots()  # back this tick's write positions
                for pool in self._pools[1:]:
                    with span("serve/grow_slots", **pool.ids):
                        for slot in self._active:
                            n = int(self._lengths[slot])
                            self._back_window(pool, slot,
                                              pool.tables[slot], n, n + 1)
            if self.per_slot_limits and self._limits_dirty:
                self._stamp_slot_limits()
            if self._active and self.spec_k:
                decoded = self._spec_step()
            elif self._active:
                decoded = self._decode_step()
        return {"admitted": admitted, "decoded": decoded,
                "expired": expired, "active": len(self._active),
                "queued": len(self._queue)}

    def _decode_step(self) -> int:
        """One plain decode tick over all slots and its host bookkeeping:
        build the operands and dispatch (`serve/tick_dispatch`: every
        put of the tick under `serve/tick_operands`, the call of the
        jitted program up to its return under `serve/tick_call`), wait
        for the device (`serve/tick_sync`), hand each slot's token to its
        request (`serve/deliver`). Returns the number of delivered
        tokens."""
        # the tick's length is `serve/decode_tick` in the ring; the clock
        # is read again only for a ServingTelemetry's row
        t0 = time.perf_counter() if self.telemetry is not None else 0.0
        with span("serve/decode_tick"), self._mesh_ctx():
            with span("serve/tick_dispatch"):
                with span("serve/tick_operands"):
                    tick, args = self._tick_program()
                with span("serve/tick_call"):
                    out = tick(self._tick_model, *args,
                               candidates=self.candidates)
                self._cache, nxt = out[:2]
            with span("serve/tick_sync"):
                if self._counter_names:
                    # the device's counters come back with the tokens:
                    # the collection's one vector, wherever in its tree
                    # the model keeps it
                    toks, counted = jax.device_get(
                        (nxt, jax.tree.leaves(out[2])[0]))
                    self._stats["device_counters"] += counted
                else:
                    toks = np.asarray(nxt)  # host sync: streaming delivery
        dt = time.perf_counter() - t0 if self.telemetry is not None else 0.0
        self._counts += 1
        self._progress += 1
        st = self._stats
        st["ticks"] += 1
        st["occupancy_sum"] += len(self._active) / self.num_slots
        row = {}
        if self.paged:
            used = self._alloc.usable - self._alloc.free_count
            st["block_used_sum"] += used / self._alloc.usable
            st["peak_blocks_used"] = max(st["peak_blocks_used"], used)
            row = dict(blocks_used=used,
                       blocks_free=self._alloc.free_count)
            for pool in self._pools[1:]:
                st[f"{pool.kind}_block_used_sum"] += (
                    pool.in_use / pool.alloc.usable)
            for slot in self._active:
                self._lengths[slot] += 1  # this tick's write landed
        decoded = 0
        with span("serve/deliver", tokens=len(self._active)):
            for slot, req in list(self._active.items()):
                self._deliver(req, int(toks[slot]))
                decoded += 1
        st["decode_tokens"] += decoded
        if self.telemetry is not None:
            self.telemetry.tick(
                tick=st["ticks"], tick_ms=round(dt * 1e3, 3),
                active=len(self._active), queued=len(self._queue),
                slot_occupancy=round(decoded / self.num_slots, 4),
                **row)
        return decoded

    def _spec_step(self) -> int:
        """One speculative decode tick over all slots (spec_decode_tick)
        and its host bookkeeping: each active slot advances by its own
        accepted length + 1, delivery stops early at a stop id or the
        token budget (the undelivered remainder of a round is simply
        discarded — it was never part of the request's stream), and the
        per-slot length/count vectors move by exactly the delivered-or-
        accepted span so the next tick's verify writes cover this round's
        rejected suffix. Returns the number of delivered tokens."""
        st = self._stats
        heads = self._spec_heads > 0
        adaptive = self.adaptive_k
        t0 = time.perf_counter() if self.telemetry is not None else 0.0
        with span("serve/spec_tick"), self._mesh_ctx():
            with span("serve/tick_dispatch"):
                with span("serve/tick_operands"):
                    # the heads draft from last round's emitted buffer
                    prev = ((jnp.asarray(self._spec_prev_start),
                             jnp.asarray(self._spec_prev_tokens),
                             jnp.asarray(self._spec_prev_idx))
                            if heads else ())
                    # adaptive off keeps the k_eff=None operand list — the
                    # exact pre-ISSUE-16 program, so the serve_spec_tick
                    # invariant pin stays valid
                    tail = ((jnp.asarray(self._k_eff),) if adaptive else ())
                    operands = (
                        self._device_tables(), jnp.asarray(self._lengths),
                        *prev, jnp.asarray(self._tokens),
                        jnp.asarray(self._key_data),
                        jnp.asarray(self._counts),
                        jnp.asarray(self._temps), jnp.asarray(self._top_ks),
                        jnp.asarray(self._top_ps), *tail)
                with span("serve/tick_call"):
                    (self._cache, self._draft_cache, out, nacc) = (
                        spec_decode_tick_heads if heads
                        else spec_decode_tick)(
                            self._tick_model, self._draft_tick_model,
                            self._weights, self._draft_weights, self._cache,
                            self._draft_cache, *operands,
                            spec_k=self.spec_k, candidates=self.candidates)
            with span("serve/tick_sync"):
                toks = np.asarray(out)   # host sync: streaming delivery
                ns = np.asarray(nacc)
        dt = time.perf_counter() - t0 if self.telemetry is not None else 0.0
        n_active = len(self._active)
        self._progress += 1
        st["ticks"] += 1
        st["occupancy_sum"] += n_active / self.num_slots
        used = self._alloc.usable - self._alloc.free_count
        st["block_used_sum"] += used / self._alloc.usable
        st["peak_blocks_used"] = max(st["peak_blocks_used"], used)
        decoded = accepted = 0
        with span("serve/deliver") as delivering:
            for slot, req in list(self._active.items()):
                n = int(ns[slot])
                k_used = int(self._k_eff[slot]) if adaptive else self.spec_k
                # the round's writes + randomness are consumed whether or not
                # every token gets delivered; a retiring request's slot state
                # is reset by _release_slot anyway
                old_len = int(self._lengths[slot])
                self._lengths[slot] += n + 1
                self._counts[slot] += n + 1
                st["draft_tokens"] += k_used
                st["accepted_tokens"] += n
                st["target_forwards"] += 1
                req.draft_tokens += k_used
                req.accepted_tokens += n
                if heads:
                    # next round's draft chunk: this round's emitted buffer,
                    # live up to n, written one past the pre-advance length
                    self._spec_prev_tokens[slot] = toks[slot]
                    self._spec_prev_idx[slot] = n
                    self._spec_prev_start[slot] = old_len + 1
                if adaptive:
                    # acceptance EMA -> next round's depth: propose about as
                    # many tokens as this slot has been accepting (never 0 —
                    # one proposal costs nothing extra, never > spec_k — the
                    # compiled width)
                    ema = ((1.0 - self.SPEC_EMA_ALPHA) * self._accept_ema[slot]
                           + self.SPEC_EMA_ALPHA * (n / max(k_used, 1)))
                    self._accept_ema[slot] = ema
                    self._k_eff[slot] = min(
                        self.spec_k, max(1, int(round(ema * self.spec_k))))
                accepted += n
                for j in range(n + 1):
                    self._deliver(req, int(toks[slot, j]))
                    decoded += 1
                    if req.done:
                        break
            delivering.note(tokens=decoded)
        st["decode_tokens"] += decoded
        if self.telemetry is not None:
            self.telemetry.tick(
                tick=st["ticks"], tick_ms=round(dt * 1e3, 3),
                active=len(self._active), queued=len(self._queue),
                slot_occupancy=round(n_active / self.num_slots, 4),
                blocks_used=used, blocks_free=self._alloc.free_count,
                spec_k=self.spec_k, accepted_tokens=accepted,
                decoded_tokens=decoded,
                accept_ema=round(float(self._accept_ema.mean()), 4),
                k_eff=round(float(self._k_eff.mean()), 3))
        return decoded

    # ------------------------------------------------------------------
    # paged admission: chunked prefill + prefix reuse + block accounting

    @staticmethod
    def _round_up(n: int, q: int) -> int:
        return -(-n // q) * q

    def _refuse_two_kinds(self, what: str | None) -> None:
        """What the engine has and a model with two cache kinds cannot
        use yet raises with its reason; nothing falls back silently."""
        if what is not None and self._stateful:
            raise ValueError(
                f"{what} is not built for a model with a recurrent state "
                f"a stream: a state cannot be rebuilt from kept blocks (it "
                f"is overwritten in place every step, and no snapshot of "
                f"it is kept at a block's edge), so a prefix, a draft, an "
                f"exported or a parked stream would resume without it")
        if what is not None and len(self._kinds) > 1:
            raise ValueError(
                f"{what} is not built for a model with two cache kinds: "
                f"a prefix, a draft, an exported or a parked stream is "
                f"one list of blocks of one pool, and this model's "
                f"streams hold blocks of two (the window pool's are "
                f"retired while the stream runs, so a cached prefix "
                f"would have no window rows to resume from)")

    def _with_window_pool(self, model, kind, block_size, chunk):
        """`model` with its window pool sized (the trash block included).
        Sliding: a stream that decodes holds the window's blocks and one
        at either end; the one stream that prefills holds a chunk's
        blocks more. Tumbling: a stream, decoding or prefilling, holds at
        most one window's blocks (a chunk lies in one window, and the
        window before is handed back first). Every slot is backed at
        once, so the window pool never runs dry and nothing is preempted
        on its account."""
        cfg = model.cfg
        back = kind.window - 1
        chunk = min(self._round_up(chunk, block_size), cfg.max_seq_len)
        if kind.tumbling:
            need = self.num_slots * (kind.window // block_size) + 1
        else:
            need = (self.num_slots * (-(-back // block_size) + 2)
                    + -(-(chunk + back) // block_size) + 2)
        return model.clone(cfg=dataclasses.replace(cfg,
                                                   window_blocks=need))

    def _back_window(self, pool: SlotPool, slot: int, row, lo: int,
                     hi: int) -> None:
        """Back positions [lo, hi) of `slot` in a windowed `pool`, after
        handing back every block that the earliest query still to come
        (at `lo`) can no longer see. `row` is the table row to keep in
        step: the tick's view of the slot, or the row of the admission
        in flight."""
        blocks = pool.blocks[slot]
        dead = min(pool.retired_before(lo), len(blocks))
        first = int(pool.first[slot])
        if dead > first:
            with span("serve/retire_window", pool=pool.kind):
                for bi in range(first, dead):
                    pool.alloc.decref(blocks[bi])
                    blocks[bi] = 0
                    row[bi] = 0
                self._stats[f"{pool.kind}_blocks_retired"] += dead - first
                pool.first[slot] = dead
        last = pool.block_of(min(hi, self.cfg.max_seq_len) - 1)
        while len(blocks) <= last:
            fresh = pool.alloc.alloc(1)
            if fresh is None:
                raise RuntimeError(
                    f"the {pool.kind} pool ran dry, which its size rules "
                    f"out (_with_window_pool): a block was leaked")
            row[len(blocks)] = fresh[0]
            blocks.append(fresh[0])

    def _clamp_limits(self, kv_sink: int | None,
                      kv_window: int | None) -> tuple[int, int]:
        """Clamp a per-request sink/window override to the pool config
        (tighten-only — you can never widen past what every slot's HBM
        budget was sized for) and round UP to whole blocks: retirement
        frees whole blocks, and a window shorter than one block would
        retire the block the next write needs. submit() and
        import_kv_blocks() funnel here so a wire-carried override lands
        on the importer exactly as the exporter clamped it."""
        bs = self.block_size
        win = self.kv_window_tokens if kv_window is None else kv_window
        win = min(self.kv_window_tokens, self._round_up(win, bs))
        sink = self.kv_sink_tokens if kv_sink is None else kv_sink
        sink = min(self.kv_sink_tokens, self._round_up(sink, bs))
        return int(sink), int(win)

    def _paged_admissions(self) -> int:
        """Advance the admission pipeline: while nothing is decoding,
        push the current prefill to completion and keep admitting (an
        idle engine has no TTFT to protect); once slots are live, spend
        at most ``prefill_chunks_per_step`` chunk calls so resident
        streams keep ticking between chunks."""
        admitted = chunks = 0
        while True:
            if self._prefilling is None:
                if not (self._queue and self._free):
                    break
                with span("serve/start_prefill",
                          request=self._queue[0].id):
                    started = self._start_prefill()
                if not started:
                    # pool pressure (not the lane): wait for retirements
                    self._stats["admit_blocked"] += 1
                    break
            admitted += self._prefill_chunk_step()
            chunks += 1
            if self._active and chunks >= self._chunks_per_step:
                break
        return admitted

    def _alloc_blocks(self, n: int):
        """Allocate n blocks, evicting prefix-cache LRU entries if the
        free list is short — but only when eviction can actually cover
        the shortfall (a doomed allocation must not destroy reusable
        cached prefixes on its way to failing anyway). None when it
        cannot be covered."""
        fresh = self._alloc.alloc(n)
        if fresh is None and self._radix is not None:
            # parked sessions must never deadlock a live admission:
            # byte pressure outranks the session_hbm_max count, so
            # demote LRU residents down the hierarchy (store / spill —
            # lossless either way) until eviction covers the shortfall
            while (self._sessions
                   and self._radix.evictable_count()
                   < n - self._alloc.free_count):
                self._demote_session(next(iter(self._sessions)))
            short = n - self._alloc.free_count
            if short <= 0:
                fresh = self._alloc.alloc(n)
            elif self._radix.evictable_count() >= short:
                self._radix.reclaim(short)
                fresh = self._alloc.alloc(n)
        return fresh

    def _start_prefill(self) -> bool:
        """Begin admitting the queue head: match its prompt against the
        radix cache (matched FULL blocks are admitted by reference — no
        prefill compute), allocate private blocks for the rest, claim a
        slot. The last prompt token is never taken from the cache: its
        forward pass produces the logits the first sampled token needs.
        Returns False when the pool cannot back it yet."""
        req = self._queue[0]
        # a preempted request resumes by re-prefilling prompt + what it
        # already generated — continuation tokens, sampling stream and
        # the delivered output are unchanged
        tokens = np.concatenate(
            [req.prompt, np.asarray(req.new_tokens, np.int32)])
        true_len = int(tokens.size)
        bs = self.block_size
        lookup_len = ((true_len - 1) // bs) * bs
        matched_nodes: list = []
        if self._radix is not None:
            matched_nodes = self._radix.match_nodes(tokens[:lookup_len])
        matched = [n.block for n in matched_nodes]
        for b in matched:  # hold them before eviction can reap them
            self._alloc.incref(b)
        m = len(matched) * bs
        span = min(self._round_up(true_len - m, self.chunk),
                   self.cfg.max_seq_len - m)
        # the stream's own pool backs the whole span at once, by the
        # positions a row of it stands for; a windowed pool is backed a
        # chunk at a time (_chunk_call)
        own = self._pools[0]
        fresh = self._alloc_blocks(own.blocks_for(m + span) - len(matched))
        if fresh is None and not self._active and m:
            # nothing will retire and the shared prefix is squatting the
            # pool: fall back to a full private prefill so the lone
            # request can make progress
            for b in matched:
                self._alloc.decref(b)
            matched, matched_nodes, m = [], [], 0
            if self._radix is not None:
                self._radix.clear()
            span = min(self._round_up(true_len, self.chunk),
                       self.cfg.max_seq_len)
            fresh = self._alloc_blocks(own.blocks_for(span))
        if fresh is None:
            for b in matched:
                self._alloc.decref(b)
            return False
        self._queue.popleft()
        if req.admit_time is None:
            req.admit_time = time.perf_counter()
        # fleet-shipped (remote) prefix nodes count separately: their
        # tokens were prefilled on ANOTHER replica, so the local
        # prefix_hit_rate must stay comparable to single-engine runs
        remote_m = sum(1 for n in matched_nodes if n.remote)
        if self._radix is not None:  # ONE stat row per landed admission
            self._radix.record_admission(len(matched), lookup_len,
                                         remote_blocks=remote_m)
        slot = self._free.pop()
        blocks = matched + fresh
        self._slot_blocks[slot] = blocks
        # the TICK's view of this slot (self._tables/_lengths) stays
        # all-trash until activation: decode ticks keep running between
        # prefill chunks, and the mid-prefill slot's garbage tick must
        # write the trash block, not position 0 of the request's first
        # real block. The chunk program reads the real row from pf state.
        # A row a pool: the stream's own holds its blocks, a windowed
        # pool's is backed a chunk at a time (_chunk_call).
        table_row = {pool.table: np.zeros(pool.tables.shape[1], np.int32)
                     for pool in self._pools}
        table_row[self._pools[0].table][:len(blocks)] = blocks
        req.prefix_hit_tokens += m
        req.remote_hit_tokens += remote_m * bs
        st = self._stats
        st["admissions"] += 1
        st["admitted_tokens"] += true_len
        st["prefix_hit_tokens"] += m
        st["remote_hit_tokens"] += remote_m * bs
        self._prefilling = dict(
            req=req, slot=slot, tokens=tokens, true_len=true_len, pos=m,
            resume=len(req.new_tokens), table_row=table_row,
            # spec: the draft prefill also starts at the prefix-hit
            # offset — radix-held blocks keep their draft K/V resident
            # (same block ids into the draft pool, written by the
            # admission that cached them), and every position below a
            # slot's length is rewritten with ACCEPTED tokens before the
            # length passes it (the covering-writes property), so cached
            # draft K/V is always conditioned on the true prefix
            dpos=m, first=None,
            kd=np.asarray(jax.random.key_data(
                jax.random.key(req.sampling.seed))))
        return True

    def _chunk_call(self, model, weights, cache, pf, pos):
        """One paged_prefill_chunk call for the admission in flight, at
        absolute position ``pos`` of its token stream — shared by the
        target and (spec mode) draft cache fills (same shapes, different
        static model). The chunk's numpy build and its puts are
        `serve/chunk_operands`, the call of the jitted program up to its
        return `serve/chunk_call`."""
        req = pf["req"]
        for pool in self._pools[1:]:
            with span("serve/grow_slots", **pool.ids):
                self._back_window(pool, pf["slot"],
                                  pf["table_row"][pool.table], pos,
                                  pos + self.chunk)
        with span("serve/chunk_operands"):
            chunk = np.zeros((1, self.chunk), np.int32)
            n = min(self.chunk, pf["true_len"] - pos)
            chunk[0, :n] = pf["tokens"][pos:pos + n]
            operands = (
                jnp.asarray(chunk), jnp.int32(pos),
                # copies, made on the host: the next chunk's
                # `_back_window` edits the window row in place while this
                # chunk may still run, and on the CPU `jnp.asarray` can
                # alias host memory (`jnp.array` of a numpy array does
                # too: it copies jax arrays only)
                {name: jnp.asarray(row.copy())
                 for name, row in pf["table_row"].items()},
                jnp.int32(pf["true_len"]),
                jnp.asarray(pf["kd"]),
                jnp.int32(pf["resume"]),
                jnp.float32(req.sampling.temperature),
                jnp.int32(req.sampling.top_k),
                jnp.float32(req.sampling.top_p))
            if self._stateful:
                # the slot whose state row the chunk reads and writes
                operands += (jnp.int32(pf["slot"]),)
        with span("serve/chunk_call"):
            return paged_prefill_chunk(model, weights, cache, *operands,
                                       candidates=self.candidates)

    def _prefill_chunk_step(self) -> int:
        """Run ONE chunk step of the in-flight admission — a target
        chunk while the target cache is short of the prompt, plus (spec
        mode) a draft chunk filling the draft pool over the SAME blocks
        (both start at the prefix-hit offset: matched blocks carry valid
        draft K/V from the admission that cached them) — and, once both
        caches cover the prompt, activate the slot with the target's
        sampled next token. Returns 1 on completed admission, else 0."""
        pf = self._prefilling
        req, slot = pf["req"], pf["slot"]
        with (span("serve/prefill", request=req.id, pos=pf["pos"]),
              self._mesh_ctx()):
            if pf["pos"] < pf["true_len"]:
                pos = pf["pos"]
                final_t = pos + self.chunk >= pf["true_len"]
                self._cache, first, counted = self._chunk_call(
                    self._chunk_model, self._weights, self._cache, pf, pos)
                if self._counter_names:
                    # the chunk's device counters, on their way to the
                    # host while the device works on; summed at the
                    # prompt's last chunk (`_fold_chunk_counters`)
                    counted = jax.tree.leaves(counted)[0]
                    counted.copy_to_host_async()
                    self._chunk_counted.append(counted)
                if final_t:
                    # sync: the TTFT timestamp is honest
                    with span("serve/prefill_sync", request=req.id):
                        pf["first"] = int(first)
                    self._fold_chunk_counters()
                pf["pos"] = pos + self.chunk
            if self.spec_k and pf["dpos"] < pf["true_len"]:
                self._draft_cache, *_ = self._chunk_call(
                    self._draft_chunk_model, self._draft_weights,
                    self._draft_cache, pf, pf["dpos"])
                pf["dpos"] += self.chunk
        now = time.perf_counter()
        self._progress += 1
        st = self._stats
        st["prefill_chunks"] += 1
        req.prefill_chunks += 1
        if pf["pos"] < pf["true_len"] or (
                self.spec_k and pf["dpos"] < pf["true_len"]):
            return 0
        first = pf["first"]
        # admission complete: cache the prompt's full blocks for future
        # arrivals, publish the real table to the tick's view, rewind to
        # the true length, activate the slot
        for pool in self._pools:
            row = pf["table_row"][pool.table]
            if pool.window:
                # the last chunk's pad positions were backed too: hand
                # back what lies past the block the first tick writes into
                keep = pf["true_len"] // self.block_size + 1
                for b in pool.blocks[slot][keep:]:
                    pool.alloc.decref(b)
                del pool.blocks[slot][keep:]
                row[keep:] = 0
            pool.tables[slot, :] = row
        self._lengths[slot] = pf["true_len"]
        if self._radix is not None:
            nb = pf["true_len"] // self.block_size
            self._radix.insert(pf["tokens"][:nb * self.block_size],
                               self._slot_blocks[slot][:nb])
        self._prefilling = None
        st["prefills"] += 1
        req.slot = slot
        if req.first_token_time is None:
            req.first_token_time = now
            if req.submit_time is not None:
                self._note_ttft(req, now)
        self._trace_span(req, "prefill", req.admit_time, now,
                         chunks=req.prefill_chunks,
                         parked=bool(req.prefill_only and not req.done),
                         resumed_from=req.resumed_from)
        self._active[slot] = req
        self._admit_order[slot] = next(self._admit_seq)
        if self.per_slot_limits:
            self._set_slot_limits(slot, req.kv_sink, req.kv_window)
        self._key_data[slot] = pf["kd"]
        self._counts[slot] = pf["resume"] + 1
        self._temps[slot] = req.sampling.temperature
        self._top_ks[slot] = req.sampling.top_k
        self._top_ps[slot] = req.sampling.top_p
        if self.spec_k:
            self._reset_spec_slot(slot, first, pf["true_len"])
        self._deliver(req, first)
        if req.prefill_only and not req.done:
            # PARK for handoff (ISSUE 12): the first token is
            # delivered, the blocks hold exact K/V for positions
            # [0, true_len) — custody now belongs to export_kv_blocks.
            # The slot leaves the tick's view (all-trash table, length
            # 0: garbage ticks must not write the parked K/V) and
            # leaves _active so growth/preemption/delivery skip it.
            del self._active[slot]
            self._prefilled[req.id] = dict(req=req, slot=slot,
                                           length=pf["true_len"])
            self._tables[slot, :] = 0
            self._lengths[slot] = 0
            req.parked = True
        return 1

    def _grow_slots(self) -> None:
        """Back every active slot's next write position with a physical
        block, oldest admissions first — a speculative tick writes
        [len, len+spec_k], so spec serving backs the whole span (clamped
        to the context: past-max_seq_len writes go to the trash block and
        need no backing). When the pool is exhausted even after
        prefix-cache eviction, preempt the YOUNGEST resident request
        (free its blocks, requeue it at the front — it resumes later by
        re-prefilling prompt + generated, output unchanged) until the
        older stream can proceed.

        With a sliding window configured (kv_window_tokens > 0) this is
        also where blocks RETIRE: before growing a slot, any middle
        block whose every position has fallen out of the sink+window
        visible set — for this tick's MINIMUM query position, so spec
        rounds are covered too — is decref'd back to the allocator, its
        table entry pointed at the trash block, and its list entry
        zeroed as a sentinel. Dead is forever (positions only grow), so
        each block retires exactly once, and the freed capacity backs
        the very growth loop below — a long stream's footprint is
        sink + window + a block, not its whole history."""
        bs = self.block_size
        for slot in sorted(self._active,
                           key=lambda s: self._admit_order[s]):
            if slot not in self._active:
                continue  # preempted by an older slot's growth
            # retirement horizon = this slot's EFFECTIVE sink/window
            # (per-request overrides, ISSUE 15) — must agree with the
            # compiled mask's per-slot leaves or retired garbage would
            # be attended
            if self.per_slot_limits:
                win = int(self._slot_windows[slot])
                sink = int(self._slot_sinks[slot])
            else:
                win, sink = self.kv_window_tokens, self.kv_sink_tokens
            blocks = self._slot_blocks[slot]
            if win:
                qlo = int(self._lengths[slot])  # this tick's first query
                for bi in range(sink // bs, len(blocks)):
                    if (bi + 1) * bs > qlo - win + 1:
                        break  # first live block; younger ones follow
                    if blocks[bi]:
                        self._alloc.decref(blocks[bi])
                        blocks[bi] = 0
                        self._tables[slot, bi] = 0
                        self._stats["retired_blocks"] += 1
            bi = self._pools[0].block_of(
                min(int(self._lengths[slot]) + self.spec_k,
                    self.cfg.max_seq_len - 1))
            while bi >= len(blocks):
                fresh = self._alloc_blocks(1)
                if fresh is not None:
                    self._tables[slot, len(blocks)] = fresh[0]
                    blocks.append(fresh[0])
                    continue
                victim = max(self._active,
                             key=lambda s: self._admit_order[s])
                self._preempt(victim)
                if victim == slot:
                    break  # this very request went back to the queue

    def _set_slot_limits(self, slot: int, sink: int | None,
                         window: int | None) -> None:
        """Record one slot's effective sink/window (None = engine
        defaults) and mark the compiled mask leaves stale — they are
        re-stamped lazily before the next tick."""
        s = self.kv_sink_tokens if sink is None else sink
        w = self.kv_window_tokens if window is None else window
        if (self._slot_sinks[slot] != s
                or self._slot_windows[slot] != w):
            self._slot_sinks[slot] = s
            self._slot_windows[slot] = w
            self._limits_dirty = True

    def _stamp_slot_limits(self) -> None:
        """Push the host per-slot sink/window vectors into the cache's
        ``kv_sinks``/``kv_windows`` leaves (one of each a stack, read
        by every layer), exactly like _override_paging's table stamp,
        just host-initiated because the values change on
        admission/release, not every tick."""
        sinks = jnp.asarray(self._slot_sinks)
        windows = jnp.asarray(self._slot_windows)

        def fix(path, leaf):
            name = _leaf_name(path)
            if name == "kv_sinks":
                return jnp.broadcast_to(sinks, leaf.shape).astype(leaf.dtype)
            if name == "kv_windows":
                return jnp.broadcast_to(windows,
                                        leaf.shape).astype(leaf.dtype)
            return leaf

        with self._mesh_ctx():
            self._cache = jax.tree_util.tree_map_with_path(fix, self._cache)
            if self.spec_k:
                self._draft_cache = jax.tree_util.tree_map_with_path(
                    fix, self._draft_cache)
        self._limits_dirty = False

    def preempt_request(self, req: Request) -> bool:
        """Release ``req``'s resources NOW and retire it with
        finish_reason "preempted", keeping every delivered token — the
        ROUTER-level preemption hook (ISSUE 15): the router requeues
        the stream and a later submit(generated=req.new_tokens) resumes
        it losslessly, exactly like failover redispatch. Queued
        requests just leave the queue; an active slot's blocks return
        to the pool. Returns False (no-op) for requests this engine
        cannot cleanly release mid-flight: already done, mid-chunked-
        prefill, or parked for KV handoff."""
        if req.done:
            return False
        if req in self._queue:
            self._queue.remove(req)
        elif (self.paged and self._prefilling is not None
                and self._prefilling["req"] is req):
            return False
        elif self.paged and req.id in self._prefilled:
            return False
        elif req.slot is not None and self._active.get(req.slot) is req:
            slot = req.slot
            del self._active[slot]
            if self.paged:
                self._release_slot(slot)
            else:
                self._free.append(slot)
                self._temps[slot] = 0.0
            req.slot = None
            req.preemptions += 1
        else:
            return False
        req.done = True
        req.finish_reason = "preempted"
        req.finish_time = time.perf_counter()
        self._stats["preempted_requests"] += 1
        return True

    def _preempt(self, slot: int) -> None:
        req = self._active.pop(slot)
        self._release_slot(slot)
        req.slot = None
        req.preemptions += 1
        self._stats["preemptions"] += 1
        self._queue.appendleft(req)

    def _release_slot(self, slot: int) -> None:
        """Return a slot's blocks to the pool (radix-cached blocks
        survive via the cache's own reference) and point its table at
        the trash block so its garbage ticks stay harmless. Zero
        entries are window-retirement sentinels — those refs were
        already returned mid-stream."""
        for b in self._slot_blocks[slot]:
            if b:
                self._alloc.decref(b)
        self._slot_blocks[slot] = []
        self._tables[slot, :] = 0
        self._lengths[slot] = 0
        for pool in self._pools[1:]:
            for b in pool.blocks[slot]:
                if b:
                    pool.alloc.decref(b)
            pool.blocks[slot] = []
            pool.first[slot] = 0
            pool.tables[slot, :] = 0
        if self.per_slot_limits:
            self._set_slot_limits(slot, None, None)
        if self.spec_k:
            self._reset_spec_slot(slot, 0, 0)
        self._free.append(slot)
        self._temps[slot] = 0.0

    def _reset_spec_slot(self, slot: int, first: int,
                         true_len: int) -> None:
        """Fresh per-slot speculative round state (ISSUE 16) — every
        activation path (chunked-prefill completion, KV import) and
        _release_slot funnel here: full proposal depth, EMA at 1.0, and
        the heads-mode round-1 draft chunk = [first, pad...] written at
        ``true_len`` (the first committed token's position — exactly the
        offline path's prev_pos = plen init)."""
        self._accept_ema[slot] = 1.0
        self._k_eff[slot] = self.spec_k
        self._spec_prev_tokens[slot] = 0
        self._spec_prev_tokens[slot, 0] = first
        self._spec_prev_idx[slot] = 0
        self._spec_prev_start[slot] = true_len

    # ------------------------------------------------------------------
    # KV block streaming (ISSUE 12): the disaggregation transfer unit

    @property
    def parked_requests(self) -> list[Request]:
        """Prefill-only requests parked awaiting export (in park
        order) — what a router's handoff sweep polls."""
        if not self.paged:
            return []
        return [rec["req"] for rec in self._prefilled.values()]

    def _pool_leaf_names(self) -> list[str]:
        """Tree-path names of the pool's leaves (K/V codes plus, on an
        int8 pool, the scale planes), in the flatten order
        kv_block_gather emits — the payload's integrity tags."""
        return ["/".join(str(getattr(p, "key", p)) for p in path)
                for path, leaf in
                jax.tree_util.tree_leaves_with_path(self._cache)
                if _leaf_name(path) in POOL_LEAF_AXIS]

    def _gather_blocks(self, blocks) -> list:
        """Run the ONE fixed-shape gather program over ``blocks`` (ids
        padded to kv_pages with trash) and return named host arrays
        with the pad rows sliced off."""
        nb = len(blocks)
        ids = np.zeros(self.cfg.kv_pages, np.int32)
        ids[:nb] = blocks
        with self._mesh_ctx():
            gathered = kv_block_gather(self._cache, jnp.asarray(ids))
        out = []
        for name, leaf in zip(self._pool_leaf_names(), gathered):
            a = np.asarray(leaf)  # host sync
            out.append((name, np.take(a, np.arange(nb),
                                      axis=_pool_block_axis(name, a.ndim))))
        self._progress += 1
        return out

    def _scatter_blocks(self, blocks, arrays) -> None:
        """Run the ONE fixed-shape scatter program: pad ids and the
        payload's block axis to kv_pages (pad zeros land in the trash
        block) and write into the donated pool."""
        nb = len(blocks)
        ids = np.zeros(self.cfg.kv_pages, np.int32)
        ids[:nb] = blocks
        padded = []
        for name, a in zip(self._pool_leaf_names(), arrays):
            axis = _pool_block_axis(name, a.ndim)
            pad = [(0, 0)] * a.ndim
            pad[axis] = (0, self.cfg.kv_pages - a.shape[axis])
            padded.append(jnp.asarray(np.pad(a, pad)))
        with self._mesh_ctx():
            self._cache = kv_block_scatter(self._cache, jnp.asarray(ids),
                                           padded)

    def export_kv_blocks(self, req: Request) -> KVBlockPayload:
        """Gather a PARKED request's KV blocks off the pool into a
        host payload and release its slot — the prefill-role half of a
        disaggregated handoff. The payload carries the prompt, the
        delivered first token (in ``generated``), the sampling
        contract and the exact K/V of [0, true_len), so the importing
        engine continues the stream bitwise as if it had prefilled
        locally. After export this engine holds NOTHING for the
        request (radix-cached prefix blocks live on through the
        cache's own reference)."""
        self._refuse_two_kinds("export of a stream's blocks")
        if not self.paged:
            raise ValueError("export_kv_blocks requires the paged engine")
        rec = self._prefilled.pop(req.id, None)
        if rec is None:
            raise ValueError(
                f"request {req.id} is not parked for export")
        slot, true_len = rec["slot"], rec["length"]
        nb = -(-true_len // self.block_size)
        payload = KVBlockPayload(
            prompt=req.prompt.copy(), generated=list(req.new_tokens),
            true_len=true_len, block_size=self.block_size,
            max_new_tokens=req.max_new_tokens, sampling=req.sampling,
            stop_ids=tuple(req.stop_ids),
            leaves=self._gather_blocks(self._slot_blocks[slot][:nb]),
            kv_dtype=self.kv_dtype,
            # the effective per-request window rides the wire (ISSUE 18
            # bug fix): without it the importer would ATTEND positions
            # the exporter's tightened mask had retired
            kv_sink=req.kv_sink, kv_window=req.kv_window,
            # the ORIGIN submit + trace identity ride the handoff
            # (ISSUE 17): unix-epoch so two processes agree on it
            origin_t=(None if req.origin_submit_time is None
                      else _trace_to_unix(req.origin_submit_time)),
            trace=(None if req.trace is None else req.trace.to_wire()))
        self._release_slot(slot)
        req.slot = None
        req.parked = False
        st = self._stats
        st["kv_exports"] += 1
        st["kv_exported_blocks"] += nb
        st["kv_stream_bytes"] += payload.nbytes
        return payload

    def import_kv_blocks(self, payload: KVBlockPayload, *,
                         on_token=None,
                         deadline_s: float | None = None
                         ) -> Request | None:
        """Scatter a KVBlockPayload into free pool blocks and ACTIVATE
        the stream mid-flight — the decode-role half. Returns the live
        Request handle (its ``new_tokens`` is pre-seeded with the
        exporter's delivered tokens; ``resumed_from`` guards
        re-delivery exactly like submit(generated=...)), or None on a
        resource shortfall (no free slot / pool blocks) — the caller
        falls back to resume-from-tokens redispatch, which is lossless
        by construction. Geometry/model mismatches raise ValueError:
        importing foreign K/V silently would serve garbage."""
        self._refuse_two_kinds("import of a stream's blocks")
        if not self.paged:
            raise ValueError("import_kv_blocks requires the paged engine")
        if self.spec_k:
            raise ValueError(
                "import_kv_blocks does not compose with spec_k > 0 "
                "(the draft pool is not on the KV stream)")
        if payload.wire_version != KV_WIRE_VERSION:
            raise ValueError(
                f"KV payload wire_version {payload.wire_version} != "
                f"engine wire_version {KV_WIRE_VERSION} — the sender "
                f"speaks a different KV stream schema; upgrade both "
                f"ends before disaggregating")
        if payload.kv_dtype != self.kv_dtype:
            raise ValueError(
                f"payload kv_dtype {payload.kv_dtype!r} != engine "
                f"kv_dtype {self.kv_dtype!r} — an int8 payload cannot "
                f"be scattered into a bf16 pool (or vice versa); run "
                f"prefill- and decode-role replicas with the same "
                f"kv_dtype")
        if payload.block_size != self.block_size:
            raise ValueError(
                f"payload block_size {payload.block_size} != engine "
                f"block_size {self.block_size}")
        if not payload.generated:
            raise ValueError(
                "payload carries no generated tokens — the exporter "
                "always delivers the first token before parking")
        if payload.true_len != payload.prompt.size + len(
                payload.generated) - 1:
            raise ValueError(
                f"payload true_len {payload.true_len} != prompt "
                f"{payload.prompt.size} + generated "
                f"{len(payload.generated)} - 1")
        if payload.prompt.size + payload.max_new_tokens > \
                self.cfg.max_seq_len:
            raise ValueError(
                f"prompt_len {payload.prompt.size} + max_new_tokens "
                f"{payload.max_new_tokens} exceeds max_seq_len "
                f"{self.cfg.max_seq_len}")
        names = self._pool_leaf_names()
        if [n for n, _ in payload.leaves] != names:
            raise ValueError(
                "payload pool leaves do not match this engine's pool "
                "(different model or layer stacking)")
        if payload.kv_window is not None or payload.kv_sink is not None:
            if not (self.kv_window_tokens and self.per_slot_limits):
                raise ValueError(
                    "payload carries a per-request kv_window/kv_sink "
                    "override but this engine has no per-slot mask "
                    "leaves (kv_window_tokens == 0 or paged_attn="
                    "'pallas') — importing it would ATTEND positions "
                    "the exporter's tightened mask retired")
        if not self._free:
            return None
        nb = payload.num_blocks
        blocks = self._alloc_blocks(nb)
        if blocks is None:
            return None
        self._scatter_blocks(blocks, [a for _, a in payload.leaves])
        req = Request(payload.prompt, payload.max_new_tokens,
                      payload.sampling, tuple(payload.stop_ids),
                      on_token, deadline_s=deadline_s,
                      generated=payload.generated)
        req.submit_time = time.perf_counter()
        # the exporter timed the real TTFT; this engine's EMA must not
        # absorb a handoff as a near-zero first token
        req.first_token_time = req.submit_time
        # end-to-end identity (ISSUE 17): the ORIGIN router submit and
        # the TraceContext arrive in the payload — ttft_e2e_s and the
        # decode-side spans stay on the request's one fleet-wide trace
        req.origin_submit_time = (
            req.submit_time if payload.origin_t is None
            else _trace_from_unix(float(payload.origin_t)))
        if payload.trace is not None:
            req.trace = TraceContext.from_wire(payload.trace)
        slot = self._free.pop()
        req.slot = slot
        self._slot_blocks[slot] = list(blocks)
        self._tables[slot, :] = 0
        self._tables[slot, :nb] = blocks
        self._lengths[slot] = payload.true_len
        self._active[slot] = req
        self._admit_order[slot] = next(self._admit_seq)
        self._key_data[slot] = np.asarray(jax.random.key_data(
            jax.random.key(payload.sampling.seed)))
        # the activation invariants, verbatim: token n samples with
        # fold_in(key, n), the next tick's input is the last delivered
        # token, and the next write position is true_len (backed by
        # _grow_slots exactly like a local activation — when true_len
        # is a block multiple the write lands in a FRESH block, never
        # in an imported/radix-shared one)
        self._counts[slot] = len(payload.generated)
        self._tokens[slot] = payload.generated[-1]
        self._temps[slot] = payload.sampling.temperature
        self._top_ks[slot] = payload.sampling.top_k
        self._top_ps[slot] = payload.sampling.top_p
        if payload.kv_window is not None or payload.kv_sink is not None:
            # re-apply the exporter's tightened mask (ISSUE 18 bug
            # fix): re-clamp against THIS pool's config — tighten-only
            # both ways — and stamp the slot's mask leaves so the
            # resumed stream masks exactly what the exporter's would
            req.kv_sink, req.kv_window = self._clamp_limits(
                payload.kv_sink, payload.kv_window)
            self._set_slot_limits(slot, req.kv_sink, req.kv_window)
        if self.spec_k:
            # the imported blocks carry no DRAFT K/V, so heads-mode
            # proposals start cold here — acceptance suffers, tokens
            # never do (the rejection kernel is lossless at any draft
            # quality)
            self._reset_spec_slot(slot, payload.generated[-1],
                                  payload.true_len)
        if self._radix is not None:
            full = np.concatenate(
                [payload.prompt,
                 np.asarray(payload.generated[:-1], np.int32)])
            nbf = payload.true_len // self.block_size
            if nbf:
                self._radix.insert(full[:nbf * self.block_size],
                                   blocks[:nbf])
        st = self._stats
        st["kv_imports"] += 1
        st["kv_imported_blocks"] += nb
        st["kv_stream_bytes"] += payload.nbytes
        return req

    def export_prefix_blocks(self, tokens) -> PrefixBlockPayload | None:
        """Gather the radix-cached prefix of ``tokens`` for fleet
        shipping (the remote-hit path: this replica owns the longest
        match, another replica is about to prefill it from scratch).
        None when nothing is cached."""
        self._refuse_two_kinds("export of prefix blocks")
        if not self.paged or self._radix is None:
            return None
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        nodes = self._radix.match_nodes(tokens)
        if not nodes:
            return None
        blocks = [n.block for n in nodes]
        payload = PrefixBlockPayload(
            tokens=tokens[:len(blocks) * self.block_size].copy(),
            block_size=self.block_size,
            leaves=self._gather_blocks(blocks),
            kv_dtype=self.kv_dtype)
        self._stats["kv_stream_bytes"] += payload.nbytes
        return payload

    def import_prefix_blocks(self, payload: PrefixBlockPayload) -> int:
        """Adopt a fleet-shipped prefix into the local pool + radix as
        REMOTE entries (steered hits on them count separately from
        local ones). Best-effort by design — returns the number of
        blocks adopted, 0 on any mismatch or pool pressure: a failed
        ship just means this replica prefills the prefix itself."""
        self._refuse_two_kinds("import of prefix blocks")
        if (not self.paged or self._radix is None or self.spec_k
                or payload.block_size != self.block_size
                or payload.kv_dtype != self.kv_dtype
                or payload.wire_version != KV_WIRE_VERSION
                or [n for n, _ in payload.leaves]
                != self._pool_leaf_names()):
            return 0
        tokens = np.asarray(payload.tokens, np.int32).reshape(-1)
        nb = len(tokens) // self.block_size
        matched = self._radix.match(tokens)
        m = len(matched)
        if m >= nb:
            return 0  # already holds the whole prefix
        fresh = self._alloc_blocks(nb - m)
        if fresh is None:
            return 0
        suffix = [np.take(a, np.arange(m, nb),
                          axis=_pool_block_axis(n, a.ndim))
                  for n, a in payload.leaves]
        self._scatter_blocks(fresh, suffix)
        self._radix.insert(tokens[:nb * self.block_size],
                           matched + fresh, remote=True)
        for b in fresh:  # the radix reference is now the sole owner
            self._alloc.decref(b)
        st = self._stats
        st["kv_imported_blocks"] += nb - m
        st["kv_stream_bytes"] += payload.nbytes
        return nb - m

    # ------------------------------------------------------------------
    # persistent sessions (ISSUE 18): the HBM-resident tier + the
    # detach/attach/seed surface the tiered store and router ride

    def detach_request(self, handle: Request) -> KVBlockPayload:
        """Export a LIVE mid-stream request's KV + continuation
        contract as a KVBlockPayload and retire it locally with
        finish_reason "detached" — the suspend half of a fleet-wide
        session reattach. ``import_kv_blocks`` on ANY replica (this
        one included) continues the stream bitwise as if it had never
        been interrupted: the payload is exactly the disagg handoff
        wire format, including the partial tail block PAST the radix
        full-block boundary, the per-request kv_sink/kv_window
        override and the trace identity. Parked prefill_only requests
        delegate to export_kv_blocks."""
        self._refuse_two_kinds("detaching a stream")
        if not self.paged:
            raise ValueError("detach_request requires the paged engine")
        if self.spec_k:
            raise ValueError(
                "detach_request does not compose with spec_k > 0 "
                "(the draft pool is not on the KV stream)")
        if handle.id in self._prefilled:
            return self.export_kv_blocks(handle)
        slot = handle.slot
        if slot is None or self._active.get(slot) is not handle:
            raise ValueError(
                f"request {handle.id} is not resident (queued, "
                f"mid-prefill or already finished) — nothing to "
                f"detach")
        true_len = int(self._lengths[slot])
        nb = -(-true_len // self.block_size)
        payload = KVBlockPayload(
            prompt=handle.prompt.copy(),
            generated=list(handle.new_tokens),
            true_len=true_len, block_size=self.block_size,
            max_new_tokens=handle.max_new_tokens,
            sampling=handle.sampling,
            stop_ids=tuple(handle.stop_ids),
            leaves=self._gather_blocks(self._slot_blocks[slot][:nb]),
            kv_dtype=self.kv_dtype,
            kv_sink=handle.kv_sink, kv_window=handle.kv_window,
            origin_t=(None if handle.origin_submit_time is None
                      else _trace_to_unix(handle.origin_submit_time)),
            trace=(None if handle.trace is None
                   else handle.trace.to_wire()))
        del self._active[slot]
        self._release_slot(slot)
        handle.slot = None
        handle.done = True
        handle.finish_reason = "detached"
        handle.finish_time = time.perf_counter()
        st = self._stats
        st["kv_exports"] += 1
        st["kv_exported_blocks"] += nb
        st["kv_stream_bytes"] += payload.nbytes
        st["session_detaches"] += 1
        if self.telemetry is not None:
            self.telemetry.request(handle)
        return payload

    def seed_session_blocks(self, payload: KVBlockPayload, *,
                            remote: bool = False) -> int:
        """Adopt a stored session's FULL KV blocks into the pool +
        radix so the reattaching turn's prefill rides them as a prefix
        hit — bitwise-equal to re-prefilling them, minus the compute.
        The partial tail block (true_len past the full-block boundary)
        is NOT published — radix granularity is full blocks — so the
        reattaching turn re-prefills at most block_size - 1 positions.
        Best-effort by design: returns the number of prefix TOKENS now
        backed, 0 on ANY mismatch (wire version, dtype, geometry,
        window-retired payloads whose gathered trash rows must never
        enter the prefix cache) or pool pressure — a declined seed
        just means a plain re-prefill, lossless by construction."""
        self._refuse_two_kinds("seeding a session's blocks")
        if (not self.paged or self._radix is None or self.spec_k
                or payload.block_size != self.block_size
                or payload.kv_dtype != self.kv_dtype
                or payload.wire_version != KV_WIRE_VERSION
                or payload.kv_window is not None
                or payload.kv_sink is not None
                or [n for n, _ in payload.leaves]
                != self._pool_leaf_names()):
            return 0
        if not payload.generated or payload.true_len != (
                payload.prompt.size + len(payload.generated) - 1):
            return 0
        bs = self.block_size
        nbf = payload.true_len // bs
        if not nbf:
            return 0
        tokens = np.concatenate(
            [payload.prompt,
             np.asarray(payload.generated[:-1], np.int32)])
        st = self._stats
        matched = self._radix.match(tokens[:nbf * bs])
        m = len(matched)
        if m < nbf:
            fresh = self._alloc_blocks(nbf - m)
            if fresh is None:
                return 0
            suffix = [np.take(a, np.arange(m, nbf),
                              axis=_pool_block_axis(n, a.ndim))
                      for n, a in payload.leaves]
            self._scatter_blocks(fresh, suffix)
            self._radix.insert(tokens[:nbf * bs], matched + fresh,
                               remote=remote)
            for b in fresh:  # the radix reference is the sole owner
                self._alloc.decref(b)
            st["kv_imported_blocks"] += nbf - m
            st["kv_stream_bytes"] += payload.nbytes
        st["session_attaches"] += 1
        st["session_seed_tokens"] += nbf * bs
        return nbf * bs

    def take_demoted_sessions(self
                              ) -> list[tuple[str, str, KVBlockPayload]]:
        """Drain the spill queue: ``(session_id, tenant, payload)``
        triples the HBM-budget sweep demoted while NO session_store is
        attached — what a router/worker absorbs into the fleet store
        (the subprocess wire's pull side)."""
        if not self.paged:
            return []
        out, self._session_spill = self._session_spill, []
        return out

    def _reattach_session(self, sid: str) -> None:
        """Pull a session's KV as close to HBM as it can get BEFORE
        the request queues, so its prefill rides the radix prefix hit:
        a resident session publishes its full blocks into the radix; a
        store-tier session seeds its payload back into the pool. A
        miss at every tier is SILENT — the prefill behind it is the
        lossless fallback, the router's fallback counter the loud
        part."""
        if sid in self._sessions:
            self._adopt_resident_session(sid)
            self._stats["session_attaches"] += 1
        elif self.session_store is not None:
            got = self.session_store.get(sid)
            if got is not None:
                self.seed_session_blocks(got[0])

    def _adopt_resident_session(self, sid: str) -> None:
        """Move a parked session from the resident tier into the radix
        prefix cache: its contiguous non-retired full blocks publish
        under the conversation tokens (the reattaching prefill matches
        them like any shared prefix), then the session's own references
        drop — the radix is the sole owner, and the partial tail block
        frees (its positions re-prefill with the new turn)."""
        rec = self._sessions.pop(sid)
        req = rec["req"]
        bs = self.block_size
        nbf = rec["true_len"] // bs
        blocks = rec["blocks"]
        # a windowed session's retired blocks are zero sentinels — the
        # radix may only ever see the contiguous LIVE prefix (a trash
        # block published as cached KV would serve garbage)
        k = 0
        while k < nbf and blocks[k]:
            k += 1
        if k and self._radix is not None:
            tokens = np.concatenate(
                [req.prompt, np.asarray(req.new_tokens, np.int32)])
            self._radix.insert(tokens[:k * bs], blocks[:k])
        for b in blocks:
            if b:
                self._alloc.decref(b)

    def _park_session(self, req: Request) -> None:
        """Park a finishing session stream's KV in the HBM-resident
        tier: ownership of the slot's blocks transfers to the session
        record (the list empties, so the _release_slot that follows
        frees everything EXCEPT them), and the LRU budget sweep demotes
        the eldest resident down the hierarchy."""
        slot = req.slot
        true_len = int(self._lengths[slot])
        if true_len < 1:
            return
        nb = -(-true_len // self.block_size)
        blocks = list(self._slot_blocks[slot][:nb])
        # blocks past true_len (grown for the write the retirement
        # preempted) stay with the slot and free in _release_slot
        self._slot_blocks[slot] = self._slot_blocks[slot][nb:]
        old = self._sessions.pop(req.session_id, None)
        if old is not None:  # superseded turn: the newer KV wins
            for b in old["blocks"]:
                if b:
                    self._alloc.decref(b)
        self._sessions[req.session_id] = dict(
            req=req, blocks=blocks, true_len=true_len,
            tenant=req.tenant)
        self._stats["session_detaches"] += 1
        self._enforce_session_budget()

    def _enforce_session_budget(self) -> None:
        while len(self._sessions) > self.session_hbm_max:
            self._demote_session(next(iter(self._sessions)))

    def _demote_session(self, sid: str) -> None:
        """Demote one resident session down the hierarchy: gather its
        blocks into a KVBlockPayload (the PR 11 wire format — the same
        bytes a disagg handoff ships) bound for the attached
        session_store's host-DRAM/disk tiers, or the bounded spill
        queue a router drains over the subprocess wire. The HBM blocks
        free either way."""
        rec = self._sessions.pop(sid)
        payload = self._session_to_payload(rec)
        st = self._stats
        st["session_demotes"] += 1
        if self.session_store is not None:
            self.session_store.put(sid, payload, tenant=rec["tenant"])
        elif len(self._session_spill) >= 64:
            # bounded: an unattended engine must not hoard host copies
            self._session_spill.pop(0)
            self._session_spill.append((sid, rec["tenant"], payload))
            st["session_dropped"] += 1
        else:
            self._session_spill.append((sid, rec["tenant"], payload))

    def _demote_all_sessions(self) -> None:
        for sid in list(self._sessions):
            self._demote_session(sid)

    def _session_to_payload(self, rec: dict) -> KVBlockPayload:
        """Gather a resident session record into the wire payload and
        free its HBM blocks — the record must already be popped."""
        req = rec["req"]
        nb = -(-rec["true_len"] // self.block_size)
        payload = KVBlockPayload(
            prompt=req.prompt.copy(), generated=list(req.new_tokens),
            true_len=rec["true_len"], block_size=self.block_size,
            max_new_tokens=req.max_new_tokens, sampling=req.sampling,
            stop_ids=tuple(req.stop_ids),
            leaves=self._gather_blocks(rec["blocks"][:nb]),
            kv_dtype=self.kv_dtype,
            kv_sink=req.kv_sink, kv_window=req.kv_window)
        for b in rec["blocks"]:
            if b:
                self._alloc.decref(b)
        self._stats["kv_stream_bytes"] += payload.nbytes
        return payload

    def export_session(self, session_id: str) -> KVBlockPayload | None:
        """Pop a RESIDENT parked session and hand it over as a
        KVBlockPayload (blocks gathered, then freed) — the fleet
        reattach's cross-replica pull: when a reattaching turn lands
        on a different replica than the session's HBM home, the router
        pulls the payload here and seeds it there. None when this
        engine holds nothing for the id (the caller falls through to
        the store tiers, then to re-prefill)."""
        self._refuse_two_kinds("export of a session")
        if not self.paged:
            return None
        rec = self._sessions.pop(session_id, None)
        if rec is None:
            return None
        payload = self._session_to_payload(rec)
        st = self._stats
        st["kv_exports"] += 1
        st["kv_exported_blocks"] += payload.num_blocks
        return payload

    def warmup_kv_stream(self) -> None:
        """Compile the KV stream's two programs with one empty-blocks
        roundtrip mirroring the real export→host→import data path, so
        the first real handoff performs zero compiles (the disagg A/B's
        tripwire). Call AFTER warmup(): the gather must see the
        steady-state (committed) pool. No-op on the dense engine."""
        if not self.paged:
            return
        leaves = self._gather_blocks([])
        self._scatter_blocks([], [a for _, a in leaves])

    def _expire_deadlines(self) -> int:
        """Retire every request past its ``deadline_s`` — still queued
        (shed before wasting a prefill on it) or resident in a slot (the
        slot frees for this very step's admissions). The engine keeps
        serving everything else; each expiry is a telemetry span plus the
        usual per-request row with the distinct finish reason."""
        now = time.perf_counter()

        def overdue(req: Request) -> bool:
            return (req.deadline_s is not None and req.submit_time is not None
                    and now - req.submit_time >= req.deadline_s)

        expired = ([r for r in self._queue if overdue(r)]
                   + [r for r in self._active.values() if overdue(r)])
        pf = getattr(self, "_prefilling", None) if self.paged else None
        if pf is not None and overdue(pf["req"]):
            # mid-chunked-prefill expiry: abandon the admission, free
            # its blocks and slot before it ever decodes
            self._release_slot(pf["slot"])
            self._prefilling = None
            expired.append(pf["req"])
        if not expired:
            return 0
        with span("serve/deadline_retire"):
            for req in expired:
                if req.slot is None and req in self._queue:
                    self._queue.remove(req)
                self._retire(req, "deadline")
        return len(expired)

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        """Step until queue, in-flight prefill and slots drain (tests /
        batch-mode use)."""
        while (self._queue or self._active
               or (self.paged and self._prefilling is not None)):
            if max_steps <= 0:
                raise RuntimeError("serving loop did not drain")
            self.step()
            max_steps -= 1

    def stream(self, req: Request):
        """Iterator over one request's tokens, stepping the engine (and
        every other resident request) as needed — the single-consumer
        streaming shape; concurrent consumers share the same step()s.
        Starts past any resume-from-tokens prefix: the client already
        holds those tokens (submit's delivery contract)."""
        sent = req.resumed_from
        while True:
            while sent < len(req.new_tokens):
                yield req.new_tokens[sent]
                sent += 1
            if req.done:
                return
            self.step()

    def warmup(self, prompt_lens=None, max_new_tokens: int = 2) -> None:
        """Compile the steady state up front: run dummy requests through
        each prefill bucket plus the decode tick, then reset stats —
        after this, serving performs ZERO recompiles (TRACE_COUNTS and the
        jitted programs' _cache_size are the tests' tripwires) and the
        first real TTFT pays no compile.

        TWO serial rounds per bucket on purpose: the engine's fresh
        cache is an uncommitted array, so round one compiles each
        program against it, and jit then recompiles — without
        retracing — when the cache next arrives committed from another
        executable's output. Round two runs every program with exactly
        the steady-state (committed) input shardings."""
        lens = tuple(prompt_lens) if prompt_lens else (self.bucket,)
        for n in lens * 2:
            n = max(1, min(n, self.cfg.max_seq_len - max_new_tokens))
            self.submit(np.zeros(n, np.int32), max_new_tokens=max_new_tokens)
            self.run_until_idle()
        # warm the health probe too: a router polling
        # check_params_finite() must find it compiled, or the first
        # steady-state health check pays a trace
        self.check_params_finite()
        # warmup TTFTs include COMPILES — a router balancing on the
        # TTFT EMA would permanently shun whichever replica compiled
        # first (the others warm from the shared jit cache in ms)
        self._ttft_ema = None
        if self.paged and self._radix is not None:
            self._radix.clear()  # don't serve real traffic warmup zeros
            self._radix.reset_stats()
        self.reset_stats()

    def drain(self) -> list[Request]:
        """Retire EVERY request — queued, mid-prefill, resident — with
        finish_reason "drained" and free their slots/blocks: the SIGTERM
        / shutdown exit path (pair with request_drain() from a signal
        handler; close() also drains). Returns the drained requests."""
        self._draining = False
        out: list[Request] = []
        if self.paged and self._sessions:
            # resident sessions demote down the hierarchy on shutdown
            # (store or spill queue) — restart-survival for the warm
            # tier, and close()'s leak assertion sees a clean pool
            self._demote_all_sessions()
        if self.paged and self._prefilling is not None:
            pf, self._prefilling = self._prefilling, None
            self._release_slot(pf["slot"])
            out.append(pf["req"])
        if self.paged and self._prefilled:
            # parked handoffs: release blocks before retiring (a parked
            # req's slot is NOT in _active — clear req.slot first so
            # _retire doesn't try to release it a second way)
            for rec in [self._prefilled.pop(k)
                        for k in list(self._prefilled)]:
                self._release_slot(rec["slot"])
                rec["req"].slot = None
                rec["req"].parked = False
                out.append(rec["req"])
        while self._queue:
            out.append(self._queue.popleft())
        out.extend(self._active.values())
        with span("serve/drain"):
            for req in out:
                self._retire(req, "drained")
        return out

    def request_drain(self) -> None:
        """Signal-handler-safe drain request: sets a flag the next
        step() honors (draining involves device/telemetry work that must
        not run inside a signal frame — the same finish-the-step
        discipline as the Trainer's SIGTERM checkpoint)."""
        self._draining = True

    def install_sigterm_drain(self) -> None:
        """Route SIGTERM to request_drain() — a preempted serving tier
        sheds its requests (streams get finish_reason "drained") instead
        of dying mid-tick with the pool in limbo."""
        import signal

        signal.signal(signal.SIGTERM, lambda *_: self.request_drain())

    def close(self) -> None:
        """Drain outstanding work, assert the paged pool's leak
        invariant (free + resident == pool: every retirement path must
        have returned its blocks), and flush telemetry."""
        self.drain()
        if self.paged:
            if self.telemetry is not None:
                st = self._stats
                spec = (dict(spec_k=self.spec_k,
                             draft_tokens=st["draft_tokens"],
                             accepted_tokens=st["accepted_tokens"],
                             acceptance_rate=(
                                 round(st["accepted_tokens"]
                                       / st["draft_tokens"], 4)
                                 if st["draft_tokens"] else None),
                             # learned-drafting identity (ISSUE 16):
                             # which draft served this engine, and how
                             # many hot-swaps it absorbed mid-serve
                             spec_heads=self._spec_heads,
                             draft_swaps=self.draft_swaps,
                             draft_params_hash=self.draft_params_hash(),
                             **(dict(accept_ema=round(
                                         float(self._accept_ema.mean()),
                                         4),
                                     effective_k=round(
                                         float(self._k_eff.mean()), 3))
                                if self.adaptive_k else {}))
                        if self.spec_k else {})
                per_block = self.kv_hbm_bytes // self.num_blocks
                self.telemetry.pool(
                    kv_hbm_bytes=self.kv_hbm_bytes,
                    block_size=self.block_size,
                    num_blocks=self.num_blocks,
                    kv_dtype=self.kv_dtype,
                    kv_bytes_resident=st["peak_blocks_used"] * per_block,
                    kv_tokens_capacity=(self._alloc.usable
                                        * self.block_size),
                    retired_blocks=st["retired_blocks"],
                    prefill_chunks=st["prefill_chunks"],
                    preemptions=st["preemptions"],
                    prefix_hit_tokens=st["prefix_hit_tokens"],
                    admitted_tokens=st["admitted_tokens"],
                    **spec,
                    **(self._radix.stats() if self._radix is not None
                       else {}))
            cached = (self._radix.block_count
                      if self._radix is not None else 0)
            self._alloc.check_leaks(expected_resident=cached)
            if self._radix is not None:
                self._radix.clear()
            for pool in self._pools:
                pool.alloc.check_leaks(0)
        if self.telemetry is not None:
            self.telemetry.close()

    # ------------------------------------------------------------------
    # internals

    def _device_tables(self) -> dict:
        """{table leaf: [slots, pages]} of every pool, for a tick."""
        return {pool.table: jnp.asarray(pool.tables)
                for pool in self._pools}

    def _tick_program(self):
        """(jitted program, dynamic args) of the plain decode tick over
        the live host state: one shared per-slot argument tail; the
        paged tick just prepends the host-stamped block tables and
        lengths."""
        tick, head = ((paged_decode_tick, (self._device_tables(),
                                           jnp.asarray(self._lengths)))
                      if self.paged else (decode_tick, ()))
        return tick, (self._weights, self._cache, *head,
                      jnp.asarray(self._tokens),
                      jnp.asarray(self._key_data),
                      jnp.asarray(self._counts),
                      jnp.asarray(self._temps),
                      jnp.asarray(self._top_ks),
                      jnp.asarray(self._top_ps))

    def lower_tick(self, *, platforms: tuple[str, ...] | None = None):
        """AOT-lower this engine's plain decode tick from its live
        operands (nothing runs, nothing is donated) — the serving twin of
        `Trainer.lower_step`, ``platforms`` included:
        ``.compile().as_text()`` is the HLO the tick dispatches, which is
        how chip_smoke.py and the lowering tests see whether the paged
        kernel is really in it."""
        tick, args = self._tick_program()
        with self._mesh_ctx():
            if platforms is None:
                return tick.lower(self._tick_model, *args,
                                  candidates=self.candidates)
            return tick.trace(self._tick_model, *args,
                              candidates=self.candidates).lower(
                                  lowering_platforms=platforms)

    def placement(self) -> dict[str, list[int]]:
        """Ids of the devices the weights and the KV cache live on — how
        a fleet of one-chip replicas is checked for having all landed on
        chip 0."""
        def ids(tree):
            return sorted({d.id for leaf in jax.tree.leaves(tree)
                           for d in leaf.devices()})

        return {"weights": ids(self._weights), "kv": ids(self._cache)}

    def _mesh_ctx(self):
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _admit(self, req: Request) -> None:
        if req.admit_time is None:
            req.admit_time = time.perf_counter()
        slot = self._free.pop()
        # a resume-from-tokens submit (router failover) prefills
        # prompt + already-generated — the dense twin of the paged
        # engine's preempt-requeue re-prefill; the continuation token is
        # sampled with fold_in count == resume so seeded streams pick up
        # exactly where they stopped
        tokens = np.concatenate(
            [req.prompt, np.asarray(req.new_tokens, np.int32)])
        n = int(tokens.size)
        resume = len(req.new_tokens)
        padded_len = min(-(-n // self.bucket) * self.bucket,
                         self.cfg.max_seq_len)
        padded = np.zeros((1, padded_len), np.int32)
        padded[0, :n] = tokens
        kd = np.asarray(jax.random.key_data(
            jax.random.key(req.sampling.seed)))
        with span("serve/prefill", request=req.id), self._mesh_ctx():
            # one jit signature per prefill bucket length
            self._cache, first = prefill_into_slot(
                self._prefill_model, self._weights, self._cache,
                jnp.asarray(padded), jnp.int32(n), jnp.int32(slot),
                jnp.asarray(kd), jnp.int32(resume),
                jnp.float32(req.sampling.temperature),
                jnp.int32(req.sampling.top_k),
                jnp.float32(req.sampling.top_p),
                candidates=self.candidates)
            with span("serve/prefill_sync", request=req.id):
                first = int(first)  # sync: the TTFT timestamp is honest
        now = time.perf_counter()
        self._progress += 1
        st = self._stats
        st["prefills"] += 1
        req.slot = slot
        if req.first_token_time is None:
            req.first_token_time = now
            if req.submit_time is not None:
                self._note_ttft(req, now)
        self._trace_span(req, "prefill", req.admit_time, now,
                         resumed_from=req.resumed_from)
        self._active[slot] = req
        self._key_data[slot] = kd
        self._counts[slot] = resume + 1  # token n samples fold_in(key, n)
        self._temps[slot] = req.sampling.temperature
        self._top_ks[slot] = req.sampling.top_k
        self._top_ps[slot] = req.sampling.top_p
        self._deliver(req, first)

    def _deliver(self, req: Request, tok: int) -> None:
        req.new_tokens.append(tok)
        self._tokens[req.slot] = tok  # next tick's input for this slot
        if req.on_token is not None:
            req.on_token(req, tok)
        if tok in req.stop_ids:
            self._retire(req, "stop")
        elif len(req.new_tokens) >= req.max_new_tokens:
            self._retire(req, "length")

    def _retire(self, req: Request, reason: str) -> None:
        req.done = True
        req.finish_reason = reason
        req.finish_time = time.perf_counter()
        if req.slot is not None:  # deadline-expired in queue: no slot yet
            del self._active[req.slot]
            if self.paged:
                if (req.session_id is not None
                        and reason in ("stop", "length")):
                    # a CLEANLY finishing session turn parks its KV in
                    # the resident tier (ownership transfers off the
                    # slot before the release below); sheds —
                    # deadline, drain — free normally, the store's
                    # older copy (if any) stays the session's truth
                    self._park_session(req)
                # EVERY retirement path funnels here: the slot's blocks
                # go back to the pool (or live on only through the radix
                # cache's own reference) — close() asserts none leak
                self._release_slot(req.slot)
            else:
                self._free.append(req.slot)
                self._temps[req.slot] = 0.0  # idle slots tick greedy
        self._stats["completed"] += 1
        if reason == "deadline":
            self._stats["deadline_expired"] += 1
        self._trace_span(
            req, "decode",
            (req.first_token_time if req.first_token_time is not None
             else req.submit_time),
            req.finish_time, new_tokens=len(req.new_tokens),
            finish_reason=reason, preemptions=req.preemptions)
        if self.telemetry is not None:
            self.telemetry.request(req)

    def _trace_span(self, req: Request, stage: str, t0, t1,
                    **attrs) -> None:
        """Emit one request-trace span (ISSUE 17) — a no-op unless BOTH
        a tracer is wired and the request carries a TraceContext, so
        tracing off costs one attribute read per lifecycle edge."""
        if self.trace is None or req.trace is None or t0 is None:
            return
        if self.telemetry is not None:
            attrs.setdefault("replica", self.telemetry.rank)
        self.trace.span(req.trace, stage, t0, t1, **attrs)

    def _note_ttft(self, req: Request, now: float) -> None:
        """The first token of ``req`` landed at ``now``: its TTFT, and the
        two parts that sum to it — the wait in this engine's queue
        (submit to admit) and the prefill span (admit to first token)."""
        dt = now - req.submit_time
        st = self._stats
        st["ttft_s"].append(dt)
        st["queue_wait_s"].append(req.admit_time - req.submit_time)
        st["prefill_span_s"].append(now - req.admit_time)
        self._trace_span(req, "queue", req.submit_time, req.admit_time,
                         where="engine")
        self._ttft_ema = (dt if self._ttft_ema is None
                          else 0.8 * self._ttft_ema + 0.2 * dt)

    # ------------------------------------------------------------------
    # health (ISSUE 9): the snapshot the replica router polls

    def health(self) -> dict:
        """One host-side health/load snapshot — NO device work (the
        params-finite probe is ``check_params_finite``, priced
        separately so the router chooses its cadence):

          * ``progress`` — monotonic count of completed compiled calls
            (ticks + prefills + chunks). A replica with work whose
            watermark stops moving is hung (the serving analog of
            runtime/heartbeat.py's device-sync rule: every increment
            sits after a host sync of device results, so it can't be
            the async-dispatch illusion);
          * ``occupancy`` / ``queued`` / ``free_slots`` /
            ``prefilling`` — the load-balancing signals;
          * ``pool_free_frac`` — paged pool headroom (1.0 dense);
          * ``ttft_ema_s`` — smoothed recent time-to-first-token;
          * ``sick`` — the last params-finite probe verdict (True
            after a NaN poisoning until the probe passes again)."""
        free_frac = 1.0
        if self.paged:
            free_frac = self._alloc.free_count / max(1, self._alloc.usable)
        out = {
            "alive": True,
            "progress": self._progress,
            "active": len(self._active),
            "queued": len(self._queue),
            "free_slots": len(self._free),
            "prefilling": self.prefilling_count,
            "num_slots": self.num_slots,
            "occupancy": len(self._active) / self.num_slots,
            "pool_free_frac": round(free_frac, 4),
            "ttft_ema_s": self._ttft_ema,
            "sick": self._sick,
            # process-wide compiled-program census: a soak's invariant
            # checker watches this NOT grow on survivors (fresh XLA
            # traces mid-serving mean the warmup contract broke)
            "trace_count": int(sum(TRACE_COUNTS.values())),
        }
        if self.paged:
            # the disagg signals (ISSUE 12): parked handoffs awaiting
            # export, the pool geometry a router needs to hash prompts
            # for fleet prefix steering, this replica's published
            # block-hash frontier, and the cross-replica hit counters
            out["parked"] = len(self._prefilled)
            out["block_size"] = self.block_size
            out["kv_dtype"] = self.kv_dtype
            out["remote_hit_tokens"] = self._stats["remote_hit_tokens"]
            out["admitted_tokens"] = self._stats["admitted_tokens"]
            if self._radix is not None:
                out["prefix_frontier"] = self._radix.frontier()
            # the session signals (ISSUE 18): how many sessions park
            # in this replica's HBM tier, and WHICH — the router's
            # FleetSessionIndex steers reattaching requests by this
            # frontier exactly like prefix steering
            out["sessions_resident"] = len(self._sessions)
            out["session_frontier"] = list(self._sessions)[-64:]
        return out

    def check_params_finite(self) -> bool:
        """Run the compiled params-finite probe (one scalar sync) and
        record the verdict in ``health()['sick']``. False = this
        replica's weights carry NaN/Inf — every token it emits is
        garbage and a router must quarantine it. `serve/probe` is the
        whole check, `serve/probe_sync` under it the wait for the
        device's answer."""
        with span("serve/probe"), self._mesh_ctx():
            finite = params_finite(self._weights)
            with span("serve/probe_sync"):
                ok = bool(finite)
        self._sick = not ok
        return ok

    def set_params(self, params) -> None:
        """Swap the serving weights in place (same treedef — the
        compiled programs retrace on a structure change, never on new
        values). The quarantine/rejoin path: an operator repairs a
        NaN'd replica by reloading a verified checkpoint here, then the
        router's warmup re-admission probes it healthy again.

        The engine keeps ONE tree, in the type the programs compute in
        and the layout they read: a leaf stored wider than ``cfg.dtype``
        that the model would only cast to it (every matrix it multiplies
        by, the embeddings) is cast here, once, a scanned stack's fused
        kernels are held as planes, and every other leaf (a norm's
        float32 gain, a tree already stored in the compute type) is kept
        as it came (serving/weights.py). So a float32 checkpoint and the
        served tree of another replica are both good arguments, and
        neither retraces."""
        (self._weights, self._weight_bytes_cast,
         self._weight_bytes_relaid) = self._compute_copy(
            self._tick_model, self._cache,
            params["params"] if "params" in params else params)

    def _compute_copy(self, model, cache, tree):
        """`tree` as `model`'s programs take it (`serving/weights.py:
        served`), the bytes the leaves cast here held before, and the
        bytes of the leaves re-laid here. The forward pass is traced only
        for a tree with a leaf wider than the compute type; a tree with
        nothing to cast or re-lay comes back as it is."""
        dtype = model.cfg.dtype
        leaves, treedef = jax.tree.flatten(tree)
        if not any(wider(leaf, dtype) for leaf in leaves):
            return served(tree, None, dtype)
        flags = self._cast_only.get((model, treedef))
        if flags is None:
            tokens = jnp.zeros((self.num_slots, 1), jnp.int32)
            # a model with proposal heads reads them in `spec_logits`
            # alone, which reads every other leaf as the call does
            method = ("spec_logits" if getattr(model.cfg, "spec_heads", 0)
                      else None)
            with self._mesh_ctx():
                flags = self._cast_only[model, treedef] = cast_only(
                    lambda w, c: model.apply(
                        {"params": w, "cache": c}, tokens, method=method,
                        mutable=["cache", "counters"]),
                    tree, dtype, cache)
        return served(tree, flags, dtype)

    def set_draft_params(self, params) -> None:
        """Hot-swap the DRAFT weights mid-serving (ISSUE 16) — the
        distill→swap→measure loop's serve-side handle. The new tree must
        match the current draft's structure and leaf shapes exactly (the
        draft ARCHITECTURE is baked into the compiled tick; only values
        may move), which also guarantees no retrace: resident streams
        keep ticking and their tokens never change — draft quality moves
        ACCEPTANCE only, the rejection kernel is lossless either way
        (greedy streams are bitwise-identical across the swap; tests pin
        that mid-stream)."""
        if not self.spec_k:
            raise ValueError(
                "set_draft_params on a non-speculative engine (spec_k "
                "== 0): there is no draft to swap")
        import flax.linen as nn

        # in the layout the resident draft is served in (its fused
        # kernels as planes), so that the two compare leaf for leaf
        new, _, _ = served(nn.meta.unbox(params["params"] if "params" in
                                         params else params),
                           None, self._draft_tick_model.cfg.dtype)
        old_leaves = jax.tree_util.tree_flatten_with_path(
            self._draft_weights)
        new_leaves = jax.tree_util.tree_flatten_with_path(new)
        if old_leaves[1] != new_leaves[1]:
            raise ValueError(
                "draft param tree structure mismatch — a hot-swap may "
                "only replace VALUES for the architecture the engine "
                "compiled (same num_layers / spec_heads; rebuild the "
                "engine to change the draft's shape)")
        for (path, a), (_, b) in zip(old_leaves[0], new_leaves[0]):
            if getattr(a, "shape", None) != getattr(b, "shape", None):
                raise ValueError(
                    f"draft param shape mismatch at "
                    f"{jax.tree_util.keystr(path)}: engine has "
                    f"{getattr(a, 'shape', None)}, swap brings "
                    f"{getattr(b, 'shape', None)}")
        # the swap in the type the tick computes in, as the resident tree
        # is held (set_params): a float32 checkpoint of a draft that was
        # booted from float32 matches, leaf for leaf
        new, _, _ = self._compute_copy(self._draft_tick_model,
                                       self._draft_cache, new)
        for (path, a), b in zip(old_leaves[0], jax.tree.leaves(new)):
            if jnp.asarray(b).dtype != getattr(a, "dtype", None):
                raise ValueError(
                    f"draft param dtype mismatch at "
                    f"{jax.tree_util.keystr(path)}: engine compiled "
                    f"{getattr(a, 'dtype', None)}, swap brings "
                    f"{jnp.asarray(b).dtype} — precision is baked into "
                    f"the tick; a cast here would not be value-lossless")
        # re-place each leaf to be cache-key-identical to the RESIDENT
        # leaf: the pjit cache keys on sharding AND committedness, so a
        # checkpoint restored under a trainer mesh (committed
        # NamedSharding leaves vs the boot tree's uncommitted
        # default-device ones) would silently retrace the tick — and
        # the first post-swap step would stall a subprocess replica
        # straight into the router's hang watchdog
        def _like(b, a):
            if not hasattr(a, "sharding"):
                return jnp.asarray(b)
            if getattr(a, "_committed", True):
                return jax.device_put(b, a.sharding)
            # uncommitted resident leaf: round-trip through host so the
            # result is an uncommitted default-device array too
            return jnp.asarray(np.asarray(b))

        self._draft_weights = jax.tree.map(_like, new,
                                           self._draft_weights)
        self.draft_swaps += 1
        self._draft_hash = None  # recomputed lazily on next read

    def draft_params_hash(self) -> str | None:
        """8-hex fingerprint of the CURRENT draft weights (None when
        spec is off) — per-leaf fp32 sums hashed with the tree paths, so
        a replica row can show WHICH draft it serves and a fleet
        broadcast can be audited replica-by-replica without shipping
        trees around. Computed lazily, cached until the next swap."""
        if not self.spec_k:
            return None
        if getattr(self, "_draft_hash", None) is None:
            h = hashlib.sha1()
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                    self._draft_weights):
                h.update(jax.tree_util.keystr(path).encode())
                h.update(np.float64(
                    jnp.sum(jnp.asarray(leaf, jnp.float32))).tobytes())
            self._draft_hash = h.hexdigest()[:8]
        return self._draft_hash

    def invalidate_prefix_cache(self) -> None:
        """Drop every radix-cached prefix block (refcounts released; a
        block still referenced by a resident slot survives until that
        slot retires). A rejoining quarantined replica must do this:
        blocks cached while its params were NaN hold poisoned K/V that
        a future prefix hit would serve as truth."""
        if self.paged and self._radix is not None:
            self._radix.clear()

    # ------------------------------------------------------------------
    # stats

    def reset_stats(self) -> None:
        self._stats = dict(ticks=0, prefills=0, decode_tokens=0,
                           occupancy_sum=0.0, completed=0,
                           deadline_expired=0, ttft_s=[],
                           # one entry a request, beside its ttft_s:
                           # submit -> admit and admit -> first token;
                           # admit_blocked = steps whose queue head the
                           # POOL (not the prefill lane) kept waiting
                           queue_wait_s=[], prefill_span_s=[],
                           admit_blocked=0,
                           # paged-mode counters (stay 0 on dense)
                           admissions=0, admitted_tokens=0,
                           prefix_hit_tokens=0, prefill_chunks=0,
                           preemptions=0, preempted_requests=0,
                           block_used_sum=0.0,
                           # KV-compression counters (ISSUE 13):
                           # high-water pool occupancy in blocks (the
                           # kv_bytes_resident numerator) and blocks
                           # retired mid-stream by the sliding window
                           peak_blocks_used=0, retired_blocks=0,
                           # disaggregation counters (ISSUE 12; stay 0
                           # colocated)
                           remote_hit_tokens=0, kv_exports=0,
                           kv_imports=0, kv_exported_blocks=0,
                           kv_imported_blocks=0, kv_stream_bytes=0,
                           # speculative counters (stay 0 when spec off)
                           draft_tokens=0, accepted_tokens=0,
                           target_forwards=0,
                           # persistent-session counters (ISSUE 18):
                           # detaches = turns parked/exported, attaches
                           # = reattach KV hits (any tier), seed_tokens
                           # = prefix tokens seeded from stored
                           # payloads, demotes = HBM -> store/spill
                           # evictions, dropped = spill-queue overflow
                           session_detaches=0, session_attaches=0,
                           session_seed_tokens=0, session_demotes=0,
                           session_dropped=0)
        # what the model counts on the device a tick and a chunk (summed
        # here; a chunk's still on its way are dropped with the stats),
        # and each windowed pool's share of the host's bookkeeping
        self._chunk_counted = []
        if self._counter_names:
            self._stats["device_counters"] = np.zeros(
                len(self._counter_names))
            self._stats["chunk_device_counters"] = np.zeros(
                len(self._counter_names))
        for pool in self._pools[1:]:
            self._stats[f"{pool.kind}_blocks_retired"] = 0
            self._stats[f"{pool.kind}_block_used_sum"] = 0.0

    def _fold_chunk_counters(self) -> None:
        """The device counters of the chunks dispatched so far, into the
        stats (each fetched as it was dispatched: no wait here but for
        a chunk still running)."""
        if self._chunk_counted:
            self._stats["chunk_device_counters"] += np.sum(
                jax.device_get(self._chunk_counted), axis=0)
            self._chunk_counted = []

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def active_count(self) -> int:
        return len(self._active)

    @property
    def prefilling_count(self) -> int:
        """Admissions mid-chunked-prefill (0 or 1; always 0 dense) —
        include it in any is-there-work-left check alongside queue_depth
        and active_count."""
        return int(self.paged and self._prefilling is not None)

    def summary(self) -> dict:
        """Aggregate serving metrics since the last reset_stats(): the
        counts, TTFT percentiles and their two parts, mean slot
        occupancy, and what the pool held. A rate is the benchmark's to
        compute (benchmark/metrics/), from its own window."""
        st = self._stats
        ttfts = np.asarray(st["ttft_s"], np.float64)
        out = {
            "requests_completed": st["completed"],
            "deadline_expired": st["deadline_expired"],
            "ticks": st["ticks"],
            "prefills": st["prefills"],
            "slot_occupancy": (
                round(st["occupancy_sum"] / st["ticks"], 4)
                if st["ticks"] else None),
        }
        if ttfts.size:
            out["ttft_ms_p50"] = round(
                float(np.percentile(ttfts, 50)) * 1e3, 3)
            out["ttft_ms_p99"] = round(
                float(np.percentile(ttfts, 99)) * 1e3, 3)
            # TTFT's two parts, one entry a request each: the wait in
            # this engine's queue, and admission to first token
            for key, q in (("queue_wait", 50), ("queue_wait", 95),
                           ("prefill_span", 50)):
                out[f"{key}_ms_p{q}"] = round(float(np.percentile(
                    np.asarray(st[f"{key}_s"], np.float64), q)) * 1e3, 3)
        out["admit_blocked"] = st["admit_blocked"]
        out["kv_hbm_bytes"] = self.kv_hbm_bytes
        # the tree the programs take, what its leaves held before the
        # last set_params cast them to the compute type (0: served in the
        # type it came in), and the bytes of the fused kernels it re-laid
        # as planes (0: none, or a tree that came as planes)
        out["weight_bytes_served"] = sum(
            leaf.nbytes for leaf in jax.tree.leaves(self._weights))
        out["weight_bytes_cast"] = self._weight_bytes_cast
        out["weight_bytes_relaid"] = self._weight_bytes_relaid
        if self.paged:
            out["block_size"] = self.block_size
            out["num_blocks"] = self.num_blocks
            # KV-compression telemetry (ISSUE 13): the pool's storage
            # dtype, its token capacity after the reserved trash block,
            # the high-water HBM actually resident in KV blocks
            # (peak blocks x bytes/block, scale planes included), and
            # how many blocks the sliding window retired mid-stream
            out["kv_dtype"] = self.kv_dtype
            out["kv_tokens_capacity"] = (self._alloc.usable
                                         * self.block_size)
            out["kv_bytes_resident"] = (
                st["peak_blocks_used"]
                * (self.kv_hbm_bytes // self.num_blocks))
            out["peak_blocks_used"] = st["peak_blocks_used"]
            out["retired_blocks"] = st["retired_blocks"]
            if self.kv_window_tokens:
                out["kv_window_tokens"] = self.kv_window_tokens
                out["kv_sink_tokens"] = self.kv_sink_tokens
            out["paged_attn"] = self.paged_attn
            if getattr(self.cfg, "router_experts", 0):
                from pytorchdistributed_tpu.models.moe import banks_read

                out["expert_banks"] = banks_read(self._tick_model.cfg,
                                                 self._weights)
            out["prefill_chunks"] = st["prefill_chunks"]
            out["preemptions"] = st["preemptions"]
            out["preempted_requests"] = st["preempted_requests"]
            out["block_utilization"] = (
                round(st["block_used_sum"] / st["ticks"], 4)
                if st["ticks"] else None)
            # prefix_hit_rate stays LOCAL-only (comparable to
            # single-engine runs); fleet-shipped prefix hits report as
            # cross_replica_hit_rate — the steering win, priced apart
            out["prefix_hit_rate"] = (
                round((st["prefix_hit_tokens"]
                       - st["remote_hit_tokens"])
                      / st["admitted_tokens"], 4)
                if st["admitted_tokens"] else None)
            out["prefix_hit_tokens"] = st["prefix_hit_tokens"]
            out["remote_hit_tokens"] = st["remote_hit_tokens"]
            out["admitted_tokens"] = st["admitted_tokens"]
            out["cross_replica_hit_rate"] = (
                round(st["remote_hit_tokens"] / st["admitted_tokens"], 4)
                if st["admitted_tokens"] else None)
            out["kv_exports"] = st["kv_exports"]
            out["kv_imports"] = st["kv_imports"]
            out["kv_exported_blocks"] = st["kv_exported_blocks"]
            out["kv_imported_blocks"] = st["kv_imported_blocks"]
            out["kv_stream_bytes"] = st["kv_stream_bytes"]
            # persistent-session telemetry (ISSUE 18): the HBM tier's
            # current residency and the lifecycle counters — the
            # host-DRAM/disk tiers report from SessionStore.stats()
            per_block = self.kv_hbm_bytes // self.num_blocks
            out["sessions"] = dict(
                resident=len(self._sessions),
                resident_blocks=sum(
                    len(r["blocks"])
                    for r in self._sessions.values()),
                resident_bytes=per_block * sum(
                    len(r["blocks"])
                    for r in self._sessions.values()),
                detaches=st["session_detaches"],
                attaches=st["session_attaches"],
                seed_tokens=st["session_seed_tokens"],
                demotes=st["session_demotes"],
                dropped=st["session_dropped"])
            if self._radix is not None:
                out["prefix_cache"] = self._radix.stats()
        if self._counter_names:
            # the device-side counters (models/latent.py: COUNTERS),
            # summed over the window's ticks, and under `chunk_` names
            # over its prefill chunks
            self._fold_chunk_counters()
            out.update(zip(self._counter_names,
                           (float(v) for v in st["device_counters"])))
            out.update(zip((f"chunk_{n}" for n in self._counter_names),
                           (float(v) for v in st["chunk_device_counters"])))
        if len(self._pools) > 1:
            # each pool by its kind; the first's share over the ticks is
            # `block_utilization`, above, and under its kind's name here
            for pool in self._pools:
                out[f"{pool.kind}_blocks_in_use"] = pool.in_use
            out[f"{self._pools[0].kind}_block_utilization"] = out[
                "block_utilization"]
            for pool in self._pools[1:]:
                out[f"{pool.kind}_blocks_retired"] = st[
                    f"{pool.kind}_blocks_retired"]
                out[f"{pool.kind}_block_utilization"] = (
                    round(st[f"{pool.kind}_block_used_sum"] / st["ticks"],
                          4) if st["ticks"] else None)
        if self.spec_k:
            out["spec_k"] = self.spec_k
            out["draft_tokens"] = st["draft_tokens"]
            out["accepted_tokens"] = st["accepted_tokens"]
            out["acceptance_rate"] = (
                round(st["accepted_tokens"] / st["draft_tokens"], 4)
                if st["draft_tokens"] else None)
            # emitted tokens per target-model forward — the speculative
            # multiplier on the memory-bound decode path (1.0 when spec
            # is off; up to spec_k + 1 at full acceptance)
            out["tokens_per_target_forward"] = (
                round(st["decode_tokens"] / st["target_forwards"], 3)
                if st["target_forwards"] else None)
            out["draft_kv_hbm_bytes"] = self.draft_kv_hbm_bytes
            # learned-drafting telemetry (ISSUE 16): which draft this
            # engine serves (fingerprint + how many hot-swaps it has
            # absorbed), the head-parallel flag, and — adaptive mode —
            # the fleet-mean acceptance EMA and effective depth
            out["spec_heads"] = self._spec_heads
            out["adaptive_k"] = self.adaptive_k
            out["draft_swaps"] = self.draft_swaps
            out["draft_params_hash"] = self.draft_params_hash()
            if self.adaptive_k:
                out["accept_ema"] = round(
                    float(self._accept_ema.mean()), 4)
                out["effective_k"] = round(float(self._k_eff.mean()), 3)
        return out

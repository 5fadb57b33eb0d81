"""Autoregressive generation with a KV cache.

The reference's only inference ambition is the llama-7b
`device_map="auto"` cell (reference 03_model_parallel.ipynb:86-89), which
never ran. This is the TPU-native realization: a jitted `lax.scan` decode
loop over the model's "cache" collection (TransformerConfig(decode=True) —
each attention layer keeps a [b, max_seq_len, kv_heads, head_dim] K/V cache
updated in place per step), with greedy / temperature / top-k sampling.

Design notes (XLA semantics):
  * the whole generate call is ONE compiled program — a single chunked
    prefill forward fills the cache over the whole prompt, then a
    `lax.scan` emits one token per tick; no per-token dispatch from Python;
  * static shapes: the cache is allocated at `max_seq_len` up front and the
    scan always runs `max_new_tokens` ticks; stop ids freeze finished rows
    (they keep emitting the pad/stop id) instead of exiting early;
  * sharding: params may be sharded (dp/tp rules) — the decode einsums
    partition the same way the training ones do; generate runs under
    whatever mesh the params live on;
  * retrace control: every distinct (prompt_len, max_new_tokens) pair is a
    distinct compiled program; `generate_bucketed` pads both up to
    128-lane buckets so variable-length traffic hits a handful of programs
    (TRACE_COUNTS is the regression counter the tests pin).

The sampling helpers (`_sample` for batch-uniform params,
`sample_slots` for the per-row vectorized variant) and the
`attend_window` cache-window rule are shared with the continuous-batching
serving engine (serving/).
"""

from __future__ import annotations

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Traced-body invocation counter, keyed by program name: the python body
# of a jitted function runs only when jax actually (re)traces it, so this
# is the retrace tripwire the bucketing tests pin (a cache hit never
# touches it).
TRACE_COUNTS: collections.Counter = collections.Counter()


def attend_window(max_seq_len: int, total: int, lanes: int = 128) -> int:
    """The decode-time attention window for a generation reaching ``total``
    tokens: 128-lane-rounded, clamped to the model's context. Shared by
    generate() and the serving engine so both bound per-tick score work
    the same way."""
    return min(max_seq_len, -(-total // lanes) * lanes)


def stop_ids_tuple(eos_id) -> tuple[int, ...]:
    """Normalize the ``eos_id`` argument (None | int | sequence of ints) to
    the static tuple the jitted programs hash on. Tokenizers commonly have
    several stop ids (e.g. <|eot_id|> and <|end_of_text|>); any of them
    freezes a row, and frozen rows keep emitting the FIRST id as pad."""
    if eos_id is None:
        return ()
    if isinstance(eos_id, (int, np.integer)):
        return (int(eos_id),)
    return tuple(int(e) for e in eos_id)


def matches_stop(tok, stop_ids: tuple[int, ...]):
    """[b] bool: does each token match any of the (static) stop ids?"""
    if not stop_ids:
        return jnp.zeros(tok.shape, bool)
    hit = tok == stop_ids[0]
    for s in stop_ids[1:]:
        hit = hit | (tok == s)
    return hit


# Width of a group of the candidate search: a lane tile, so that the
# gather of the chosen groups moves whole rows (v5e: groups of 64 sort
# half the numbers and read within 0.05 ms a tick of these).
_GROUP = 128


def _order_key(bits):
    """Signed integers whose order is ``lax.top_k``'s order of the floats
    they are the bit patterns of (XLA's total order: -NaN < -inf < ... <
    -0 < +0 < ... < +inf < +NaN). Its own inverse."""
    width = bits.dtype.itemsize * 8
    return bits ^ ((bits >> (width - 1)) & jnp.iinfo(bits.dtype).max)


def _top_candidates(logits, c: int):
    """``lax.top_k(logits, c)`` bit for bit -- values descending, of equal
    values the lower id first -- without a sort of the vocabulary where it
    is wider than the ``c * _GROUP`` numbers sorted here: each whole
    contiguous group's maximum, the ``c`` best groups, then ``lax.top_k``
    over their members gathered in ascending group order, with the
    ``v % _GROUP`` numbers past the last whole group behind them. Exact,
    not approximate: a member of the row's top ``c`` whose group were not
    chosen would have ``c`` groups before it, each holding a number that
    ranks before it; and positions in the gathered row rise with the id,
    so ties fall as they do in the whole row. The path follows from the
    static shape alone; a narrow vocabulary takes ``lax.top_k`` itself."""
    *lead, v = logits.shape
    g = _GROUP
    if v <= c * g:
        return lax.top_k(logits, c)
    groups, whole = v // g, v // g * g
    # the search reads the logits as their producer writes them: without
    # the barrier XLA folds the cut into groups into the head's matmul
    # and, to feed it, copies the whole head into another layout every
    # call (v5e, SmallThinker's [2,560, 151,936]: 2.4 ms a tick)
    logits = lax.optimization_barrier(logits).reshape(-1, v)
    x = logits[:, :whole].reshape(-1, groups, g)
    # a group's maximum in top_k's own order (jnp.max would rank a group
    # holding a -NaN last and leaves the sign of a zero open)
    keys = _order_key(lax.bitcast_convert_type(
        x, jnp.dtype(f"int{x.dtype.itemsize * 8}")))
    best = lax.bitcast_convert_type(
        _order_key(jnp.max(keys, axis=-1)), x.dtype)
    chosen = jnp.sort(lax.top_k(best, c)[1], axis=-1)       # [n, c] ascending
    members = jax.vmap(lambda row, ids: row[ids])(x, chosen)  # [n, c, g]
    members = jnp.concatenate(
        [members.reshape(-1, c * g), logits[:, whole:]], axis=-1)
    ids = jnp.concatenate(
        [(chosen[:, :, None] * g + jnp.arange(g)).reshape(-1, c * g),
         jnp.broadcast_to(jnp.arange(whole, v), (x.shape[0], v - whole))],
        axis=-1)
    vals, pos = lax.top_k(members, c)
    idxs = jnp.take_along_axis(ids, pos, axis=-1)
    return vals.reshape(*lead, c), idxs.reshape(*lead, c)


def _sample(logits, key, *, temperature: float, top_k: int | None,
            top_p: float | None = None, top_p_candidates: int = 256):
    """One sampling step over [b, vocab] fp32 logits (batch-uniform
    params — every row shares temperature/top_k/top_p; the per-row
    variant is sample_slots)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_p is not None:
        # Nucleus sampling over the top-C candidates (C = top_k or
        # top_p_candidates), found exactly by groups (_top_candidates:
        # a row's sort is of C * _GROUP numbers, not of the vocabulary);
        # in practice the p-mass lives far inside the top 256. For
        # flat/high-temperature distributions where the true nucleus may
        # be wider, raise top_p_candidates (vocab_size recovers exact
        # nucleus sampling). Drop candidates
        # once the cumulative probability BEFORE them reaches p (the
        # first token always survives); the retained mass is
        # renormalized over the candidate set.
        c = min(top_k or top_p_candidates, logits.shape[-1])
        vals, idxs = _top_candidates(logits, c)  # descending
        probs = jax.nn.softmax(vals, axis=-1)
        cum = jnp.cumsum(probs, axis=-1) - probs
        vals = jnp.where(cum >= top_p, -jnp.inf, vals)
        choice = jax.random.categorical(key, vals, axis=-1)
        return jnp.take_along_axis(
            idxs, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
    if top_k is not None:
        # the k-th largest logit, exact, without a sort of the vocabulary
        kth = _top_candidates(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _slot_candidates(logits, temperature, top_k, top_p, candidates: int):
    """The shared per-row candidate filter behind ``sample_slots`` and
    ``slot_filtered_probs``: top-``candidates`` logits per row (exactly
    ``lax.top_k``'s, values descending and of equal values the lower id
    first; found by groups where the vocabulary is wide, so that a row's
    sort is of ``candidates * _GROUP`` numbers: ``_top_candidates``),
    rank-masked by the dynamic per-row top_k, temperature-scaled,
    nucleus-masked
    (drop candidates once the cumulative probability BEFORE them reaches
    p — the first candidate always survives, same rule as _sample).
    Returns ``(vals, idxs)``: [n, c] filtered/scaled logits (-inf at
    dropped candidates) and their vocab ids. One function so the sampler
    and the speculative-decoding probability vectors can never drift
    apart — losslessness of the rejection kernel depends on q/p being
    EXACTLY the distributions the sampler draws from."""
    c = min(candidates, logits.shape[-1])
    vals, idxs = _top_candidates(logits, c)      # [n, c] descending
    k = jnp.where(top_k > 0, jnp.minimum(top_k, c), c)
    vals = jnp.where(jnp.arange(c)[None, :] < k[:, None], vals, -jnp.inf)
    vals = vals / jnp.maximum(temperature, 1e-6)[:, None]
    probs = jax.nn.softmax(vals, axis=-1)
    cum = jnp.cumsum(probs, axis=-1) - probs
    vals = jnp.where(cum >= top_p[:, None], -jnp.inf, vals)
    return vals, idxs


def sample_slots(logits, keys, temperature, top_k, top_p, *,
                 candidates: int = 64):
    """Per-row sampling over ``[n, vocab]`` fp32 logits where every row
    carries its OWN (dynamic) sampling params — the serving engine's one
    compiled sampler for any mix of requests.

      keys:        [n] typed PRNG keys (one stream per request).
      temperature: [n] f32; <= 0 means greedy for that row.
      top_k:       [n] i32; <= 0 disables (row keeps all candidates).
      top_p:       [n] f32; >= 1 disables.
      candidates:  static candidate-set width C — per-row top_k is a rank
        mask over the shared top-C prefix (a dynamic per-row k cannot
        be a static top_k argument), so effective top_k caps at C. The
        prefix is ``lax.top_k(logits, C)`` bit for bit, found without
        sorting the vocabulary (``_top_candidates``).

    Greedy rows take idxs[:, 0] == argmax (the search is index-stable), so
    a temperature-0 row is bitwise `jnp.argmax` — the parity property the
    serving tests pin against generate()."""
    vals, idxs = _slot_candidates(logits, temperature, top_k, top_p,
                                  candidates)
    greedy = idxs[:, 0]
    choice = jax.vmap(jax.random.categorical)(keys, vals)
    sampled = jnp.take_along_axis(idxs, choice[:, None], axis=-1)[:, 0]
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


def slot_filtered_probs(logits, temperature, top_k, top_p, *,
                        candidates: int = 64):
    """Full-vocab probability vectors ``[n, vocab]`` of the EXACT per-row
    distribution ``sample_slots`` draws from (same candidate filter, same
    renormalization — they share `_slot_candidates`). Greedy rows
    (temperature <= 0) return an exact one-hot at idxs[:, 0] == argmax,
    so rejection sampling against these vectors degenerates to
    accept-iff-argmax-matches — the bitwise-greedy property the
    speculative tests pin. The speculative decoder's q (draft) and p
    (target) are both computed here."""
    n, v = logits.shape
    vals, idxs = _slot_candidates(logits, temperature, top_k, top_p,
                                  candidates)
    probs = jax.nn.softmax(vals, axis=-1)        # 0 at dropped candidates
    rows = jnp.arange(n)[:, None]
    full = jnp.zeros((n, v), jnp.float32).at[rows, idxs].set(probs)
    onehot = jnp.zeros((n, v), jnp.float32).at[
        jnp.arange(n), idxs[:, 0]].set(1.0)
    return jnp.where((temperature <= 0.0)[:, None], onehot, full)


def speculative_accept(draft_tokens, q_probs, p_probs, unif, res_keys,
                       greedy, k_eff=None):
    """Vectorized lossless rejection sampling (Leviathan et al. 2023;
    Chen et al. 2023): decide, per row, how many draft proposals the
    target model keeps, and sample the one correction/bonus token that
    follows — the emitted tokens are distributed EXACTLY as if the target
    had sampled them one by one.

      draft_tokens: [n, k] draft proposals.
      q_probs:      [n, k, vocab] the draft distributions each proposal
        was sampled from (slot_filtered_probs of the draft logits).
      p_probs:      [n, k+1, vocab] target distributions at every
        position of the verify forward (position i scores the token
        AFTER draft_tokens[:, :i]).
      unif:         [n, k] uniforms in [0, 1) — the accept coin flips.
      res_keys:     [n] PRNG keys for the residual/bonus sample.
      greedy:       [n] bool — rows whose correction must be the exact
        argmax (their p/q are one-hots, so acceptance is deterministic
        and no randomness is consumed).

    Proposal i is accepted with probability min(1, p_i(x_i)/q_i(x_i));
    the first rejection at position i resamples from the residual
    norm(max(p_i - q_i, 0)), and a fully-accepted row draws a BONUS
    token from p_{k+1} — the q=0 degenerate of the same residual formula.
    Returns ``(tokens [n, k+1], n_accept [n])``: tokens[:, :n_accept] are
    the kept proposals and tokens[:, n_accept] the correction/bonus; the
    caller reads exactly n_accept+1 tokens per row (later positions hold
    leftover proposals).

    ``k_eff`` (optional [n] int32 in [1, k]) is the per-row EFFECTIVE
    proposal depth — adaptive k (ISSUE 16) as a masked width inside the
    fixed k-wide program, so a per-slot depth change never retraces.
    Proposals at positions >= a row's k_eff are treated as never made:
    acceptance stops there, and a row that accepts all k_eff proposals
    draws its bonus from the FULL target distribution at position k_eff
    (q forced to 0 — that position's proposal was not offered, so the
    rejection-resample residual would be the wrong measure). The emitted
    prefix stays exactly target-distributed for every k_eff; greedy rows
    are bitwise-invariant to it (the correction is argmax(p) either
    way)."""
    n, k = draft_tokens.shape
    rows = jnp.arange(n)
    p_at = jnp.take_along_axis(
        p_probs[:, :k], draft_tokens[..., None], axis=-1)[..., 0]
    q_at = jnp.take_along_axis(
        q_probs, draft_tokens[..., None], axis=-1)[..., 0]
    # u < min(1, p/q)  <=>  u*q < p for u in [0,1): no division, and the
    # greedy one-hot case stays exact (q_at == 1.0 exactly)
    accept = unif * q_at < p_at                              # [n, k]
    if k_eff is not None:
        accept = accept & (jnp.arange(k)[None, :] < k_eff[:, None])
    n_accept = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)
    p_cut = p_probs[rows, n_accept]                          # [n, vocab]
    lim = k if k_eff is None else jnp.minimum(k_eff, k)
    q_cut = jnp.where((n_accept < lim)[:, None],
                      q_probs[rows, jnp.minimum(n_accept, k - 1)], 0.0)
    res = jnp.maximum(p_cut - q_cut, 0.0)
    tot = res.sum(axis=-1, keepdims=True)
    # a rejection with p <= q everywhere is impossible in exact math but
    # can appear under fp rounding: fall back to the target distribution
    res = jnp.where(tot > 0, res / jnp.where(tot > 0, tot, 1.0), p_cut)
    sampled = jax.vmap(jax.random.categorical)(res_keys, jnp.log(res))
    corr = jnp.where(greedy, jnp.argmax(p_cut, axis=-1),
                     sampled).astype(jnp.int32)
    out = jnp.concatenate(
        [draft_tokens, jnp.zeros((n, 1), jnp.int32)], axis=1)
    out = jnp.where(jnp.arange(k + 1)[None, :] == n_accept[:, None],
                    corr[:, None], out)
    return out, n_accept


def reset_cache_positions(cache, new_index):
    """Set every position counter in a decode cache collection ("index"
    per attention layer, "pos_index" in the embedder) to ``new_index`` —
    the bucketing trick: after a PADDED prefill advanced the counters to
    the bucket length, rewind them to the true prompt length so decode
    overwrites the pad rows (which the position mask keeps unattendable
    until then). ``new_index`` may be a scalar or, for a slot-decode
    (``decode_slots > 0``) cache, a per-row [slots] vector — the
    speculative decoder rewinds each row to its OWN accepted length this
    way (scanned-layer counter leaves are [L, slots]; the vector
    broadcasts up the scan axis)."""
    def fix(path, leaf):
        name = getattr(path[-1], "key", str(path[-1]))
        if name in ("index", "pos_index"):
            return jnp.broadcast_to(new_index, leaf.shape).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


#: the "cache" collection's K/V payload leaves, by name: per-head keys
#: and values with their int8 scale planes (models/transformer.py), and
#: the rows of a model with two cache kinds (models/latent.py: the full
#: layers' latent rows with the indexer's keys beside them, the sliding
#: layers' window rows; models/eva.py: a chunk's summary key and value
#: beside the window's exact ones; models/periodic.py: the window
#: layers' keys and values beside the full layers'). Everything else in
#: the collection is counters and tables.
KV_POOL_LEAVES = ("cached_key", "cached_value", "cached_key_scale",
                  "cached_value_scale", "cached_latent",
                  "cached_index_key", "cached_window",
                  "cached_summary_key", "cached_summary_value",
                  "cached_window_key", "cached_window_value")


#: the "cache" collection's recurrent states (models/ssm.py): a fixed row
#: a slot, ``[layers, slots, ...]``, overwritten in place every call; no
#: blocks, so nothing that moves blocks touches them
STATE_LEAVES = ("cached_ssm_state", "cached_conv_state")


def kv_cache_bytes(cache) -> int:
    """HBM bytes of a decode cache collection's K/V payload (dense rows
    or the paged block pool, and a recurrent model's states — the
    counter/table leaves are noise).
    Includes the int8 pool's fp32 scale planes: they are real HBM the
    compressed pool pays, so "same HBM budget" A/Bs charge for them.
    The serving engine's summary reads it, so both sides of a "same HBM
    budget" comparison are measured by the one function."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        name = getattr(path[-1], "key", str(path[-1]))
        if name in KV_POOL_LEAVES or name in STATE_LEAVES:
            total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    return total


def _zero_cache(model, prompt):
    """A fresh all-zero cache collection for ``model`` at ``prompt``'s
    batch size (shapes via eval_shape — nothing is initialized)."""
    cache = jax.eval_shape(
        lambda: model.init(jax.random.key(0), prompt[:, :1])["cache"])
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache)


def _decode_ticks(model, weights, cache, first, rng, done, *, length,
                  temperature, top_k, top_p, top_p_candidates, eos_ids):
    """The shared decode loop: ``length`` single-token ticks from ``first``
    under a lax.scan. Returns [b, length] sampled tokens (frozen rows
    emit the first stop id)."""
    def tick(carry, _):
        cache, tok, key, done = carry
        logits, mut = model.apply(
            {"params": weights, "cache": cache}, tok[:, None],
            mutable=["cache"])
        key, sub = jax.random.split(key)
        nxt = _sample(logits[:, 0].astype(jnp.float32), sub,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      top_p_candidates=top_p_candidates)
        if eos_ids:
            nxt = jnp.where(done, eos_ids[0], nxt)
            done = done | matches_stop(nxt, eos_ids)
        return (mut["cache"], nxt, key, done), nxt

    (_, _, _, _), toks = lax.scan(
        tick, (cache, first, rng, done), None, length=length)
    return toks.T.astype(jnp.int32)


def _windowed(model, total: int):
    """Clone ``model`` with the decode attention window bounded to the
    slots this generation can actually reach (128-lane-rounded): at long
    max_seq_len with a short generation the dense-over-whole-cache score
    work is almost all waste."""
    cfg = model.cfg
    attend = attend_window(cfg.max_seq_len, total)
    if (cfg.decode_attend_len or cfg.max_seq_len) != attend:
        model = model.clone(
            cfg=dataclasses.replace(cfg, decode_attend_len=attend))
    return model


@functools.partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "temperature", "top_k",
                     "top_p", "top_p_candidates", "eos_ids"))
def generate_jit(
    model,
    params,
    prompt,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    top_p_candidates: int = 256,
    eos_ids: tuple[int, ...] = (),
    rng=None,
):
    """The jitted body behind generate() (stop ids pre-normalized to a
    static tuple). Prefer generate(); this is exposed for AOT lowering
    (tests/test_compiled_invariants.decode_lowered)."""
    TRACE_COUNTS["generate"] += 1
    if rng is None:  # same default as generate() (unused when greedy)
        rng = jax.random.key(0)
    b, prompt_len = prompt.shape
    model = _windowed(model, prompt_len + max_new_tokens)
    cache = _zero_cache(model, prompt)
    weights = params["params"] if "params" in params else params

    # Chunked prefill: ONE apply over the whole prompt fills every layer's
    # cache and yields the logits for the first new token — prompt cost is
    # a single parallel forward, not prompt_len sequential ticks.
    logits, mut = model.apply(
        {"params": weights, "cache": cache}, prompt, mutable=["cache"])
    rng, sub = jax.random.split(rng)
    first = _sample(logits[:, -1].astype(jnp.float32), sub,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    top_p_candidates=top_p_candidates)
    done = matches_stop(first, eos_ids)
    toks = _decode_ticks(model, weights, mut["cache"], first, rng, done,
                         length=max_new_tokens - 1, temperature=temperature,
                         top_k=top_k, top_p=top_p,
                         top_p_candidates=top_p_candidates, eos_ids=eos_ids)
    return jnp.concatenate([prompt, first[:, None], toks], axis=1)


def _validate(model, prompt_len: int, max_new_tokens: int) -> None:
    cfg = model.cfg
    if not cfg.decode:
        raise ValueError(
            "generate() needs a decode-mode model: build it with "
            "TransformerConfig(decode=True) / *_config(..., decode=True)")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if prompt_len + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt_len {prompt_len} + max_new_tokens {max_new_tokens} "
            f"exceeds max_seq_len {cfg.max_seq_len}")


def generate(
    model,
    params,
    prompt,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    top_p_candidates: int = 256,
    eos_id=None,
    rng=None,
):
    """Generate ``max_new_tokens`` continuations of ``prompt``.

    Args:
      model: a causal LM module built with ``decode=True`` in its config
        (GPT2 / Llama). ``cfg.max_seq_len`` bounds prompt + new tokens.
      params: the trained variables (``{"params": ...}``), same tree as the
        decode=False model — training params load unmodified.
      prompt: int32 ``[batch, prompt_len]`` token ids (prompt_len ≥ 1).
      temperature: 0 = greedy argmax; otherwise softmax temperature.
      top_k: restrict sampling to the k highest-logit tokens.
      top_p: nucleus sampling — keep the smallest candidate set with
        cumulative probability >= p (evaluated over the top-(top_k or
        top_p_candidates) candidates; see _sample). Composes with top_k.
      top_p_candidates: how many top logits nucleus sampling considers
        (default 256; set vocab_size for exact nucleus at full-sort cost —
        matters for flat/high-temperature distributions).
      eos_id: a stop id or a sequence of stop ids — rows that emit any of
        them freeze and keep emitting the first id (static-shape early
        stop).
      rng: PRNG key for sampling (defaults to key(0); unused when greedy).

    Returns int32 ``[batch, prompt_len + max_new_tokens]``: the prompt
    followed by the generated continuation.
    """
    _validate(model, prompt.shape[1], max_new_tokens)
    if rng is None:
        rng = jax.random.key(0)
    return generate_jit(model, params, prompt,
                        max_new_tokens=max_new_tokens,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        top_p_candidates=top_p_candidates,
                        eos_ids=stop_ids_tuple(eos_id), rng=rng)


@functools.partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "temperature", "top_k",
                     "top_p", "top_p_candidates", "eos_ids"))
def _generate_padded(
    model,
    params,
    prompt,          # [b, padded_len] — true prompt in [:, :true_len]
    true_len,        # dynamic scalar: the unpadded prompt length
    *,
    max_new_tokens: int,
    temperature: float,
    top_k: int | None,
    top_p: float | None,
    top_p_candidates: int,
    eos_ids: tuple[int, ...],
    rng,
):
    """generate_jit over a right-padded prompt with a DYNAMIC true length:
    prefill runs at the (static) bucket length, then the cache position
    counters rewind to ``true_len`` so decode starts there — pad rows sit
    beyond every row's position mask until the ticks overwrite them.
    Returns [b, padded_len + max_new_tokens] (continuation starts at
    column padded_len)."""
    TRACE_COUNTS["generate_padded"] += 1
    b, padded_len = prompt.shape
    model = _windowed(model, padded_len + max_new_tokens)
    cache = _zero_cache(model, prompt)
    weights = params["params"] if "params" in params else params

    logits, mut = model.apply(
        {"params": weights, "cache": cache}, prompt, mutable=["cache"])
    cache = reset_cache_positions(mut["cache"], true_len)
    last = lax.dynamic_slice_in_dim(logits, true_len - 1, 1, axis=1)[:, 0]
    rng, sub = jax.random.split(rng)
    first = _sample(last.astype(jnp.float32), sub,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    top_p_candidates=top_p_candidates)
    done = matches_stop(first, eos_ids)
    toks = _decode_ticks(model, weights, cache, first, rng, done,
                         length=max_new_tokens - 1, temperature=temperature,
                         top_k=top_k, top_p=top_p,
                         top_p_candidates=top_p_candidates, eos_ids=eos_ids)
    return jnp.concatenate([prompt, first[:, None], toks], axis=1)


def _round_up(n: int, q: int) -> int:
    return -(-n // q) * q


def generate_bucketed(
    model,
    params,
    prompt,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    top_p_candidates: int = 256,
    eos_id=None,
    rng=None,
    bucket: int = 128,
    pad_id: int = 0,
):
    """generate() behind a retrace-bucketing wrapper (thin, non-jit).

    generate()'s compiled program is keyed on the STATIC
    (prompt_len, max_new_tokens) pair, so variable-length traffic — a
    chat frontend, an eval harness — retraces per distinct shape. This
    wrapper pads the prompt up to a ``bucket``-multiple (true length rides
    along as a dynamic scalar) and rounds max_new_tokens up the same way
    (extra ticks cost compute, not correctness — the tail is sliced off),
    so repeated calls hit a handful of compiled programs. Greedy outputs
    are bitwise-equal to generate()'s: pad positions sit beyond the
    position mask until decode overwrites them, and masked attention
    contributes exact zeros. Falls back to exact generate() when the
    bucketed shapes cannot fit max_seq_len. TRACE_COUNTS["generate_padded"]
    counts the compiles (the regression test's tripwire)."""
    b, prompt_len = prompt.shape
    _validate(model, prompt_len, max_new_tokens)
    max_seq_len = model.cfg.max_seq_len
    padded_len = min(_round_up(prompt_len, bucket), max_seq_len)
    new_bucket = min(_round_up(max_new_tokens, bucket),
                     max_seq_len - padded_len)
    if padded_len < prompt_len or new_bucket < max_new_tokens:
        # bucketing can't fit the context — take the exact-shape program
        return generate(model, params, prompt,
                        max_new_tokens=max_new_tokens,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        top_p_candidates=top_p_candidates, eos_id=eos_id,
                        rng=rng)
    if rng is None:
        rng = jax.random.key(0)
    padded = jnp.pad(prompt, ((0, 0), (0, padded_len - prompt_len)),
                     constant_values=pad_id)
    out = _generate_padded(model, params, padded,
                           jnp.asarray(prompt_len, jnp.int32),
                           max_new_tokens=new_bucket,
                           temperature=temperature, top_k=top_k, top_p=top_p,
                           top_p_candidates=top_p_candidates,
                           eos_ids=stop_ids_tuple(eos_id), rng=rng)
    return jnp.concatenate(
        [prompt, out[:, padded_len:padded_len + max_new_tokens]], axis=1)


# ---------------------------------------------------------------------------
# Speculative decoding (ISSUE 8): draft-and-verify with lossless rejection
# sampling. Decode is memory-bound — every tick streams the whole target
# model through HBM for ONE token — so a cheap draft proposes k tokens and
# the target scores all k+1 positions in ONE batched forward; the rejection
# kernel (speculative_accept) keeps a provably target-distributed prefix.
# Greedy outputs are BITWISE-equal to generate()'s (the kernel degenerates
# to accept-iff-argmax-matches); sampled outputs are distribution-equal.


def truncated_draft(model, params, num_layers: int):
    """(draft_model, draft_params) built by TRUNCATING the target to its
    first ``num_layers`` transformer blocks — embedder, final norm and LM
    head shared, so vocab/embedding shapes match by construction. A free
    draft for speculative decoding: no extra training, and correctness
    never depends on its quality (the rejection kernel is lossless); only
    the acceptance rate — and hence the speedup — does."""
    cfg = model.cfg
    if not 0 < num_layers < cfg.num_layers:
        raise ValueError(
            f"draft num_layers {num_layers} must be in "
            f"[1, {cfg.num_layers - 1}] (a strict truncation of the target)")
    p = params["params"] if "params" in params else params
    h = dict(p["h"])
    if cfg.scan_layers:
        # scan-stacked block leaves are [L, ...]: slice the layer axis
        h["block"] = jax.tree.map(lambda a: a[:num_layers], h["block"])
    else:
        for name in list(h):
            if (name.startswith("block_")
                    and int(name.rsplit("_", 1)[1]) >= num_layers):
                del h[name]
    out = dict(p)
    out["h"] = h
    draft = model.clone(cfg=dataclasses.replace(cfg, num_layers=num_layers))
    return draft, {"params": out}


def make_draft(model, params, *, num_layers: int | None = None,
               spec_heads: int = 0, seed: int = 0):
    """(draft_model, draft_params) for speculative decoding — the one
    constructor behind every draft shape (ISSUE 16): ``num_layers`` < the
    target's truncates the block stack (truncated_draft, the free warm
    init), None/equal keeps the full stack (self-draft-sized);
    ``spec_heads`` > 0 attaches that many multi-token proposal heads
    (models ProposalHeads), ZERO-initialized so at step 0 every head
    reproduces the base head's distribution exactly — init is
    deterministic whatever ``seed`` (kept for API symmetry). The result
    drops straight into generate_speculative / ServingEngine
    ``draft_config``/``draft_params``, and training/distill.py uses it as
    the student's warm start."""
    import flax.linen as nn

    from pytorchdistributed_tpu.models.transformer import ProposalHeads

    cfg = model.cfg
    if num_layers is None or num_layers == cfg.num_layers:
        draft = model
        dparams = {"params": params["params"] if "params" in params
                   else params}
    else:
        draft, dparams = truncated_draft(model, params, num_layers)
    if spec_heads:
        if spec_heads < 0:
            raise ValueError(f"spec_heads must be >= 0, got {spec_heads}")
        dcfg = dataclasses.replace(draft.cfg, spec_heads=spec_heads)
        draft = draft.clone(cfg=dcfg)
        head_tree = nn.meta.unbox(ProposalHeads(dcfg).init(
            jax.random.key(seed),
            jnp.zeros((1, dcfg.embed_dim), dcfg.dtype))["params"])
        p = dict(dparams["params"])
        p["heads"] = head_tree
        dparams = {"params": p}
    return draft, dparams


def _verify_chunk(model, weights, cache, tok, d_prop, q_probs, unif,
                  res_keys, temperature, top_k, top_p, *, spec_k: int,
                  candidates: int, k_eff=None):
    """The verify half of one speculative round — ONE target forward
    over [tok, d_1..d_k] plus the lossless rejection kernel. Shared by
    the sequential-rollout and head-parallel draft paths (ISSUE 16), so
    the losslessness-critical math exists exactly once whatever proposed
    the tokens. Returns ``(cache, emitted [n, spec_k+1], n_accept)``."""
    n = tok.shape[0]
    chunk = jnp.concatenate([tok[:, None], d_prop], axis=1)
    logits, mut = model.apply(
        {"params": weights, "cache": cache}, chunk, mutable=["cache"])
    flat = logits.reshape(n * (spec_k + 1), -1).astype(jnp.float32)

    def rep(a):
        return jnp.repeat(a, spec_k + 1, axis=0)

    p_probs = slot_filtered_probs(
        flat, rep(temperature), rep(top_k), rep(top_p),
        candidates=candidates).reshape(n, spec_k + 1, -1)
    emitted, n_accept = speculative_accept(
        d_prop, q_probs, p_probs, unif, res_keys, temperature <= 0.0,
        k_eff=k_eff)
    return mut["cache"], emitted, n_accept


def draft_and_verify(model, draft_model, weights, draft_weights, cache,
                     draft_cache, tok, draft_keys, unif, res_keys,
                     temperature, top_k, top_p, *, spec_k: int,
                     candidates: int, k_eff=None):
    """One draft-and-verify round over per-row decode state — the
    losslessness-critical core shared by generate_speculative and the
    serving engine's spec_decode_tick (they differ only in how caches
    persist and keys derive; this math must never fork).

    Rolls the draft ``spec_k + 1`` single-token steps from ``tok`` (k
    proposals, plus one extra step that only writes the last proposal's
    K/V so a fully-accepted row's next round attends a complete draft
    cache), scores all k+1 positions with ONE target forward over
    [tok, d_1..d_k], and rejection-samples per row. ``draft_keys`` is a
    [spec_k+1, n] key array (one stream per rollout step per row);
    ``unif`` [n, spec_k] are the accept coins, ``res_keys`` [n] the
    residual/bonus streams; ``k_eff`` (optional [n]) masks each row's
    effective proposal depth (see speculative_accept). Returns
    ``(cache, draft_cache, emitted [n, spec_k+1], n_accept [n])`` — the
    caller consumes exactly n_accept+1 tokens per row."""

    def dstep(carry, keys_j):
        dc, t = carry
        logits, mut = draft_model.apply(
            {"params": draft_weights, "cache": dc}, t[:, None],
            mutable=["cache"])
        lg = logits[:, 0].astype(jnp.float32)
        nxt = sample_slots(lg, keys_j, temperature, top_k, top_p,
                           candidates=candidates)
        q = slot_filtered_probs(lg, temperature, top_k, top_p,
                                candidates=candidates)
        return (mut["cache"], nxt), (nxt, q)

    (draft_cache, _), (dtoks, qs) = lax.scan(
        dstep, (draft_cache, tok), draft_keys)
    d_prop = dtoks[:spec_k].T                        # [n, k]
    q_probs = jnp.moveaxis(qs[:spec_k], 0, 1)        # [n, k, vocab]
    cache, emitted, n_accept = _verify_chunk(
        model, weights, cache, tok, d_prop, q_probs, unif, res_keys,
        temperature, top_k, top_p, spec_k=spec_k, candidates=candidates,
        k_eff=k_eff)
    return cache, draft_cache, emitted, n_accept


def draft_propose_heads(draft_model, draft_weights, draft_cache,
                        prev_tokens, prev_idx, draft_keys, temperature,
                        top_k, top_p, *, spec_k: int, candidates: int):
    """ONE head-parallel draft forward proposing all spec_k tokens
    (ISSUE 16, the Medusa shape): the draft processes ``prev_tokens`` —
    the PREVIOUS round's emitted buffer [n, spec_k+1], whose writes land
    at the caller-stamped draft positions and cover that round's
    rejected-suffix draft K/V (the same covering-writes property the
    target cache relies on) — reads the hidden state at each row's last
    live index ``prev_idx``, and samples proposal 1 from the base head
    and proposals 2..k from the multi-token heads, all conditioned on
    the same hidden state (head proposals are offset-specialized, not
    sequentially conditioned — the acceptance-for-latency trade).
    ``draft_keys`` is the SAME [spec_k+1, n] key array the sequential
    rollout consumes: proposal j samples with stream j either way.
    Returns ``(draft_cache, d_prop [n, k], q_probs [n, k, vocab])``."""
    n = prev_tokens.shape[0]
    hid, mut = draft_model.apply(
        {"params": draft_weights, "cache": draft_cache}, prev_tokens,
        method="hidden_states", mutable=["cache"])
    draft_cache = mut["cache"]
    hsel = jnp.take_along_axis(
        hid, prev_idx[:, None, None], axis=1)[:, 0]   # [n, embed]
    # the cache collection rides along read-only: decode-mode setup
    # declares position variables even on the projection-only methods
    base = draft_model.apply(
        {"params": draft_weights, "cache": draft_cache}, hsel,
        method="logits_from_hidden")
    heads = draft_model.apply(
        {"params": draft_weights, "cache": draft_cache}, hsel,
        method="head_logits")
    all_lg = jnp.concatenate(
        [base[:, None], heads[:, :spec_k - 1]],
        axis=1).astype(jnp.float32)                   # [n, k, vocab]
    flat = all_lg.reshape(n * spec_k, -1)

    def rep(a):
        return jnp.repeat(a, spec_k, axis=0)

    keys = jnp.swapaxes(draft_keys[:spec_k], 0, 1).reshape(n * spec_k)
    d_prop = sample_slots(flat, keys, rep(temperature), rep(top_k),
                          rep(top_p), candidates=candidates)
    q_probs = slot_filtered_probs(flat, rep(temperature), rep(top_k),
                                  rep(top_p), candidates=candidates)
    return (draft_cache, d_prop.reshape(n, spec_k),
            q_probs.reshape(n, spec_k, -1))


def draft_and_verify_heads(model, draft_model, weights, draft_weights,
                           cache, draft_cache, tok, prev_tokens, prev_idx,
                           draft_keys, unif, res_keys, temperature, top_k,
                           top_p, *, spec_k: int, candidates: int,
                           k_eff=None):
    """The head-parallel twin of draft_and_verify: the draft's k+1-step
    sequential rollout collapses to a single forward over the previous
    round's emitted buffer (draft_propose_heads), and the verify half is
    the SAME _verify_chunk — rejection kernel, covering-writes, and the
    no-rollback property are untouched, so losslessness never forks.
    Caller contract: ``draft_cache`` positions are stamped at the
    previous round's start (one round behind the target's), so this
    forward writes the emitted tokens' draft K/V exactly where the next
    round attends them."""
    draft_cache, d_prop, q_probs = draft_propose_heads(
        draft_model, draft_weights, draft_cache, prev_tokens, prev_idx,
        draft_keys, temperature, top_k, top_p, spec_k=spec_k,
        candidates=candidates)
    cache, emitted, n_accept = _verify_chunk(
        model, weights, cache, tok, d_prop, q_probs, unif, res_keys,
        temperature, top_k, top_p, spec_k=spec_k, candidates=candidates,
        k_eff=k_eff)
    return cache, draft_cache, emitted, n_accept


@functools.partial(
    jax.jit,
    static_argnames=("model", "draft_model", "spec_k", "max_new_tokens",
                     "temperature", "top_k", "top_p", "eos_ids",
                     "candidates"))
def _speculative_jit(model, draft_model, params, draft_params, prompt, rng,
                     *, spec_k: int, max_new_tokens: int, temperature: float,
                     top_k: int | None, top_p: float | None,
                     eos_ids: tuple[int, ...], candidates: int):
    """The jitted body behind generate_speculative: chunked prefill of
    BOTH caches, then a lax.while_loop of draft-and-verify rounds. Both
    models are slot-decode clones (``decode_slots == batch``) because
    per-row accepted lengths diverge — every round re-stamps the position
    counters from the per-row length vector (reset_cache_positions), so
    rejected-suffix K/V needs no rollback: the next round's k+1 writes
    land at [len, len+k] and always cover the stale region, and the
    position mask keeps anything beyond a row's length unattendable.

    When the draft carries proposal heads (cfg.spec_heads > 0, ISSUE 16)
    the carry gains the head-parallel round state — prev_toks (last
    round's emitted buffer, the NEXT draft forward's input chunk),
    prev_idx (each row's last live index in it) and prev_pos (the draft
    positions it writes at, one round behind the target's) — and the
    draft's sequential rollout becomes one forward; the verify half and
    everything below it are byte-for-byte the same code path."""
    TRACE_COUNTS["generate_speculative"] += 1
    heads_mode = draft_model.cfg.spec_heads > 0
    b, plen = prompt.shape
    weights = params["params"] if "params" in params else params
    dweights = (draft_params["params"] if "params" in draft_params
                else draft_params)
    temps = jnp.full((b,), temperature, jnp.float32)
    tks = jnp.full((b,), top_k or 0, jnp.int32)
    tps = jnp.full((b,), 1.0 if top_p is None else top_p, jnp.float32)

    t_cache = _zero_cache(model, prompt)
    d_cache = _zero_cache(draft_model, prompt)
    logits, mut = model.apply(
        {"params": weights, "cache": t_cache}, prompt, mutable=["cache"])
    t_cache = mut["cache"]
    _, dmut = draft_model.apply(
        {"params": dweights, "cache": d_cache}, prompt, mutable=["cache"])
    d_cache = dmut["cache"]

    rng, sub = jax.random.split(rng)
    first = sample_slots(logits[:, -1].astype(jnp.float32),
                         jax.random.split(sub, b), temps, tks, tps,
                         candidates=candidates)
    width = max_new_tokens + spec_k + 1
    out = jnp.zeros((b, width), jnp.int32).at[:, 0].set(first)
    n_out = jnp.ones((b,), jnp.int32)
    done = matches_stop(first, eos_ids) | (n_out >= max_new_tokens)
    pos = jnp.full((b,), plen, jnp.int32)

    def cond(carry):
        return jnp.any(~carry[5])

    def body(carry):
        if heads_mode:
            (t_cache, d_cache, out, n_out, tok, done, pos, key,
             prev_toks, prev_idx, prev_pos) = carry
        else:
            t_cache, d_cache, out, n_out, tok, done, pos, key = carry
        t_cache = reset_cache_positions(t_cache, pos)
        key, kd, ka, kr = jax.random.split(key, 4)
        draft_keys = jax.vmap(lambda kj: jax.random.split(kj, b))(
            jax.random.split(kd, spec_k + 1))
        unif = jax.random.uniform(ka, (b, spec_k))
        if heads_mode:
            # the draft writes last round's emitted buffer, so its
            # positions lag the target's by one round
            d_cache = reset_cache_positions(d_cache, prev_pos)
            t_cache, d_cache, emitted, n_acc = draft_and_verify_heads(
                model, draft_model, weights, dweights, t_cache, d_cache,
                tok, prev_toks, prev_idx, draft_keys, unif,
                jax.random.split(kr, b), temps, tks, tps,
                spec_k=spec_k, candidates=candidates)
        else:
            d_cache = reset_cache_positions(d_cache, pos)
            t_cache, d_cache, emitted, n_acc = draft_and_verify(
                model, draft_model, weights, dweights, t_cache, d_cache,
                tok, draft_keys, unif, jax.random.split(kr, b), temps,
                tks, tps, spec_k=spec_k, candidates=candidates)
        if eos_ids:
            # a stop id freezes the rest of the round: everything after
            # it emits the first stop id, exactly generate()'s frozen-row
            # padding
            hit = matches_stop(emitted, eos_ids)
            prior = jnp.cumsum(hit, axis=1) - hit > 0
            emitted = jnp.where(prior, eos_ids[0], emitted)

        def wrow(buf, vals, start, skip):
            return jnp.where(
                skip, buf, lax.dynamic_update_slice(buf, vals, (start,)))

        out = jax.vmap(wrow)(out, emitted, n_out, done)
        m_emit = n_acc + 1
        tok = jnp.where(done, tok, emitted[jnp.arange(b), n_acc])
        n_out = jnp.where(done, n_out, n_out + m_emit)
        new_done = done | (n_out >= max_new_tokens)
        if eos_ids:
            live = jnp.arange(spec_k + 1)[None, :] <= n_acc[:, None]
            new_done = new_done | (
                ~done & (matches_stop(emitted, eos_ids) & live).any(axis=1))
        if heads_mode:
            # next round's draft input: this round's emitted buffer,
            # whose row-0 token sits one past the pre-advance pos
            prev_toks = jnp.where(done[:, None], prev_toks, emitted)
            prev_idx = jnp.where(done, prev_idx, n_acc)
            prev_pos = jnp.where(done, prev_pos, pos + 1)
        # freeze pos at the pre-round value for rows that just finished:
        # live rows keep pos == plen + n_out - 1 <= plen + max_new - 2,
        # so verify writes never pass plen + max_new + spec_k - 2 (the
        # wrapper's validation slack)
        pos = jnp.where(new_done, pos, pos + m_emit)
        if heads_mode:
            return (t_cache, d_cache, out, n_out, tok, new_done, pos, key,
                    prev_toks, prev_idx, prev_pos)
        return (t_cache, d_cache, out, n_out, tok, new_done, pos, key)

    carry = (t_cache, d_cache, out, n_out, first, done, pos, rng)
    if heads_mode:
        # round 1's draft chunk: the first committed token plus padding
        # (index 0 is the only live position), written at the target's
        # current pos — the draft cache holds only the prompt so far
        prev_toks = jnp.zeros((b, spec_k + 1), jnp.int32).at[:, 0].set(first)
        carry = carry + (prev_toks, jnp.zeros((b,), jnp.int32), pos)
    fin = lax.while_loop(cond, body, carry)
    out, n_out = fin[2], fin[3]
    pad = eos_ids[0] if eos_ids else 0
    res = jnp.where(jnp.arange(width)[None, :] < n_out[:, None], out, pad)
    return jnp.concatenate([prompt, res[:, :max_new_tokens]], axis=1)


def generate_speculative(
    model,
    params,
    prompt,
    *,
    max_new_tokens: int,
    draft_model=None,
    draft_params=None,
    spec_k: int = 4,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    eos_id=None,
    rng=None,
    candidates: int = 64,
):
    """generate() with draft-and-verify speculative decoding: ``spec_k``
    draft proposals per target forward, losslessly verified (Leviathan
    et al. 2023). Greedy output is BITWISE-equal to generate()'s; sampled
    output is distribution-equal (the tokens follow exactly the filtered
    target distribution sample_slots draws from, whatever the draft).

    Args beyond generate()'s:
      draft_model / draft_params: the proposer — any causal LM sharing
        the target's vocab (e.g. `truncated_draft(model, params, n)`).
        None self-drafts with the target itself (acceptance ~1: the
        correctness/plumbing configuration, not a speedup).
      spec_k: static draft length per round (0 falls back to generate()).
      candidates: the sampler's candidate-set width (see sample_slots) —
        spec and plain sampling share the same filtered distribution.

    Falls back to plain generate() when the context cannot absorb the
    verify overshoot (prompt + max_new + spec_k must fit max_seq_len:
    each round's k+1 verify writes may run past the budget before the
    accepted length is known — rejected-suffix K/V is never rolled back,
    just overwritten by the next round)."""
    _validate(model, prompt.shape[1], max_new_tokens)
    b, plen = prompt.shape
    kw = dict(max_new_tokens=max_new_tokens, temperature=temperature,
              top_k=top_k, top_p=top_p, eos_id=eos_id, rng=rng)
    if spec_k < 1 or plen + max_new_tokens + spec_k > model.cfg.max_seq_len:
        return generate(model, params, prompt, **kw)
    if draft_model is None:
        draft_model, draft_params = model, params
    if draft_params is None:
        raise ValueError("draft_model without draft_params — pass both "
                         "(truncated_draft() builds the pair)")
    if draft_model.cfg.vocab_size != model.cfg.vocab_size:
        raise ValueError(
            f"draft vocab {draft_model.cfg.vocab_size} != target vocab "
            f"{model.cfg.vocab_size} (the draft proposes target tokens)")
    if 0 < draft_model.cfg.spec_heads < spec_k - 1:
        raise ValueError(
            f"draft has {draft_model.cfg.spec_heads} proposal heads but "
            f"spec_k={spec_k} needs {spec_k - 1} (base head proposes token "
            f"1, head j token j+2; build the draft with make_draft("
            f"spec_heads=spec_k-1))")

    def slot_clone(m, seq_len):
        return m.clone(cfg=dataclasses.replace(
            m.cfg, decode=True, attention="dense", decode_attend_len=None,
            decode_slots=b, kv_block_size=0, kv_blocks=0,
            max_seq_len=seq_len))

    if rng is None:
        rng = jax.random.key(0)
    return _speculative_jit(
        slot_clone(model, model.cfg.max_seq_len),
        slot_clone(draft_model, model.cfg.max_seq_len),
        params, draft_params, prompt, rng, spec_k=spec_k,
        max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, top_p=top_p, eos_ids=stop_ids_tuple(eos_id),
        candidates=candidates)

"""KV-cache generation tests (inference.generate).

Correctness bar: the cached decode path must reproduce the no-cache model
exactly — greedy generation is checked token-by-token against argmax of a
full decode=False forward pass over the generated sequence (this catches
cache indexing, RoPE offsets, learned-position offsets, GQA cache layout,
and mask bugs all at once)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorchdistributed_tpu import inference
from pytorchdistributed_tpu.inference import (
    TRACE_COUNTS,
    generate,
    generate_bucketed,
)
from pytorchdistributed_tpu.models import (
    GPT2,
    Llama,
    gpt2_config,
    llama_config,
)


def _greedy_consistency(train_model, decode_model, vocab):
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, vocab, (2, 5)), jnp.int32)
    params = train_model.init(jax.random.key(1), prompt)

    out = generate(decode_model, params, prompt, max_new_tokens=8,
                   temperature=0.0)
    assert out.shape == (2, 13)
    np.testing.assert_array_equal(out[:, :5], prompt)

    # dense re-check: feeding the generated sequence through the normal
    # (uncached) model, every generated token must be the argmax of the
    # logits one position earlier
    logits = train_model.apply(params, out)
    want = jnp.argmax(logits[:, 4:-1].astype(jnp.float32), axis=-1)
    np.testing.assert_array_equal(out[:, 5:], want)


def test_gpt2_greedy_matches_dense():
    cfg = gpt2_config("test", num_layers=2, max_seq_len=32)
    _greedy_consistency(GPT2(cfg), GPT2(dataclasses.replace(cfg, decode=True)),
                        cfg.vocab_size)


def test_llama_greedy_matches_dense():
    """RoPE offsets + GQA cache layout under decode."""
    cfg = llama_config("test", max_seq_len=32)
    _greedy_consistency(Llama(cfg),
                        Llama(dataclasses.replace(cfg, decode=True)),
                        cfg.vocab_size)


def test_decode_attend_window_bounds_cost_not_output():
    """generate() bounds per-tick attention to the (128-rounded)
    prompt+new total (cfg.decode_attend_len) instead of max_seq_len. At
    max_seq_len=512 with a 13-token sequence the window is 128 — and the
    output must still match the uncached model exactly (RoPE params are
    max_seq_len-independent, so the same check as _greedy_consistency
    covers the windowed path)."""
    cfg = llama_config("test", max_seq_len=512)
    decode_model = Llama(dataclasses.replace(cfg, decode=True))
    _greedy_consistency(Llama(cfg), decode_model, cfg.vocab_size)


def test_decode_non_dense_attention_warns():
    """The training-time attention backend knob does not apply to decode;
    building a decode config with one must say so (ADVICE r2)."""
    with pytest.warns(UserWarning, match="attention"):
        gpt2_config("test", decode=True, attention="pallas")


def test_gpt2_unrolled_layers_decode():
    cfg = gpt2_config("test", num_layers=2, max_seq_len=32, scan_layers=False)
    _greedy_consistency(GPT2(cfg), GPT2(dataclasses.replace(cfg, decode=True)),
                        cfg.vocab_size)


def test_sampling_deterministic_and_in_range():
    cfg = gpt2_config("test", num_layers=2, max_seq_len=32, decode=True)
    model = GPT2(cfg)
    rng = np.random.default_rng(1)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 4)), jnp.int32)
    params = model.init(jax.random.key(0), prompt[:, :1])
    kw = dict(max_new_tokens=6, temperature=0.8, top_k=10)
    a = generate(model, params, prompt, rng=jax.random.key(7), **kw)
    b = generate(model, params, prompt, rng=jax.random.key(7), **kw)
    c = generate(model, params, prompt, rng=jax.random.key(8), **kw)
    np.testing.assert_array_equal(a, b)
    assert ((a >= 0) & (a < cfg.vocab_size)).all()
    # different keys must change the sampled continuation (fixed seeds —
    # deterministic; a regression that ignores rng would make these equal)
    assert not np.array_equal(a, c)


def test_top_p_sampling():
    """Nucleus sampling: p→0 degenerates to greedy (only the max survives);
    moderate p is deterministic per key and in-vocab."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=32, decode=True)
    model = GPT2(cfg)
    rng = np.random.default_rng(4)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 4)), jnp.int32)
    params = model.init(jax.random.key(0), prompt[:, :1])
    greedy = generate(model, params, prompt, max_new_tokens=6,
                      temperature=0.0)
    tiny_p = generate(model, params, prompt, max_new_tokens=6,
                      temperature=0.7, top_p=1e-9, rng=jax.random.key(1))
    np.testing.assert_array_equal(tiny_p, greedy)
    a = generate(model, params, prompt, max_new_tokens=6, temperature=0.9,
                 top_p=0.9, rng=jax.random.key(2))
    b = generate(model, params, prompt, max_new_tokens=6, temperature=0.9,
                 top_p=0.9, rng=jax.random.key(2))
    np.testing.assert_array_equal(a, b)
    assert ((a >= 0) & (a < cfg.vocab_size)).all()


def test_eos_freezes_rows():
    cfg = gpt2_config("test", num_layers=2, max_seq_len=32, decode=True)
    model = GPT2(cfg)
    rng = np.random.default_rng(2)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 4)), jnp.int32)
    params = model.init(jax.random.key(0), prompt[:, :1])
    first = generate(model, params, prompt, max_new_tokens=8, temperature=0.0)
    eos = int(first[0, 4])  # whatever greedy emits first becomes "eos"
    out = generate(model, params, prompt, max_new_tokens=8, temperature=0.0,
                   eos_id=eos)
    assert (np.asarray(out[0, 4:]) == eos).all()


def test_eos_in_prompt_is_inert():
    """A prompt that happens to contain eos_id must pass through intact —
    prefill is not sampling, so it can't trip the done latch."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=32, decode=True)
    model = GPT2(cfg)
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 6)), jnp.int32)
    params = model.init(jax.random.key(0), prompt[:, :1])
    eos = int(prompt[0, 2])  # mid-prompt token doubles as eos
    out = generate(model, params, prompt, max_new_tokens=4, temperature=0.0,
                   eos_id=eos)
    np.testing.assert_array_equal(out[:, :6], prompt)
    ref = generate(model, params, prompt, max_new_tokens=4, temperature=0.0)
    # generation proceeds identically until (if ever) eos is emitted
    gen, ref_gen = np.asarray(out[0, 6:]), np.asarray(ref[0, 6:])
    stop = np.argmax(ref_gen == eos) if (ref_gen == eos).any() else len(ref_gen)
    np.testing.assert_array_equal(gen[:stop], ref_gen[:stop])


def test_stop_id_sequence():
    """eos_id accepts a SEQUENCE of stop ids (tokenizers commonly have
    several): any of them freezes a row, frozen rows keep emitting the
    first id, and a singleton sequence behaves exactly like the scalar."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=32, decode=True)
    model = GPT2(cfg)
    rng = np.random.default_rng(6)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 4)), jnp.int32)
    params = model.init(jax.random.key(0), prompt[:, :1])
    free = np.asarray(generate(model, params, prompt, max_new_tokens=8,
                               temperature=0.0))
    stop_a, stop_b = int(free[0, 5]), int(free[1, 6])  # mid-run tokens
    out = np.asarray(generate(model, params, prompt, max_new_tokens=8,
                              temperature=0.0, eos_id=[stop_a, stop_b]))
    # row 0 froze at its stop and pads with the FIRST id of the set
    cut0 = int(np.argmax(free[0, 4:] == stop_a))
    np.testing.assert_array_equal(out[0, 4:4 + cut0 + 1],
                                  free[0, 4:4 + cut0 + 1])
    assert (out[0, 4 + cut0:] == stop_a).all()
    # row 1 froze on the OTHER id of the set
    cut1 = int(np.argmax(free[1, 4:] == stop_b))
    assert out[1, 4 + cut1] == stop_b
    assert (out[1, 5 + cut1:] == stop_a).all()
    # singleton sequence == scalar (same compiled program key)
    one = generate(model, params, prompt, max_new_tokens=8,
                   temperature=0.0, eos_id=stop_a)
    seq = generate(model, params, prompt, max_new_tokens=8,
                   temperature=0.0, eos_id=(stop_a,))
    np.testing.assert_array_equal(np.asarray(one), np.asarray(seq))


def test_bucketed_matches_generate_bitwise():
    """generate_bucketed pads prompt AND rounds max_new_tokens up to the
    bucket, yet the returned tokens are bitwise-equal to exact-shape
    generate() — greedy and seeded-sampling alike (pad rows sit beyond
    the position mask until decode overwrites them; masked attention
    contributes exact zeros)."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=512)
    model = GPT2(cfg)
    rng = np.random.default_rng(7)
    params = model.init(jax.random.key(1), jnp.zeros((1, 4), jnp.int32))
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    for L, n in [(5, 8), (17, 3), (33, 40)]:
        p = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, L)), jnp.int32)
        ref = generate(dm, params, p, max_new_tokens=n)
        got = generate_bucketed(dm, params, p, max_new_tokens=n, bucket=64)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    p = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 9)), jnp.int32)
    kw = dict(max_new_tokens=6, temperature=0.8, top_k=10,
              rng=jax.random.key(3))
    ref = generate(dm, params, p, **kw)
    got = generate_bucketed(dm, params, p, bucket=64, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_bucketed_trace_count_regression():
    """The retrace tripwire: many distinct (prompt_len, max_new_tokens)
    pairs inside one bucket pair must compile exactly ONE padded program
    (generate() would have compiled one per pair), and repeat calls
    compile nothing. max_seq_len 384 is unique to this test on purpose:
    jit caches by config, so sharing another test's config would let ITS
    compiles absorb ours and zero the delta."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=384)
    model = GPT2(cfg)
    rng = np.random.default_rng(8)
    params = model.init(jax.random.key(1), jnp.zeros((1, 4), jnp.int32))
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    before = TRACE_COUNTS["generate_padded"]
    for L, n in [(3, 2), (11, 7), (29, 13), (64, 64), (40, 1)]:
        p = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, L)), jnp.int32)
        out = generate_bucketed(dm, params, p, max_new_tokens=n, bucket=64)
        assert out.shape == (2, L + n)
    assert TRACE_COUNTS["generate_padded"] - before == 1
    # a second bucket pair is a second (and final) program
    p = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 70)), jnp.int32)
    generate_bucketed(dm, params, p, max_new_tokens=80, bucket=64)
    generate_bucketed(dm, params, p[:, :65], max_new_tokens=66, bucket=64)
    assert TRACE_COUNTS["generate_padded"] - before == 2


def test_bucketed_fallback_when_bucket_overflows_context():
    """When the rounded shapes cannot fit max_seq_len the wrapper falls
    back to the exact-shape program (correctness over retrace thrift)."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=16, decode=True)
    model = GPT2(cfg)
    rng = np.random.default_rng(9)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 10)), jnp.int32)
    params = model.init(jax.random.key(0), prompt[:, :1])
    ref = generate(model, params, prompt, max_new_tokens=6)
    got = generate_bucketed(model, params, prompt, max_new_tokens=6,
                            bucket=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_generate_with_tensor_sharded_params():
    """Sharding is a deployment choice, not a code path: generate() with
    Megatron tensor-sharded params on a dp x tp mesh must emit exactly the
    tokens the unsharded model emits (the decode einsums partition under
    the same logical rules the training step uses)."""
    import optax

    from pytorchdistributed_tpu.runtime.mesh import Axis, create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    cfg = llama_config("test", max_seq_len=32)
    rng = np.random.default_rng(5)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 6)), jnp.int32)
    model = Llama(cfg)
    params = model.init(jax.random.key(1), prompt)
    dm = Llama(dataclasses.replace(cfg, decode=True))
    ref = generate(dm, params, prompt, max_new_tokens=5, temperature=0.0)

    tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                 mesh=create_mesh(data=2, tensor=4), strategy="tp")
    big = np.tile(np.asarray(prompt), (4, 1))
    tr.init({"tokens": big, "targets": big})
    shardings = jax.tree.map(lambda a: a.sharding, tr.state.params)
    sharded = jax.device_put(params, shardings)
    spec = tuple(jax.tree.leaves(shardings)[0].spec)  # proves it's sharded
    assert any(Axis.TENSOR in (e if isinstance(e, tuple) else (e,))
               for leaf in jax.tree.leaves(shardings)
               for e in tuple(leaf.spec)), spec
    with jax.set_mesh(tr.mesh):
        out = generate(dm, sharded, prompt, max_new_tokens=5, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_generate_validations():
    cfg = gpt2_config("test", num_layers=2, max_seq_len=8)
    model = GPT2(cfg)
    prompt = jnp.zeros((1, 4), jnp.int32)
    params = model.init(jax.random.key(0), prompt)
    with pytest.raises(ValueError, match="decode"):
        generate(model, params, prompt, max_new_tokens=2)
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    with pytest.raises(ValueError, match="max_seq_len"):
        generate(dm, params, prompt, max_new_tokens=100)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(dm, params, prompt, max_new_tokens=0)
    with pytest.raises(ValueError, match="pipeline"):
        gpt2_config("test", decode=True, pipeline_stages=2)


def test_generate_exactly_fills_max_seq_len():
    """prompt_len + max_new_tokens == max_seq_len is legal: the last cache
    write lands on the final slot, one token past raises."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=16, decode=True)
    model = GPT2(cfg)
    prompt = jnp.asarray(np.arange(8)[None] % cfg.vocab_size, jnp.int32)
    params = model.init(jax.random.key(0), prompt[:, :1])
    out = generate(model, params, prompt, max_new_tokens=8, temperature=0.0)
    assert out.shape == (1, 16)
    with pytest.raises(ValueError, match="max_seq_len"):
        generate(model, params, prompt, max_new_tokens=9)


# -- the candidate search (ISSUE 39) ----------------------------------------
#
# `_top_candidates(logits, c)` must be `lax.top_k(logits, c)` bit for bit:
# the same values, and of equal values the lower id first. The rows below
# are made to break a search by groups; the widths lie on both sides of
# the shape rule (`c * _GROUP` numbers and under: `lax.top_k` itself).

_C, _G = 64, inference._GROUP
_NEG_NAN = np.array([0xFFC00000], np.uint32).view(np.float32)[0]


_ROWS = {}


def _kind(fn):
    """Enter a row maker `_row_<kind>(rng, v)` under its kind."""
    _ROWS[fn.__name__[len("_row_"):]] = fn
    return fn


def _plant(row, positions, value):
    positions = np.asarray(positions)
    row[positions[positions < row.size]] = value


@_kind
def _row_random(rng, v):
    return rng.standard_normal(v)


@_kind
def _row_rounded(rng, v):
    """Quarters: equal numbers by the hundred, in groups whose maxima
    differ, so the order of the gathered groups decides the ids."""
    return np.round(rng.standard_normal(v) * 4) / 4


@_kind
def _row_all_equal(rng, v):
    return np.full(v, 1.5)


@_kind
def _row_equal_maxima_in_more_than_c_groups(rng, v):
    """Every group's maximum is the same number, at another place in each:
    of equal maxima the lower group must win."""
    row = rng.standard_normal(v)
    groups = np.arange(-(-v // _G))
    _plant(row, groups * _G + (groups * 37) % _G, 9.0)
    return row


@_kind
def _row_ties_straddle_group_edges(rng, v):
    """The last number of a group and the first of the next are equal, at
    c + 16 edges: the top c are the lowest ids among them."""
    row = rng.standard_normal(v)
    edges = np.arange(1, _C + 17) * _G
    _plant(row, np.concatenate([edges - 1, edges]), 9.0)
    return row


@_kind
def _row_tie_across_chosen_and_unchosen(rng, v):
    """c + 8 groups hold the maximum once, the first c as their last
    number and the others as their first: positions c*g - 1 (chosen) and
    c*g (not chosen) tie."""
    row = rng.standard_normal(v)
    groups = np.arange(_C + 8)
    _plant(row, groups * _G + np.where(groups < _C, _G - 1, 0), 9.0)
    return row


@_kind
def _row_neg_inf(rng, v):
    return np.full(v, -np.inf)


@_kind
def _row_neg_inf_but_ten(rng, v):
    """Ten finite numbers, one of them the row's last (past the last
    whole group where the width has such numbers): 54 candidates are
    -inf at the lowest ids."""
    row = np.full(v, -np.inf)
    row[rng.choice(v - 1, 9, replace=False)] = rng.standard_normal(9)
    row[-1] = 0.25
    return row


@_kind
def _row_pos_inf(rng, v):
    row = rng.standard_normal(v)
    row[[0, v // 2, v - 1]] = np.inf
    return row


@_kind
def _row_more_than_c_pos_inf(rng, v):
    row = rng.standard_normal(v)
    row[rng.choice(v, _C + 30, replace=False)] = np.inf
    return row


@_kind
def _row_nan(rng, v):
    row = rng.standard_normal(v)
    row[(2 * v) // 3] = np.nan
    return row


@_kind
def _row_neg_nan_beside_the_maximum(rng, v):
    """A NaN with its sign bit set ranks last for `lax.top_k`; the row's
    largest number shares its group and must still be found."""
    row = rng.standard_normal(v).astype(np.float32)
    at = (v // 2) // _G * _G
    row[at], row[at + 1] = _NEG_NAN, 50.0
    return row


@_kind
def _row_neg_nan_but_ten(rng, v):
    """Fewer than c numbers rank over -inf: the last candidates are the
    row's own -NaN at the lowest ids (a search that padded the row's end
    with -inf would return the padding's)."""
    row = np.full(v, _NEG_NAN)
    row[rng.choice(v - 1, 9, replace=False)] = rng.standard_normal(9)
    row[-1] = 0.25
    return row


@_kind
def _row_signed_zeros(rng, v):
    """+0 ranks before -0 for `lax.top_k`."""
    return np.where(rng.random(v) < 0.5, 0.0, -0.0)


_WIDTHS = (320, _C * _G, _C * _G + 1, 19_008, 50_257, 151_936)


def candidate_rows(v: int) -> np.ndarray:
    """[kinds, v] float32, a row a kind in `_ROWS`' order."""
    rng = np.random.default_rng(v)
    return np.stack([np.asarray(fn(rng, v), np.float32)
                     for fn in _ROWS.values()])


@functools.lru_cache(maxsize=None)
def _both_searches(v: int):
    rows = jnp.asarray(candidate_rows(v))
    want = jax.jit(lambda x: jax.lax.top_k(x, _C))(rows)
    got = jax.jit(lambda x: inference._top_candidates(x, _C))(rows)
    return rows, jax.device_get(want), jax.device_get(got)


def _same_bits(got, want):
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    bits = f"uint{np.asarray(want[0]).dtype.itemsize * 8}"
    np.testing.assert_array_equal(np.asarray(got[0]).view(bits),
                                  np.asarray(want[0]).view(bits))


@pytest.mark.parametrize("kind", list(_ROWS))
@pytest.mark.parametrize("v", _WIDTHS)
def test_top_candidates_is_lax_top_k_bit_for_bit(v, kind):
    rows, want, got = _both_searches(v)
    i = list(_ROWS).index(kind)
    _same_bits((got[0][i], got[1][i]), (want[0][i], want[1][i]))
    assert got[1].dtype == np.int32
    row, ids = np.asarray(rows[i]), got[1][i]
    if kind == "nan":
        # what lax.top_k does today: a NaN ranks before +inf
        assert ids[0] == (2 * v) // 3 and np.isnan(got[0][i][0])
    elif kind == "neg_nan_beside_the_maximum":
        assert ids[0] == (v // 2) // _G * _G + 1
    elif kind == "signed_zeros":
        assert ids[0] == np.flatnonzero(~np.signbit(row))[0]
    elif kind != "neg_nan_but_ten":
        assert ids[0] == np.argmax(row)        # the greedy parity


@pytest.mark.parametrize("c,dtype", [(10, "float32"), (256, "float32"),
                                     (64, "bfloat16")])
def test_top_candidates_other_counts_and_types(c, dtype):
    """`_sample`'s counts (a request's top_k, its 256 nucleus candidates)
    and a 16-bit row, whose values tie by the hundred."""
    rows = jnp.asarray(candidate_rows(50_257)).astype(dtype)
    assert rows.shape[-1] > c * _G
    want = jax.jit(lambda x: jax.lax.top_k(x, c))(rows)
    got = jax.jit(lambda x: inference._top_candidates(x, c))(rows)
    _same_bits(jax.device_get(got), jax.device_get(want))


def test_wide_vocabulary_samples_what_a_whole_sort_samples(monkeypatch):
    """`sample_slots`, `slot_filtered_probs` and `_sample` over a
    vocabulary the search cuts into groups, against themselves with
    `lax.top_k` in its place: greedy, sampling and nucleus rows."""
    v, n = 19_008, 6
    logits = jnp.asarray(candidate_rows(v)[:n]) * 3.0
    keys = jax.random.split(jax.random.key(7), n)
    temps = jnp.asarray([0.0, 0.7, 1.0, 1.3, 0.0, 0.9], jnp.float32)
    tks = jnp.asarray([0, 5, 0, 40, 0, 64], jnp.int32)
    tps = jnp.asarray([1.0, 1.0, 0.9, 0.5, 1.0, 0.95], jnp.float32)

    def everything():
        return (inference.sample_slots(logits, keys, temps, tks, tps),
                inference.slot_filtered_probs(logits, temps, tks, tps),
                inference._sample(logits, keys[0], temperature=0.8,
                                  top_k=50, top_p=0.9),
                inference._sample(logits, keys[1], temperature=0.8,
                                  top_k=50))

    got = jax.device_get(everything())
    monkeypatch.setattr(inference, "_top_candidates", jax.lax.top_k)
    for a, b in zip(got, jax.device_get(everything())):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(got[0][[0, 4]],
                                  np.argmax(np.asarray(logits)[[0, 4]], -1))

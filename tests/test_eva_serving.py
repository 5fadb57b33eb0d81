"""EVA attention (an exact tumbling window beside one learned summary row a
chunk: models/eva.py, an attention kind of models/transformer.py's
decoder) through the paged engine, at a toy size on the CPU, against the
benchmark's plain reference (benchmark/families/evabyte.py) on seeded
weights.

The program runs in float32 here, on the same bf16-rounded matrices as the
reference, so the two differ only in the order of their sums (chunks and
ticks through two pools against one pass a window): logits agree within
2e-4 of the largest logit. bf16 would not (its own rounding is 4e-3), so
the tolerance also says that nothing of the mathematics is left out:
prefill and decoding through both pools, summaries written by the chunk
and by the tick, windows retired a whole window at a time.
"""

import math

import jax
import numpy as np
import pytest

from benchmark import manifest, reference
from pytorchdistributed_tpu.serving import ServingEngine
from tests.test_latent_serving import (
    LogitSpy,
    check_against_reference,
    serve,
)

WIN, CHUNK = 32, 4
TOY = {
    "model_type": "evabyte", "attention_class": "eva",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "intermediate_size": 128, "vocab_size": 96,
    "window_size": WIN, "chunk_size": CHUNK, "rope_theta": 100000,
    "rms_norm_eps": 1e-5, "norm_add_unit_offset": True,
    "fp32_skip_add": True, "fp32_logits": True, "mixedp_attn": True,
    "max_position_embeddings": 192, "served_positions": 192,
    "param_dtype": "bfloat16", "compute_dtype": "float32",
    "initializer_range": 0.02,
    # at 16 numbers a head, keys drawn from N(0, 0.02) matrices are small:
    # 1.0 gives `phi . k` the deviation the published head size has under
    # 0.1, so that a chunk's weights are uneven here too
    "summary_init_std": 1.0,
}


@pytest.fixture(scope="module")
def fam():
    return manifest.load_family(manifest.BENCH_DIR, "evabyte")


@pytest.fixture(scope="module")
def weights(fam):
    return jax.jit(lambda s: fam.make_weights(TOY, s))(
        reference.seed_u32(2 ** 31 + 34))


def make_engine(fam, w, cfg=TOY, **kw):
    kw = {"num_slots": 3, "block_size": 4, "prefill_chunk": 8,
          "prefix_cache": False, **kw}
    return ServingEngine(fam.program_model(cfg, {}),
                         fam.to_program_tree(w, cfg, {}), **kw)


def rows_attended(prompt: int, new: int) -> tuple[int, int]:
    """(window rows, summary rows) the ticks of one stream attend in one
    layer: the tick at length n queries position n."""
    ticks = range(prompt, prompt + new - 1)
    return (sum(n % WIN + 1 for n in ticks),
            sum(WIN // CHUNK * (n // WIN) for n in ticks))


@pytest.mark.parametrize("chunk", [WIN // 4, WIN])
@pytest.mark.parametrize("prompt", [WIN // 2, WIN, 5 * WIN // 2, 4 * WIN])
def test_prefill_then_decode_matches_reference_logits(fam, weights, prompt,
                                                      chunk, monkeypatch):
    """Prompts of 0.5, 1, 2.5 and 4 windows, prefilled in chunks of a
    quarter and of a whole window, then 40 decoded tokens, which cross a
    window's boundary: every logit against the reference's full pass."""
    new = 40
    eng = make_engine(fam, weights, prefill_chunk=chunk)
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(prompt, new)], TOY["vocab_size"], seed=prompt)
    assert (prompt + new) // WIN > prompt // WIN or prompt % WIN == 0
    check_against_reference(fam, TOY, weights, spy, reqs)
    s = eng.summary()
    layers = TOY["num_hidden_layers"]
    wr, sr = rows_attended(prompt, new)
    # the masks' own counts, over the ticks and the layers
    assert s["eva_window_rows"] == layers * wr
    assert s["eva_summary_rows"] == layers * sr
    assert s["eva_summaries_written"] == layers * sum(
        (n + 1) % CHUNK == 0 for n in range(prompt, prompt + new - 1))
    assert s["window_blocks_retired"] >= WIN // 4   # a whole window went
    eng.close()                                     # both pools' leak checks


def test_mixed_lengths_share_the_slots_and_blocks_are_reused(fam, weights,
                                                             monkeypatch):
    """Streams in different windows tick in one batch (the summaries'
    gather follows the longest); a window block that one stream retired is
    handed to another while the first still runs."""
    eng = make_engine(fam, weights, block_size=8, prefill_chunk=16)
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(5, 30), (70, 12), (40, 30), (130, 9), (33, 4)],
                 TOY["vocab_size"], seed=3)
    check_against_reference(fam, TOY, weights, spy, reqs)
    s = eng.summary()
    assert s["window_blocks_retired"] > 0
    assert 0 < s["eva_summary_rows"] < s["eva_window_rows"]
    eng.close()


def test_a_stream_preempted_and_resumed_serves_the_same_tokens(
        fam, weights, monkeypatch):
    """Preemption frees both pools; the stream resumes by prefilling its
    prompt and what it had generated, and every logit before and after
    still agrees with the reference."""
    eng = make_engine(fam, weights)
    spy = LogitSpy(eng, monkeypatch)
    rng = np.random.default_rng(9)
    reqs = [eng.submit(rng.integers(0, TOY["vocab_size"], n).astype(
        np.int32), max_new_tokens=m) for n, m in ((70, 30), (20, 24))]
    while len(reqs[0].new_tokens) < 11:
        eng.step()
    slot = reqs[0].slot
    held = [pool.in_use for pool in eng._pools]
    eng._preempt(slot)
    assert all(pool.blocks[slot] == [] for pool in eng._pools)
    assert all(pool.in_use < h for pool, h in zip(eng._pools, held))
    eng.run_until_idle()
    assert reqs[0].preemptions == 1
    check_against_reference(fam, TOY, weights, spy, reqs)
    eng.close()


@pytest.mark.parametrize("crossed", [1, 2, 4])
def test_the_pools_books_after_crossing_windows(fam, weights, crossed):
    """A stream that decodes across `crossed` window boundaries: no block
    of a window goes back before the stream crosses into the next, all of
    them then; the summary pool holds a block per `block_size` chunks and
    never hands one back; both allocators are clean at teardown."""
    bs = 4
    eng = make_engine(fam, weights, num_slots=2, block_size=bs,
                      prefill_chunk=WIN)
    summary, window = eng._pools
    assert (summary.kind, summary.stride, summary.tumbling) == (
        "summary", CHUNK, False)
    assert (window.kind, window.window, window.tumbling) == (
        "window", WIN, True)
    prompt = WIN // 2
    req = eng.submit(np.arange(prompt, dtype=np.int32) % TOY["vocab_size"],
                     max_new_tokens=crossed * WIN + 4)
    seen = 0
    while not req.done:
        eng.step()
        if req.done:
            break
        slot = req.slot
        n = int(eng._lengths[slot])        # the next tick's query position
        k = n // WIN                       # windows finished
        blocks = window.blocks[slot]
        # finished windows: every block back in the allocator (zeros are
        # the retired entries); the current window: none before its end
        assert all(b == 0 for b in blocks[:k * WIN // bs]) or n % WIN == 0
        live = [b for b in blocks[(n - 1) // WIN * WIN // bs:] if b]
        assert len(live) == math.ceil(((n - 1) % WIN + 1) / bs)
        assert window.in_use == sum(1 for b in blocks if b)
        assert window.in_use <= WIN // bs
        # the summary pool: one block a `bs` chunks, none ever retired
        held = summary.blocks[slot]
        assert all(held) and len(held) >= math.ceil(
            k * (WIN // CHUNK) / bs)
        assert len(held) == summary.blocks_for(n)
        seen = max(seen, k)
    assert seen == crossed
    s = eng.summary()
    assert s["window_blocks_retired"] == crossed * WIN // bs
    assert s["summary_blocks_in_use"] == 0 == s["window_blocks_in_use"]
    eng.close()
    for pool in eng._pools:
        pool.alloc.check_leaks(0)


def test_the_summary_carries_both_pools_by_their_kinds(fam, weights):
    eng = make_engine(fam, weights)
    serve(eng, [(40, 6)], TOY["vocab_size"])
    s = eng.summary()
    for key in ("window_blocks_in_use", "window_blocks_retired",
                "window_block_utilization", "summary_blocks_in_use",
                "summary_block_utilization", "eva_window_rows",
                "eva_summary_rows", "eva_summaries_written"):
        assert key in s, key
    assert s["summary_block_utilization"] == s["block_utilization"]
    assert 0 < s["window_block_utilization"] <= 1
    eng.close()


@pytest.mark.parametrize("kw,what", [
    ({"prefill_chunk": 12}, "does not divide"),   # 12 does not divide 32
    ({"prefill_chunk": 64}, "does not divide"),   # nor do two windows
    ({"block_size": 3}, "multiple of eva_chunk"),
    ({"block_size": 0}, "paged engine only"),
    ({"prefix_cache": True}, "radix prefix cache"),
    ({"spec_k": 2}, "speculative tick"),
    ({"kv_dtype": "int8"}, "int8 pool"),
    ({"paged_attn": "pallas"}, "paged_attn='pallas'.*one causal mask"),
])
def test_what_the_two_pools_cannot_run_under_is_refused_with_its_reason(
        fam, weights, kw, what):
    cfg = dict(TOY, served_positions=96) if kw.get("block_size") == 3 \
        else TOY
    with pytest.raises(ValueError, match=what) as e:
        make_engine(fam, weights, cfg, **kw)
    # no reason given is the one that is true of latent rows alone
    assert "latents that all heads share" not in str(e.value)

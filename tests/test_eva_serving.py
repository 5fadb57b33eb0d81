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
and by the tick, windows retired a whole window at a time. A tick has two
reads of the pools (ISSUE 35): XLA's gathers, and the paged decode kernel
called once a pool with the two calls merged by their log-sum-exp (in
interpret mode here); both are held to the reference and to each other.
"""

import math

import jax
import numpy as np
import pytest

from benchmark import manifest, reference
from pytorchdistributed_tpu.models import eva
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
    ({"paged_attn": "mosaic"}, "'auto', 'gather' or 'pallas'"),
])
def test_what_the_two_pools_cannot_run_under_is_refused_with_its_reason(
        fam, weights, kw, what):
    cfg = dict(TOY, served_positions=96) if kw.get("block_size") == 3 \
        else TOY
    with pytest.raises(ValueError, match=what) as e:
        make_engine(fam, weights, cfg, **kw)
    # no reason given is the one that is true of latent rows alone
    assert "latents that all heads share" not in str(e.value)


# What a tick's query can meet, as (prompt, new tokens) of the streams that
# share the ticks: the kernel path (`paged_attn="pallas"`, interpreted
# here) against the gather path and the reference (ISSUE 35)
TICK_CASES = {
    # no summary yet: the summary pool's call has nothing to read
    "window_0": [(5, 6)],
    # ticks at W - 3 .. W + 4: the last rows of a window, its first row
    # alone (one position after crossing), and the first summaries seen
    "crossing_a_window": [(WIN - 3, 9)],
    # ticks at positions that fill a chunk, whose summary the tick writes
    "filling_chunks": [(WIN + CHUNK - 2, 2 * CHUNK + 2)],
    "several_finished_windows": [(3 * WIN + 5, 6)],
    # three streams in different windows beside a free slot
    "mixed_beside_a_free_slot": [(5, 6), (WIN - 3, 9),
                                 (2 * WIN + CHUNK - 2, 6)],
}


@pytest.mark.parametrize("case", TICK_CASES)
def test_a_tick_through_the_kernel_matches_the_gather_and_the_reference(
        fam, weights, case, monkeypatch):
    """Every logit of every tick (`paged_tick_logits` at the engine's own
    operands) on the kernel path: against the reference's full pass,
    against the gather path's at the same positions, and the three
    device counters equal between the two paths."""
    served = {}
    for mode in ("gather", "pallas"):
        with monkeypatch.context() as patch:
            eng = make_engine(fam, weights, num_slots=4, paged_attn=mode)
            assert eng.summary()["paged_attn"] == mode
            spy = LogitSpy(eng, patch)
            reqs = serve(eng, TICK_CASES[case], TOY["vocab_size"], seed=35)
            check_against_reference(fam, TOY, weights, spy, reqs)
            s = eng.summary()
            served[mode] = ([spy.logits[r.id] for r in reqs],
                            [r.new_tokens for r in reqs],
                            {k: s[k] for k in eva.COUNTERS})
            eng.close()
    (glog, gtok, gcount), (plog, ptok, pcount) = (served["gather"],
                                                  served["pallas"])
    assert gtok == ptok
    assert gcount == pcount and gcount["eva_window_rows"] > 0
    layers = TOY["num_hidden_layers"]
    rows = [rows_attended(n, m) for n, m in TICK_CASES[case]]
    assert pcount["eva_window_rows"] == layers * sum(r[0] for r in rows)
    assert pcount["eva_summary_rows"] == layers * sum(r[1] for r in rows)
    for g, p, (n, m) in zip(glog, plog, TICK_CASES[case]):
        ticks = range(n, n + m - 1)
        top = max(np.abs(g[i]).max() for i in ticks)
        assert max(np.abs(g[i] - p[i]).max() for i in ticks) < 2e-5 * top


def wide_toy():
    """The toy with one head of 128: a pool row is one whole lane tile."""
    return dict(TOY, hidden_size=128, num_attention_heads=1,
                num_key_value_heads=1)


def test_auto_takes_the_kernel_where_every_pool_is_per_head_rows(
        fam, monkeypatch):
    """On a TPU `"auto"` is the kernel where every kind of cache the model
    declares holds per-head key and value rows of whole 128-lane tiles:
    GPT-2's one pool and EVA's two alike, by the kinds' `lanes`, never by
    the model's name; rows of 64 lanes keep the gather, and asking for
    the kernel over them is refused by their width. On the CPU `"auto"`
    is the gather whatever the rows."""
    from pytorchdistributed_tpu.models import GPT2, gpt2_config

    def choice(model, params, **kw):
        eng = ServingEngine(model, params, num_slots=2, block_size=4,
                            prefill_chunk=8, prefix_cache=False, **kw)
        mode = eng.summary()["paged_attn"]
        eng.close()
        return mode

    def eva_engine(cfg, **kw):
        w = jax.jit(lambda s: fam.make_weights(cfg, s))(
            reference.seed_u32(35))
        return choice(fam.program_model(cfg, {}),
                      fam.to_program_tree(w, cfg, {}), **kw)

    gpt2 = GPT2(gpt2_config("test", embed_dim=128, num_heads=2,
                            num_layers=1, max_seq_len=64))
    gpt2_params = gpt2.init(jax.random.key(0), np.zeros((1, 4), np.int32))
    wide = wide_toy()
    kinds = fam.program_model(wide, {}).cfg.cache_kinds
    assert [(k.kind, k.lanes) for k in kinds] == [("summary", 128),
                                                  ("window", 128)]
    assert [k.lanes for k in gpt2.cfg.cache_kinds] == [128]
    assert eva_engine(wide) == "gather" == choice(gpt2, gpt2_params)
    assert eva_engine(wide, paged_attn="pallas") == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert choice(gpt2, gpt2_params) == "pallas"      # one kind
    assert eva_engine(wide) == "pallas"               # EVA's two
    assert eva_engine(TOY) == "gather"                # rows of 64 lanes
    with pytest.raises(ValueError, match="whole 128-lane tiles.* is 64"):
        eva_engine(TOY, paged_attn="pallas")


def test_a_latent_kind_keeps_the_gather_and_is_refused_by_its_name(
        monkeypatch):
    """A model one of whose pools holds latent rows that all heads share
    stays on the gather on a TPU, and `paged_attn="pallas"` is refused
    with a message that names that pool."""
    from tests import test_latent_serving as latent

    fam = manifest.load_family(manifest.BENCH_DIR, "dots3_note")
    w = jax.jit(lambda s: fam.make_weights(latent.TOY, s))(
        reference.seed_u32(35))
    model = fam.program_model(latent.TOY, {})
    assert [(k.kind, k.lanes) for k in model.cfg.cache_kinds] == [
        ("latent", 0), ("window", 0)]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = latent.make_engine(fam, latent.TOY, w)
    assert eng.summary()["paged_attn"] == "gather"
    eng.close()
    with pytest.raises(ValueError, match="paged_attn='pallas' is not "
                       "built for this model's 'latent' pool.*latents "
                       "that all heads share"):
        latent.make_engine(fam, latent.TOY, w, paged_attn="pallas")


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_planes_serve_what_the_checkpoint_layout_serves(fam, weights,
                                                        compute, monkeypatch):
    """The scanned stack's fused q/k/v and gate/up, held by the engine as
    planes (serving/weights.py:served): tokens and every logit of every
    chunk and tick bitwise what the checkpoint's layout serves, with the
    program in float32 and in bfloat16."""
    from tests.test_serving_weights import served_both_ways

    cfg = dict(TOY, compute_dtype=compute)
    served_both_ways(lambda: make_engine(fam, weights, cfg),
                     [(WIN + 5, 8), (5, 6)], TOY["vocab_size"], LogitSpy,
                     monkeypatch)

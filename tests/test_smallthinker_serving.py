"""Layers of two kinds in one scanned stack (a full layer without
positional encoding, then three RoPE layers over a sliding window:
`TransformerConfig.period`, models/periodic.py), grouped-query heads, and
softmax-routed ReGLU experts routed before attention
(models/moe.py:DroplessMoE, all experts held) through the paged engine's
two K/V pools, at a toy size on the CPU, against the benchmark's plain
reference (benchmark/families/smallthinker.py) on seeded weights.

The program runs in float32 here, on the same bf16-rounded matrices as the
reference, so the two differ only in the order of their sums (chunks and
ticks through two pools against one pass, a grouped against a gathered
expert product): logits agree within 2e-4 of the largest logit. bf16 would
not (its own rounding is 4e-3), so the tolerance also says that nothing of
the mathematics is left out, and each of the readings the config rules out
(`FAULTS`, planted in the reference) is caught by it. A tick has two reads
of the pools: XLA's gathers, and the paged decode kernel, one call a layer
(in interpret mode here); both are held to the reference.
"""

import jax
import numpy as np
import pytest

from benchmark import manifest, reference
from pytorchdistributed_tpu.models import periodic
from pytorchdistributed_tpu.serving import ServingEngine
from tests.test_latent_serving import (
    TOL,
    LogitSpy,
    check_against_reference,
    serve,
)

WIN, BLOCK = 32, 16
TOY = {
    "model_type": "smallthinker", "hidden_size": 64, "head_dim": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 8, "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "sliding_window_size": WIN, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 2, "moe_ffn_hidden_size": 32,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1500000, "vocab_size": 96,
    "max_position_embeddings": 128, "served_positions": 128,
    "param_dtype": "bfloat16", "compute_dtype": "float32",
    "initializer_range": 0.02,
    # at width 64 a router drawn N(0, 0.02) gives logits of 0.16, all but
    # a uniform softmax; 0.125 gives the logits the published width has (1)
    "router_init_std": 0.125,
}
FULL = TOY["sliding_window_layout"].count(0)
WINDOWED = TOY["sliding_window_layout"].count(1)


@pytest.fixture(scope="module")
def fam():
    return manifest.load_family(manifest.BENCH_DIR, "smallthinker")


@pytest.fixture(scope="module")
def weights(fam):
    return jax.jit(lambda s: fam.make_weights(TOY, s))(
        reference.seed_u32(2 ** 31 + 36))


def make_engine(fam, w, cfg=TOY, **kw):
    kw = {"num_slots": 3, "block_size": BLOCK, "prefill_chunk": BLOCK,
          "prefix_cache": False, **kw}
    return ServingEngine(fam.program_model(cfg, {}),
                         fam.to_program_tree(w, cfg, {}), **kw)


@pytest.mark.parametrize("prompt,new,chunk,query_block", [
    (5, 30, 16, None),     # below the window, then up to it
    (40, 30, 16, None),    # prefilled past the window, decodes across one
    (70, 40, 32, None),    # three windows long, a chunk a window
    (90, 20, 64, None),    # a chunk of two windows, its last one padded
    (52, 12, 32, 8),       # each chunk walked in four blocks of queries
])
def test_prefill_then_decode_matches_reference_logits(
        fam, weights, prompt, new, chunk, query_block, monkeypatch):
    """Chunks, then ticks, through both pools: every logit against the
    reference's full pass, and the rows the ticks' queries attended as
    the masks counted them."""
    cfg = TOY
    if query_block:
        # a context of its own, so that the programs are traced anew
        # under the smaller block and not found in the jit's cache
        monkeypatch.setattr(periodic, "QUERY_BLOCK", query_block)
        cfg = dict(TOY, served_positions=112)
    eng = make_engine(fam, weights, cfg, prefill_chunk=chunk)
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(prompt, new)], TOY["vocab_size"], seed=prompt)
    check_against_reference(fam, cfg, weights, spy, reqs)
    s = eng.summary()
    # a tick at length n queries position n
    ticks = range(prompt, prompt + new - 1)
    assert s["attn_full_rows"] == FULL * sum(n + 1 for n in ticks)
    assert s["attn_window_rows"] == WINDOWED * sum(
        min(n + 1, WIN) for n in ticks)
    assert s["moe_dropped"] == 0
    assert s["moe_assignments_total"] == s["moe_assignments_held"] == (
        len(ticks) * TOY["num_hidden_layers"]
        * TOY["moe_num_active_primary_experts"])
    assert 0 < s["moe_experts_hit"] <= s["moe_assignments_total"]
    if prompt + new > WIN + chunk + BLOCK:
        assert s["window_blocks_retired"] > 0
    eng.close()                                     # both pools' leak checks


@pytest.mark.parametrize("fault", [
    "rope_in_full_layer", "no_rope_in_window_layer",
    "router_after_attention", "window_off_by_one"])
def test_a_planted_fault_in_the_reference_is_caught(fam, weights, fault,
                                                    monkeypatch):
    """Each reading of the model that the config or its description rules
    out moves the logits past the tolerance the true reading is held to
    (a stream past the window, so that one position more is there to
    attend)."""
    assert fault in fam.FAULTS
    eng = make_engine(fam, weights)
    spy = LogitSpy(eng, monkeypatch)
    (req,) = serve(eng, [(40, 8)], TOY["vocab_size"], seed=1)
    eng.close()
    seq = np.concatenate([req.prompt, np.asarray(req.new_tokens,
                                                 np.int32)])[:-1]
    got = np.stack([spy.logits[req.id][i] for i in range(len(seq))])
    errs = {}
    for planted in (None, fault):
        ref = np.asarray(jax.jit(lambda p, t, planted=planted: fam.forward(
            TOY, p, t, "f32", fault=planted))(weights, seq[None]))[0]
        errs[planted] = np.abs(got - ref).max() / np.abs(ref).max()
    assert errs[None] < TOL < errs[fault] / 5, errs


def test_mixed_lengths_share_the_slots_and_window_blocks_are_bounded(
        fam, weights, monkeypatch):
    """Streams below, across and far past the window tick in one batch; a
    slot never holds more window blocks than the window, a chunk and one
    block more; a block that one stream retired is handed to another
    while the first still runs; nothing leaks at teardown."""
    chunk = 2 * BLOCK
    eng = make_engine(fam, weights, prefill_chunk=chunk)
    spy = LogitSpy(eng, monkeypatch)
    pool = eng._pools[1]
    assert (pool.kind, pool.window, pool.tumbling) == ("window", WIN, False)
    assert eng._pools[0].kind == "full" and not eng._pools[0].window
    held, retired, reused = [], {}, []
    decref, alloc = pool.alloc.decref, pool.alloc.alloc

    def spy_decref(block):
        retired[block] = bool(eng._active)
        return decref(block)

    def spy_alloc(n):
        out = alloc(n)
        for b in out or []:
            if retired.pop(b, False):
                reused.append(b)
        held.append(max(sum(1 for b in blocks if b)
                        for blocks in pool.blocks))
        return out

    pool.alloc.decref, pool.alloc.alloc = spy_decref, spy_alloc
    reqs = serve(eng, [(5, 30), (75, 30), (40, 12), (100, 20), (33, 40)],
                 TOY["vocab_size"], seed=3)
    check_against_reference(fam, TOY, weights, spy, reqs)
    assert max(held) * BLOCK <= WIN + chunk + BLOCK, max(held)
    assert reused, "no retired window block was handed out again"
    s = eng.summary()
    assert s["window_blocks_retired"] > 0 and s["moe_dropped"] == 0
    assert 0 < s["attn_window_rows"] and 0 < s["attn_full_rows"]
    assert 1.0 < s["moe_load_max"] / s["moe_load_mean"]
    eng.close()
    assert all(p.in_use == 0 for p in eng._pools)


def test_the_kernel_reads_both_pools_as_the_gather_does(fam, weights,
                                                       monkeypatch):
    """The paged decode kernel (interpret mode here) a layer: a full
    layer's pool from the stream's first row, a window layer's under
    `window_tokens`, grouped queries; the same logits as the gathered
    read within the tolerance both are held to the reference by, and the
    same counts."""
    runs = {}
    for read in ("gather", "pallas"):
        eng = make_engine(fam, weights, paged_attn=read)
        assert eng.summary()["paged_attn"] == read
        spy = LogitSpy(eng, monkeypatch)
        reqs = serve(eng, [(70, 12), (20, 30)], TOY["vocab_size"], seed=9)
        check_against_reference(fam, TOY, weights, spy, reqs)
        s = eng.summary()
        runs[read] = (spy.logits, [r.id for r in reqs],
                      s["attn_full_rows"], s["attn_window_rows"])
        eng.close()
        monkeypatch.undo()
    (la, ia, *ca), (lb, ib, *cb) = runs["gather"], runs["pallas"]
    assert ca == cb
    for a, b in zip(ia, ib):
        top = max(np.abs(v).max() for v in la[a].values())
        for pos in la[a]:
            assert np.abs(la[a][pos] - lb[b][pos]).max() < TOL * top


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "radix prefix cache"),
    ({"spec_k": 2}, "speculative tick"),
    ({"session_store": object()}, "session store"),
    ({"kv_dtype": "int8"}, "int8 pool"),
    ({"block_size": 0}, "paged engine only"),
])
def test_what_two_cache_kinds_cannot_use_is_refused_at_construction(
        fam, weights, kw, what):
    with pytest.raises(ValueError, match=what):
        make_engine(fam, weights, **kw)


def test_the_config_refuses_what_a_period_is_not_built_for():
    from pytorchdistributed_tpu.models.llama import llama_config

    ok = dict(period=((False, 0), (True, 32)), num_layers=4)
    llama_config("test", **ok)
    for bad, what in (
            (dict(ok, num_layers=3), "multiple of its length"),
            (dict(ok, period=((True, 32), (True, 32))), "every position"),
            (dict(ok, period=((False, 0), (True, 32), (True, 64))),
             "one window size"),
            (dict(ok, scan_layers=False), "scanned stack"),
            (dict(ok, router_experts=8, experts_held=(0, 9),
                  experts_per_token=2, moe_dim=16), "experts_held"),
            (dict(ok, router_experts=8, experts_held=(0, 8),
                  experts_per_token=2, moe_dim=16, moe_scoring="top"),
             "moe_scoring")):
        with pytest.raises(ValueError, match=what):
            llama_config("test", **bad)


def test_the_parameter_tree_goes_there_and_back(fam, weights):
    tree = fam.to_program_tree(weights, TOY, {})
    block = tree["params"]["h"]["block"]
    assert sorted(block) == ["layer_0", "layer_1", "layer_2", "layer_3"]
    # layer 5 is layer_1 of the second period
    np.testing.assert_array_equal(
        np.asarray(block["layer_1"]["moe"]["router"][1]),
        np.asarray(weights["router"][5]))
    back = fam.from_program_tree(tree, TOY, {})
    assert sorted(back) == sorted(weights)
    for name, leaf in weights.items():
        assert back[name].dtype == leaf.dtype
        np.testing.assert_array_equal(np.asarray(back[name], np.float32),
                                      np.asarray(leaf, np.float32))
    model = fam.program_model(TOY, {})
    assert model.cfg.head_dim == 32 and model.cfg.kv_heads == 2
    assert model.counters[-2:] == periodic.COUNTERS
    assert [k.kind for k in model.cfg.cache_kinds] == ["full", "window"]

"""A model with two cache kinds through the paged engine, at a toy size on
the CPU: latent attention with a learned sparse selection in the full
layers, latent window layers beside it, sigmoid-routed experts of which a
share is held (models/latent.py, models/moe.py:DroplessMoE), against the
benchmark's plain reference (benchmark/families/dots3_note.py) on seeded
weights.

The program runs in float32 here, on the same bf16-rounded weights as the
reference, so the two differ only in the order of their sums (absorbed
against per-head attention, a grouped against a gathered expert product,
chunks and ticks against one pass): logits agree within 2e-4 of the
largest logit. bf16 would not (its own rounding is 4e-3), so this
tolerance also says that nothing of the mathematics is left out.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, reference
from pytorchdistributed_tpu.models import latent
from pytorchdistributed_tpu.models.moe import DroplessMoE
from pytorchdistributed_tpu.serving import ServingEngine
from pytorchdistributed_tpu.serving import engine as engine_mod

TOL = 2e-4

TOY = {
    "model_type": "dots3_note", "hidden_size": 64, "num_hidden_layers": 5,
    "layer_types": ["full_attention", "full_attention",
                    "sliding_attention", "sliding_attention",
                    "sliding_attention"],
    "first_k_dense_replace": 1, "intermediate_size": 128,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 80000000, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk": 8, "swa_num_attention_heads": 2, "swa_q_lora_rank": 32,
    "swa_kv_lora_rank": 32, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16,
    "swa_rope_theta": 50000, "sliding_window_size": 9,
    "apply_mla_qkv_lora_rescale": True, "moe_intermediate_size": 32,
    "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-5, "vocab_size": 96,
    "max_position_embeddings": 64, "served_positions": 64,
    "param_dtype": "bfloat16", "compute_dtype": "float32",
    "initializer_range": 0.02,
    # at width 64 a router drawn N(0, 0.02) gives logits of 0.16 and the
    # selection bias N(0, 0.1) would choose alone; 0.18 gives the logits
    # the published width has (1.4)
    "router_init_std": 0.18,
}


@pytest.fixture(scope="module")
def fam():
    return manifest.load_family(manifest.BENCH_DIR, "dots3_note")


@pytest.fixture(scope="module")
def weights(fam):
    return jax.jit(lambda s: fam.make_weights(TOY, s))(
        reference.seed_u32(2 ** 31 + 5))


def make_engine(fam, cfg, w, **kw):
    kw = {"num_slots": 3, "block_size": 4, "prefill_chunk": 8,
          "prefix_cache": False, **kw}
    return ServingEngine(fam.program_model(cfg, {}),
                         fam.to_program_tree(w, cfg, {}), **kw)


class LogitSpy:
    """Every logit the engine's two programs compute, by request and
    position: it stands in front of `paged_prefill_chunk` and
    `paged_decode_tick` in the engine's module and, before each call,
    runs the program's own model part (`paged_chunk_logits`,
    `paged_tick_logits`) on the same operands."""

    def __init__(self, eng, monkeypatch):
        self.eng, self.logits = eng, {}
        self._chunk = jax.jit(engine_mod.paged_chunk_logits,
                              static_argnums=0)
        self._tick = jax.jit(engine_mod.paged_tick_logits,
                             static_argnums=0)
        for name, spy in (("paged_prefill_chunk", self.chunk),
                          ("paged_decode_tick", self.tick)):
            monkeypatch.setattr(engine_mod, name, functools.partial(
                spy, getattr(engine_mod, name)))

    def chunk(self, program, model, *args, **kw):
        eng, pf = self.eng, self.eng._prefilling
        start = int(args[3])
        logits, _ = self._chunk(model, *args[:5])
        rows = self.logits.setdefault(pf["req"].id, {})
        for i in range(min(eng.chunk, pf["true_len"] - start)):
            rows[start + i] = np.asarray(logits[0, i])
        return program(model, *args, **kw)

    def tick(self, program, model, *args, **kw):
        eng = self.eng
        logits, _ = self._tick(model, *args[:5])
        for slot, req in eng._active.items():
            self.logits.setdefault(req.id, {})[
                int(eng._lengths[slot])] = np.asarray(logits[slot, 0])
        return program(model, *args, **kw)


def check_against_reference(fam, cfg, w, spy, reqs, vocab=None):
    fwd = jax.jit(lambda p, t: fam.forward(cfg, p, t, "f32"))
    for r in reqs:
        assert r.finish_reason == "length"
        seq = np.concatenate([r.prompt, np.asarray(r.new_tokens,
                                                   np.int32)])[:-1]
        ref = np.asarray(fwd(w, jnp.asarray(seq[None])))[0]
        if vocab is not None:
            ref = ref[:, :vocab]
        got = spy.logits[r.id]
        assert sorted(got) == list(range(len(seq)))  # every position
        got = np.stack([got[i] for i in range(len(seq))])
        err = np.abs(got - ref).max() / np.abs(ref).max()
        assert err < TOL, (len(r.prompt), err)
        # and the served tokens are the reference's first choice
        n = len(r.prompt)
        assert (ref[n - 1:].argmax(-1) == np.asarray(r.new_tokens)).all()


def serve(eng, prompts_and_lengths, vocab, seed=0):
    rng = np.random.default_rng(seed)
    reqs = [eng.submit(rng.integers(0, vocab, n).astype(np.int32),
                       max_new_tokens=m) for n, m in prompts_and_lengths]
    eng.run_until_idle()
    return reqs


@pytest.mark.parametrize("prompt,new,query_block", [
    (5, 3, None),    # below index_topk (8) and the window (9): attends all
    (7, 6, None),    # crosses both while it decodes
    (40, 12, None),  # five chunks of 8; selection and window throughout
    (20, 8, 4),      # three chunks, each walked in two blocks of queries
])
def test_prefill_then_decode_matches_reference_logits(fam, weights, prompt,
                                                      new, query_block,
                                                      monkeypatch):
    cfg = TOY
    if query_block:
        # a context of its own, so that the programs are traced anew
        # under the smaller block and not found in the jit's cache
        monkeypatch.setattr(latent, "QUERY_BLOCK", query_block)
        cfg = dict(TOY, served_positions=32)
    eng = make_engine(fam, cfg, weights)
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(prompt, new)], TOY["vocab_size"])
    check_against_reference(fam, cfg, weights, spy, reqs)
    s = eng.summary()
    assert s["moe_dropped"] == 0
    # the masks' own counts, over the ticks: a tick at length n attends
    # min(n + 1, index_topk) of n + 1 live positions in each full layer
    ticks = range(prompt, prompt + new - 1)
    assert s["sparse_live"] == 2 * sum(n + 1 for n in ticks)
    assert s["sparse_selected"] == 2 * sum(
        min(n + 1, TOY["index_topk"]) for n in ticks)
    if prompt + new > TOY["sliding_window_size"] + 8:
        assert s["window_blocks_retired"] > 0
    eng.close()  # both pools' leak checks


def test_mixed_lengths_in_one_batch_and_a_retired_block_reused(
        fam, weights, monkeypatch):
    """Streams of different lengths share the slots; a window block that
    one stream retired is handed to another stream while the first still
    runs, and every logit of both still agrees with the reference."""
    eng = make_engine(fam, TOY, weights)
    spy = LogitSpy(eng, monkeypatch)
    retired, reused = {}, []
    win = eng._pools[1].alloc
    decref, alloc = win.decref, win.alloc

    def spy_decref(block):
        live = {id(r) for r in eng._active.values()}
        retired[block] = live
        return decref(block)

    def spy_alloc(n):
        out = alloc(n)
        for b in out or []:
            if b in retired and retired.pop(b):
                reused.append(b)
        return out

    win.decref, win.alloc = spy_decref, spy_alloc
    reqs = serve(eng, [(5, 6), (23, 9), (40, 12), (13, 20), (31, 4)],
                 TOY["vocab_size"])
    check_against_reference(fam, TOY, weights, spy, reqs)
    assert reused, "no retired window block was handed out again"
    s = eng.summary()
    assert s["window_blocks_retired"] > 0 and s["moe_dropped"] == 0
    # selected <= live in the full layers, and fewer once contexts pass
    # index_topk
    assert 0 < s["sparse_selected"] < s["sparse_live"]
    assert s["moe_assignments_total"] > 0
    eng.close()


def test_a_vocabulary_slice_gives_the_reference_logits_over_the_slice(
        fam, weights, monkeypatch):
    half = TOY["vocab_size"] // 2
    cfg = dict(TOY, vocab_size=half)
    w = dict(weights, embed=weights["embed"][:half],
             head=weights["head"][:, :half])
    eng = make_engine(fam, cfg, w)
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(19, 5)], half)
    # the uncut reference (all 96 rows), read over the slice
    check_against_reference(fam, TOY, weights, spy, reqs, vocab=half)
    eng.close()


def moe_layer(fam, cfg):
    lm_cfg = fam.program_model(cfg, {}).cfg
    return DroplessMoE(lm_cfg)


def moe_params(w, layer, lo, hi):
    pre = f"l{layer}."
    p = {k: w[pre + k] for k in ("router", "router_bias", "s_gate", "s_up",
                                 "s_down")}
    p.update({k: w[pre + k][lo:hi] for k in ("e_gate", "e_up", "e_down")})
    return p


def test_the_softmax_reglu_shares_add_up_to_the_uncut_layer():
    """`DroplessMoE` as SmallThinker configures it (softmax over the
    chosen logits, ReGLU experts, no shared expert, routed from another
    tensor than the experts read) on a `TransformerConfig`: four shares
    of 16 experts, each its held experts' part for the tokens routed to
    them, add up to the uncut layer of the family's plain reference."""
    from pytorchdistributed_tpu.models.transformer import TransformerConfig

    st = manifest.load_family(manifest.BENCH_DIR, "smallthinker")
    d, e, k, f = 64, 16, 4, 32
    toy = {"hidden_size": d, "moe_num_primary_experts": e,
           "moe_num_active_primary_experts": k, "moe_ffn_hidden_size": f,
           "moe_primary_router_apply_softmax": True, "norm_topk_prob": True}
    keys = jax.random.split(jax.random.key(11), 6)
    lp = {"router": 0.125 * jax.random.normal(keys[0], (d, e)),
          **{name: (0.02 * jax.random.normal(key, shape)).astype(
              jnp.bfloat16)
             for name, key, shape in (("e_gate", keys[1], (e, d, f)),
                                      ("e_up", keys[2], (e, d, f)),
                                      ("e_down", keys[3], (e, f, d)))}}
    x = jax.random.normal(keys[4], (2, 24, d))
    route = jax.random.normal(keys[5], (2, 24, d))
    r = jnp.matmul(route.reshape(-1, d), lp["router"],
                   precision=jax.lax.Precision.HIGHEST)
    uncut = np.asarray(st._experts_out(toy, "f32", lp, x.reshape(-1, d), r))
    total, held = 0.0, 0.0
    for lo in range(0, e, 4):
        cfg = TransformerConfig(
            embed_dim=d, num_heads=4, router_experts=e,
            experts_held=(lo, lo + 4), experts_per_token=k, moe_dim=f,
            moe_scoring="softmax", moe_activation="relu",
            router_input="attn", dtype=jnp.float32,
            param_dtype=jnp.bfloat16)
        params = {"router": lp["router"],
                  **{n: lp[n][lo:lo + 4]
                     for n in ("e_gate", "e_up", "e_down")}}
        out, counters = DroplessMoE(cfg).apply({"params": params}, x,
                                               None, route)
        total = total + np.asarray(out).reshape(uncut.shape)
        held += float(counters["moe_assignments_held"])
        assert counters["moe_dropped"] == 0
    np.testing.assert_allclose(total, uncut,
                               atol=TOL * np.abs(uncut).max())
    assert held == 48 * k                    # every assignment once
    # the other tensor decides the routing: routed from `x`, another layer
    other, _ = DroplessMoE(cfg).apply({"params": params}, x)
    assert np.abs(np.asarray(other) - np.asarray(out)).max() > 10 * TOL * \
        np.abs(uncut).max()


def test_the_shares_add_up_to_the_uncut_layer(fam, weights):
    """Each share's part (its held experts for the tokens routed to them,
    plus the shared expert every share computes alike) summed over the
    four shares, the shared expert counted once, is the uncut
    reference's layer output."""
    x = jax.random.normal(jax.random.key(3), (2, 24, TOY["hidden_size"]))
    flat = x.reshape(-1, TOY["hidden_size"])
    uncut = np.asarray(fam._moe(TOY, "f32", weights, "l2.", flat))
    shared = np.asarray(fam._swiglu(
        flat, *(weights[f"l2.{k}"].astype(jnp.float32)
                for k in ("s_gate", "s_up", "s_down")), "f32"))
    total, held = 0.0, 0.0
    for lo in range(0, 16, 4):
        cfg = dict(TOY, n_routed_experts=4,
                   published_n_routed_experts=16,
                   experts_held=[lo, lo + 4])
        out, counters = moe_layer(fam, cfg).apply(
            {"params": moe_params(weights, 2, lo, lo + 4)}, x)
        total = total + (np.asarray(out).reshape(uncut.shape) - shared)
        held += float(counters["moe_assignments_held"])
        assert counters["moe_dropped"] == 0
        # the reference, given the same share, gives the same part
        part = np.asarray(fam._moe(cfg, "f32", {
            "l2." + k: v for k, v in moe_params(
                weights, 2, lo, lo + 4).items()}, "l2.", flat))
        np.testing.assert_allclose(np.asarray(out).reshape(uncut.shape),
                                   part, atol=TOL * np.abs(uncut).max())
    np.testing.assert_allclose(total + shared, uncut,
                               atol=TOL * np.abs(uncut).max())
    assert held == 48 * TOY["num_experts_per_tok"]  # every assignment once


def test_routing_drops_nothing_under_a_planted_imbalance(fam, weights):
    """A bias that sends every token to expert 1: every assignment to a
    held expert is computed, and the counters say so."""
    w = dict(weights)
    w["l2.router_bias"] = weights["l2.router_bias"].at[1].set(10.0)
    cfg = dict(TOY, n_routed_experts=4, published_n_routed_experts=16,
               experts_held=[0, 4])
    x = jax.random.normal(jax.random.key(4), (1, 64, TOY["hidden_size"]))
    out, c = moe_layer(fam, cfg).apply(
        {"params": moe_params(w, 2, 0, 4)}, x)
    assert c["moe_dropped"] == 0
    assert c["moe_load_max"] == 64          # all 64 tokens at expert 1
    assert c["moe_assignments_held"] > 64   # and others beside it
    assert c["moe_load_max"] > 1.5 * c["moe_load_mean"]
    ref = fam._moe(cfg, "f32", {"l2." + k: v for k, v in moe_params(
        w, 2, 0, 4).items()}, "l2.", x[0])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref),
                               atol=TOL * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "radix prefix cache"),
    ({"spec_k": 2}, "speculative tick"),
    ({"session_store": object()}, "session store"),
    ({"kv_dtype": "int8"}, "int8 pool"),
    ({"kv_window_tokens": 8}, "kv_window_tokens"),
    ({"block_size": 0}, "paged engine only"),
    ({"paged_attn": "pallas"}, "paged_attn='pallas'"),
])
def test_what_two_cache_kinds_cannot_use_is_refused_at_construction(
        fam, weights, kw, what):
    with pytest.raises(ValueError, match=what):
        make_engine(fam, TOY, weights, **kw)


def test_block_transport_and_sessions_are_refused_at_the_call(fam, weights):
    eng = make_engine(fam, TOY, weights)
    req = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=2)
    for call in (lambda: eng.export_kv_blocks(req),
                 lambda: eng.import_kv_blocks(None),
                 lambda: eng.export_prefix_blocks([1, 2, 3]),
                 lambda: eng.import_prefix_blocks(None),
                 lambda: eng.detach_request(req),
                 lambda: eng.seed_session_blocks(None),
                 lambda: eng.export_session("s"),
                 lambda: eng.submit(req.prompt, max_new_tokens=2,
                                    session_id="s"),
                 lambda: eng.submit(req.prompt, max_new_tokens=2,
                                    prefill_only=True)):
        with pytest.raises(ValueError, match="two cache kinds"):
            call()
    eng.run_until_idle()
    eng.close()


def test_the_counts_give_the_published_share(fam):
    """`total_params` of the configuration as it is run: 4,087 M within
    half a percent (the issue's table), and an eighth of the routed
    experts a token."""
    import json

    cfg = json.loads((manifest.BENCH_DIR / "configs"
                      / "dots3-note-prev.json").read_text())
    assert abs(fam.total_params(cfg) / 4.087e9 - 1) < 0.005
    assert cfg["params"] == fam.total_params(cfg)
    moe = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    dense = fam.forward_flops_token(cfg, 1, head=False)
    more = fam.forward_flops_token(
        dict(cfg, experts_held=[0, 64], n_routed_experts=64), 1,
        head=False)
    assert more - dense == pytest.approx(
        2.0 * moe * fam.moe_layers(cfg) * 8 * (64 - 32) / 256)
    # attention follows min(context, 2,048) and min(context, 513)
    a, b = (fam.forward_flops_token(cfg, n, head=False)
            for n in (8192, 16384))
    indexer = 2.0 * 2 * cfg["index_n_heads"] * (
        cfg["index_head_dim"] + 1) * 8192
    assert b - a == pytest.approx(indexer)


def test_the_reference_computes_an_expert_over_its_bound_under_a_mask(
        fam, weights, monkeypatch):
    """The reference gathers an expert's tokens under a bound; an expert
    that draws more (a compared request's padded tail is one token
    repeated, and all of it routes alike) is computed on every token
    under a mask, and gives the same numbers."""
    x = jax.random.normal(jax.random.key(5), (40, TOY["hidden_size"]))
    whole = fam._moe(TOY, "f32", weights, "l3.", x)
    monkeypatch.setattr(fam, "expert_bound", lambda s: 3)
    tight = fam._moe(TOY, "f32", weights, "l3.", x)
    np.testing.assert_allclose(np.asarray(tight), np.asarray(whole),
                               atol=1e-6)

"""The parallel block (one LayerNorm without a bias that attention, the
router and the experts all read: `TransformerConfig.block_form`),
sigmoid-routed SwiGLU experts of which a share is held beside averaged
shared experts (models/moe.py:DroplessMoE, `shared_experts`), GPT-J RoPE
window layers and NoPE full layers over two K/V pools (models/
periodic.py) and a tied head, through the paged engine at a toy size on
the CPU, against the benchmark's plain reference
(benchmark/families/cohere2_moe.py) on seeded weights.

In float32 the program runs on the same bf16-rounded matrices as the
reference, so the two differ only in the order of their sums (chunks and
ticks through two pools against one pass, a grouped against a gathered
expert product, the shared experts as one product against four): logits
agree within 2e-4 of the largest logit, and each reading the config rules
out (`FAULTS`, planted in the reference) lies past five times that. In
bf16 the program rounds its activations where the configuration says
(0.7% of the largest logit on this toy, as the reference's own bf16 pass
reads against its float32 one): it is held within twice what the
reference's own bf16 arithmetic moves.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, reference
from pytorchdistributed_tpu.models import periodic
from pytorchdistributed_tpu.models.llama import llama_config
from pytorchdistributed_tpu.models.moe import DroplessMoE
from pytorchdistributed_tpu.models.transformer import apply_rope
from pytorchdistributed_tpu.serving import ServingEngine
from tests.test_latent_serving import (
    TOL,
    LogitSpy,
    check_against_reference,
    serve,
)

WIN, BLOCK, HEADS = 16, 16, 8
TOY = {
    "model_type": "cohere2_moe", "hidden_size": 64, "head_dim": 16,
    "num_attention_heads": HEADS, "num_key_value_heads": 2,
    "num_hidden_layers": 8,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "sliding_window": WIN, "num_experts": 8, "published_num_experts": 16,
    "experts_held": [0, 8], "num_experts_per_tok": 4,
    "num_shared_experts": 2, "intermediate_size": 32,
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "shared_expert_combination_strategy": "average",
    "use_parallel_block": True, "tie_word_embeddings": True,
    "position_embedding_type": "rope_gptj", "rotary_pct": 1,
    "logit_scale": 1, "layer_norm_eps": 1e-5, "rope_theta": 50000,
    "vocab_size": 96, "max_position_embeddings": 128,
    "served_positions": 128, "param_dtype": "bfloat16",
    "compute_dtype": "float32", "initializer_range": 0.02,
    # at width 64 a router drawn N(0, 0.02) gives logits of 0.16, sigmoid
    # scores all but tied; 0.125 gives the logits the published width
    # has (1.3)
    "router_init_std": 0.125,
}
LAYERS, K = TOY["num_hidden_layers"], TOY["num_experts_per_tok"]
FULL = TOY["layer_types"].count("full_attention")
WINDOWED = LAYERS - FULL


@pytest.fixture(scope="module")
def fam():
    return manifest.load_family(manifest.BENCH_DIR, "cohere2_moe")


@pytest.fixture(scope="module")
def weights(fam):
    return jax.jit(lambda s: fam.make_weights(TOY, s))(
        reference.seed_u32(2 ** 31 + 44))


def make_engine(fam, w, cfg=TOY, **kw):
    kw = {"num_slots": 3, "block_size": BLOCK, "prefill_chunk": BLOCK,
          "prefix_cache": False, **kw}
    return ServingEngine(fam.program_model(cfg, {}),
                         fam.to_program_tree(w, cfg, {}), **kw)


def hand_counts(prompt: int, new: int) -> dict:
    """What the device counters must read for one stream: its prompt's
    positions in the chunks, a tick a token after the first."""
    chunk = range(prompt)
    ticks = range(prompt, prompt + new - 1)   # a tick at n queries n
    return {
        "chunk_attn_full_rows": FULL * sum(n + 1 for n in chunk),
        "chunk_attn_window_rows": WINDOWED * sum(min(n + 1, WIN)
                                                 for n in chunk),
        "chunk_moe_assignments_total": prompt * LAYERS * K,
        "attn_full_rows": FULL * sum(n + 1 for n in ticks),
        "attn_window_rows": WINDOWED * sum(min(n + 1, WIN) for n in ticks),
        "moe_assignments_total": len(ticks) * LAYERS * K,
    }


@pytest.mark.parametrize("prompt,new,chunk,query_head_rows", [
    (5, 24, 16, None),    # below the window, then across it
    (40, 20, 16, None),   # prefilled past the window, in three chunks
    (70, 12, 32, 64),     # chunks walked in blocks of 8 queries (8 heads)
])
def test_prefill_then_decode_matches_reference_logits(
        fam, weights, prompt, new, chunk, query_head_rows, monkeypatch):
    """Chunks, then ticks, through both pools: every logit against the
    reference's full pass, and the device's counters of the chunks and
    of the ticks against a hand count."""
    cfg = TOY
    if query_head_rows:
        # a context of its own, so that the programs are traced anew
        # under the smaller block and not found in the jit's cache
        monkeypatch.setattr(periodic, "QUERY_HEAD_ROWS", query_head_rows)
        assert periodic.query_block(HEADS, chunk) == 8
        cfg = dict(TOY, served_positions=112)
    eng = make_engine(fam, weights, cfg, prefill_chunk=chunk)
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(prompt, new)], TOY["vocab_size"], seed=prompt)
    check_against_reference(fam, cfg, weights, spy, reqs)
    s = eng.summary()
    for name, want in hand_counts(prompt, new).items():
        assert s[name] == want, name
    for pre, calls in (("", s["ticks"]), ("chunk_", s["prefill_chunks"])):
        assert s[pre + "moe_dropped"] == 0
        assert 0 < s[pre + "moe_assignments_held"] < s[
            pre + "moe_assignments_total"]
        # distinct held experts a call, summed over the layers and calls
        assert 0 < s[pre + "moe_experts_hit"] <= calls * LAYERS * 8
    eng.close()                                     # both pools' leak checks


def test_bf16_is_held_to_what_the_reference_rounds(fam, weights,
                                                   monkeypatch):
    """The program in bf16 (the configuration's compute type) against the
    float32 reference: within twice the distance of the reference's own
    bf16 pass from its float32 one."""
    cfg = dict(TOY, compute_dtype="bfloat16")
    eng = make_engine(fam, weights, cfg)
    spy = LogitSpy(eng, monkeypatch)
    (req,) = serve(eng, [(40, 16)], TOY["vocab_size"], seed=2)
    eng.close()
    seq = np.concatenate([req.prompt, np.asarray(req.new_tokens,
                                                 np.int32)])[:-1]
    got = np.stack([spy.logits[req.id][i] for i in range(len(seq))])
    fwd = jax.jit(lambda p, t, mode: fam.forward(cfg, p, t, mode),
                  static_argnums=2)
    ref, ref16 = (np.asarray(fwd(weights, seq[None], mode))[0]
                  for mode in ("f32", "bf16"))
    top = np.abs(ref).max()
    own = np.abs(ref16 - ref).max() / top
    assert TOL < own < 0.02
    assert np.abs(got - ref).max() / top < 2 * own


@pytest.fixture(scope="module")
def served(fam, weights):
    """One stream past the window through the engine: its tokens and
    every logit the programs computed, and the true reading's gap."""
    mp = pytest.MonkeyPatch()
    eng = make_engine(fam, weights)
    spy = LogitSpy(eng, mp)
    (req,) = serve(eng, [(40, 8)], TOY["vocab_size"], seed=1)
    eng.close()
    mp.undo()
    seq = np.concatenate([req.prompt, np.asarray(req.new_tokens,
                                                 np.int32)])[:-1]
    got = np.stack([spy.logits[req.id][i] for i in range(len(seq))])
    ref = np.asarray(jax.jit(lambda p, t: fam.forward(TOY, p, t, "f32"))(
        weights, seq[None]))[0]
    return seq, got, np.abs(got - ref).max() / np.abs(ref).max()


@pytest.mark.parametrize("fault", [
    "sequential_block", "shared_summed", "rope_in_full_layer",
    "rope_split_halves"])
def test_a_planted_fault_in_the_reference_is_caught(fam, weights, served,
                                                    fault):
    """Each reading of the model that the config rules out moves the
    logits past the tolerance the true reading is held to (a stream past
    the window)."""
    assert fault in fam.FAULTS
    seq, got, true_err = served
    ref = np.asarray(jax.jit(lambda p, t: fam.forward(
        TOY, p, t, "f32", fault=fault))(weights, seq[None]))[0]
    err = np.abs(got - ref).max() / np.abs(ref).max()
    assert true_err < TOL < err / 5, (true_err, err)


def test_the_eight_shares_sum_to_the_uncut_layer(fam, weights):
    """Expert parallelism over 8 chips, on one: the program's expert layer
    holding experts [2i, 2i + 2) of 16 on share i, the shared experts on
    share 0 alone, summed over the eight shares, plus the layer's
    attention and its stream, is the reference's layer with every expert
    held."""
    uncut = dict(TOY, num_experts=16, experts_held=[0, 16])
    w = jax.jit(lambda s: fam.make_weights(uncut, s))(
        reference.seed_u32(2 ** 31 + 8))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32).astype(
        jnp.bfloat16).astype(jnp.float32)
    layer = 1
    ref = fam._layer(uncut, "f32", w, layer, x, True, WIN)
    tree = fam.to_program_tree(w, uncut, {})
    moe = tree["params"]["h"]["block"][f"layer_{layer}"]["moe"]
    u = fam._layer_norm(x, w["ln_g"][layer], TOY["layer_norm_eps"])
    total = jnp.zeros_like(x)
    for i in range(8):
        cfg = llama_config(
            "test", embed_dim=64, router_experts=16, experts_held=(2 * i,
                                                                  2 * i + 2),
            experts_per_token=K, moe_dim=32,
            shared_experts=TOY["num_shared_experts"] if i == 0 else 0,
            dtype=jnp.float32)
        params = {"router": moe["router"][0],
                  "router_bias": moe["router_bias"][0],
                  **{name: moe[name][0, 2 * i:2 * i + 2]
                     for name in ("e_gate", "e_up", "e_down")}}
        if i == 0:
            params.update({name: moe[name][0]
                           for name in ("s_gate", "s_up", "s_down")})
        out, _ = DroplessMoE(cfg).apply({"params": params}, u[None])
        total = total + out[0]
    lp = {name: w[name][layer] for name in ("q_w", "kv_w", "o_w")}
    k, v = fam._keys_values(uncut, "f32", lp, u, True, WIN, fam._rope_gptj)
    attn = fam._attention(uncut, "f32", lp, u, 0, k, v, True, WIN,
                          fam._rope_gptj)
    got = x + attn + total
    assert np.abs(got - ref).max() / np.abs(ref).max() < 1e-5


def test_the_loaders_rope_permutation_is_the_interleaved_rotation(fam):
    """The program's split-halves rotation of a head whose columns the
    loader permuted is the published interleaved rotation of the head,
    permuted: bit for bit."""
    d, s = 16, 40
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(s, 3, d)), jnp.float32)
    order = fam.rope_permutation(d)
    pos = jnp.arange(s)
    want = fam._rope_gptj(x, pos, 50000.0)[..., order]
    from pytorchdistributed_tpu.models.transformer import rope_tables

    cos, sin = rope_tables(s, d, 50000.0)
    got = apply_rope(x[..., order][None], cos, sin)[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and a score of a query and a key is the same in either layout
    q, k = x[:, 0], x[:, 1]
    np.testing.assert_allclose(np.asarray(q[:, order] @ k[:, order].T),
                               np.asarray(q @ k.T), rtol=1e-5, atol=1e-5)


def test_the_query_block_is_128_rows_up_to_32_heads():
    for heads in (1, 4, 20, 28, 32):
        assert periodic.query_block(heads, 512) == 128
        assert periodic.query_block(heads, 1) == 1
    assert periodic.query_block(128, 512) == 32


def test_a_period_chunk_at_32_heads_or_fewer_traces_as_before(
        monkeypatch):
    """The gathered read of a chunk of SmallThinker's 28 heads traces to
    the same program as with blocks of `QUERY_BLOCK` rows alone (the rule
    before the block was sized by the heads)."""
    from tests.test_smallthinker_serving import TOY as ST

    fam = manifest.load_family(manifest.BENCH_DIR, "smallthinker")
    cfg = dataclasses.replace(
        fam.program_model(dict(ST, num_attention_heads=28,
                               num_key_value_heads=4), {}).cfg,
        decode=True, decode_slots=1, kv_block_size=BLOCK, kv_blocks=64,
        window_blocks=64)
    s, h, d = 256, 28, cfg.head_dim
    pages = cfg.max_seq_len // BLOCK
    args = (jnp.zeros((1, s, h, d)),
            jnp.zeros((cfg.pool_layers("cached_key"), 64, BLOCK, 4 * d)),
            jnp.zeros((cfg.pool_layers("cached_key"), 64, BLOCK, 4 * d)),
            0, jnp.zeros((1, pages), jnp.int32),
            jnp.arange(s, dtype=jnp.int32)[None])

    def traced(window):
        return str(jax.make_jaxpr(
            lambda q, kp, vp, table, pos: periodic._gathered_read(
                cfg, q, kp, vp, 0, table, pos, window))(
                    *args[:3], *args[4:]))

    now = [traced(w) for w in (0, 32)]
    monkeypatch.setattr(periodic, "query_block",
                        lambda heads, s: min(periodic.QUERY_BLOCK, s))
    assert [traced(w) for w in (0, 32)] == now


def test_a_chunk_cut_by_its_heads_reads_through_the_kernel(
        fam, weights, monkeypatch):
    """Where the heads cut a chunk's query block below `QUERY_BLOCK` rows
    (Command A+'s 128 heads; here 8 under a smaller `QUERY_HEAD_ROWS`),
    the engine's kernel path attends a chunk through
    `ops/pallas_attention.chunk_attention` (interpreted on the CPU): the
    logits of chunks past the window, then ticks, against the reference,
    and the device's counters against a hand count as the gathered read
    gives them."""
    monkeypatch.setattr(periodic, "QUERY_HEAD_ROWS", 64)
    calls = []
    real = periodic._gathered_read
    monkeypatch.setattr(periodic, "_gathered_read", lambda *a, **kw: (
        calls.append(kw.get("kernel", False)), real(*a, **kw))[1])
    cfg = dict(TOY, served_positions=96)
    eng = make_engine(fam, weights, cfg, prefill_chunk=32,
                      paged_attn="pallas")
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(70, 4)], TOY["vocab_size"], seed=70)
    # the chunk's layers (two periods of a full and a window layer each
    # kind, traced once) and nothing else read the gathered rows
    assert calls and all(calls)
    check_against_reference(fam, cfg, weights, spy, reqs)
    s = eng.summary()
    for name, want in hand_counts(70, 4).items():
        assert s[name] == want, name
    eng.close()


@pytest.mark.parametrize("window", [0, WIN])
@pytest.mark.parametrize("start", [0, 57])
def test_the_kernel_reads_what_the_gathered_read_does(window, start):
    """`_gathered_read` with the kernel against XLA's blocks of queries,
    on one pool of shuffled blocks, a chunk of 16 at `start`: the same
    outputs within the rounding of the probabilities to the values' type
    (bf16), and the same rows attended."""
    from types import SimpleNamespace

    bs, s, h, hk, d, pages = 4, 16, 8, 2, 16, 24
    cfg = SimpleNamespace(kv_block_size=bs, dtype=jnp.bfloat16, kv_heads=hk)
    ks = jax.random.split(jax.random.key(start + window), 3)
    nb = pages + 1
    k_pool, v_pool = (jax.random.normal(kk, (1, nb, bs, hk * d)).astype(
        jnp.bfloat16) for kk in ks[:2])
    q = jax.random.normal(ks[2], (1, s, h, d)).astype(jnp.bfloat16)
    table = jnp.asarray(np.random.default_rng(start).permutation(
        np.arange(1, nb))[None], jnp.int32)
    pos = start + jnp.arange(s, dtype=jnp.int32)[None]
    got, want = (jax.jit(lambda kernel=kernel: periodic._gathered_read(
        cfg, q, k_pool, v_pool, 0, table, pos, window, kernel=kernel))()
        for kernel in (True, False))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=1e-2 * float(jnp.abs(want[0]).max()))


def test_the_block_form_and_the_norm_without_a_bias(fam, weights):
    model = fam.program_model(TOY, {})
    assert model.cfg.block_form == "parallel"
    assert model.cfg.shared_experts == 2 and model.cfg.norm == "layernorm"
    tree = fam.to_program_tree(weights, TOY, {})["params"]
    # one norm a layer, a gain alone; the head is the embedding
    block = tree["h"]["block"]["layer_0"]
    assert sorted(block) == ["attn", "ln1", "moe"]
    assert sorted(block["ln1"]) == ["scale"] and sorted(tree["ln_f"]) == [
        "scale"]
    assert "lm_head" not in tree
    # the shared experts side by side, their average in the down
    # projection: exact, a power of two
    s_down = block["moe"]["s_down"][0]
    np.testing.assert_array_equal(
        np.asarray(s_down, np.float32),
        np.asarray(weights["s_down"][0], np.float32).reshape(64, 64) / 2)
    back = fam.from_program_tree({"params": tree}, TOY, {})
    assert sorted(back) == sorted(weights)
    for name, leaf in weights.items():
        assert back[name].dtype == leaf.dtype
        np.testing.assert_array_equal(np.asarray(back[name], np.float32),
                                      np.asarray(leaf, np.float32))


def test_the_config_refuses_what_the_block_forms_are_not():
    for bad, what in ((dict(block_form="fused"), "block_form"),
                      (dict(block_form="parallel", norm_position="post"),
                       "pre-norm"),
                      (dict(norm="layernorm", fused_norms=True),
                       "bias"),
                      (dict(shared_experts=-1), "shared_experts")):
        with pytest.raises(ValueError, match=what):
            llama_config("test", **bad)

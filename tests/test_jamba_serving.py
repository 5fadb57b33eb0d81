"""Mamba-1 mixers beside NoPE attention in one scanned stack
(`TransformerConfig.period` with ``"mamba"`` layers, models/ssm.py), a
recurrent state a slot beside the paged K/V pool, through the paged
engine at a toy size on the CPU, against the benchmark's plain reference
(benchmark/families/jamba.py) on seeded weights.

The program runs in float32 here, on the same bf16-rounded matrices as the
reference, so the two differ only in the order of their sums (chunks and
ticks through a carried state against one pass in blocks): logits agree
within 1e-5 of the largest logit (2e-7 read). bf16 arithmetic would not
(4e-3), a bf16 state moves them 1e-3, a state or a convolution window not
carried across a chunk's edge 3e-2 to 1e-1, the step, B and C left
un-normed 3e-2 to 1e-1: each fault planted in the program fails the
tolerance. A tick reads the attention pool through XLA's gathers or the
paged decode kernel, and a chunk its recurrence through `lax.scan` or the
`ssm_scan` kernel (both kernels interpreted here); all four are held to
the reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, reference
from pytorchdistributed_tpu.models import periodic, ssm
from pytorchdistributed_tpu.ops import ssm_scan
from pytorchdistributed_tpu.serving import ServingEngine
from pytorchdistributed_tpu.serving import engine as engine_mod

TOL = 1e-5
TOY = {
    "model_type": "jamba", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 1, "num_hidden_layers": 8,
    "attn_layer_period": 4, "attn_layer_offset": 2, "mamba_expand": 2,
    "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_dt_rank": 8,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "num_experts": 1,
    "intermediate_size": 96, "vocab_size": 96, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "max_position_embeddings": 128,
    "served_positions": 128, "param_dtype": "bfloat16",
    "compute_dtype": "float32", "initializer_range": 0.02,
}
MAMBA_LAYERS = 6


@pytest.fixture(scope="module")
def fam():
    return manifest.load_family(manifest.BENCH_DIR, "jamba")


@pytest.fixture(scope="module")
def weights(fam):
    return jax.jit(lambda s: fam.make_weights(TOY, s))(
        reference.seed_u32(2 ** 31 + 40))


def make_engine(fam, w, cfg=TOY, **kw):
    kw = {"num_slots": 3, "block_size": 16, "prefill_chunk": 16,
          "prefix_cache": False, **kw}
    return ServingEngine(fam.program_model(cfg, {}),
                         fam.to_program_tree(w, cfg, {}), **kw)


def traced_anew(n):
    """The toy with a longer table: a configuration of its own, so that
    the engine's programs are traced anew under what a test patched and
    not found in the jit's cache."""
    return dict(TOY, served_positions=TOY["served_positions"] + 16 * n)


class LogitSpy:
    """Every logit the engine's two programs compute, by request and
    position: before each call of `paged_prefill_chunk` or
    `paged_decode_tick` it runs the program's own model part on the same
    operands (a chunk's with its true length and slot, whose state row
    the chunk reads)."""

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
        logits, _ = self._chunk(model, *args[:5], args[5], args[11])
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


def errors(fam, w, spy, reqs, fault=None, cfg=TOY):
    """Each request's largest logit gap to the reference's full pass over
    its prompt and served tokens, over the largest logit."""
    fwd = jax.jit(lambda p, t: fam.forward(cfg, p, t, "f32"))
    out = []
    for r in reqs:
        assert r.finish_reason == "length"
        seq = np.concatenate([r.prompt, np.asarray(r.new_tokens,
                                                   np.int32)])[:-1]
        ref = np.asarray(fwd(w, jnp.asarray(seq[None])))[0]
        got = spy.logits[r.id]
        assert sorted(got) == list(range(len(seq)))  # every position
        got = np.stack([got[i] for i in range(len(seq))])
        out.append(np.abs(got - ref).max() / np.abs(ref).max())
        if fault is None:
            # the served tokens are the reference's first choice
            n = len(r.prompt)
            assert (ref[n - 1:].argmax(-1)
                    == np.asarray(r.new_tokens)).all()
    return out


def serve(eng, prompts_and_lengths, vocab, seed=0, steps_between=0):
    """Submit the requests, `steps_between` engine steps apart, and run
    the engine until every one is done."""
    rng = np.random.default_rng(seed)
    reqs = []
    for n, m in prompts_and_lengths:
        reqs.append(eng.submit(rng.integers(0, vocab, n).astype(np.int32),
                               max_new_tokens=m))
        for _ in range(steps_between):
            eng.step()
    eng.run_until_idle()
    return reqs


@pytest.mark.parametrize("prompt,new,chunk", [
    (5, 20, 16),      # one short chunk, then ticks
    (37, 20, 16),     # three chunks, the last one of 5: no chunk edge
                      # falls on a multiple of the window's 3
    (70, 30, 32),     # three chunks of 32, the last of 6
    (45, 12, 48),     # a chunk of 48, then one of 0 real tokens past 45
])
def test_prefill_then_decode_matches_reference_logits(fam, weights, prompt,
                                                      new, chunk,
                                                      monkeypatch):
    """Chunks through the carried state and window, then ticks: every
    logit against the reference's full pass, and the states the ticks
    read and wrote as the device counted them."""
    eng = make_engine(fam, weights, prefill_chunk=chunk)
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(prompt, new)], TOY["vocab_size"], seed=prompt)
    assert max(errors(fam, weights, spy, reqs)) < TOL
    s = eng.summary()
    ticks = new - 1                          # the first token is a chunk's
    assert s["ssm_states_read"] == s["ssm_states_written"] == (
        MAMBA_LAYERS * ticks)
    assert s["ssm_scan_positions"] == MAMBA_LAYERS * ticks
    assert s["attn_full_rows"] == 2 * sum(
        n + 1 for n in range(prompt, prompt + ticks))
    eng.close()


def test_streams_admitted_into_reused_slots_keep_no_trace(fam, weights,
                                                          monkeypatch):
    """Two slots, five streams admitted at different steps while others
    tick: every slot is taken again after a stream of another length
    left it, and every stream reads the reference's logits (a state or a
    window left from the slot's last stream would move them)."""
    eng = make_engine(fam, weights, num_slots=2)
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(40, 10), (7, 25), (60, 6), (23, 14), (5, 9)],
                 TOY["vocab_size"], seed=11, steps_between=3)
    assert max(errors(fam, weights, spy, reqs)) < TOL
    assert eng.summary()["prefills"] == 5
    eng.close()


def test_a_preempted_stream_resumes_with_the_same_logits(fam, weights,
                                                         monkeypatch):
    """A pool too small for both streams to grow: the younger is
    preempted (its blocks and its state dropped), resumes by a prefill of
    its prompt and what it served, and reads the reference's logits at
    every position, those computed again included."""
    eng = make_engine(fam, weights, num_slots=2, num_blocks=12)
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(40, 60), (38, 60)], TOY["vocab_size"], seed=4,
                 steps_between=1)
    assert eng.summary()["preemptions"] > 0
    assert sum(r.preemptions for r in reqs) > 0
    assert max(errors(fam, weights, spy, reqs)) < TOL
    eng.close()


@pytest.mark.parametrize("attn,scan,n", [("pallas", False, 0),
                                         ("gather", True, 1),
                                         ("pallas", True, 2)])
def test_the_kernel_reads_agree_with_the_gathered_ones(fam, weights, attn,
                                                       scan, n, monkeypatch):
    """The paged decode kernel reading the attention pool in a tick, and
    the `ssm_scan` kernel a chunk's recurrence (both interpreted here):
    the same logits as XLA's reads, within the tolerance all are held to
    the reference by, and the same counts."""
    runs = {}
    for read in (("gather", False), (attn, scan)):
        cfg = TOY
        if read[1]:
            monkeypatch.setattr(ssm_scan, "selective_scan", functools.partial(
                ssm_scan.kernel_scan, interpret=True))
            cfg = traced_anew(n)
        eng = make_engine(fam, weights, cfg, paged_attn=read[0])
        assert eng.summary()["paged_attn"] == read[0]
        spy = LogitSpy(eng, monkeypatch)
        reqs = serve(eng, [(70, 12), (20, 30)], TOY["vocab_size"], seed=9)
        assert max(errors(fam, weights, spy, reqs)) < TOL
        s = eng.summary()
        runs[read] = (spy.logits, [r.id for r in reqs],
                      s["attn_full_rows"], s["ssm_states_written"])
        eng.close()
        monkeypatch.undo()
    (la, ia, *ca), (lb, ib, *cb) = runs.values()
    assert ca == cb
    for a, b in zip(ia, ib):
        top = max(np.abs(v).max() for v in la[a].values())
        for pos in la[a]:
            assert np.abs(la[a][pos] - lb[b][pos]).max() < TOL * top


def forget(leaf):
    """A program that does not carry `leaf` across a chunk's edge: the
    chunk program handed the slot's row zeroed past the first chunk."""
    real = engine_mod.paged_prefill_chunk

    def chunk(model, weights, cache, chunk, start, *args, **kw):
        slot = args[7]
        if int(start) > 0:
            cache = jax.tree_util.tree_map_with_path(
                lambda p, x: (x.at[:, slot].set(0)
                              if getattr(p[-1], "key", None) == leaf
                              else x), cache)
        return real(model, weights, cache, chunk, start, *args, **kw)

    return chunk


def plant(fault, monkeypatch):
    """`fault` planted in the program: a bfloat16 state (rounded at every
    position of a chunk and every tick) and the step, B and C left
    un-normed are traced into the programs (the caller gives them a
    configuration of their own), a carry lost is the chunk program handed
    the slot's row zeroed."""
    if fault == "bf16_state":
        monkeypatch.setattr(ssm_scan, "STATE_DTYPE", jnp.bfloat16)
    elif fault == "no_dt_bc_norm":
        monkeypatch.setattr(ssm, "_rms", lambda x, g, eps: x)
    else:
        leaf = {"state_not_carried": "cached_ssm_state",
                "conv_not_carried": "cached_conv_state"}[fault]
        monkeypatch.setattr(engine_mod, "paged_prefill_chunk", forget(leaf))


@pytest.mark.parametrize("n,fault", enumerate(
    ["bf16_state", "state_not_carried", "conv_not_carried",
     "no_dt_bc_norm"], start=3))
def test_a_fault_planted_in_the_program_fails_the_tolerance(
        fam, weights, n, fault, monkeypatch):
    """Each reading the config rules out, or a state a served stream
    loses, moves the logits past the tolerance the program is held to
    (streams of several chunks, so that a carry can be lost)."""
    assert fault in fam.FAULTS
    plant(fault, monkeypatch)
    eng = make_engine(fam, weights, traced_anew(n))
    spy = LogitSpy(eng, monkeypatch)
    reqs = serve(eng, [(70, 20), (45, 20)], TOY["vocab_size"], seed=2)
    eng.close()
    assert min(errors(fam, weights, spy, reqs, fault)) > 10 * TOL


@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": True}, "radix prefix cache"),
    ({"spec_k": 2}, "speculative tick"),
    ({"session_store": object()}, "session store"),
    ({"kv_dtype": "int8"}, "int8 pool"),
    ({"block_size": 0}, "paged engine only"),
])
def test_what_a_recurrent_state_cannot_use_is_refused_at_construction(
        fam, weights, kw, what):
    with pytest.raises(ValueError, match=what):
        make_engine(fam, weights, **kw)
    if what != "paged engine only":
        with pytest.raises(ValueError, match="cannot be rebuilt from kept"):
            make_engine(fam, weights, **kw)


def test_streams_and_blocks_are_refused_at_the_call(fam, weights):
    eng = make_engine(fam, weights)
    prompt = np.arange(20, dtype=np.int32)
    for kw in ({"prefill_only": True}, {"session_id": "s1"}):
        with pytest.raises(ValueError, match="recurrent state"):
            eng.submit(prompt, max_new_tokens=4, **kw)
    req = eng.submit(prompt, max_new_tokens=4)
    eng.step()
    for call in (eng.export_kv_blocks, eng.detach_request):
        with pytest.raises(ValueError, match="cannot be rebuilt"):
            call(req)
    for call in (eng.export_prefix_blocks, eng.export_session):
        with pytest.raises(ValueError, match="recurrent state"):
            call("s1" if call == eng.export_session else prompt)
    eng.run_until_idle()
    eng.close()


def test_the_config_refuses_what_a_period_of_mixers_is_not_built_for():
    from pytorchdistributed_tpu.models.llama import llama_config

    ok = dict(period=("mamba", (False, 0)), num_layers=4, ssm_inner=128,
              ssm_dt_rank=8, ssm_state=4)
    llama_config("test", **ok)
    for bad, what in (
            (dict(ok, period=(("mamba", 32), (False, 0))), "no window"),
            (dict(ok, period=("mamba", "mamba")), "every position"),
            (dict(ok, ssm_inner=0), "ssm_inner"),
            (dict(ok, ssm_conv=1), "two taps"),
            (dict(ok, decode=True, decode_slots=2, kv_block_size=16,
                  kv_blocks=8, kv_dtype="int8"), "recurrent state"),
            (dict(ok, decode=True, decode_slots=2), "paged engine only"),
            (dict(ok, scan_layers=False), "scanned stack")):
        with pytest.raises(ValueError, match=what):
            llama_config("test", **bad)


def test_the_model_and_its_tree(fam, weights):
    tree = fam.to_program_tree(weights, TOY, {})
    block = tree["params"]["h"]["block"]
    assert sorted(block) == ["layer_0", "layer_1", "layer_2", "layer_3"]
    assert "attn" in block["layer_2"] and "mamba" in block["layer_0"]
    assert "lm_head" not in tree["params"]          # the head is tied
    # layer 5 is layer_1 of the second period, mamba layer 4 of 6
    np.testing.assert_array_equal(
        np.asarray(block["layer_1"]["mamba"]["dt_bias"][1]),
        np.asarray(weights["dt_b"][4]))
    back = fam.from_program_tree(tree, TOY, {})
    assert sorted(back) == sorted(weights)
    for name, leaf in weights.items():
        assert back[name].dtype == leaf.dtype
        np.testing.assert_array_equal(np.asarray(back[name], np.float32),
                                      np.asarray(leaf, np.float32))
    model = fam.program_model(TOY, {})
    cfg = model.cfg
    assert cfg.period == ("mamba", "mamba", (False, 0), "mamba")
    assert cfg.head_dim == 16 and cfg.kv_heads == 1 and cfg.tie_embeddings
    assert model.counters == periodic.COUNTERS + ssm.COUNTERS
    assert [(k.kind, k.table) for k in cfg.cache_kinds] == [
        (None, "block_table"), ("state", None)]
    eng = make_engine(fam, weights, num_slots=3)
    cache = eng._cache["h"]
    # the state a slot: [mamba layers, slots, N, D] float32 and the
    # window [mamba layers, slots, (K - 1) D]; K/V rows of the 2
    # attention layers
    assert cache["cached_ssm_state"].shape == (6, 3, 4, 128)
    assert cache["cached_ssm_state"].dtype == jnp.float32
    assert cache["cached_conv_state"].shape == (6, 3, 3 * 128)
    assert cache["cached_key"].shape[0] == 2
    assert eng.kv_hbm_bytes == sum(
        int(np.prod(cache[n].shape)) * cache[n].dtype.itemsize
        for n in ("cached_key", "cached_value", "cached_ssm_state",
                  "cached_conv_state"))
    eng.close()


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_planes_serve_what_the_checkpoint_layout_serves(fam, weights,
                                                        compute, monkeypatch):
    """The attention layers' fused k/v and every layer's gate/up, held by
    the engine as planes (serving/weights.py:served): tokens and every
    logit of every chunk and tick bitwise what the checkpoint's layout
    serves, with the program in float32 and in bfloat16."""
    from tests.test_serving_weights import served_both_ways

    cfg = dict(TOY, compute_dtype=compute)
    served_both_ways(lambda: make_engine(fam, weights, cfg),
                     [(37, 8), (5, 6)], TOY["vocab_size"], LogitSpy,
                     monkeypatch)

"""Continuous-batching serving engine tests (serving/).

Correctness bar (the ISSUE 3 acceptance): for ANY admission order, greedy
per-request outputs from the slot engine must be BITWISE-equal to
inference.generate()'s — one assertion that covers per-slot cache
indexing, position-counter rewinds after padded prefill, per-row RoPE /
learned-position offsets, GQA slot layout, the per-row attention mask and
the rank-mask sampler's greedy path all at once. On top: retirement /
readmission stress (more requests than slots), seeded-sampling
determinism across admission orders, the zero-recompile steady-state
guarantee, streaming delivery, and the telemetry bridge's file contract.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from pytorchdistributed_tpu.inference import generate
from pytorchdistributed_tpu.models import (
    GPT2,
    Llama,
    gpt2_config,
    llama_config,
)
from pytorchdistributed_tpu.serving import (
    SamplingParams,
    ServingEngine,
)
from pytorchdistributed_tpu.serving import engine as serving_engine
from pytorchdistributed_tpu.serving.engine import (
    decode_tick,
    prefill_into_slot,
)


def _init(model, seed=1):
    return model.init(jax.random.key(seed), jnp.zeros((1, 4), jnp.int32))


def _mixed_requests(vocab, seed=0, n=5):
    rng = np.random.default_rng(seed)
    lens = [5, 9, 3, 13, 7, 11, 4, 8, 6][:n]
    news = [6, 3, 8, 5, 4, 7, 2, 5, 3][:n]
    prompts = [rng.integers(0, vocab, (m,)).astype(np.int32) for m in lens]
    return prompts, news


def _assert_parity(model_cls, cfg, *, num_slots, n_requests,
                   mesh=None, params=None, ref_params=None):
    """Engine outputs (staggered admissions, mixed lengths/budgets) must
    equal generate() per request, bitwise."""
    model = model_cls(cfg)
    params = params if params is not None else _init(model)
    ref_params = ref_params if ref_params is not None else params
    dm = model_cls(dataclasses.replace(cfg, decode=True))
    prompts, news = _mixed_requests(cfg.vocab_size, n=n_requests)
    engine = ServingEngine(model, params, num_slots=num_slots,
                           prefill_bucket=16, mesh=mesh)
    engine.warmup(prompt_lens=(8, 16))
    reqs = []
    for p, n in zip(prompts, news):
        reqs.append(engine.submit(p, max_new_tokens=n))
        engine.step()  # staggered: arrivals interleave with decoding
    engine.run_until_idle()
    for p, n, r in zip(prompts, news, reqs):
        ref = generate(dm, ref_params, jnp.asarray(p)[None],
                       max_new_tokens=n)
        np.testing.assert_array_equal(r.output_ids, np.asarray(ref)[0],
                                      err_msg=f"request {r.id}")


def test_parity_greedy_gpt2():
    """Learned-position offsets + slot cache layout (quick-tier pick)."""
    _assert_parity(GPT2, gpt2_config("test", num_layers=2, max_seq_len=64),
                   num_slots=3, n_requests=5)


def test_parity_greedy_llama():
    """Per-row RoPE offsets + GQA slot cache layout."""
    _assert_parity(Llama, llama_config("test", max_seq_len=64),
                   num_slots=3, n_requests=5)


def test_parity_greedy_unrolled_layers():
    """scan_layers=False: per-layer (unstacked) cache leaves merge the
    same way."""
    _assert_parity(GPT2, gpt2_config("test", num_layers=2, max_seq_len=64,
                                     scan_layers=False),
                   num_slots=2, n_requests=4)


def test_parity_on_dp_mesh():
    """Engine under a data mesh: replicated params, same tokens."""
    from pytorchdistributed_tpu.runtime.mesh import create_mesh

    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    _assert_parity(GPT2, cfg, num_slots=3, n_requests=4,
                   mesh=create_mesh(data=8))


def test_parity_on_tp_mesh():
    """Sharding is a deployment choice, not a code path (the serving
    restatement of test_generate_with_tensor_sharded_params): the engine
    with Megatron tensor-sharded params on a dp x tp mesh must emit
    exactly the tokens the unsharded engine/generate() emit."""
    import optax

    from pytorchdistributed_tpu.runtime.mesh import Axis, create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    cfg = llama_config("test", max_seq_len=64)
    model = Llama(cfg)
    params = _init(model)
    tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                 mesh=create_mesh(data=2, tensor=4), strategy="tp")
    big = np.tile(np.arange(8, dtype=np.int32)[None] % cfg.vocab_size,
                  (8, 1))
    tr.init({"tokens": big, "targets": big})
    shardings = jax.tree.map(lambda a: a.sharding, tr.state.params)
    sharded = jax.device_put(params, shardings)
    assert any(Axis.TENSOR in (e if isinstance(e, tuple) else (e,))
               for leaf in jax.tree.leaves(shardings)
               for e in tuple(leaf.spec))
    _assert_parity(Llama, cfg, num_slots=2, n_requests=3, mesh=tr.mesh,
                   params=sharded, ref_params=params)


def test_parity_paged_on_dp_mesh():
    """Sharding composes with paging (ISSUE 7): the PAGED engine under a
    data mesh — replicated params, host-stamped block tables entering
    the compiled tick as dynamic args — emits exactly the dense
    engine's / generate()'s tokens. (The single-host paged parity
    ladder lives in tests/test_paging.py, quick tier.)"""
    from pytorchdistributed_tpu.runtime.mesh import create_mesh

    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    params = _init(model)
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    prompts, news = _mixed_requests(cfg.vocab_size, n=4)
    engine = ServingEngine(model, params, num_slots=3, prefill_bucket=16,
                           block_size=8, mesh=create_mesh(data=8))
    engine.warmup(prompt_lens=(8, 16))
    reqs = []
    for p, n in zip(prompts, news):
        reqs.append(engine.submit(p, max_new_tokens=n))
        engine.step()
    engine.run_until_idle()
    for p, n, r in zip(prompts, news, reqs):
        ref = generate(dm, params, jnp.asarray(p)[None], max_new_tokens=n)
        np.testing.assert_array_equal(r.output_ids, np.asarray(ref)[0],
                                      err_msg=f"request {r.id}")
    engine.close()


def test_retirement_readmission_stress():
    """More requests than slots: every slot retires and readmits several
    times (fresh prefill must fully overwrite the previous tenant's rows
    and rewind its counters), outputs still bitwise-equal per request."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    _assert_parity(GPT2, cfg, num_slots=2, n_requests=9)


def test_seeded_sampling_determinism():
    """Per-request sampled outputs are a function of (prompt, sampling
    params, seed) alone: resubmitting the same requests in a DIFFERENT
    order (different slots, different neighbors) reproduces each
    request's tokens exactly; a different seed moves them."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    params = _init(model)
    prompts, news = _mixed_requests(cfg.vocab_size, n=4)
    sampling = [SamplingParams(temperature=0.8, top_k=10, seed=100 + i)
                for i in range(4)]

    def run(order):
        engine = ServingEngine(model, params, num_slots=2,
                               prefill_bucket=16)
        engine.warmup(prompt_lens=(16,))
        reqs = {}
        for i in order:
            reqs[i] = engine.submit(prompts[i], max_new_tokens=news[i],
                                    sampling=sampling[i])
            engine.step()
        engine.run_until_idle()
        return {i: list(r.new_tokens) for i, r in reqs.items()}

    a = run([0, 1, 2, 3])
    b = run([3, 1, 0, 2])
    assert a == b
    # a different seed must change the sampled continuation
    engine = ServingEngine(model, params, num_slots=2, prefill_bucket=16)
    engine.warmup(prompt_lens=(16,))
    r = engine.submit(prompts[0], max_new_tokens=news[0],
                      sampling=dataclasses.replace(sampling[0], seed=999))
    engine.run_until_idle()
    assert list(r.new_tokens) != a[0]


def test_zero_recompiles_steady_state():
    """The acceptance guarantee: after warmup, a mixed serving load (any
    prompt length within the bucket set, any sampling mix, retire +
    readmit) triggers ZERO retraces AND zero recompiles — TRACE_COUNTS
    catches retraces, the pjit _cache_size catches sharding-driven
    recompiles that never rerun the python body."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    engine = ServingEngine(model, _init(model), num_slots=3,
                           prefill_bucket=16)
    engine.warmup(prompt_lens=(8, 16))
    traces = dict(serving_engine.TRACE_COUNTS)
    sizes = (prefill_into_slot._cache_size(), decode_tick._cache_size())
    rng = np.random.default_rng(3)
    for i in range(8):
        sampling = (SamplingParams() if i % 2 else
                    SamplingParams(temperature=0.7, top_k=5, top_p=0.9,
                                   seed=i))
        engine.submit(rng.integers(0, cfg.vocab_size,
                                   (int(rng.integers(1, 16)),)),
                      max_new_tokens=int(rng.integers(1, 6)),
                      sampling=sampling)
        engine.step()
    engine.run_until_idle()
    assert dict(serving_engine.TRACE_COUNTS) == traces
    assert (prefill_into_slot._cache_size(),
            decode_tick._cache_size()) == sizes


def test_stop_ids_retire_and_stream():
    """A request retires the moment it emits ANY of its stop ids
    (finish_reason "stop", budget unused); streaming sees tokens in
    emission order, via callback and iterator alike."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    params = _init(model)
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    ref = np.asarray(generate(dm, params, jnp.asarray(prompt)[None],
                              max_new_tokens=8))[0, 6:]
    stop = int(ref[3])  # the 4th greedy token doubles as a stop id

    engine = ServingEngine(model, params, num_slots=2, prefill_bucket=16)
    engine.warmup(prompt_lens=(16,))
    seen = []
    r = engine.submit(prompt, max_new_tokens=8, stop_ids=(stop, 10 ** 6),
                      on_token=lambda req, t: seen.append(t))
    engine.run_until_idle()
    assert r.finish_reason == "stop"
    # truncated at the FIRST emission of the stop id (which may precede
    # the position it was sampled from)
    cut = int(np.argmax(ref == stop)) + 1
    np.testing.assert_array_equal(r.new_tokens, ref[:cut])
    assert seen == r.new_tokens
    # iterator streaming drives the engine itself
    r2 = engine.submit(prompt, max_new_tokens=5)
    assert list(engine.stream(r2)) == r2.new_tokens
    assert r2.done and r2.finish_reason == "length"
    assert len(r2.new_tokens) == 5


def test_submit_validations():
    cfg = gpt2_config("test", num_layers=2, max_seq_len=32)
    model = GPT2(cfg)
    engine = ServingEngine(model, _init(model), num_slots=1)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(np.zeros(4, np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="max_seq_len"):
        engine.submit(np.zeros(30, np.int32), max_new_tokens=10)
    with pytest.raises(ValueError, match="prompt"):
        engine.submit(np.zeros(0, np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="num_slots"):
        ServingEngine(model, _init(model), num_slots=0)


def test_telemetry_bridge_files(tmp_path):
    """The telemetry bridge writes the serving metric JSONL (tick +
    request rows with TTFT / occupancy / queue depth) and dumps the span
    trace under the shared spans_rank*.trace.json contract on close."""
    from pytorchdistributed_tpu.serving.telemetry import SERVE_METRICS_FILE
    from pytorchdistributed_tpu.telemetry.spans import SPAN_TRACE_FILE

    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    engine = ServingEngine(model, _init(model), num_slots=2,
                           prefill_bucket=16,
                           telemetry_dir=str(tmp_path))
    engine.warmup(prompt_lens=(16,))
    rng = np.random.default_rng(0)
    for _ in range(3):
        engine.submit(rng.integers(0, cfg.vocab_size, (5,)),
                      max_new_tokens=4)
    engine.run_until_idle()
    engine.close()

    metrics_path = tmp_path / SERVE_METRICS_FILE.format(rank=0)
    rows = [json.loads(x) for x in
            metrics_path.read_text().strip().splitlines()]
    kinds = {r["kind"] for r in rows}
    assert kinds == {"tick", "request"}
    reqs = [r for r in rows if r["kind"] == "request"]
    assert len(reqs) >= 3  # warmup requests logged too
    done = [r for r in reqs if r["new_tokens"] == 4]
    assert len(done) == 3
    assert all(r["ttft_ms"] > 0 for r in done)
    ticks = [r for r in rows if r["kind"] == "tick"]
    assert all(0 <= r["slot_occupancy"] <= 1 for r in ticks)
    assert all("queued" in r and "tick_ms" in r for r in ticks)

    trace = json.loads(
        (tmp_path / SPAN_TRACE_FILE.format(rank=0)).read_text())
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"serve/engine_step", "serve/admit", "serve/prefill",
            "serve/prefill_sync", "serve/decode_tick",
            "serve/tick_dispatch", "serve/tick_sync",
            "serve/deliver"} <= names


def test_quantized_engine_matches_quantized_generate():
    """--quant int8_fwd composes: the engine's tick/prefill run the same
    quantized contractions generate() does, so greedy parity holds under
    the int8 policy too (the int8 HLO census is pinned separately in
    test_compiled_invariants)."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64,
                      quant="int8_fwd")
    _assert_parity(GPT2, cfg, num_slots=2, n_requests=3)


def test_deadline_expires_without_disturbing_other_slots(tmp_path):
    """ISSUE 4 satellite: per-request deadline_s. A request dead on the
    queue is shed before wasting a prefill; one expiring mid-decode is
    retired with the distinct "deadline" finish reason, both leave
    telemetry rows, and every OTHER slot keeps serving bitwise-correct
    tokens throughout."""
    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    params = _init(model)
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    engine = ServingEngine(model, params, num_slots=2, prefill_bucket=16,
                           telemetry_dir=tmp_path)
    engine.warmup(prompt_lens=(8,))
    rng = np.random.default_rng(3)
    pa = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
    pb = rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)
    pc = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    a = engine.submit(pa, max_new_tokens=6)
    b = engine.submit(pb, max_new_tokens=40, deadline_s=60.0)
    c = engine.submit(pc, max_new_tokens=4, deadline_s=0.0)  # dead on queue
    stats = engine.step()  # c shed pre-admission; a + b admitted
    assert stats["expired"] == 1
    assert c.done and c.finish_reason == "deadline" and not c.new_tokens
    assert c.slot is None  # never admitted, no prefill paid
    assert b.slot is not None and len(b.new_tokens) >= 1
    # lapse b's budget deterministically (no wall-clock sleep, no flake
    # under CI load): rewind its submission clock past the deadline
    b.submit_time -= 120.0
    engine.run_until_idle()
    assert b.done and b.finish_reason == "deadline"
    assert 0 < len(b.new_tokens) < 40  # delivered tokens stay delivered
    # the co-resident request was never disturbed: full budget, greedy
    # tokens bitwise-equal to generate()
    assert a.done and a.finish_reason == "length" and len(a.new_tokens) == 6
    ref = generate(dm, params, jnp.asarray(pa)[None], max_new_tokens=6)
    np.testing.assert_array_equal(a.output_ids, np.asarray(ref)[0])
    assert engine.summary()["deadline_expired"] == 2
    # the engine keeps admitting after expiries (slots were freed)
    d = engine.submit(pa, max_new_tokens=3)
    engine.run_until_idle()
    assert d.done and d.finish_reason == "length"
    engine.close()
    rows = [json.loads(x) for x in
            (tmp_path / "serve_metrics_rank0.jsonl")
            .read_text().strip().splitlines()]
    reasons = [r["finish_reason"] for r in rows if r["kind"] == "request"]
    assert reasons.count("deadline") == 2, reasons

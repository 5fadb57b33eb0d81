"""Prefill/decode disaggregation (ISSUE 12: KV block streaming + the
fleet-wide radix prefix cache).

Correctness bar, inherited from the paged engine and the router chaos
suite: a stream that prefills on one engine and decodes on another —
through ``export_kv_blocks``/``import_kv_blocks`` in-process, or over
the subprocess wire — must be BITWISE-identical (greedy AND seeded) to
the same request served colocated, because the payload carries the
exact K/V of [0, true_len) plus the per-token fold_in count. On top:
the FleetPrefixIndex/radix local-remote split units, the wire codec
round-trip, import validation walls, lossless failover when either
role dies mid-handoff, deterministic fleet prefix steering + block
shipping, and the zero-recompile guarantee across a steady-state
handoff.

Engine geometry mirrors tests/test_router.py (gpt2 "test", 2 layers,
max_seq_len 64, slots 3, bucket 16, paged block 8) so the compiled
programs are shared across the suite's jit cache.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from pytorchdistributed_tpu.faults.inject import (
    FaultInjector,
    FaultPlan,
)
from pytorchdistributed_tpu.inference import generate
from pytorchdistributed_tpu.models import GPT2, gpt2_config
from pytorchdistributed_tpu.serving import (
    ROLE_DECODE,
    ROLE_PREFILL,
    BlockAllocator,
    FleetPrefixIndex,
    KVBlockPayload,
    RadixPrefixCache,
    ReplicaRouter,
    SamplingParams,
    ServingEngine,
    block_hashes,
    kv_payload_from_wire,
    kv_payload_to_wire,
)
from pytorchdistributed_tpu.serving import engine as serving_engine
from pytorchdistributed_tpu.serving.engine import (
    kv_block_gather,
    kv_block_scatter,
    paged_decode_tick,
    paged_prefill_chunk,
)

CFG = gpt2_config("test", num_layers=2, max_seq_len=64)


@functools.cache
def _setup():
    model = GPT2(CFG)
    params = model.init(jax.random.key(1), jnp.zeros((1, 4), jnp.int32))
    dm = GPT2(dataclasses.replace(CFG, decode=True))
    return model, params, dm


def _ref(prompt, n):
    _, params, dm = _setup()
    return np.asarray(generate(dm, params, jnp.asarray(prompt)[None],
                               max_new_tokens=n))[0]


def _engine(**kw):
    model, params, _ = _setup()
    ek = dict(num_slots=3, prefill_bucket=16, block_size=8)
    ek.update(kw)
    engine = ServingEngine(model, params, **ek)
    engine.warmup(prompt_lens=(16, 32))
    engine.warmup_kv_stream()
    return engine


def _router(roles, *, faults=None, **kw):
    model, params, _ = _setup()
    router = ReplicaRouter(
        model, params, replicas=len(roles), roles=roles,
        engine_kwargs=dict(num_slots=3, prefill_bucket=16, block_size=8),
        warmup_lens=(16, 32), faults=faults, **kw)
    router.warmup()
    return router


# ----------------------------------------------------------------------
# host units (no jax work)


def test_fleet_prefix_index_units():
    idx = FleetPrefixIndex()
    chain = ["a", "ab", "abc", "abcd"]
    assert idx.best_match(chain) == (None, 0)
    idx.update(0, ["a", "ab"])
    idx.update(1, ["a", "ab", "abc"])
    assert idx.match_depth(0, chain) == 2
    assert idx.match_depth(1, chain) == 3
    assert idx.match_depth(2, chain) == 0
    assert idx.best_match(chain) == (1, 3)
    # eligibility restricts candidates (quarantined/dead replicas)
    assert idx.best_match(chain, eligible={0}) == (0, 2)
    assert idx.best_match(chain, eligible=set()) == (None, 0)
    # chained digests: membership is prefix-positional, a hole ends it
    idx.update(2, ["abc"])  # holds block 3's digest but not 1/2
    assert idx.match_depth(2, chain) == 0
    # ties break to the lowest index (deterministic steering)
    idx.update(3, ["a", "ab", "abc"])
    assert idx.best_match(chain) == (1, 3)
    # optimistic add extends; the next snapshot REPLACES (evictions and
    # frontier churn age out, nothing accumulates forever)
    idx.add(0, ["abc", "abcd"])
    assert idx.match_depth(0, chain) == 4
    idx.update(0, ["a"])
    assert idx.match_depth(0, chain) == 1
    idx.remove(1)
    assert idx.best_match(chain) == (3, 3)
    assert idx.replicas() == [0, 2, 3]


def test_radix_remote_split_and_frontier():
    """Fleet-shipped (remote) prefix blocks count as STEERED hits,
    split out of the local hit_rate; frontier() publishes the chained
    digests best_match consumes."""
    alloc = BlockAllocator(16, 4)
    cache = RadixPrefixCache(alloc)
    toks = np.arange(12, dtype=np.int32)
    blocks = alloc.alloc(3)
    assert cache.insert(toks, blocks, remote=True) == 3
    # the published frontier IS the block_hashes chain of the insert
    assert set(cache.frontier()) == set(block_hashes(toks, 4))
    assert cache.match(toks) == blocks
    remote = sum(1 for n in cache.match_nodes(toks) if n.remote)
    assert remote == 3
    cache.record_admission(3, 12, remote_blocks=3)
    st = cache.stats()
    assert st["hits"] == 0 and st["hit_tokens"] == 0
    assert st["remote_hits"] == 1 and st["remote_hit_tokens"] == 12
    assert st["remote_token_hit_rate"] == 1.0
    # a later LOCAL admission through the same nodes counts locally
    cache.record_admission(2, 12)
    st = cache.stats()
    assert st["hits"] == 1 and st["hit_tokens"] == 8
    assert st["remote_hits"] == 1


def test_kv_payload_wire_roundtrip():
    """The subprocess handoff codec is lossless for every field —
    including non-native dtypes (bf16 pools) via the ml_dtypes name
    path — so a wire hop cannot perturb the bitwise guarantee."""
    import ml_dtypes

    rng = np.random.default_rng(0)
    leaves = [
        ("layer/cached_key", rng.standard_normal(
            (2, 3, 8, 8)).astype(np.float32)),
        ("layer/cached_value", rng.standard_normal(
            (2, 3, 8, 8)).astype(ml_dtypes.bfloat16)),
    ]
    payload = KVBlockPayload(
        prompt=np.arange(17, dtype=np.int32), generated=[5, 9],
        true_len=18, block_size=8, max_new_tokens=6,
        sampling=SamplingParams(temperature=0.7, top_k=8, seed=3),
        stop_ids=(2, 4), leaves=leaves)
    back = kv_payload_from_wire(kv_payload_to_wire(payload))
    assert back.generated == [5, 9] and back.true_len == 18
    assert back.block_size == 8 and back.max_new_tokens == 6
    assert back.stop_ids == (2, 4)
    assert (back.sampling.temperature, back.sampling.top_k,
            back.sampling.seed) == (0.7, 8, 3)
    np.testing.assert_array_equal(back.prompt, payload.prompt)
    for (n0, a0), (n1, a1) in zip(leaves, back.leaves):
        assert n0 == n1 and a0.dtype == a1.dtype
        np.testing.assert_array_equal(
            a0.view(np.uint8), a1.view(np.uint8))  # bit-exact
    assert back.num_blocks == 3 and back.nbytes == payload.nbytes


# ----------------------------------------------------------------------
# engine-level KV stream


def _handoff_all(src, dst, handles):
    """Drive ``src`` until every prefill_only handle parks + exports,
    importing each into ``dst`` as it lands; returns {id: imported}."""
    moved, pending = {}, []
    for _ in range(500):
        if len(moved) == len(handles) and not pending:
            return moved
        src.step()
        for req in list(src.parked_requests):
            pending.append((req, src.export_kv_blocks(req)))
        still = []
        for req, payload in pending:
            out = dst.import_kv_blocks(payload)
            if out is None:        # importer full: the payload is
                still.append((req, payload))  # self-contained, retry
            else:
                moved[req.id] = (req, out)
        pending = still
        dst.step()  # imports decode while later prefills still chunk
    raise AssertionError(f"only {len(moved)}/{len(handles)} landed")


def test_kv_roundtrip_bitwise_ragged_lengths():
    """The acceptance anchor: prompts straddling the block grid
    (k*bs - 1, k*bs, k*bs + 1 at bs=8) prefill on engine A, hand their
    KV blocks to engine B, and the merged stream is bitwise-equal to
    generate() — the partial-tail-block and exact-boundary export
    paths both survive the gather→host→scatter trip."""
    src, dst = _engine(), _engine()
    rng = np.random.default_rng(7)
    lens, news = [7, 8, 9, 16, 17], [9, 8, 7, 6, 5]
    prompts = [rng.integers(0, CFG.vocab_size, (m,)).astype(np.int32)
               for m in lens]
    handles = [src.submit(p, max_new_tokens=n, prefill_only=True)
               for p, n in zip(prompts, news)]
    moved = _handoff_all(src, dst, handles)
    # after export the prefill engine holds NOTHING for the streams
    assert not src.parked_requests
    assert all(h.slot is None for h in handles)
    dst.run_until_idle()
    for h, p, n in zip(handles, prompts, news):
        _, out = moved[h.id]
        assert out.finish_reason == "length"
        # the exporter delivered exactly the first token; the importer's
        # resume guard means it never re-delivers it
        assert h.new_tokens == out.new_tokens[:1]
        assert out.resumed_from == 1
        np.testing.assert_array_equal(out.output_ids, _ref(p, n))
    st = src.summary()
    assert st["kv_exports"] == 5 and st["kv_stream_bytes"] > 0
    assert dst.summary()["kv_imports"] == 5
    src.close()  # block-leak invariant on both halves
    dst.close()


def test_kv_roundtrip_bitwise_seeded_sampling():
    """Seeded sampling across a handoff: the importer continues the
    per-token fold_in count at len(generated), so the sampled stream is
    the one an uninterrupted colocated engine draws."""
    sampling = SamplingParams(temperature=0.8, top_k=10, seed=123)
    rng = np.random.default_rng(11)
    p = rng.integers(0, CFG.vocab_size, (13,)).astype(np.int32)
    colo = _engine()
    want = colo.submit(p, max_new_tokens=8, sampling=sampling)
    colo.run_until_idle()
    colo.close()
    src, dst = _engine(), _engine()
    h = src.submit(p, max_new_tokens=8, sampling=sampling,
                   prefill_only=True)
    moved = _handoff_all(src, dst, [h])
    dst.run_until_idle()
    _, out = moved[h.id]
    assert out.new_tokens == want.new_tokens
    src.close()
    dst.close()


def test_kv_export_after_prefix_hit_bitwise():
    """A prefill-role admission that lands on cached prefix blocks
    (radix hit) exports a payload whose leading blocks are the SHARED
    ones — the importer's stream must still be bitwise, and the
    exporter's radix reference must survive the export (the next
    sibling still hits)."""
    src, dst = _engine(), _engine()
    rng = np.random.default_rng(3)
    system = rng.integers(0, CFG.vocab_size, (24,)).astype(np.int32)
    # warm the radix: one colocated stream through the shared prefix
    warm = src.submit(system, max_new_tokens=4)
    src.run_until_idle()
    np.testing.assert_array_equal(warm.output_ids, _ref(system, 4))
    tail = rng.integers(0, CFG.vocab_size, (5,)).astype(np.int32)
    p = np.concatenate([system, tail])
    h = src.submit(p, max_new_tokens=6, prefill_only=True)
    moved = _handoff_all(src, dst, [h])
    assert h.prefix_hit_tokens >= 16  # admitted through cached blocks
    dst.run_until_idle()
    _, out = moved[h.id]
    np.testing.assert_array_equal(out.output_ids, _ref(p, 6))
    # the cache kept its reference through the export: a sibling hits
    sib = src.submit(np.concatenate([system, tail[:2]]),
                     max_new_tokens=4)
    src.run_until_idle()
    assert sib.prefix_hit_tokens >= 16
    src.close()
    dst.close()


def test_import_validation_walls():
    """Geometry/model mismatches must raise, not serve garbage; a
    resource shortfall returns None (the router's lossless
    resume-from-tokens fallback)."""
    model, params, _ = _setup()
    src = _engine()
    rng = np.random.default_rng(5)
    p = rng.integers(0, CFG.vocab_size, (9,)).astype(np.int32)
    h = src.submit(p, max_new_tokens=5, prefill_only=True)
    for _ in range(100):
        src.step()
        if src.parked_requests:
            break
    payload = src.export_kv_blocks(src.parked_requests[0])
    # exporting twice is a caller bug, loudly
    with pytest.raises(ValueError, match="not parked"):
        src.export_kv_blocks(h)
    dense = ServingEngine(model, params, num_slots=2, prefill_bucket=16)
    with pytest.raises(ValueError, match="paged engine"):
        dense.submit(p, max_new_tokens=4, prefill_only=True)
    with pytest.raises(ValueError, match="paged engine"):
        dense.import_kv_blocks(payload)
    dense.close()
    dst = _engine()
    with pytest.raises(ValueError, match="block_size"):
        dst.import_kv_blocks(dataclasses.replace(payload, block_size=16))
    with pytest.raises(ValueError, match="generated"):
        dst.import_kv_blocks(dataclasses.replace(payload, generated=[]))
    with pytest.raises(ValueError, match="true_len"):
        dst.import_kv_blocks(
            dataclasses.replace(payload, true_len=payload.true_len + 1))
    with pytest.raises(ValueError, match="pool leaves"):
        dst.import_kv_blocks(dataclasses.replace(
            payload, leaves=[("bogus", a) for _, a in payload.leaves]))
    # the untampered payload still lands and finishes bitwise
    out = dst.import_kv_blocks(payload)
    assert out is not None
    dst.run_until_idle()
    np.testing.assert_array_equal(out.output_ids, _ref(p, 5))
    src.close()
    dst.close()


# ----------------------------------------------------------------------
# compressed (int8) pools over the KV stream (ISSUE 13)


def _colocated_int8_want(prompts, news, samplings=None):
    """Reference streams from an uninterrupted colocated int8 engine —
    the int8 handoff's bitwise anchor (generate() is the bf16 oracle;
    a quantized pool is its own exactness contract)."""
    samplings = samplings or [None] * len(prompts)
    colo = _engine(kv_dtype="int8")
    want = []
    for p, n, s in zip(prompts, news, samplings):
        r = colo.submit(p, max_new_tokens=n,
                        sampling=s or SamplingParams())
        colo.run_until_idle()
        want.append(list(r.new_tokens))
    colo.close()
    return want


def test_kv_roundtrip_int8_compressed_blocks():
    """ISSUE 13 acceptance: the handoff round-trips COMPRESSED blocks
    exactly — int8 codes and their fp32 scale planes ride the same
    pool-leaf path — so the importer's stream is bitwise-equal to an
    uninterrupted colocated int8 engine's, greedy AND seeded, at
    block-grid-straddling prompt lengths; the payload advertises its
    dtype and wire version and carries the scale leaves."""
    rng = np.random.default_rng(33)
    lens, news = [7, 8, 9, 17], [6, 6, 6, 6]
    prompts = [rng.integers(0, CFG.vocab_size, (m,)).astype(np.int32)
               for m in lens]
    samplings = [None, SamplingParams(temperature=0.8, top_k=10, seed=5),
                 None, SamplingParams(temperature=0.7, top_k=8, seed=9)]
    want = _colocated_int8_want(prompts, news, samplings)
    src, dst = _engine(kv_dtype="int8"), _engine(kv_dtype="int8")
    handles = [src.submit(p, max_new_tokens=n,
                          sampling=s or SamplingParams(),
                          prefill_only=True)
               for p, n, s in zip(prompts, news, samplings)]
    # peek at one payload before the batch drive: the self-description
    # a mismatched receiver rejects on, plus the scale planes
    for _ in range(100):
        src.step()
        if src.parked_requests:
            break
    req0 = src.parked_requests[0]
    peek = src.export_kv_blocks(req0)
    assert peek.kv_dtype == "int8"
    assert peek.wire_version == serving_engine.KV_WIRE_VERSION
    names = [n.rsplit("/", 1)[-1] for n, _ in peek.leaves]
    assert "cached_key_scale" in names and "cached_value_scale" in names
    codes = dict(zip(names, (a for _, a in peek.leaves)))
    assert codes["cached_key"].dtype == np.int8
    assert codes["cached_key_scale"].dtype == np.float32
    # the wire codec keeps all of it bit-exact
    back = kv_payload_from_wire(kv_payload_to_wire(peek))
    assert back.kv_dtype == "int8"
    assert back.wire_version == peek.wire_version
    out0 = dst.import_kv_blocks(back)
    assert out0 is not None
    rest = [h for h in handles if h.id != req0.id]
    moved = _handoff_all(src, dst, rest)
    dst.run_until_idle()
    outs = {req0.id: out0, **{i: o for i, (_, o) in moved.items()}}
    for h, w in zip(handles, want):
        out = outs[h.id]
        assert out.finish_reason == "length"
        assert list(out.new_tokens) == w, f"request {h.id}"
    src.close()
    dst.close()


def test_import_rejects_dtype_and_version_mismatch():
    """A bf16 replica must REFUSE an int8 payload (scattering codes
    into a bf16 pool would serve garbage) with a clear error naming
    both dtypes, and any engine refuses a stale wire version; the
    best-effort prefix-ship path declines (0 blocks) instead of
    raising."""
    src = _engine(kv_dtype="int8")
    rng = np.random.default_rng(35)
    p = rng.integers(0, CFG.vocab_size, (9,)).astype(np.int32)
    src.submit(p, max_new_tokens=5, prefill_only=True)
    for _ in range(100):
        src.step()
        if src.parked_requests:
            break
    payload = src.export_kv_blocks(src.parked_requests[0])
    bf16 = _engine()
    with pytest.raises(ValueError, match="kv_dtype 'int8'"):
        bf16.import_kv_blocks(payload)
    dst8 = _engine(kv_dtype="int8")
    with pytest.raises(ValueError, match="wire_version"):
        dst8.import_kv_blocks(
            dataclasses.replace(payload, wire_version=1))
    # prefix shipping is best-effort: mismatches decline, never raise
    ship = src.export_prefix_blocks(p)
    assert ship is not None and ship.kv_dtype == "int8"
    assert bf16.import_prefix_blocks(ship) == 0
    assert dst8.import_prefix_blocks(
        dataclasses.replace(ship, wire_version=1)) == 0
    # the untampered payload still lands on the matching pool
    out = dst8.import_kv_blocks(payload)
    assert out is not None
    dst8.run_until_idle()
    assert out.finish_reason == "length"
    src.close()
    bf16.close()
    dst8.close()


def test_fleet_prefix_ships_int8_blocks():
    """Fleet prefix steering over COMPRESSED pools: an int8 fleet ships
    int8 blocks + scales to the overflow sibling, which admits through
    them as remote hits — every stream bitwise-equal to the colocated
    int8 engine."""
    rng = np.random.default_rng(37)
    system = rng.integers(0, CFG.vocab_size, (16,)).astype(np.int32)
    tails = [rng.integers(0, CFG.vocab_size, (3 + i,)).astype(np.int32)
             for i in range(5)]
    prompts = [system] + [np.concatenate([system, t]) for t in tails]
    want = _colocated_int8_want(prompts, [4] * len(prompts))
    model, params, _ = _setup()
    router = ReplicaRouter(
        model, params, replicas=2, roles=["both", "both"],
        engine_kwargs=dict(num_slots=3, prefill_bucket=16, block_size=8,
                           kv_dtype="int8"),
        warmup_lens=(16, 32))
    router.warmup()
    leader = router.submit(prompts[0], max_new_tokens=4)
    router.run_until_idle()
    sibs = [router.submit(p, max_new_tokens=4) for p in prompts[1:]]
    router.run_until_idle()
    s = router.summary()
    assert s["prefix_ships"] >= 1
    assert s["cross_replica_hit_rate"] > 0
    for r, w in zip([leader] + sibs, want):
        assert list(r.tokens) == w, f"request {r.id} (hops {r.replicas})"
    router.close()


# ----------------------------------------------------------------------
# router-level disaggregation


def test_disagg_router_bitwise_and_handoffs():
    """The tentpole anchor: a prefill-role + decode-role fleet serves
    every stream bitwise-equal to the colocated engine — greedy AND
    seeded — with one handoff per request and zero failures."""
    model, params, _ = _setup()
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, CFG.vocab_size, (m,)).astype(np.int32)
               for m in (5, 9, 7, 11)]
    samplings = [None, SamplingParams(temperature=0.7, top_k=8, seed=4),
                 None, SamplingParams(temperature=0.9, top_k=6, seed=8)]
    colo = _engine()
    want = []
    for p, s in zip(prompts, samplings):
        r = colo.submit(p, max_new_tokens=6,
                        sampling=s or SamplingParams())
        colo.run_until_idle()
        want.append(list(r.new_tokens))
    colo.close()
    router = _router([ROLE_PREFILL, ROLE_DECODE])
    reqs = [router.submit(p, max_new_tokens=6, sampling=s)
            for p, s in zip(prompts, samplings)]
    router.run_until_idle()
    for r, w in zip(reqs, want):
        assert r.finish_reason == "length"
        assert r.tokens == w, f"request {r.id}"
        assert r.replicas == [0, 1]  # prefilled on 0, decoded on 1
        assert r.retries == 0
    s = router.summary()
    assert s["roles"] == [ROLE_PREFILL, ROLE_DECODE]
    assert s["handoffs"] == 4 and s["handoff_failures"] == 0
    assert s["kv_stream_bytes"] > 0
    assert s["served_by"] == {1: 4}
    router.close()


def test_disagg_decode_death_after_import_is_lossless():
    """A decode-role replica dying AFTER imports landed loses no
    stream: the router's failover requeues its residents and the
    resume-from-tokens path re-prefills prompt+generated elsewhere —
    tokens identical to the uninterrupted run."""
    inj = FaultInjector(FaultPlan.parse("replica_crash@tick=6,replica=1"))
    router = _router([ROLE_PREFILL, ROLE_DECODE, ROLE_DECODE],
                     faults=inj)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, CFG.vocab_size, (m,)).astype(np.int32)
               for m in (6, 10, 8, 5)]
    reqs = [router.submit(p, max_new_tokens=8) for p in prompts]
    router.run_until_idle()
    s = router.summary()
    assert s["replicas_lost"] == 1
    assert s["handoffs"] >= 1
    for r, p in zip(reqs, prompts):
        assert r.finish_reason == "length"
        np.testing.assert_array_equal(
            np.asarray(r.tokens), _ref(p, 8)[p.size:],
            err_msg=f"request {r.id} (hops {r.replicas})")
    router.close()


def test_disagg_prefill_death_with_parked_streams_is_lossless():
    """The other half of the chaos acceptance: the prefill-role
    replica dying while streams are parked (KV not yet exported) must
    not lose them — failover re-prefills them on a survivor."""
    inj = FaultInjector(FaultPlan.parse("replica_crash@tick=3,replica=0"))
    router = _router([ROLE_PREFILL, ROLE_PREFILL, ROLE_DECODE],
                     faults=inj)
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, CFG.vocab_size, (m,)).astype(np.int32)
               for m in (7, 11, 6, 9)]
    reqs = [router.submit(p, max_new_tokens=8) for p in prompts]
    router.run_until_idle()
    s = router.summary()
    assert s["replicas_lost"] == 1
    for r, p in zip(reqs, prompts):
        assert r.finish_reason == "length"
        np.testing.assert_array_equal(
            np.asarray(r.tokens), _ref(p, 8)[p.size:],
            err_msg=f"request {r.id} (hops {r.replicas})")
    router.close()


def test_fleet_prefix_steering_ships_blocks():
    """The fleet-radix anchor, deterministic: same-prefix siblings are
    steered to the replica that published the prefix until it
    saturates; the overflow sibling's target ADOPTS the blocks over
    the KV stream (prefix_ships), admits through them as remote hits
    (cross_replica_hit_rate > 0), and every stream stays bitwise."""
    router = _router(["both", "both"])
    rng = np.random.default_rng(21)
    system = rng.integers(0, CFG.vocab_size, (16,)).astype(np.int32)
    leader = router.submit(system, max_new_tokens=4)
    router.run_until_idle()  # replica 0 serves + publishes its frontier
    np.testing.assert_array_equal(
        np.asarray(leader.tokens), _ref(system, 4)[system.size:])
    assert leader.replicas == [0]
    sibs, prompts = [], []
    for i in range(5):
        tail = rng.integers(0, CFG.vocab_size, (3 + i,)).astype(np.int32)
        p = np.concatenate([system, tail])
        prompts.append(p)
        # no stepping between submits: the first four pile onto the
        # prefix owner (depth dominates the dispatch key) until its
        # load cap excludes it; the fifth lands on replica 1 + ships
        sibs.append(router.submit(p, max_new_tokens=4))
    router.run_until_idle()
    s = router.summary()
    assert s["prefix_ships"] >= 1
    assert s["cross_replica_hit_rate"] > 0
    assert s["kv_stream_bytes"] > 0
    assert 1 in s["served_by"]  # the overflow sibling really moved
    remote = sum(h.get("remote_hit_tokens", 0) for h in router.health())
    assert remote > 0
    for r, p in zip(sibs, prompts):
        np.testing.assert_array_equal(
            np.asarray(r.tokens), _ref(p, 4)[p.size:],
            err_msg=f"request {r.id} (hops {r.replicas})")
    router.close()


def test_zero_recompiles_steady_state_disagg():
    """warmup_kv_stream pre-compiles the gather/scatter pair, so a
    steady-state disaggregated trace — chunked prefill, park, export,
    import, mid-flight activation, fleet prefix ship — performs ZERO
    retraces and zero recompiles (the disagg A/B's tripwire)."""
    router = _router([ROLE_PREFILL, ROLE_DECODE])
    traces = dict(serving_engine.TRACE_COUNTS)
    sizes = (paged_prefill_chunk._cache_size(),
             paged_decode_tick._cache_size(),
             kv_block_gather._cache_size(),
             kv_block_scatter._cache_size())
    rng = np.random.default_rng(25)
    shared = rng.integers(0, CFG.vocab_size, (16,)).astype(np.int32)
    reqs = []
    for i in range(6):
        if i % 2:
            p = np.concatenate([shared, rng.integers(
                0, CFG.vocab_size, (1 + i,)).astype(np.int32)])
        else:
            p = rng.integers(0, CFG.vocab_size,
                             (5 + i,)).astype(np.int32)
        reqs.append(router.submit(p, max_new_tokens=5))
        router.step()
    router.run_until_idle()
    assert router.summary()["handoffs"] == 6
    assert all(r.finish_reason == "length" for r in reqs)
    assert dict(serving_engine.TRACE_COUNTS) == traces
    assert (paged_prefill_chunk._cache_size(),
            paged_decode_tick._cache_size(),
            kv_block_gather._cache_size(),
            kv_block_scatter._cache_size()) == sizes
    router.close()


def test_report_cli_renders_disagg_columns(tmp_path):
    """The report CLI's router section grows the role column and the
    handoff/KV-stream summary line (ISSUE 12 satellite)."""
    from pytorchdistributed_tpu.telemetry.report import render

    router = _router([ROLE_PREFILL, ROLE_DECODE],
                     telemetry_dir=str(tmp_path))
    rng = np.random.default_rng(29)
    reqs = [router.submit(
        rng.integers(0, CFG.vocab_size, (6 + i,)).astype(np.int32),
        max_new_tokens=4) for i in range(3)]
    router.run_until_idle()
    assert all(r.finish_reason == "length" for r in reqs)
    router.close()
    out = render(tmp_path)
    assert "replica router" in out
    assert "handoffs 3" in out
    assert "kv_stream" in out
    assert "prefill" in out and "decode" in out  # per-replica roles


# ----------------------------------------------------------------------
# subprocess wire (full-suite-only: spawns jax-importing workers)


def test_subprocess_disagg_e2e():
    """The multi-host shape: prefill and decode roles as subprocess
    workers, the KV payload serialized over the line-JSON wire — the
    handed-off streams stay bitwise-equal to generate()."""
    spec = {"model": "gpt2", "size": "test",
            "overrides": {"num_layers": 2, "max_seq_len": 64},
            "init_seed": 1,
            "engine": {"num_slots": 3, "prefill_bucket": 16,
                       "block_size": 8}}
    router = ReplicaRouter(workers=[spec, spec],
                           roles=[ROLE_PREFILL, ROLE_DECODE],
                           warmup_lens=(16, 32), faults=None)
    try:
        router.warmup()
        rng = np.random.default_rng(31)
        prompts = [rng.integers(0, CFG.vocab_size, (m,)).astype(np.int32)
                   for m in (5, 9, 12)]
        reqs = [router.submit(p, max_new_tokens=6) for p in prompts]
        router.run_until_idle(max_steps=200000)
        s = router.summary()
        assert s["handoffs"] == 3 and s["handoff_failures"] == 0
        for p, r in zip(prompts, reqs):
            assert r.finish_reason == "length"
            assert r.replicas == [0, 1]
            np.testing.assert_array_equal(
                np.asarray(r.tokens), _ref(p, 6)[p.size:],
                err_msg=f"request {r.id}")
    finally:
        router.close()


def test_subprocess_disagg_int8_e2e():
    """ISSUE 13 over the real wire: an int8-pool prefill worker hands
    compressed blocks (codes + scale planes, the engine's wire_version) to an
    int8-pool decode worker over the line-JSON subprocess transport —
    streams bitwise-equal to the colocated int8 engine's."""
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, CFG.vocab_size, (m,)).astype(np.int32)
               for m in (5, 9, 12)]
    want = _colocated_int8_want(prompts, [6] * 3)
    spec = {"model": "gpt2", "size": "test",
            "overrides": {"num_layers": 2, "max_seq_len": 64},
            "init_seed": 1,
            "engine": {"num_slots": 3, "prefill_bucket": 16,
                       "block_size": 8, "kv_dtype": "int8"}}
    router = ReplicaRouter(workers=[spec, spec],
                           roles=[ROLE_PREFILL, ROLE_DECODE],
                           warmup_lens=(16, 32), faults=None)
    try:
        router.warmup()
        reqs = [router.submit(p, max_new_tokens=6) for p in prompts]
        router.run_until_idle(max_steps=200000)
        s = router.summary()
        assert s["handoffs"] == 3 and s["handoff_failures"] == 0
        for r, w in zip(reqs, want):
            assert r.finish_reason == "length"
            assert r.replicas == [0, 1]
            assert list(r.tokens) == w, f"request {r.id}"
    finally:
        router.close()

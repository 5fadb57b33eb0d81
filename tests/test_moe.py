"""Switch-MoE tests (SURVEY.md §2c "EP"). The reference has no MoE, so the
correctness bar is internal: the routed computation must equal a per-token
reference loop, degenerate to the dense MLP at one expert, respect capacity,
and actually shard experts over the "expert" mesh axis under the tp rules.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorchdistributed_tpu.models import GPT2, SwitchMoE, gpt2_config
from pytorchdistributed_tpu.models.transformer import TransformerConfig
from pytorchdistributed_tpu.runtime.mesh import Axis, create_mesh
from pytorchdistributed_tpu.training import (
    Trainer,
    moe_token_cross_entropy_loss,
)


def _moe(e, cf=2.0, d=16, f=32):
    cfg = TransformerConfig(
        embed_dim=d, mlp_dim=f, dtype=jnp.float32, moe_experts=e,
        moe_capacity_factor=cf)
    return SwitchMoE(cfg)


def test_single_expert_is_dense_mlp():
    """e=1 degenerates: gate==1, every token kept, output == gelu(xW_i)W_o."""
    moe = _moe(1, cf=1.0)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 8, 16)),
                    jnp.float32)
    params = moe.init(jax.random.key(0), x)
    out = moe.apply(params, x)
    import flax.linen as nn
    p = jax.tree.map(lambda l: l.unbox() if hasattr(l, "unbox") else l,
                     params["params"],
                     is_leaf=lambda l: isinstance(l, nn.Partitioned))
    ref = nn.gelu(x @ p["wi"][0]) @ p["wo"][0]
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_moe_matches_per_token_reference():
    """Dense one-hot dispatch == an explicit per-token route-and-apply loop
    (capacity generous enough that nothing overflows)."""
    moe = _moe(4, cf=4.0)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
    params = moe.init(jax.random.key(1), x)
    out = np.asarray(moe.apply(params, x)).reshape(-1, 16)

    import flax.linen as nn
    p = jax.tree.map(lambda l: l.unbox() if hasattr(l, "unbox") else l,
                     params["params"],
                     is_leaf=lambda l: isinstance(l, nn.Partitioned))
    toks = np.asarray(x, np.float32).reshape(-1, 16)
    probs = np.asarray(jax.nn.softmax(
        jnp.asarray(toks) @ p["router"], axis=-1))
    for g in range(toks.shape[0]):
        e = int(probs[g].argmax())
        ref = probs[g, e] * np.asarray(
            nn.gelu(jnp.asarray(toks[g]) @ p["wi"][e]) @ p["wo"][e])
        np.testing.assert_allclose(out[g], ref, atol=1e-4)


def test_capacity_overflow_rides_residual():
    """With capacity 1 slot per expert, at most e tokens get an expert
    output; the rest must be exactly zero (the block's residual carries
    them)."""
    e = 2
    moe = _moe(e, cf=2 / 16)  # 16 tokens → capacity 1
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 8, 16)),
                    jnp.float32)
    params = moe.init(jax.random.key(2), x)
    out = np.asarray(moe.apply(params, x)).reshape(-1, 16)
    nonzero = (np.abs(out).sum(-1) > 1e-9).sum()
    assert nonzero <= e, f"{nonzero} tokens routed with {e} capacity slots"


def test_moe_gpt2_trains_sharded():
    """End to end: GPT-2 with Switch MLP blocks trains under the tp rules on
    an expert-axis mesh; expert kernels are actually split; the aux loss is
    reported and the model still learns (loss falls over steps)."""
    mesh = create_mesh(data=2, expert=4)
    model = GPT2(gpt2_config(
        "test", num_layers=2, dtype=jnp.float32, moe_experts=4,
        moe_capacity_factor=2.0))
    tr = Trainer(model, optax.adamw(1e-2), moe_token_cross_entropy_loss,
                 mesh=mesh, strategy="tp")
    rng = np.random.default_rng(3)
    batch = {"tokens": rng.integers(0, 128, (16, 32)).astype(np.int32),
             "targets": rng.integers(0, 128, (16, 32)).astype(np.int32)}
    losses, metrics = [], None
    for _ in range(5):
        metrics = tr.train_step(batch)
        losses.append(float(metrics["loss"]))
    assert "moe_aux" in metrics and np.isfinite(float(metrics["moe_aux"]))
    assert losses[-1] < losses[0], losses

    wi = tr.state.params["params"]["h"]["block"]["moe"]["wi"]
    spec = wi.sharding.spec
    assert Axis.EXPERT in jax.tree.leaves(tuple(spec)), (
        f"expert kernels not sharded over the expert axis: {spec}")
    # per-device shard holds 1/4 of the experts
    shard = wi.addressable_shards[0].data
    assert shard.shape[1] == wi.shape[1] // 4, (wi.shape, shard.shape)


def test_moe_aux_loss_uniform_at_balance():
    """The Switch aux term is exactly 1 when routing is uniform."""
    e = 4
    probs = jnp.full((64, e), 1 / e)
    onehot = jax.nn.one_hot(jnp.arange(64) % e, e)
    aux = e * jnp.sum(onehot.mean(0) * probs.mean(0))
    assert np.isclose(float(aux), 1.0)


# ---------------------------------------------------------------------------
# expert-parallel a2a dispatch (ISSUE 14)
# ---------------------------------------------------------------------------

def _grouped_cfg(**kw):
    """Grouped-routing config pinned to G=8 — the dp2 x expert4 layout —
    so the single-device dense reference computes the IDENTICAL routing
    function the sharded a2a path runs."""
    base = dict(embed_dim=16, mlp_dim=32, dtype=jnp.float32,
                param_dtype=jnp.float32, moe_experts=4,
                moe_capacity_factor=2.0, moe_groups=8)
    base.update(kw)
    return TransformerConfig(**base)


def _unboxed(params):
    import flax.linen as nn

    return jax.tree.map(lambda l: l.unbox() if hasattr(l, "unbox") else l,
                        params, is_leaf=lambda l: isinstance(l,
                                                             nn.Partitioned))


def test_expert_parallel_a2a_matches_single_device():
    """The tentpole parity pin: the explicit all_to_all dispatch/combine
    (shard_map + custom_vjp, ops/overlap.expert_a2a_ffn) on a
    dp2 x expert4 mesh computes the SAME function as the dense grouped
    einsums on one device — fp32 forward and grads to float roundoff
    (the backward reuses both exchange directions, so this also pins the
    hand-written cotangent einsums against autodiff of the dense path).
    The bar is atol 1e-7 on O(1e-3) outputs: the two paths contract in a
    different order, and XLA on jax 0.9.0 leaves them 1.4e-9 apart where
    0.4.x happened to leave them bitwise."""
    mesh = create_mesh(data=2, expert=4)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((8, 8, 16)),
                    jnp.float32)
    dense = SwitchMoE(_grouped_cfg(moe_dispatch="dense"))
    params = dense.init(jax.random.key(5), x)

    def loss(m):
        return lambda p, v: jnp.sum(m.apply(p, v) ** 2)

    ref = dense.apply(params, x)
    ref_g = jax.grad(loss(dense), argnums=(0, 1))(params, x)

    a2a = SwitchMoE(_grouped_cfg(moe_dispatch="a2a"))
    with jax.set_mesh(mesh):
        out = jax.jit(a2a.apply)(params, x)
        g = jax.jit(jax.grad(loss(a2a), argnums=(0, 1)))(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-7)
    for got, want in zip(jax.tree.leaves(_unboxed(g)),
                         jax.tree.leaves(_unboxed(ref_g))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-7)


def test_expert_parallel_int8_parity():
    """int8 payloads compose with the a2a path (pre-quantized dispatch +
    int8 expert matmuls): outputs track the fp32 path within quantization
    tolerance, and the "int8" backward (stochastic-rounded gradient
    exchanges) still produces finite grads of the right structure."""
    mesh = create_mesh(data=2, expert=4)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((8, 8, 16)),
                    jnp.float32)
    fp = SwitchMoE(_grouped_cfg(moe_dispatch="dense"))
    params = fp.init(jax.random.key(6), x)
    ref = np.asarray(fp.apply(params, x))

    q = SwitchMoE(_grouped_cfg(moe_dispatch="a2a", quant="int8_fwd"))
    with jax.set_mesh(mesh):
        out = np.asarray(jax.jit(q.apply)(params, x))
    np.testing.assert_allclose(out, ref, atol=1e-2)

    sr = SwitchMoE(_grouped_cfg(moe_dispatch="a2a", quant="int8"))
    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(
            lambda p, v: jnp.sum(sr.apply(p, v) ** 2)))(params, x)
    for leaf in jax.tree.leaves(_unboxed(g)):
        assert np.isfinite(np.asarray(leaf)).all()


def test_moe_chunked_overlap_bitwise():
    """Capacity chunking (the combine-a2a-behind-next-chunk's-matmul
    pipeline) is a pure schedule change: chunks=2 output must be BITWISE
    the chunks=1 output — every einsum contracts within a chunk, so not
    even the reduction order moves."""
    mesh = create_mesh(data=2, expert=4)
    x = jnp.asarray(np.random.default_rng(7).standard_normal((8, 8, 16)),
                    jnp.float32)
    mono = SwitchMoE(_grouped_cfg(moe_dispatch="a2a", moe_chunks=1))
    params = mono.init(jax.random.key(7), x)
    piped = SwitchMoE(_grouped_cfg(moe_dispatch="a2a", moe_chunks=2))
    with jax.set_mesh(mesh):
        a = np.asarray(jax.jit(mono.apply)(params, x))
        b = np.asarray(jax.jit(piped.apply)(params, x))
    np.testing.assert_array_equal(a, b)


def test_top2_matches_per_token_reference():
    """k=2 routing with generous capacity == an explicit per-token
    top-2 loop with renormalized gates."""
    import flax.linen as nn

    moe = SwitchMoE(TransformerConfig(
        embed_dim=16, mlp_dim=32, dtype=jnp.float32, moe_experts=4,
        moe_capacity_factor=8.0, moe_top_k=2))
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
    params = moe.init(jax.random.key(8), x)
    out = np.asarray(moe.apply(params, x)).reshape(-1, 16)
    p = _unboxed(params)["params"]
    toks = np.asarray(x, np.float32).reshape(-1, 16)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(toks) @ p["router"],
                                      axis=-1))
    for t in range(toks.shape[0]):
        top2 = np.argsort(-probs[t])[:2]
        gates = probs[t, top2] / probs[t, top2].sum()
        ref = sum(
            gates[j] * np.asarray(
                nn.gelu(jnp.asarray(toks[t]) @ p["wi"][e]) @ p["wo"][e])
            for j, e in enumerate(top2))
        np.testing.assert_allclose(out[t], ref, atol=1e-4)


def test_top2_first_choices_win_capacity_race():
    """The deterministic k-major priority cumsum: with capacity 1 and
    every token's FIRST choice on expert 0 except token 0 (which first-
    chooses expert 1), the two slots must go to token 0's first choice
    and token 1's first choice — token 0's SECOND choice must NOT steal
    expert 0's slot from token 1 (the interleaved-order bug this
    ordering exists to prevent). Overflow diagnostics count the losers:
    30 of 32 assignments."""
    cfg = TransformerConfig(
        embed_dim=2, mlp_dim=4, dtype=jnp.float32, moe_experts=2,
        moe_capacity_factor=1 / 8, moe_top_k=2)  # 16 tokens -> capacity 1
    moe = SwitchMoE(cfg)
    x = np.zeros((1, 16, 2), np.float32)
    x[0, 0] = [1.0, 0.0]   # token 0 prefers expert 1 (via W below)
    x[0, 1:] = [0.0, 1.0]  # tokens 1.. prefer expert 0
    x = jnp.asarray(x)
    params = moe.init(jax.random.key(9), x)
    router = params["params"]["router"]
    W = jnp.asarray([[0.0, 1.0], [1.0, 0.0]], jnp.float32)
    params = {"params": {**params["params"],
                         "router": (router.replace(value=W)
                                    if hasattr(router, "replace") else W)}}
    out, mods = moe.apply(params, x, mutable=["diagnostics"])
    routed = np.flatnonzero(np.abs(np.asarray(out)[0]).sum(-1) > 1e-9)
    np.testing.assert_array_equal(routed, [0, 1])
    overflow = jax.tree.leaves(mods["diagnostics"])[-1]
    assert np.isclose(float(jnp.asarray(overflow)), 30 / 32)


def test_moe_serving_bitwise_vs_generate_expert_sharded():
    """MoE serves (ISSUE 14): a GPT-2 MoE model with EXPERT-SHARDED
    weights on a dp2 x expert4 mesh, through the stock ServingEngine —
    greedy tokens bitwise-equal to offline generate() on replicated
    params (decode routes per token, so a request's output is
    independent of its batch neighbours), with ZERO steady-state
    retraces/recompiles after warmup."""
    from pytorchdistributed_tpu.inference import generate
    from pytorchdistributed_tpu.serving import ServingEngine
    from pytorchdistributed_tpu.serving import engine as serving_engine
    from pytorchdistributed_tpu.serving.engine import (
        decode_tick,
        prefill_into_slot,
    )

    cfg = gpt2_config("test", num_layers=2, max_seq_len=64,
                      moe_experts=4, moe_capacity_factor=2.0)
    model = GPT2(cfg)
    # plain {"params": ...}: init also returns the sown "losses"
    # collection (the router aux terms), which is not a weight
    params = {"params": _unboxed(model.init(
        jax.random.key(11), jnp.zeros((1, 4), jnp.int32))["params"])}
    mesh = create_mesh(data=2, expert=4)
    tr = Trainer(model, optax.sgd(1e-2), moe_token_cross_entropy_loss,
                 mesh=mesh, strategy="dp")
    big = np.tile(np.arange(8, dtype=np.int32)[None] % cfg.vocab_size,
                  (8, 1))
    tr.init({"tokens": big, "targets": big})
    shardings = jax.tree.map(lambda a: a.sharding, tr.state.params)
    sharded = jax.device_put(params, shardings)
    wi = sharded["params"]["h"]["block"]["moe"]["wi"]
    assert Axis.EXPERT in jax.tree.leaves(tuple(wi.sharding.spec)), (
        f"expert kernels not sharded: {wi.sharding.spec}")

    import dataclasses
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, (m,)).astype(np.int32)
               for m in (5, 9, 3, 13)]
    news = [6, 3, 8, 5]
    engine = ServingEngine(model, sharded, num_slots=2, prefill_bucket=16,
                           mesh=mesh)
    engine.warmup(prompt_lens=(8, 16))
    traces = dict(serving_engine.TRACE_COUNTS)
    sizes = (prefill_into_slot._cache_size(), decode_tick._cache_size())
    reqs = []
    for p, n in zip(prompts, news):
        reqs.append(engine.submit(p, max_new_tokens=n))
        engine.step()
    engine.run_until_idle()
    for p, n, r in zip(prompts, news, reqs):
        ref = generate(dm, params, jnp.asarray(p)[None], max_new_tokens=n)
        np.testing.assert_array_equal(r.output_ids, np.asarray(ref)[0],
                                      err_msg=f"request {r.id}")
    assert dict(serving_engine.TRACE_COUNTS) == traces
    assert (prefill_into_slot._cache_size(),
            decode_tick._cache_size()) == sizes

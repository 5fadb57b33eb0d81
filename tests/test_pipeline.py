"""GPipe pipeline-parallelism tests (SURVEY.md §7 hard part (a)).

The correctness bar mirrors the reference's lesson: a pipelined model must
compute exactly what the unpipelined one computes (the reference's
PipelineParallelResNet50 returns the same logits as ModelParallelResNet50,
03_model_parallel.ipynb:538-560) — here enforced as loss-curve equality
against the sequential-scan stack.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorchdistributed_tpu.models import GPT2, gpt2_config
from pytorchdistributed_tpu.parallel.pipeline import gpipe_spmd, one_f_one_b
from pytorchdistributed_tpu.runtime.mesh import create_mesh
from pytorchdistributed_tpu.training import Trainer, token_cross_entropy_loss


def test_gpipe_spmd_matches_sequential():
    """Functional core: pipelined stage chain == sequential chain."""
    rng = np.random.default_rng(0)
    p, b, d = 4, 16, 32
    params = jnp.asarray(rng.standard_normal((p, d, d)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)

    def stage_apply(w, h):
        return jnp.tanh(h @ w[0])

    mesh = create_mesh(data=2, pipe=4)
    with jax.set_mesh(mesh):
        out = gpipe_spmd(
            stage_apply, params.reshape(p, 1, d, d), x, num_microbatches=4)

    ref = x
    for i in range(p):
        ref = jnp.tanh(ref @ params[i])
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_gpipe_gradients_match():
    rng = np.random.default_rng(1)
    p, b, d = 2, 8, 16
    params = jnp.asarray(rng.standard_normal((p, 1, d, d)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)

    def stage_apply(w, h):
        return jnp.tanh(h @ w[0])

    def seq_loss(params):
        h = x
        for i in range(p):
            h = jnp.tanh(h @ params[i, 0])
        return (h**2).sum()

    mesh = create_mesh(data=2, pipe=2, tensor=2)
    with jax.set_mesh(mesh):
        def pp_loss(params):
            return (gpipe_spmd(stage_apply, params, x,
                               num_microbatches=4)**2).sum()
        g_pp = jax.grad(pp_loss)(params)
    g_seq = jax.grad(seq_loss)(params)
    np.testing.assert_allclose(g_pp, g_seq, atol=1e-4)


_BATCH_RNG = np.random.default_rng(7)
_BATCH = {
    "tokens": _BATCH_RNG.integers(0, 128, (16, 32)).astype(np.int32),
    "targets": _BATCH_RNG.integers(0, 128, (16, 32)).astype(np.int32),
}


def _run_losses(cfg_kw, axes, strategy="dp", steps=3):
    model = GPT2(gpt2_config("test", num_layers=4, dtype=jnp.float32,
                             **cfg_kw))
    tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                 mesh=create_mesh(**axes), strategy=strategy)
    return [float(tr.train_step(_BATCH)["loss"]) for _ in range(steps)]


@pytest.fixture(scope="module")
def sequential_losses():
    return _run_losses(dict(), dict())


@pytest.mark.parametrize("pp_kw,axes,strategy", [
    (dict(pipeline_stages=4, pipeline_microbatches=4),
     dict(data=2, pipe=4), "dp"),
    (dict(pipeline_stages=2, pipeline_microbatches=8),
     dict(data=2, pipe=2, tensor=2), "tp"),
    (dict(pipeline_stages=2, pipeline_microbatches=2, remat=True),
     dict(data=4, pipe=2), "dp"),
    # 1F1B fused-step schedule: same bar — loss curve == sequential — and
    # same strategy composition (pure PP, PP×TP, PP×FSDP).
    (dict(pipeline_stages=4, pipeline_microbatches=4, pp_schedule="1f1b"),
     dict(data=2, pipe=4), "dp"),
    (dict(pipeline_stages=2, pipeline_microbatches=8, pp_schedule="1f1b"),
     dict(data=2, pipe=2, tensor=2), "tp"),
    (dict(pipeline_stages=2, pipeline_microbatches=4, pp_schedule="1f1b"),
     dict(data=2, fsdp=2, pipe=2), "fsdp"),
])
def test_gpt2_pipeline_loss_equivalence(sequential_losses, pp_kw, axes,
                                        strategy):
    got = _run_losses(pp_kw, axes, strategy)
    np.testing.assert_allclose(got, sequential_losses, atol=2e-5)


def test_bert_1f1b_masked_loss_equivalence():
    """BERT MLM under 1F1B: the globally-normalized mask weights must make
    micro-batch losses compose to exactly the full-batch masked mean, no
    matter how unevenly masked tokens fall across micro-batches."""
    from pytorchdistributed_tpu.models import BertMLM, bert_config

    rng = np.random.default_rng(9)
    batch = {
        "tokens": rng.integers(0, 128, (16, 32)).astype(np.int32),
        "targets": rng.integers(0, 128, (16, 32)).astype(np.int32),
        # lopsided mask: rows 0-3 heavily masked, rows 12-15 barely
        "loss_mask": (rng.random((16, 32)) <
                      np.linspace(0.9, 0.05, 16)[:, None]).astype(np.int32),
    }

    def run(cfg_kw, axes, steps=3):
        model = BertMLM(bert_config("test", num_layers=4, dtype=jnp.float32,
                                    **cfg_kw))
        tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                     mesh=create_mesh(**axes), strategy="dp")
        return [float(tr.train_step(batch)["loss"]) for _ in range(steps)]

    seq = run(dict(), dict())
    f1b = run(dict(pipeline_stages=4, pipeline_microbatches=4,
                   pp_schedule="1f1b"), dict(data=2, pipe=4))
    np.testing.assert_allclose(f1b, seq, atol=2e-5)


def test_one_f_one_b_matches_sequential_grads():
    """Core 1F1B primitive: loss, stage grads, head grads and the input
    cotangent all equal sequential AD (the PipeDream-flush schedule is a
    reordering, not an approximation)."""
    rng = np.random.default_rng(3)
    p, b, d, m = 4, 16, 8, 8
    sp = jnp.asarray(rng.standard_normal((p, d, d)) * 0.3, jnp.float32)
    hw = jnp.asarray(rng.standard_normal((d, 3)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((b, 3)), jnp.float32)

    def stage_apply(w, h):
        return jnp.tanh(h @ w)

    def head_loss(w, h, tt):
        return jnp.mean((h @ w - tt) ** 2)

    mesh = create_mesh(data=2, pipe=4)
    with jax.set_mesh(mesh):
        loss, sg, hg, dx = one_f_one_b(
            stage_apply, sp, head_loss, hw, x, t, num_microbatches=m)

    def ref(sp, hw, xx):
        h = xx
        for i in range(p):
            h = jnp.tanh(h @ sp[i])
        return jnp.mean((h @ hw - t) ** 2)

    rl, (rsg, rhg, rdx) = jax.value_and_grad(ref, argnums=(0, 1, 2))(sp, hw, x)
    np.testing.assert_allclose(float(loss), float(rl), atol=1e-6)
    np.testing.assert_allclose(sg, rsg, atol=1e-5)
    np.testing.assert_allclose(hg, rhg, atol=1e-5)
    np.testing.assert_allclose(dx, rdx, atol=1e-5)


def test_1f1b_bounds_activation_memory():
    """The schedule's point (reference 03_model_parallel.ipynb:668-697):
    in-flight residuals bounded by stage count, not micro-batch count. At
    M=16 >> P=4 the compiled 1F1B step must use measurably less scratch than
    the GPipe step (whose AD keeps one residual set per micro-batch)."""
    rng = np.random.default_rng(11)
    batch = {
        "tokens": rng.integers(0, 128, (32, 64)).astype(np.int32),
        "targets": rng.integers(0, 128, (32, 64)).astype(np.int32),
    }

    def temp_bytes(schedule):
        model = GPT2(gpt2_config(
            "test", num_layers=4, dtype=jnp.float32, pipeline_stages=4,
            pipeline_microbatches=16, pp_schedule=schedule, remat=True,
            remat_policy="full"))
        tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                     mesh=create_mesh(data=2, pipe=4), strategy="dp")
        tr.init(batch)
        from pytorchdistributed_tpu.data.loader import shard_batch
        with jax.set_mesh(tr.mesh):
            sharded = shard_batch(batch, tr.batch_sharding)
            compiled = tr._step_fn.lower(tr.state, sharded).compile()
        ma = compiled.memory_analysis()
        return getattr(ma, "temp_size_in_bytes", None)

    gpipe, f1b = temp_bytes("gpipe"), temp_bytes("1f1b")
    if gpipe is None or f1b is None:
        pytest.skip("memory_analysis unavailable on this backend")
    assert f1b < 0.8 * gpipe, (
        f"1F1B scratch {f1b} not materially below GPipe's {gpipe}")


def test_1f1b_validations():
    # the fused schedule needs the scanned (stage-stacked) parameter layout
    model = GPT2(gpt2_config("test", num_layers=4, scan_layers=False,
                             pipeline_stages=2, pp_schedule="1f1b"))
    with pytest.raises(ValueError, match="scan_layers"):
        model.pipeline_parts()
    # models without a pipeline decomposition reject the 1f1b step builder
    import dataclasses

    from pytorchdistributed_tpu.models.resnet import ResNet, ResNetConfig
    from pytorchdistributed_tpu.training import cross_entropy_loss

    @dataclasses.dataclass(frozen=True)
    class _PipeResNetConfig(ResNetConfig):
        # pipeline knobs so the Trainer picks the 1f1b builder; ResNet
        # itself has no pipeline_parts() decomposition
        pipeline_stages: int = 2
        pp_schedule: str = "1f1b"
        dropout_rate: float = 0.0

    resnet = ResNet(_PipeResNetConfig(num_classes=10, cifar_stem=True,
                                      stage_sizes=(1, 1), bottleneck=False))
    tr = Trainer(resnet, optax.sgd(1e-2), cross_entropy_loss,
                 mesh=create_mesh(data=4, pipe=2), strategy="dp")
    batch = {"image": np.zeros((8, 32, 32, 3), np.float32),
             "label": np.zeros((8,), np.int32)}
    with pytest.raises(ValueError, match="pipeline_parts"):
        tr.train_step(batch)


def test_vit_1f1b_loss_equivalence():
    """ViT rides the fused 1F1B schedule too (PatchEmbed pre-stage, CLS
    classifier head): pipelined loss curve == sequential."""
    from pytorchdistributed_tpu.models import ViT, vit_config
    from pytorchdistributed_tpu.training import cross_entropy_loss

    rng = np.random.default_rng(12)
    batch = {"image": rng.standard_normal((16, 32, 32, 3)).astype(np.float32),
             "label": rng.integers(0, 10, (16,)).astype(np.int32)}

    def run(cfg_kw, axes):
        model = ViT(vit_config("test", image_size=32, patch_size=8,
                               num_classes=10, num_layers=4,
                               dtype=jnp.float32, **cfg_kw))
        tr = Trainer(model, optax.sgd(1e-2), cross_entropy_loss,
                     mesh=create_mesh(**axes), strategy="dp")
        return [float(tr.train_step(batch)["loss"]) for _ in range(3)]

    seq = run(dict(), dict())
    f1b = run(dict(pipeline_stages=4, pipeline_microbatches=4,
                   pp_schedule="1f1b"), dict(data=2, pipe=4))
    np.testing.assert_allclose(f1b, seq, atol=2e-5)


def test_pipeline_validations():
    # micro-batch count must divide the global batch (16)
    with pytest.raises(ValueError, match="divisible"):
        _run_losses(dict(pipeline_stages=2, pipeline_microbatches=3),
                    dict(data=4, pipe=2), steps=1)
    # stage count must match the mesh's pipe axis
    with pytest.raises(ValueError, match="pipe axis"):
        _run_losses(dict(pipeline_stages=2, pipeline_microbatches=2),
                    dict(data=2, pipe=4), steps=1)


def test_gpipe_dropout_key_routing():
    """Dropout keys must route to the right (stage, micro-batch) pair: the
    pipelined output with a stochastic stage equals a handwritten
    sequential loop using stage_microbatch_key — exact, not statistical."""
    from pytorchdistributed_tpu.parallel.pipeline import stage_microbatch_key

    rng = np.random.default_rng(5)
    p, b, d, m = 2, 8, 16, 4
    params = jnp.asarray(rng.standard_normal((p, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
    base = jax.random.key(42)

    def stage_apply(w, h, key):
        h = jnp.tanh(h @ w)
        keep = jax.random.bernoulli(key, 0.5, h.shape)
        return jnp.where(keep, h / 0.5, 0.0)

    mesh = create_mesh(data=4, pipe=2)
    with jax.set_mesh(mesh):
        out = gpipe_spmd(stage_apply, params, x, num_microbatches=m,
                         remat=False, dropout_rng=base)

    mb = b // m
    chunks = []
    for k in range(m):
        h = x[k * mb:(k + 1) * mb]
        for s in range(p):
            h = stage_apply(params[s], h, stage_microbatch_key(base, s, k))
        chunks.append(h)
    np.testing.assert_allclose(out, jnp.concatenate(chunks), atol=1e-5)


def test_one_f_one_b_dropout_matches_sequential_grads():
    """1F1B with dropout: loss AND grads equal sequential AD with the same
    per-(stage, micro-batch) keys — which also proves the backward slot's
    recompute re-derives the forward's exact dropout masks (mismatched
    masks would corrupt every gradient)."""
    from pytorchdistributed_tpu.parallel.pipeline import stage_microbatch_key

    rng = np.random.default_rng(6)
    p, b, d, m = 2, 8, 8, 4
    sp = jnp.asarray(rng.standard_normal((p, d, d)) * 0.3, jnp.float32)
    hw = jnp.asarray(rng.standard_normal((d, 3)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((b, 3)), jnp.float32)
    base = jax.random.key(13)

    def stage_apply(w, h, key):
        h = jnp.tanh(h @ w)
        keep = jax.random.bernoulli(key, 0.8, h.shape)
        return jnp.where(keep, h / 0.8, 0.0)

    def head_loss(w, h, tt):
        return jnp.mean((h @ w - tt) ** 2)

    mesh = create_mesh(data=4, pipe=2)
    with jax.set_mesh(mesh):
        loss, sg, hg, dx = one_f_one_b(
            stage_apply, sp, head_loss, hw, x, t, num_microbatches=m,
            dropout_rng=base)

    mb = b // m

    def ref(sp, hw, xx):
        tot = 0.0
        for k in range(m):
            h = xx[k * mb:(k + 1) * mb]
            for s in range(p):
                h = stage_apply(sp[s], h, stage_microbatch_key(base, s, k))
            tot = tot + head_loss(hw, h, t[k * mb:(k + 1) * mb])
        return tot / m

    rl, (rsg, rhg, rdx) = jax.value_and_grad(ref, argnums=(0, 1, 2))(sp, hw, x)
    np.testing.assert_allclose(float(loss), float(rl), atol=1e-6)
    np.testing.assert_allclose(sg, rsg, atol=1e-5)
    np.testing.assert_allclose(hg, rhg, atol=1e-5)
    np.testing.assert_allclose(dx, rdx, atol=1e-5)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_gpt2_pipelined_dropout_trains(schedule):
    """Dropout now rides both pipeline schedules (VERDICT r2 next #3): the
    stochastic run is finite and differs from the deterministic one (units
    actually drop), and training still converges stepwise."""
    def run(rate):
        model = GPT2(gpt2_config(
            "test", num_layers=4, dropout_rate=rate, dtype=jnp.float32,
            pipeline_stages=2, pipeline_microbatches=2,
            pp_schedule=schedule))
        tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                     mesh=create_mesh(data=4, pipe=2), strategy="dp")
        return [float(tr.train_step(_BATCH)["loss"]) for _ in range(3)]

    dropped, det = run(0.2), run(0.0)
    assert all(np.isfinite(dropped)), dropped
    assert dropped != det, "dropout_rate=0.2 changed nothing in the pipeline"


def test_moe_pipeline_gpipe_1f1b_equivalence():
    """Switch-MoE rides both schedules (VERDICT r2 next #4) with the same
    objective: ce + aux averaged over micro-batches and layers — so the
    GPipe loss curve (aux collected through the schedule and re-sown) must
    equal the fused 1F1B one (aux seeded in the backward slots)."""
    from pytorchdistributed_tpu.training import moe_token_cross_entropy_loss

    def run(schedule):
        model = GPT2(gpt2_config(
            "test", num_layers=4, dtype=jnp.float32, moe_experts=4,
            moe_capacity_factor=2.0, pipeline_stages=2,
            pipeline_microbatches=2, pp_schedule=schedule))
        tr = Trainer(model, optax.sgd(1e-2), moe_token_cross_entropy_loss,
                     mesh=create_mesh(data=2, expert=2, pipe=2),
                     strategy="tp")
        return [float(tr.train_step(_BATCH)["loss"]) for _ in range(3)]

    np.testing.assert_allclose(run("1f1b"), run("gpipe"), atol=2e-5)


def test_1f1b_custom_loss_raises():
    """A custom loss_fn cannot ride the fused pipeline — must raise, not
    warn-and-train-a-different-objective (VERDICT r2 weak #3)."""
    def my_loss(model, params, batch, rng=None):
        return jnp.float32(0.0), {}

    model = GPT2(gpt2_config("test", num_layers=4, pipeline_stages=2,
                             pipeline_microbatches=2, pp_schedule="1f1b"))
    tr = Trainer(model, optax.sgd(1e-2), my_loss,
                 mesh=create_mesh(data=4, pipe=2), strategy="dp")
    with pytest.raises(ValueError, match="loss"):
        tr.train_step(_BATCH)

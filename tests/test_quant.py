"""Int8 quantized-training tests (ops/quant.py, ISSUE 1).

Two bars, mirroring the suite's loss-curve-equivalence discipline:

  * unit numerics — the quantized ``dot_general`` is EXACT for
    power-of-two-scaled inputs (per-channel scales hit representable
    grids), the ``int8_fwd`` backward is bit-identical to the reference
    dot's VJP (it runs on the saved full-precision operands), stochastic
    rounding is unbiased;
  * training parity — ``--quant int8_fwd`` reproduces the bf16 loss curve
    on the small GPT-2/MLP configs across dp, fsdp and tp on the 8-device
    CPU sim within ``PARITY_TOL`` nats (the documented tolerance for the
    acceptance criterion: same data, same init, 8 steps at lr 1e-2 —
    measured deltas sit at 0.003-0.11, the bound leaves ~2x headroom while
    still catching a wrong-scale or wrong-transpose bug, which blows the
    curve apart immediately).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from pytorchdistributed_tpu.ops.quant import (
    absmax_scale,
    dot_general_for,
    quantized_dot_general,
    stochastic_quantize,
)

# documented acceptance tolerance: |final bf16 loss - final int8_fwd loss|
# after 8 steps on the test-width configs (see module docstring)
PARITY_TOL = 0.25

_2D = (((1,), (0,)), ((), ()))


class TestQuantDot:
    def test_power_of_two_exact(self):
        """Per-channel scales make the int8 dot EXACT when every channel
        is integers in [-127, 127] times a power-of-two scale: absmax/127
        is then itself a power of two, quantization is lossless, the int32
        contraction is exact, and the fp32 rescale multiplies by exact
        powers of two (ISSUE 1 satellite)."""
        rng = np.random.default_rng(0)
        kx = rng.integers(-3, 4, (16, 1)).astype(np.float32)
        xv = rng.integers(-127, 128, (16, 64)).astype(np.float32)
        xv[:, 0] = 127  # pin each row's absmax to the full code range
        x = jnp.asarray(xv * 2.0 ** kx)
        kw = rng.integers(-3, 4, (1, 8)).astype(np.float32)
        wv = rng.integers(-127, 128, (64, 8)).astype(np.float32)
        wv[0, :] = 127
        w = jnp.asarray(wv * 2.0 ** kw)
        out = quantized_dot_general("int8_fwd")(x, w, _2D)
        ref = lax.dot_general(x, w, _2D)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_close_to_fp_reference(self):
        """Random gaussians: int8 with per-channel scales lands within ~2%
        relative error of the fp32 dot (the expected quantization noise
        level — a wrong scale axis is an order of magnitude off)."""
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((128, 32)), jnp.float32)
        out = quantized_dot_general("int8_fwd")(x, w, _2D)
        ref = lax.dot_general(x, w, _2D)
        rel = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
        assert rel < 0.02, rel

    def test_int8_fwd_backward_is_reference_vjp(self):
        """mode="int8_fwd" saves the UNquantized operands and runs the
        ordinary dot VJP on them — gradients must equal the reference
        dot's exactly (bit-for-bit, same dtypes)."""
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((16, 3, 24)), jnp.bfloat16)
        dg = quantized_dot_general("int8_fwd")

        def loss(dot):
            return lambda x, k: jnp.einsum(
                "bse,ecf->bscf", x, k, _dot_general=dot
            ).astype(jnp.float32).sum()

        gx, gk = jax.grad(loss(dg), argnums=(0, 1))(x, k)
        rx, rk = jax.grad(loss(lax.dot_general), argnums=(0, 1))(x, k)
        np.testing.assert_array_equal(np.asarray(gx), np.asarray(rx))
        np.testing.assert_array_equal(np.asarray(gk), np.asarray(rk))

    def test_int8_backward_close(self):
        """mode="int8" quantizes both grad contractions (stochastic
        rounding on the cotangent): grads land within int8 noise of the
        reference — and the transpose bookkeeping (_grad_dims) is
        exercised on a non-identity permutation (contraction over lhs
        dim 0)."""
        rng = np.random.default_rng(3)
        for dims, xs, ws in [
            (_2D, (16, 32), (32, 8)),
            ((((0,), (0,)), ((), ())), (32, 16), (32, 8)),
        ]:
            x = jnp.asarray(rng.standard_normal(xs), jnp.float32)
            w = jnp.asarray(rng.standard_normal(ws), jnp.float32)
            g8 = jax.grad(
                lambda x, w: quantized_dot_general("int8")(
                    x, w, dims).sum(), argnums=(0, 1))(x, w)
            gr = jax.grad(
                lambda x, w: lax.dot_general(x, w, dims).sum(),
                argnums=(0, 1))(x, w)
            for a, b in zip(g8, gr):
                rel = float(jnp.abs(a - b).max()
                            / jnp.maximum(jnp.abs(b).max(), 1e-6))
                assert rel < 0.05, (dims, rel)

    def test_stochastic_rounding_unbiased(self):
        """E[dequantize(sr_quantize(x))] = x: over a dense value sweep the
        mean rounding error stays < 1e-3 of one quantum — ~5 standard
        errors at N=2e6 (SE = sqrt(1/12)/sqrt(N) ≈ 2e-4) plus the hash
        mixer's measured ~3e-4 residual non-ideality. Round-to-nearest
        has no such bound at ±0.5 fractional offsets — the systematic
        bias SR exists to kill is O(0.5) there."""
        rng = np.random.default_rng(4)
        y = jnp.asarray(rng.uniform(0, 100, (2_000_000,)), jnp.float32)
        scale = jnp.float32(100.0 / 127.0)
        deq = stochastic_quantize(y, scale).astype(jnp.float32) * scale
        bias = float((deq - y).mean()) / float(scale)
        assert abs(bias) < 1e-3, bias

    def test_scale_shapes_per_channel(self):
        x = jnp.ones((4, 8, 16))
        assert absmax_scale(x, (2,)).shape == (4, 8, 1)
        assert absmax_scale(x, (0, 1)).shape == (1, 1, 16)

    def test_preferred_element_type_and_promotion(self):
        x = jnp.ones((4, 8), jnp.bfloat16)
        w = jnp.ones((8, 2), jnp.bfloat16)
        dg = quantized_dot_general("int8_fwd")
        assert dg(x, w, _2D).dtype == jnp.bfloat16
        assert dg(x, w, _2D,
                  preferred_element_type=jnp.float32).dtype == jnp.float32

    def test_batch_dims_rejected(self):
        x = jnp.ones((2, 4, 8))
        w = jnp.ones((2, 8, 3))
        with pytest.raises(NotImplementedError):
            quantized_dot_general("int8")(
                x, w, (((2,), (1,)), ((0,), (0,))))

    def test_mode_validation(self):
        with pytest.raises(ValueError):
            quantized_dot_general("int4")
        assert dot_general_for("none") is None
        assert dot_general_for(None) is None
        # cached: every call site shares one callable per mode (jit/flax
        # caches key on identity)
        assert (quantized_dot_general("int8_fwd")
                is quantized_dot_general("int8_fwd"))


# ---------------------------------------------------------------------------
# training parity (the ISSUE 1 acceptance criterion)
# ---------------------------------------------------------------------------


def _train_losses(strategy, axes, quant, steps=8):
    """8 steps on one repeated batch through config.make_trainer — the
    full --quant flag wiring (ExperimentConfig → TransformerConfig.quant +
    Policy.int8_fwd) is what's under test, not a hand-built Trainer."""
    from pytorchdistributed_tpu.config import ExperimentConfig, make_trainer

    cfg = ExperimentConfig(
        model="gpt2", model_size="test", strategy=strategy, quant=quant,
        seq_len=32, batch_size=8, dataset_size=64, learning_rate=1e-2,
        seed=0, watchdog=False, **axes)
    trainer, loader = make_trainer(cfg)
    batch = next(iter(loader))
    return [float(trainer.train_step(batch)["loss"]) for _ in range(steps)]


def _assert_parity(strategy, axes):
    bf16 = _train_losses(strategy, axes, "none")
    int8 = _train_losses(strategy, axes, "int8_fwd")
    assert int8[-1] < int8[0], f"{strategy}: int8_fwd did not learn {int8}"
    assert bf16[-1] < bf16[0], f"{strategy}: bf16 did not learn {bf16}"
    delta = abs(bf16[-1] - int8[-1])
    assert delta < PARITY_TOL, (
        f"{strategy}: |bf16 - int8_fwd| final-loss delta {delta:.4f} "
        f"exceeds the documented tolerance {PARITY_TOL} "
        f"(bf16 {bf16}, int8_fwd {int8})")


def test_parity_dp():
    _assert_parity("dp", {})


def test_parity_fsdp():
    _assert_parity("fsdp", dict(data=2, fsdp=4))


def test_parity_tp():
    _assert_parity("tp", dict(data=2, tensor=4))


def test_mlp_parity_dp():
    """The MLP toy through Policy.dot_general() (the non-transformer
    injection path): quantized regression training tracks bf16."""
    import optax

    from pytorchdistributed_tpu.data import SyntheticRegressionDataset
    from pytorchdistributed_tpu.models import MLP
    from pytorchdistributed_tpu.parallel import Policy
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import Trainer, mse_loss

    ds = SyntheticRegressionDataset(64, seed=0)
    batch = ds[np.arange(32)]

    def run(policy):
        model = MLP(dot_general=policy.dot_general())
        tr = Trainer(model, optax.adamw(1e-2), mse_loss,
                     mesh=create_mesh(), strategy="dp", watchdog=False)
        return [float(tr.train_step(batch)["loss"]) for _ in range(8)]

    bf16 = run(Policy.bf16())
    int8 = run(Policy.int8_fwd())
    assert int8[-1] < int8[0]
    assert abs(bf16[-1] - int8[-1]) < PARITY_TOL, (bf16, int8)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_parity_pipeline(schedule):
    """Quant x pipeline parallelism: the README claims every strategy picks
    the int8 operands up unmodified, so the pipeline schedules need the
    same parity evidence as dp/fsdp/tp."""
    import dataclasses

    import optax

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    rng = np.random.default_rng(9)
    batch = {
        "tokens": rng.integers(0, 128, (16, 32)).astype(np.int32),
        "targets": rng.integers(0, 128, (16, 32)).astype(np.int32),
    }
    cfg = gpt2_config("test", num_layers=4, pipeline_stages=4,
                      pipeline_microbatches=4, pp_schedule=schedule)

    def run(quant):
        model = GPT2(dataclasses.replace(cfg, quant=quant))
        tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                     mesh=create_mesh(data=2, pipe=4), strategy="dp",
                     watchdog=False)
        return [float(tr.train_step(batch)["loss"]) for _ in range(8)]

    bf16, int8 = run("none"), run("int8_fwd")
    assert int8[-1] < int8[0], int8
    assert abs(bf16[-1] - int8[-1]) < PARITY_TOL, (bf16, int8)


def test_bert_vit_quant_configs_train():
    """The other two transformer families: one quantized step each, finite and learning-shaped."""
    import optax

    from pytorchdistributed_tpu.data import MLMDataset, SyntheticTokenDataset
    from pytorchdistributed_tpu.models import (
        BertMLM,
        ViT,
        bert_config,
        vit_config,
    )
    from pytorchdistributed_tpu.runtime.mesh import create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        cross_entropy_loss,
        token_cross_entropy_loss,
    )

    rng = np.random.default_rng(3)
    bcfg = bert_config("test", quant="int8_fwd")
    ds = MLMDataset(SyntheticTokenDataset(16, 32, bcfg.vocab_size, 0),
                    bcfg.vocab_size, seed=0)
    tr = Trainer(BertMLM(bcfg), optax.adamw(1e-3),
                 token_cross_entropy_loss, mesh=create_mesh(),
                 strategy="dp", watchdog=False)
    losses = [float(tr.train_step(ds[np.arange(16)])["loss"])
              for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses

    vcfg = vit_config("test", image_size=32, num_classes=10,
                      quant="int8_fwd")
    tr = Trainer(ViT(vcfg), optax.adamw(1e-3), cross_entropy_loss,
                 mesh=create_mesh(), strategy="dp", watchdog=False)
    batch = {
        "image": rng.standard_normal((16, 32, 32, 3)).astype(np.float32),
        "label": rng.integers(0, 10, (16,)).astype(np.int32),
    }
    losses = [float(tr.train_step(batch)["loss"]) for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses


def test_int8_full_mode_trains():
    """mode="int8" (quantized backward + stochastic rounding): the loss
    still decreases and stays finite — the convergence smoke for the
    aggressive mode (parity vs bf16 is only claimed for int8_fwd)."""
    losses = _train_losses("dp", {}, "int8", steps=10)
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_quant_flag_validation():
    from pytorchdistributed_tpu.config import ExperimentConfig, _build_model
    from pytorchdistributed_tpu.models import gpt2_config

    with pytest.raises(ValueError, match="quant"):
        _build_model(ExperimentConfig(model="gpt2", model_size="test",
                                      quant="int7"))
    with pytest.raises(ValueError, match="quant"):
        gpt2_config("test", quant="fp8")


def test_quant_preserves_tp_sharding():
    """Sharding annotations survive quantization: under TP the quantized
    model's MLP kernel still splits over the tensor axis (the int8
    converts are elementwise — the partitioner shards them like the bf16
    operands they replace)."""
    import optax

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.runtime.mesh import Axis, create_mesh
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    rng = np.random.default_rng(0)
    model = GPT2(gpt2_config("test", quant="int8_fwd"))
    tr = Trainer(model, optax.adamw(1e-3), token_cross_entropy_loss,
                 mesh=create_mesh(data=2, tensor=4), strategy="tp",
                 watchdog=False)
    batch = {
        "tokens": rng.integers(0, 128, (8, 32)).astype(np.int32),
        "targets": rng.integers(0, 128, (8, 32)).astype(np.int32),
    }
    tr.init(batch)
    wi = tr.state.params["params"]["h"]["block"]["mlp"]["wi"]["kernel"]
    flat = []
    for entry in tuple(wi.sharding.spec):
        flat.extend(entry if isinstance(entry, tuple) else (entry,))
    assert Axis.TENSOR in flat
    assert wi.addressable_shards[0].data.shape[-1] * 4 == wi.shape[-1]

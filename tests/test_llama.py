"""Llama-family tests — the working replacement for the reference's failed
llama-7b `device_map="auto"` cell (03_model_parallel.ipynb:86-89). Bar:
the Llama dialect (RMSNorm/SwiGLU/RoPE/GQA/no-bias) must train under every
strategy of the shared core, with loss equivalence across reshardings."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorchdistributed_tpu.models import Llama, llama_config
from pytorchdistributed_tpu.models.transformer import apply_rope, rope_tables
from pytorchdistributed_tpu.runtime.mesh import Axis, create_mesh
from pytorchdistributed_tpu.training import Trainer, token_cross_entropy_loss


def _token_batch(rng, batch=8, seq=32, vocab=128):
    return {
        "tokens": rng.integers(0, vocab, (batch, seq)).astype(np.int32),
        "targets": rng.integers(0, vocab, (batch, seq)).astype(np.int32),
    }


def test_rope_rotation_properties():
    """RoPE is a pure rotation: it preserves norms, and q·k scores depend
    only on the relative position (the property that makes it a position
    encoding at all)."""
    rng = np.random.default_rng(0)
    s, d = 16, 8
    q = jnp.asarray(rng.standard_normal((1, s, 1, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, 1, d)), jnp.float32)
    cos, sin = rope_tables(s, d, 10000.0)
    qr, kr = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    np.testing.assert_allclose(
        jnp.linalg.norm(qr, axis=-1), jnp.linalg.norm(q, axis=-1), rtol=1e-5)
    # score(i, j) for fixed content must equal score(i+Δ, j+Δ): plant the
    # same q/k content at two absolute offsets and compare the dot products.
    qc = jnp.asarray(rng.standard_normal((d,)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((d,)), jnp.float32)

    def score(i, j):
        qi = apply_rope(jnp.broadcast_to(qc, (1, s, 1, d)), cos, sin)[0, i, 0]
        kj = apply_rope(jnp.broadcast_to(kc, (1, s, 1, d)), cos, sin)[0, j, 0]
        return float(qi @ kj)

    assert score(2, 5) == pytest.approx(score(9, 12), rel=1e-4)
    assert score(5, 2) == pytest.approx(score(12, 9), rel=1e-4)


@pytest.mark.parametrize("strategy,axes", [
    ("dp", dict()),
    ("tp_fsdp", dict(data=2, fsdp=2, tensor=2)),
])
def test_llama_strategies_train(strategy, axes):
    rng = np.random.default_rng(0)
    model = Llama(llama_config("test"))
    tr = Trainer(model, optax.adamw(1e-3), token_cross_entropy_loss,
                 mesh=create_mesh(**axes), strategy=strategy)
    batch = _token_batch(rng)
    l0 = float(tr.train_step(batch)["loss"])
    for _ in range(3):
        m = tr.train_step(batch)
    assert float(m["loss"]) < l0


def test_llama_gqa_params_no_bias():
    """GQA splits the projection into q + fused kv kernels (both head-dim
    sharded under TP), and use_bias=False leaves no bias anywhere."""
    rng = np.random.default_rng(0)
    cfg = llama_config("test")  # 4 heads, 2 kv heads
    model = Llama(cfg)
    tr = Trainer(model, optax.adamw(1e-3), token_cross_entropy_loss,
                 mesh=create_mesh(data=2, tensor=4), strategy="tp")
    tr.init(_token_batch(rng))
    attn = tr.state.params["params"]["h"]["block"]["attn"]
    assert attn["q_kernel"].shape[1:] == (
        cfg.embed_dim, cfg.num_heads * cfg.head_dim)
    assert attn["kv_kernel"].shape[1:] == (
        cfg.embed_dim, 2, cfg.kv_heads * cfg.head_dim)
    flat = jax.tree_util.tree_leaves_with_path(tr.state.params)
    names = ["/".join(str(k) for k in path) for path, _ in flat]
    assert not any("bias" in n for n in names)
    spec = []
    for entry in tuple(attn["q_kernel"].sharding.spec):
        spec.extend(entry if isinstance(entry, tuple) else (entry,))
    assert Axis.TENSOR in spec


def test_llama_fsdp_matches_dp_loss():
    rng = np.random.default_rng(1)
    batch = _token_batch(rng)
    losses = {}
    for strategy, axes in [("dp", dict()), ("fsdp", dict(data=2, fsdp=4))]:
        model = Llama(llama_config("test", dtype=np.float32))
        tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                     mesh=create_mesh(**axes), strategy=strategy)
        losses[strategy] = [float(tr.train_step(batch)["loss"])
                            for _ in range(3)]
    from tests.test_models import FSDP_EQUIVALENCE_TOL

    tol = FSDP_EQUIVALENCE_TOL
    np.testing.assert_allclose(losses["dp"], losses["fsdp"],
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_llama_pipeline_loss_equivalence(schedule):
    rng = np.random.default_rng(7)
    batch = _token_batch(rng, batch=16)

    def run(cfg_kw, axes):
        model = Llama(llama_config("test", num_layers=4, dtype=jnp.float32,
                                   **cfg_kw))
        tr = Trainer(model, optax.sgd(1e-2), token_cross_entropy_loss,
                     mesh=create_mesh(**axes), strategy="dp")
        return [float(tr.train_step(batch)["loss"]) for _ in range(3)]

    seq = run(dict(), dict())
    pp = run(dict(pipeline_stages=4, pipeline_microbatches=4,
                  pp_schedule=schedule), dict(data=2, pipe=4))
    np.testing.assert_allclose(pp, seq, atol=2e-5)

"""Migrate a PyTorch model into the TPU framework and keep working.

The reference lives in the torch ecosystem; this is the bridge for its
users: take a ``GPT2LMHeadModel`` (here randomly initialized — substitute
``from_pretrained(...)`` where downloads are available), relay its
``state_dict`` into this framework (models/torch_import.py), verify the
logits agree with the torch forward, fine-tune a few sharded DDP steps,
and sample from the result with the KV-cache decode loop. Run anywhere:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/torch_migrate.py

or on TPU hardware with no flags.
"""

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import optax

import jax
import jax.numpy as jnp
from pytorchdistributed_tpu.inference import generate
from pytorchdistributed_tpu.models import GPT2, gpt2_config
from pytorchdistributed_tpu.models.torch_import import gpt2_params_from_torch
from pytorchdistributed_tpu.runtime.mesh import create_mesh
from pytorchdistributed_tpu.training import Trainer, token_cross_entropy_loss
from pytorchdistributed_tpu.training.trainer import TrainState


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    args = parser.parse_args()

    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    import torch
    import transformers

    # 1. the torch model (stand-in for a pretrained checkpoint)
    hf_cfg = transformers.GPT2Config(
        vocab_size=256, n_positions=128, n_embd=64, n_layer=2, n_head=4,
        activation_function="gelu_new",
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    torch.manual_seed(0)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()

    # 2. import the weights
    cfg = gpt2_config("test", vocab_size=256, dtype=jnp.float32,
                      attention="dense", scan_layers=False)
    params = gpt2_params_from_torch(hf.state_dict(), cfg)

    # 3. parity check against the torch forward
    tokens = np.random.default_rng(0).integers(0, 256, (2, 16))
    with torch.no_grad():
        want = hf(torch.asarray(tokens)).logits.numpy()
    got = GPT2(cfg).apply(params, jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    print(f"parity: imported logits match torch "
          f"(max |Δ| = {np.abs(np.asarray(got) - want).max():.2e})")

    # 4. fine-tune, sharded DDP over every device
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 256, (32, 17)).astype(np.int32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    tr = Trainer(GPT2(cfg), optax.adamw(1e-3), token_cross_entropy_loss,
                 mesh=create_mesh(), strategy="dp", log_every=10)
    tr.init(batch)
    tr.state = TrainState(step=tr.state.step,
                          params=jax.device_put(params,
                                                tr.state_shardings.params),
                          opt_state=tr.state.opt_state)
    metrics = None
    for _ in range(args.steps):
        metrics = tr.train_step(batch)
    loss = f", loss {float(metrics['loss']):.4f}" if metrics else ""
    print(f"fine-tuned {args.steps} steps on "
          f"{tr.mesh.devices.size} device(s){loss}")

    # 5. sample with the KV-cache decode loop
    dm = GPT2(dataclasses.replace(cfg, decode=True))
    out = generate(dm, tr.state.params,
                   jnp.asarray(tokens[:, :8], jnp.int32),
                   max_new_tokens=8, temperature=0.0)
    print(f"generated: {np.asarray(out)[:, 8:].tolist()}")


if __name__ == "__main__":
    main()

"""Train-then-generate example — the working version of the reference's
inference ambition (the llama-7b `device_map="auto"` cell,
03_model_parallel.ipynb:86-89, which never ran).

Trains a tiny Llama on a synthetic identity task (predict the current
token), then samples continuations with the KV-cache decode loop — greedy
generation visibly repeats the prompt's last token, the learned behavior. Run anywhere:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/generate.py --steps 200

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
import pytorchdistributed_tpu as ptd
from pytorchdistributed_tpu.models import Llama, llama_config
from pytorchdistributed_tpu.training import Trainer, token_cross_entropy_loss


def main():
    parser = argparse.ArgumentParser(description="train + generate demo")
    parser.add_argument("--steps", type=int, default=200)
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--top_k", type=int, default=None)
    args = parser.parse_args()

    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    ptd.init_process_group()
    cfg = llama_config("test", max_seq_len=64)
    model = Llama(cfg)
    trainer = Trainer(model, optax.adamw(3e-3), token_cross_entropy_loss,
                      mesh=ptd.create_mesh(), strategy="dp", log_every=50)

    # identity task: target[t] = token[t] — generalizes to unseen prompts,
    # so greedy generation visibly repeats the prompt's last token forever
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab_size, (32, 32)).astype(np.int32)
    batch = {"tokens": tokens, "targets": tokens.copy()}
    for step in range(args.steps):
        metrics = trainer.train_step(batch)
        # force the async dispatch each step: XLA:CPU's collective
        # rendezvous deadlocks past ~dozens of queued 8-device programs
        # (Trainer.fit's per-step logging does this for real jobs)
        float(metrics["loss"])
    print(f"trained {args.steps} steps, loss "
          f"{float(metrics['loss']):.4f}")

    gen_model = Llama(dataclasses.replace(cfg, decode=True))
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 8)), jnp.int32)
    out = ptd.generate(gen_model, {"params": trainer.state.params["params"]},
                       prompt, max_new_tokens=12,
                       temperature=args.temperature, top_k=args.top_k,
                       rng=jax.random.key(0))
    for row in np.asarray(out):
        print("prompt:", row[:8].tolist(), "->", row[8:].tolist())
    ptd.destroy_process_group()


if __name__ == "__main__":
    main()

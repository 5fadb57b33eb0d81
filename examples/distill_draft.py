"""Learned-drafting demo (ISSUE 16): distill a draft, serve, hot-swap.

The full loop on a CPU-sized model, end to end:

  1. TRAIN a tiny GPT-2 target on a seeded successor-permutation
     language (token t+1 = succ[token t]) — depth has to do real work,
     or a truncated draft is trivially close to its teacher;
  2. DISTILL a 1-layer student with multi-token proposal heads against
     the target's logits over a logged-traffic corpus (DistillTrainer:
     the unchanged Trainer loop under the hood);
  3. SERVE with the UNTRAINED truncated warm start and measure
     acceptance;
  4. HOT-SWAP the distilled draft in MID-STREAM via set_draft_params —
     resident requests keep their token-for-token identity (speculative
     decoding is lossless under any draft; the demo asserts bitwise
     parity vs generate()) while acceptance and decode throughput jump.

Run anywhere:

    JAX_PLATFORMS=cpu python examples/distill_draft.py

A fleet does the same swap in one call: ReplicaRouter.set_draft_params
broadcasts a DistillTrainer checkpoint path to every replica (see
README "Learned drafting").
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import optax

import jax
import jax.numpy as jnp
from pytorchdistributed_tpu.inference import generate, make_draft
from pytorchdistributed_tpu.models import GPT2, gpt2_config
from pytorchdistributed_tpu.serving import ServingEngine
from pytorchdistributed_tpu.training import (
    DistillTrainer,
    Trainer,
    distill_corpus,
    token_cross_entropy_loss,
)


def main():
    parser = argparse.ArgumentParser(description="distill-draft demo")
    parser.add_argument("--target-steps", type=int, default=150)
    parser.add_argument("--distill-epochs", type=int, default=24)
    parser.add_argument("--spec-k", type=int, default=4)
    parser.add_argument("--requests", type=int, default=6)
    args = parser.parse_args()

    from pytorchdistributed_tpu.runtime.xla_cache import use_persistent_cache

    use_persistent_cache()

    cfg = gpt2_config("test", num_layers=4, max_seq_len=128)
    model = GPT2(cfg)
    spec_k = args.spec_k

    # -- 1. train the target on the successor-permutation language ----
    succ = np.random.default_rng(11).permutation(cfg.vocab_size)

    def rows(rng, n, s):
        out = np.empty((n, s), np.int32)
        out[:, 0] = rng.integers(0, cfg.vocab_size, n)
        for t in range(1, s):
            out[:, t] = succ[out[:, t - 1]]
        return out

    trainer = Trainer(model, optax.adamw(3e-3), token_cross_entropy_loss,
                      log_every=10**9)
    rng = np.random.default_rng(5)

    def lm_batch():
        r = rows(rng, 16, 96)
        return {"tokens": r[:, :-1], "targets": r[:, 1:]}

    trainer.init(lm_batch())
    m = None
    for _ in range(args.target_steps):
        m = trainer.train_step(lm_batch())
    params = jax.device_get(trainer.state.params)
    print(f"target trained: {args.target_steps} steps, "
          f"ce {float(m['loss']):.4f}")

    # -- 2. distill the draft (truncated warm start + proposal heads) --
    corpus = distill_corpus(model, params, seed=7, num_batches=4,
                            batch_size=8, seq_len=64, max_new_tokens=12)
    dt = DistillTrainer(model, params, num_layers=1,
                        spec_heads=spec_k - 1)
    dt.init(corpus[0])
    first = last = None
    for _ in range(args.distill_epochs):
        for b in corpus:
            mm = dt.train_step(b)
            if first is None:
                first = float(mm["loss"])
    last = float(mm["loss"])
    print(f"distilled: {args.distill_epochs} epochs, "
          f"kl {first:.4f} -> {last:.4f}")
    _, distilled = dt.draft()

    # -- 3. serve on the UNTRAINED truncated warm start ---------------
    warm_model, warm = make_draft(model, params, num_layers=1,
                                  spec_heads=spec_k - 1)
    engine = ServingEngine(model, params, num_slots=3, prefill_bucket=32,
                           block_size=16, spec_k=spec_k,
                           draft_config=warm_model.cfg, draft_params=warm,
                           adaptive_k=True)
    engine.warmup(prompt_lens=(32,))
    prng = np.random.default_rng(3)
    prompts = [prng.integers(0, cfg.vocab_size, (m,)).astype(np.int32)
               for m in (9, 14, 7, 11, 6, 13)][:args.requests]
    for p in prompts:
        engine.submit(p, max_new_tokens=24)
        engine.step()
    engine.run_until_idle()
    s0 = engine.summary()
    print(f"truncated draft ({engine.draft_params_hash()}): "
          f"acceptance {s0['acceptance_rate']:.3f}, "
          f"{s0['tokens_per_target_forward']:.2f} tokens/target-forward")

    # -- 4. hot-swap the distilled draft MID-STREAM --------------------
    reqs = [engine.submit(p, max_new_tokens=24) for p in prompts]
    engine.step()
    engine.set_draft_params(distilled)
    print(f"hot-swap mid-stream -> draft {engine.draft_params_hash()} "
          f"(swap #{engine.draft_swaps})")
    engine.run_until_idle()
    s1 = engine.summary()
    drafted = s1["draft_tokens"] - s0["draft_tokens"]
    accepted = s1["accepted_tokens"] - s0["accepted_tokens"]
    print(f"distilled draft: acceptance {accepted / drafted:.3f} "
          f"over the swapped phase (fleet swap: "
          f"ReplicaRouter.set_draft_params(checkpoint=...))")

    # losslessness: streams that crossed the swap are bitwise-equal to
    # plain generate()
    import dataclasses

    dm = GPT2(dataclasses.replace(cfg, decode=True))
    for p, r in zip(prompts, reqs):
        ref = generate(dm, params, jnp.asarray(p)[None], max_new_tokens=24)
        np.testing.assert_array_equal(r.output_ids, np.asarray(ref)[0])
    print("bitwise parity vs generate() across the swap: OK")
    engine.close()


if __name__ == "__main__":
    main()

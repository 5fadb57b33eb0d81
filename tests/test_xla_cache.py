"""The one compile cache: a second process finds the programs of the
measured paths in JAX's persistent compilation cache
(runtime/xla_cache.py, the cache `benchmark/run.py` switches on and whose
misses its `compile_cache_misses` counts).

Two child processes share one ``JAX_COMPILATION_CACHE_DIR``. The first
compiles and stores; the second must count zero
``/jax/compilation_cache/cache_misses`` events and give the first's
tokens or losses bitwise. A child runs this file as a script, one device,
with the cache's floors (compile time, entry size) at zero so that the
toy programs are stored at all.
"""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

_MISS = "/jax/compilation_cache/cache_misses"
_HIT = "/jax/compilation_cache/cache_hits"
_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _serve(engine, vocab):
    """Tokens of three seeded requests through warm-up and a drain: the
    tick and the chunk (three chunks for the longest prompt)."""
    engine.warmup(prompt_lens=(8,))
    rng = np.random.default_rng(0)
    reqs = [engine.submit(rng.integers(0, vocab, n).astype(np.int32),
                          max_new_tokens=m)
            for n, m in ((5, 6), (19, 4), (11, 8))]
    engine.run_until_idle()
    engine.close()
    return [[int(t) for t in r.new_tokens] for r in reqs]


def _engine():
    import jax
    import jax.numpy as jnp

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.serving import ServingEngine

    cfg = gpt2_config("test", num_layers=2, max_seq_len=64)
    model = GPT2(cfg)
    params = model.init(jax.random.key(1), jnp.zeros((1, 4), jnp.int32))
    return _serve(ServingEngine(model, params, num_slots=3, block_size=4,
                                prefill_chunk=8, prefix_cache=False),
                  cfg.vocab_size)


def _dots3():
    import jax

    from benchmark import manifest, reference
    from tests.test_latent_serving import TOY, make_engine

    family = manifest.load_family(manifest.BENCH_DIR, "dots3_note")
    weights = jax.jit(lambda s: family.make_weights(TOY, s))(
        reference.seed_u32(2 ** 31 + 5))
    return _serve(make_engine(family, TOY, weights), TOY["vocab_size"])


def _trainer():
    import optax

    from pytorchdistributed_tpu.models import GPT2, gpt2_config
    from pytorchdistributed_tpu.training import (
        Trainer,
        token_cross_entropy_loss,
    )

    trainer = Trainer(GPT2(gpt2_config("test", num_layers=2)),
                      optax.adamw(3e-4), token_cross_entropy_loss,
                      log_every=10 ** 9)
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(3):
        batch = {k: rng.integers(0, 128, (4, 32)).astype(np.int32)
                 for k in ("tokens", "targets")}
        losses.append(float(trainer.train_step(batch)["loss"]).hex())
    return losses


CASES = {"engine": _engine, "trainer": _trainer, "dots3": _dots3}


def _child(case: str) -> None:
    import jax
    import jax.monitoring

    from pytorchdistributed_tpu.runtime.xla_cache import (
        use_persistent_cache,
    )

    cache_dir = use_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    seen = {_MISS: 0, _HIT: 0}

    def on_event(name, **_):
        if name in seen:
            seen[name] += 1

    jax.monitoring.register_event_listener(on_event)
    out = CASES[case]()
    print(json.dumps({"misses": seen[_MISS], "hits": seen[_HIT],
                      "cache_dir": cache_dir, "out": out}))


def _run_child(case, cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               PYTHONPATH=str(_ROOT))
    env.pop("XLA_FLAGS", None)  # one device: the cells' own layout
    proc = subprocess.run([sys.executable, __file__, case], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", sorted(CASES))
def test_second_process_starts_warm_from_the_persistent_cache(case,
                                                              tmp_path):
    cold = _run_child(case, tmp_path / "cache")
    warm = _run_child(case, tmp_path / "cache")
    # the directory the environment names, and no other
    assert cold["cache_dir"] == warm["cache_dir"] == str(tmp_path / "cache")
    assert cold["misses"] > 0, cold
    # every program the first process asked the cache for (it finds a
    # small one again that it stored itself) the second one finds
    assert warm["misses"] == 0, warm
    assert warm["hits"] == cold["misses"] + cold["hits"], (cold, warm)
    assert warm["out"] == cold["out"]


if __name__ == "__main__":
    _child(sys.argv[1])

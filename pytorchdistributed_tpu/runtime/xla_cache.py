"""Where JAX's persistent compilation cache lives.

One rule for every entry point (chip_smoke.py, benchmark/run.py, the
examples, the replica worker, the test bootstrap): when ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and no code names another directory — the
machine that runs the program decides where compiled programs are kept.
Unset, the cache is ``<checkout>/.jax_cache``: a fixed path, because the
path is part of what a warm start must find again, so never a temporary
name, a pid or a time.

This is JAX's own cache of XLA executables, and the only compile cache
the repo has.
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def use_persistent_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Touches only `jax.config`; initialises no backend."""
    path = os.environ.get(CACHE_DIR_ENV)
    if path:
        return path
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

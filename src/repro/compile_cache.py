# JAX's persistent compilation cache for the repository's entry points
# (chip_smoke.py, examples/, benchmarks/run.py).  Called from their main(),
# never at import and never in tests: a cold process otherwise recompiles
# every (kernel, shape bucket) it touches.
from __future__ import annotations

import os
from pathlib import Path

# A fixed in-checkout path: the directory is part of every entry's key, so a
# cache that moved between runs would never hit.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    the directory is left as it is; otherwise the cache goes to
    ``.jax_cache/`` in the checkout.  Either way every entry is kept, unless
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise: the
    engine's kernels compile in well under JAX's default one-second
    threshold, but there are many of them."""
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)

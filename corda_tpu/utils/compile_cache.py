"""The one persistent-compile-cache rule, for every entry point that may
compile a device kernel (pytest, benchmarks/run.py, chip_smoke.py, the
verifier worker, a node with the Tpu verifier).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
this sets no directory in code. Where it is not, the cache is
``<checkout>/.jax_cache``, resolved from this package's own path — never
from the working directory, a temp name, a pid or the time, because a
cache that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

#: ``<checkout>/.jax_cache`` (this file is corda_tpu/utils/compile_cache.py).
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on under the rule above; call before the
    first compile. Returns the directory in force."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return jax.config.jax_compilation_cache_dir

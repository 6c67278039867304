"""Persistent XLA compilation cache for the launchers and ``chip_smoke.py``.

Every support bucket and every chunk geometry compiles its own kernels, so a
cold process pays the whole compile bill again.  JAX's persistent cache keys
on the directory it lives in, so the directory must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself and this
  module sets nothing;
* otherwise — ``<checkout>/.jax_cache`` (git-ignored), a fixed path, never a
  temp name.
"""
from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # Kernels compile in well under the 1 s default threshold; cache them too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_DIR

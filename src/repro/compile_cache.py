"""Where compiled programs are kept between runs.

Every entry point that drives the chip (``chip_smoke.py``, the scripts in
``benchmarks/``) calls ``use_compile_cache()`` before its first compile.
JAX's persistent compilation cache is keyed, among other things, by its
directory, so the directory must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
    here overrides it;
  * unset: the cache lives at ``<checkout>/.jax_cache`` (gitignored), a
    fixed path with no temp name, pid or time in it.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)

"""Where the persistent XLA compile cache lives.

The cache directory is part of the cache key's surroundings: a directory
that moves between runs (a ``mkdtemp``, a pid, a timestamp) never hits.
So there are exactly two places, and the choice is not the program's:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and
  nothing here sets any other directory — whoever runs the program
  decides where compiled code is kept (the chip machine may come with it
  set so that one call's compiles serve the next).
- unset: ``<checkout>/.jax_cache`` (git-ignored), so two runs from the
  same checkout share compiles.
"""
import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "configure_compile_cache"]

DEFAULT_CACHE_DIR = str(
    Path(__file__).resolve().parent.parent.parent / ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory. Call before the first
    compile."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR

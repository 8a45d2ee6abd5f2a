"""Persistent XLA compilation cache — one rule, one home.

Every entry point (``init_context``, ``ClusterServing.start``,
``python -m analytics_zoo_tpu.serving``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX keeps its cache there by
  itself and this code sets nothing.
- unset: the cache goes to :data:`CACHE_DIR`, one fixed directory beside
  the package (the checkout root; git-ignored).  The directory is part
  of what a later process must find again, so it is derived from
  ``__file__`` — never from ``tempfile``, a pid or a timestamp.

An explicit CPU platform stays uncached: XLA:CPU executables are pinned
to the build machine's CPU features (the loader warns of SIGILL on
drift) and CPU compiles are fast enough not to need it.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Apply the rule above; return the cache directory (None = off)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if str(jax.config.jax_platforms or "").lower() == "cpu":
        return None
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR

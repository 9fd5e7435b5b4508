"""Persistent XLA compile cache for the command-line entry points.

Compiling a full-width model for the chip takes minutes; the persistent
cache lets a second process (or the jnp-reference engine beside a Pallas
one) reuse what an earlier one compiled. Called from each ``main``, never
at import, so importing the library never changes JAX's configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

#: ``<repo>/.jax_cache`` (this file is ``<repo>/src/repro/launch/...``); a
#: fixed path, so every run from one checkout finds the same entries
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compile cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
    that directory is the only one used; otherwise the cache goes to
    :data:`REPO_CACHE_DIR` (git-ignored).
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))

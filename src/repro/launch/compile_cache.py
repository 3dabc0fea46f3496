"""Persistent XLA compilation cache shared by the repo's entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set this module
leaves it alone.  Otherwise programs are cached under ``<repo>/.cache/jax``.
The directory is part of the cache key, so it is a fixed path inside the
checkout — never one built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".cache" / "jax"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; call
    before the first compile.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

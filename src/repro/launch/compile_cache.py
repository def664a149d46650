"""Where JAX's persistent compilation cache lives.

The entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``) call ``enable_compile_cache`` once at start-up,
before their first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX read it when it was imported
    and caches there; nothing here sets another path.
  * unset: the cache goes to ``<repo>/.jax_cache`` (gitignored). The
    path is fixed — never a temp, pid or time-based directory — because
    it is part of what makes a later process find the entry again.

Importing this module touches no jax state.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)

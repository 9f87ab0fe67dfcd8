"""JAX's persistent compilation cache, for entry points only.

A cache's path is part of its key, so it has to stay put between runs.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache`` (listed
in ``.gitignore``).  Scripts call ``enable_compile_cache`` at start-up;
library modules and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/ -> three levels up.
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_ROOT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir

"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the same cache directory,
so the directory never depends on a temp dir, a pid or the time. If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it; otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``). Entry points call ``use_compile_cache()``
once, before their first compile.

Importing this module does not import jax.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)

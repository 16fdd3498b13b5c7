"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``chip_smoke.py`` and the ``serve``,
``train`` and ``tune`` mains): when ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it and the cache lives there, with no other directory set in
code; otherwise the cache lives in :data:`DEFAULT_DIR`, a fixed directory
inside the checkout.  The path is part of each entry's key, so a directory
named from a temp name, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["ENV", "DEFAULT_DIR", "use_compile_cache"]

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (listed in ``.gitignore``)
DEFAULT_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

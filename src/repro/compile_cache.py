"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` once at start-up; importing
this module changes nothing.  ``JAX_COMPILATION_CACHE_DIR``, when set,
places the cache and nothing overrides it.  Otherwise the cache lives at
a fixed ``.jax_cache`` in the checkout: the path is part of the cache
key, so it holds no temporary name, process id or time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

CHECKOUT = Path(__file__).resolve().parents[2]


def compile_cache_dir(environ: Mapping[str, str] = os.environ
                      ) -> Optional[str]:
    """The directory to point JAX at, or None when
    ``JAX_COMPILATION_CACHE_DIR`` already places the cache."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return str(CHECKOUT / ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax
    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return jax.config.jax_compilation_cache_dir

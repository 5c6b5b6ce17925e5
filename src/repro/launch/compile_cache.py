"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``chip_smoke.py``, the ``repro.launch`` CLIs, the benches)
call :func:`enable_compile_cache` once at start-up, so programs compiled by
one run are found again by the next. It is never called on import, nor from
tests. The cache's key includes its path, so the directory must not move:

* ``$JAX_COMPILATION_CACHE_DIR`` when it is set — no other is set then;
* otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]

__all__ = ["ENV_VAR", "compile_cache_dir", "enable_compile_cache"]


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache lives in."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path

"""Persistent XLA compilation cache, placed from outside the program.

When ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache
and no other is set here.  Otherwise the cache lives at one fixed path
inside the checkout, ``.jax_cache/`` at the repository root (listed in
``.gitignore``): JAX keys its entries by what is compiled, and a
directory that moves between runs never hits.

Entry points call ``enable_compile_cache()`` once, before their first
compile (``chip_smoke.py``, ``benchmarks/run.py``).
"""

from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax
from jax.experimental.compilation_cache import compilation_cache

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$ENV_VAR`` or, when
    that is unset, at ``DEFAULT_DIR``.  Returns the directory used."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def compile_cache_off():
    """Keep the persistent cache off inside the block, for compiles it
    must neither serve nor keep; the setting before it is restored."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()

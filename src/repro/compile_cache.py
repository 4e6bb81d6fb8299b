"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once at start-up; importing the library never
sets a cache, so a program that embeds it keeps its own choice.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def use_compile_cache(root: str) -> str:
    """Turn on the persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
    nothing is set here.  Otherwise the cache goes to
    ``<root>/.jax_cache``: a fixed path, because a directory that moves
    between runs is never found again.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path

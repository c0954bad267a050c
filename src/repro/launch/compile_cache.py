"""Persistent XLA compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and no
other is used.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (git-ignored): the directory is part of each
entry's key, so a fixed path is what lets a later run of the same
checkout hit.  Entry points (``launch.serve``, ``launch.train``,
``benchmarks.run``, ``chip_smoke.py``) call :func:`enable_compile_cache`
before their first compile; nothing calls it at import.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Where the cache goes: the environment's choice, else the fixed
    directory inside the checkout."""
    return os.environ.get(ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory.  JAX reads the
    variable when it is imported, so a caller that has not imported it
    yet (the numpy-only benchmark tables) stays free of it."""
    path = compile_cache_dir()
    os.environ[ENV] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path

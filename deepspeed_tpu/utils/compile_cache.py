"""Where the persistent XLA compilation cache lives.

One rule for every start-up path (``initialize``, the inference engines,
``bench.py``, ``tools/*``, ``chip_smoke.py``, test workers): if
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing is
set in code; otherwise the cache goes to one fixed directory inside the
checkout. The directory is part of the cache key, so it never carries a
temp name, a pid or a timestamp — a directory that moves never hits.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the
    checkout's ``.jax_cache``."""
    return os.environ.get(_ENV) or REPO_CACHE_DIR


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at
    :func:`compile_cache_dir` and return it. Idempotent; call before the
    first compilation of a process."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path

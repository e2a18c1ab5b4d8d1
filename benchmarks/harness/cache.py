"""Where the persistent compilation cache of a benchmark run lives:
``JAX_COMPILATION_CACHE_DIR`` if the environment sets it (jax reads that
itself and nothing is set in code), else ``<checkout>/.jax_cache`` — the
fixed path the program's ``utils/compile_cache.py`` uses too, so program
and benchmark agree and only the first run of a cell in a checkout
compiles."""

from __future__ import annotations

import os

from benchmarks.harness.manifest import ROOT


def enable() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick its compile: a run must find all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path

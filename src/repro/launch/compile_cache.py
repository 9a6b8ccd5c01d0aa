"""JAX's persistent compilation cache for the program's entry points.

A full-width prefill and decode loop take tens of seconds to compile, and a
fresh process starts with nothing compiled.  ``enable_compile_cache`` is
called once at start-up by ``chip_smoke.py``, ``repro.launch.serve`` and
``benchmarks/run.py`` — never at import, so importing the package changes
no global JAX state.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout (gitignored): the directory is part of
# every entry's identity, so a name built from a temporary directory, a
# process id or the time would never be found again
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)

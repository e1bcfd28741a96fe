"""JAX's persistent compilation cache, in one place.

``enable()`` is called once per process by every entry point that
compiles for real (``chip_smoke.py``, ``repro.launch.serve``, the fleet
``worker_main`` and ``bench/run.py``), before its first compile.  It
never runs at import time.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache
and no other is set.  Otherwise the cache lives at one fixed path inside
the checkout, ``<checkout>/.jax_cache`` (resolved from this package's
location, listed in ``.gitignore``).  The path is part of the cache's
key, so it must not move between runs: it never comes from a temp name,
a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<checkout>/.jax_cache``: src/repro/launch -> the checkout root."""
    return str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV) or default_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path

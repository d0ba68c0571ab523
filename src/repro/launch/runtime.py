"""Process-level runtime knobs shared by the drivers.

Currently one knob: JAX's persistent compilation cache.  The drivers
(``launch.train``, ``launch.serve``, ``examples/train_bpt_cnn.py`` and
``chip_smoke.py``) call ``maybe_enable_compilation_cache`` once at
start-up, so a repeat run of the same program reads its compiled
executables back instead of compiling again.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory and
nothing here overrides it; otherwise the cache lives at one fixed path
inside the checkout (``.jax_cache/``, gitignored) — a fixed path, because
the directory is part of what makes a later run find the entries.  Of the tests, only ``tests/test_runtime.py``
calls it, and it restores the config afterwards, so the suite keeps its
hermetic no-cache behavior.
"""
from __future__ import annotations

import os

__all__ = ["maybe_enable_compilation_cache", "default_cache_dir"]

_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """The checkout's own cache directory: ``<repo>/.jax_cache``."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    return os.path.join(root, ".jax_cache")


def maybe_enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, so its
    setting is left alone.  Unset: ``default_cache_dir()``, created if
    missing.  Safe to call more than once.
    """
    import jax
    if os.environ.get(_ENV):
        return jax.config.jax_compilation_cache_dir
    path = default_cache_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
